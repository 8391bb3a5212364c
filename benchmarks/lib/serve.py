"""Driver ``serve_open_loop``: requests arrive on a schedule made from
the seed, whether or not the engine keeps up. One generator thread calls
``InferenceEngine.submit``; one reader thread per request in flight
reads ``GenerationRequest.stream()`` and stamps each token as it
reaches the client."""
import gc
import threading
import time

import jax
import numpy as np

from . import check, harness, program, traffic

BEYOND_ANY = 1e12        # a percentile that falls on a failed request
DRAIN_S = 60.0           # how long past the close an answer is awaited


# -- warm-up: every shape the mix can reach --------------------------------

def _bucket(n, cap):
    b = 1
    while b < n:
        b *= 2
    return min(b, cap)


def prompt_shapes(length, chunk, bs, width_cap):
    """The programs a prompt of ``length`` tokens runs, as the engine
    documents them: one chunk program per (padded chunk length, table
    width bucket), the slice that takes the last chunk's last logits
    (one program per padded length of a last chunk), then the decode
    program at the width bucket of its first tick."""
    shapes, start = set(), 0
    while start < length:
        c_true = min(length - start, chunk)
        c_pad = -(-c_true // bs) * bs
        shapes.add(("chunk", c_pad,
                    _bucket(-(-(start + c_pad) // bs), width_cap)))
        start += c_true
    shapes.add(("first_token", c_pad))
    shapes.add(("decode", _bucket(-(-(length + 1) // bs), width_cap)))
    return shapes


def warm_lengths(mix, engine, seq_len):
    """A small set of prompt lengths that between them run every program
    a request of this mix can: prompts, and re-prefills after a
    preemption (prompt plus what was generated)."""
    chunk, bs = engine["prefill_chunk"], engine["block_size"]
    cap = -(-seq_len // bs)
    lo, hi = traffic.length_range(mix["prompt"])
    hi = min(hi + traffic.length_range(mix["output"])[1], seq_len - 2)
    seen, picked = set(), []
    for n in range(hi, lo - 1, -1):
        new = prompt_shapes(n, chunk, bs, cap) - seen
        if new:
            seen |= new
            picked.append(n)
    return picked, seen


def warm_up(eng, mix, engine, sizes, seed):
    lens, shapes = warm_lengths(mix, engine, sizes["seq_len"])
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 11])
    samp = mix.get("sampling", {})
    took = []
    for i, n in enumerate(lens):
        t = time.perf_counter()
        sampled = i % 2 == 1
        req = eng.submit(
            rng.integers(0, sizes["vocab_size"], n).astype(np.int32),
            max_new_tokens=2,
            temperature=samp.get("temperature", 1.0) if sampled else 0.0,
            top_k=samp.get("top_k", 0) if sampled else 0,
            top_p=samp.get("top_p", 1.0) if sampled else 1.0)
        req.result(timeout=1100)
        took.append(round(time.perf_counter() - t, 2))
    harness.say(f"warm-up: {len(lens)} prompts ran {len(shapes)} programs; "
                f"seconds for each prompt: {took}")


# -- the window -------------------------------------------------------------

class Client:
    """One request as its client saw it."""

    def __init__(self, spec):
        self.spec = spec
        self.sent = None
        self.arrivals = []        # perf_counter of each token's arrival
        self.tokens = []
        self.error = None
        self.req = None
        self.thread = None

    def read(self):
        try:
            for tok in self.req.stream(timeout=DRAIN_S + 600):
                self.arrivals.append(time.perf_counter())
                self.tokens.append(int(tok))
        except Exception as e:  # noqa: BLE001 — the request failed; counted
            self.error = e

    @property
    def ok(self):
        return (self.error is None and self.req is not None
                and self.thread is not None and not self.thread.is_alive()
                and len(self.tokens) >= 1)


def generate(eng, clients, t0, stop):
    """The generator thread: send each request when it is due."""
    from paddle_tpu.serving.engine import QueueFull

    for c in clients:
        due = t0 + c.spec["due"]
        while True:
            wait = due - time.perf_counter()
            if wait <= 0 or stop.is_set():
                break
            time.sleep(min(wait, 0.02) if wait > 0.003 else 0)
        if stop.is_set():
            return
        s = c.spec
        c.sent = time.perf_counter()
        try:
            c.req = eng.submit(s["prompt"], max_new_tokens=s["max_new_tokens"],
                               temperature=s["temperature"],
                               top_k=s["top_k"], top_p=s["top_p"],
                               block=False)
        except QueueFull as e:
            c.error = e
            continue
        c.thread = threading.Thread(target=c.read, daemon=True,
                                    name=f"client-{s['index']}")
        c.thread.start()


def drive(eng, schedule, seconds, tw):
    """Open the window, offer the load, close the window, wait for the
    answers. Returns (clients, t0, peak of the block gauge)."""
    clients = [Client(s) for s in schedule]
    stop = threading.Event()
    used_peak = 0
    watch = harness.HostWatch()
    t0 = time.perf_counter()
    gen = threading.Thread(target=generate, args=(eng, clients, t0, stop),
                           name="load-generator", daemon=True)
    gen.start()
    while True:
        now = time.perf_counter() - t0
        if now >= seconds:
            break
        watch.tick()
        used_peak = max(used_peak, program.gauge("kv_blocks_used"))
        todo = tw.due(now) if tw is not None else None
        if todo:
            tw.start() if todo == "start" else tw.stop()
            watch.tick(count=False)
        time.sleep(0.05)
    if tw is not None:
        tw.stop()
    harness.say("host:", watch.report())
    gen.join(timeout=5.0)
    stop.set()
    deadline = t0 + seconds + DRAIN_S
    for c in clients:
        if c.thread is not None:
            c.thread.join(timeout=max(0.0, deadline - time.perf_counter()))
    return clients, t0, used_peak


def latencies(clients, t0):
    ttft, tpot = [], []
    for c in clients:
        if not c.ok:
            ttft.append(BEYOND_ANY)
            tpot.append(BEYOND_ANY)
            continue
        ttft.append((c.arrivals[0] - (t0 + c.spec["due"])) * 1e3)
        if len(c.arrivals) > 1:
            tpot.append((c.arrivals[-1] - c.arrivals[0]) * 1e3
                        / (len(c.arrivals) - 1))
    return ttft, tpot


def model_flops(clients, spec, t_lo, t_hi):
    """Model FLOPs of every prompt and output token processed between
    t_lo and t_hi: a prompt counts where its first token arrived."""
    flops, sizes = spec.family.forward_flops_per_token, spec.config["sizes"]
    total = 0.0
    for c in clients:
        p = len(c.spec["prompt"])
        for i, t in enumerate(c.arrivals):
            if not t_lo <= t <= t_hi:
                continue
            if i == 0:
                total += p * flops(sizes, p, causal_mean=True)
            else:
                total += flops(sizes, p + i)
    return total


def decode_contexts(clients, t_lo, t_hi):
    """Sum of the contexts that decode ticks attended to between t_lo
    and t_hi: each token after a request's first comes from one tick
    over its prompt and the tokens before it."""
    total = 0
    for c in clients:
        p = len(c.spec["prompt"])
        total += sum(p + i for i, t in enumerate(c.arrivals)
                     if i >= 1 and t_lo <= t <= t_hi)
    return total


# -- correct ----------------------------------------------------------------

def pick_sample(clients, seed, k):
    """k finished greedy requests drawn from the seed, the longest
    (prompt plus served) among them."""
    done = [c for c in clients if c.ok and c.spec["greedy"]
            and c.req.finish_reason is not None]
    if not done:
        return []
    longest = max(done, key=lambda c: len(c.spec["prompt"]) + len(c.tokens))
    rest = [c for c in done if c is not longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 13])
    idx = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in idx]


def malformed(clients, vocab):
    """Finished requests whose answer has the wrong shape."""
    bad = 0
    for c in clients:
        if not c.ok:
            continue
        toks = c.tokens
        if (len(toks) != c.spec["max_new_tokens"]
                or c.req.finish_reason != "length"
                or any(t < 0 or t >= vocab for t in toks)):
            bad += 1
    return bad


def compare_served(sample, spec, seed, pad_to, lowp=None):
    """Run the reference over each sampled prompt with its served tokens.
    Returns (widest gap, widest control gap, tokens compared)."""
    family, sizes = spec.family, spec.config["sizes"]
    params = family.make_params(sizes, seed)
    worst = worst_low = 0.0
    n = 0
    for c in sample:
        gap, low = family.served_gaps(
            params, c.spec["prompt"], c.tokens, sizes, pad_to, lowp)
        worst = max(worst, float(gap.max()))
        worst_low = max(worst_low, float(low.max()))
        n += len(gap)
    del params
    return worst, worst_low, n


def pad_length(mix, seq_len):
    hi = traffic.length_range(mix["prompt"])[1] \
        + traffic.length_range(mix["output"])[1]
    return min(-(-hi // 128) * 128, seq_len)


def run(spec, args, env):
    sizes, mix, wl = spec.config["sizes"], spec.traffic, spec.workload
    engine = wl["engine"]
    cfg = program.build_config(spec.config)
    eng = program.build_engine(cfg, spec.family.make_params(sizes, args.seed),
                               engine, args.seed)
    env["stage"]("weights made and engine built")
    try:
        warm_up(eng, mix, engine, sizes, args.seed)
        env["stage"]("warmed up")
        schedule = traffic.open_loop(mix, wl["rate_rps"], args.seconds,
                                     args.seed, sizes["vocab_size"])
        harness.say(f"schedule: {len(schedule)} requests at "
                    f"{wl['rate_rps']} a second, "
                    f"{sum(len(s['prompt']) for s in schedule)} prompt and "
                    f"{sum(s['max_new_tokens'] for s in schedule)} output "
                    "tokens")
        tw = harness.TraceWindow(env["out_dir"], wl["trace"]) \
            if args.trace else None
        stats0 = program.stats_snapshot()
        env["compiles"].mark()
        env["setup_done"]()
        clients, t0, used_peak = drive(eng, schedule, args.seconds, tw)
        compiles = env["compiles"].since_mark()
        stats1 = program.stats_snapshot()
        device = harness.device_record(jax.devices()[:spec.chips])
    finally:
        eng.shutdown(drain=False, timeout=60)
    t_close = t0 + args.seconds

    n_tokens = sum(1 for c in clients for t in c.arrivals if t <= t_close)
    ttft, tpot = latencies(clients, t0)
    failed = sum(1 for c in clients if not c.ok)
    finished = len(clients) - failed
    e2e = {"serve_tokens_per_s": n_tokens / args.seconds,
           "ttft_p95_ms": harness.percentile(ttft, 95),
           "tpot_p95_ms": harness.percentile(tpot, 95)}
    lag = [(c.sent - (t0 + c.spec["due"])) * 1e3 for c in clients
           if c.sent is not None]
    stamps = sorted(t for c in clients for t in c.arrivals)
    quiet, at = max(((b - a, a) for a, b in zip(stamps, stamps[1:])),
                  default=(0.0, t0))
    harness.say(f"longest time with no token reaching any client: "
                f"{quiet * 1e3:.0f} ms, {at - t0:.1f} s into the window")
    harness.say(f"window: {len(clients)} requests due, {finished} finished, "
                f"{failed} failed; {n_tokens} tokens reached the client "
                f"in {args.seconds} s; ttft_p95_ms over {len(ttft)} and "
                f"tpot_p95_ms over {len(tpot)} requests; ttft median "
                f"{harness.median(ttft):.1f} ms, tpot median "
                f"{harness.median(tpot):.2f} ms; {compiles} programs "
                "compiled inside it")

    # the engine's pool and weights go before the reference comes
    sample = pick_sample(clients, args.seed, wl["check"]["sample_requests"])
    bad = malformed(clients, sizes["vocab_size"])
    eng = None
    gc.collect()
    t_ref = time.perf_counter()
    gap, _, n_cmp = compare_served(sample, spec, args.seed,
                                   pad_length(mix, sizes["seq_len"]))
    longest = max((len(c.spec["prompt"]) + len(c.tokens) for c in sample),
                  default=0)
    harness.say(f"compared {n_cmp} served greedy tokens of {len(sample)} "
                f"requests, the longest of {longest} tokens; the "
                f"reference took {time.perf_counter() - t_ref:.1f} s")
    compared = check.serve(gap if sample else None, n_cmp, bad,
                           wl["check"]["limits"])

    ctx = None
    if args.trace:
        ctx = harness.trace_context(spec, env, tw, stats0, stats1, {
            "generator_lag_p95_ms": harness.percentile(lag, 95),
            "compiles_in_window": compiles,
            "kv_blocks_used_peak_share":
                100.0 * used_peak / (engine["n_blocks"] - 1),
            "model_flops_per_s":
                model_flops(clients, spec, t0, t_close) / args.seconds,
            "traced_decode_contexts":
                decode_contexts(clients, tw.t_start, tw.t_stop)})
    return {"attempted": len(clients), "failed": failed, "e2e": e2e,
            "compared": compared, "device": device, "ctx": ctx,
            "clients": clients}
