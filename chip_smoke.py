#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process drives the two main paths once on a TPU, through the entry
points a user calls, at the full width of a shipped model, with weights
and data made from a seed (no network, no files):

- ``train``:   BERT-base (hidden 768, 12 layers, seq 512, batch 32, bf16)
               through ``parallel.DistributedTrainStep`` with AdamW.
- ``serve``:   ``serving.InferenceEngine`` over ``gpt_1p3b`` (hidden 2048,
               16 heads of 128, 24 layers, bf16 weights), paged KV cache,
               8 slots, mixed greedy and sampled traffic; and the paged
               decode step of one chip's share of ``sarvam_105b``,
               compiled from shapes alone.
- ``kernels``: every Pallas family in ``paddle_tpu/ops`` compiled by
               Mosaic at a preset's shape and compared with its composed
               reference.
- ``train4``:  the BERT-base step on two four-chip meshes, when the host
               has four chips.

A phase fails by raising: nothing here turns a failure into a string, so
any failed check ends the process with a traceback and a non-zero code.
That a Pallas kernel really ran is read from the program text (the Mosaic
custom call ``tpu_custom_call``), never from a flag.

With no TPU the script exits 2 at once and names the platform it found.
The last line of stdout is the result object the driver reads.

    python3 chip_smoke.py [--phases train,serve,kernels,train4]
"""
import argparse
import contextlib
import dataclasses
import functools
import gc
import importlib
import json
import re
import sys
import time

PHASES = ("train", "serve", "kernels", "train4")
MOSAIC = "tpu_custom_call"
WRITER = "pool_write_rows"     # the decode tick's row writer, by its name

# Stated tolerances. bf16 keeps 8 bits of mantissa (eps 2^-8 = 3.9e-3), so
# results that went through bf16 matmuls are held to a few eps of the
# largest reference value; f32 elementwise kernels to a few f32 ulps.
TOL_BF16 = 2e-2            # max|got - ref| / max|ref|, bf16 kernels
TOL_F32 = 1e-5             # same measure, f32 elementwise kernels
TOL_LOSS_FLASH = 1e-2      # |loss(flash) - loss(no flash)|, first step
TOL_LOSS_MESH = 1e-2       # |loss(mesh) - loss(one chip)|, first step
TOL_LOGITS = 5e-2          # max|logits - f32 ref| / max|f32 ref|, decode


class Clock:
    """Wall time of a phase, split into compilation and execution."""

    def __init__(self):
        self.compile_s = 0.0
        self.run_s = 0.0

    @contextlib.contextmanager
    def _add(self, field):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            setattr(self, field,
                    getattr(self, field) + time.perf_counter() - t0)

    def compiling(self):
        return self._add("compile_s")

    def running(self):
        return self._add("run_s")


def check(cond, message):
    if not cond:
        raise AssertionError(message)


def rel_err(got, ref):
    """max|got - ref| / max|ref| in float64 on the host."""
    import numpy as np

    got = np.asarray(got, np.float64)
    ref = np.asarray(ref, np.float64)
    check(got.shape == ref.shape, f"shape {got.shape} != {ref.shape}")
    check(np.all(np.isfinite(got)), "non-finite values")
    return float(np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-30))


def fallbacks():
    from paddle_tpu.monitor import stats

    return stats.FUSED_KERNEL_FALLBACKS.get()


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------

def seeded_batch(cfg, batch, seed=0):
    """One fixed (tokens, labels) batch: labels are the next token."""
    import numpy as np

    rng = np.random.default_rng(seed)
    seq = rng.integers(0, cfg.vocab_size, (batch, cfg.seq_len + 1))
    return (seq[:, :-1].astype(np.int32), seq[:, 1:].astype(np.int32))


def build_step(cfg, mesh, zero=True):
    from paddle_tpu.models import gpt_init, gpt_loss, gpt_param_specs
    from paddle_tpu.parallel import DistributedTrainStep

    return DistributedTrainStep(
        lambda p, b: gpt_loss(cfg, p, b), gpt_init(cfg, seed=0),
        gpt_param_specs(cfg), optimizer="adamw", lr=2e-4, zero=zero,
        mesh=mesh)


def compile_step(step, batch, clock):
    """AOT-compile the step for its program text; the call that follows
    goes through the normal ``step(batch)`` entry (and finds this compile
    in the persistent cache)."""
    with clock.compiling():
        return step.lower(batch).compile().as_text()


def phase_train(cfg=None, batch_size=32, steps=5):
    import jax

    from paddle_tpu.models import bert_base_config
    from paddle_tpu.parallel import create_mesh

    clock = Clock()
    # as shipped: use_flash=None (auto), remat off, full layer unroll. The
    # rolled scan (scan_unroll=1) would compile faster but stacks every
    # layer's residuals: XLA counts 16.0 GB for it at batch 32 against
    # 8.7 GB unrolled, and the chip gives a program 15.75 GB.
    cfg = cfg or bert_base_config()
    batch = seeded_batch(cfg, batch_size)
    mesh = create_mesh(devices=jax.devices()[:1])
    fb0 = fallbacks()

    step = build_step(cfg, mesh)
    hlo = compile_step(step, batch, clock)
    check(MOSAIC in hlo,
          "train: the compiled step holds no Mosaic custom call — the "
          "flash kernel was not chosen at seq %d" % cfg.seq_len)
    with clock.compiling():                      # warm-up (cache read)
        losses = [float(step(batch))]
    with clock.running():
        for _ in range(steps):
            losses.append(float(step(batch)))
    check(all(l == l and abs(l) != float("inf") for l in losses),
          f"train: non-finite loss in {losses}")
    check(losses[-1] < losses[0],
          f"train: loss did not fall on a fixed batch: {losses}")
    check(fallbacks() == fb0, "train: a kernel entry fell back to jnp")
    del step
    gc.collect()

    # the same step with the kernel switched off: the check above must be
    # able to tell the two apart, and the first losses must agree
    ref_step = build_step(dataclasses.replace(cfg, use_flash=False), mesh)
    ref_hlo = compile_step(ref_step, batch, clock)
    check(MOSAIC not in ref_hlo,
          "train: use_flash=False still compiled a Mosaic call")
    with clock.compiling():
        ref_loss = float(ref_step(batch))
    del ref_step
    gc.collect()
    diff = abs(losses[0] - ref_loss)
    check(diff <= TOL_LOSS_FLASH,
          f"train: first loss {losses[0]} vs {ref_loss} without flash "
          f"(|diff| {diff} > {TOL_LOSS_FLASH})")
    return clock, {"losses": [round(l, 4) for l in losses],
                   "loss_no_flash": round(ref_loss, 4),
                   "flash_vs_xla_first_loss_diff": round(diff, 5),
                   "tol": TOL_LOSS_FLASH, "steps": steps,
                   "batch": batch_size, "seq": cfg.seq_len,
                   "layers": cfg.n_layers, "mosaic_in_hlo": True}


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------

PROMPT_LENS = (100, 180, 260, 333, 420, 512, 600)
NEW_TOKENS = 32
BLOCK = 16


def decode_logits_check(cfg, params, clock, prompt_len=112):
    """One paged decode step after a chunked prefill, against a float32
    ``jax.numpy`` forward pass of the same tokens. Logits, not argmax:
    random weights make near-ties."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.models.gpt import (gpt_decode_step_paged, gpt_forward,
                                       gpt_prefill_chunk)

    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg.vocab_size, prompt_len + 1).astype(np.int32)
    width = prompt_len // BLOCK + 1
    shape = (width + 1, cfg.n_layers, cfg.n_heads, BLOCK, cfg.head_dim)
    pool = (jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype))
    table = np.arange(1, width + 1, dtype=np.int32)      # block 0 = sink

    prefill = jax.jit(functools.partial(gpt_prefill_chunk, cfg))
    decode = jax.jit(functools.partial(gpt_decode_step_paged, cfg))
    with clock.compiling():
        _, pool = prefill(params, pool, table, toks[None, :prompt_len],
                          np.int32(0))
        args = (params, pool, table[None], np.array([prompt_len], np.int32),
                toks[prompt_len:])
        hlo = decode.lower(*args).compile().as_text()
    check(MOSAIC in hlo, "serve: gpt_decode_step_paged compiled without "
                         "the paged-attention Mosaic call")
    with clock.running():
        logits = np.asarray(decode(*args)[0][0])

    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32, use_flash=False,
                                scan_unroll=1)
    params32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32),
                                      params)
    with clock.running(), jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(functools.partial(gpt_forward, cfg32))(
            params32, toks[None])[0, -1])
    err = rel_err(logits, ref)
    check(err <= TOL_LOGITS,
          f"serve: decode logits differ from the f32 reference by {err} "
          f"of its largest value (> {TOL_LOGITS})")
    return err


def run_traffic(eng, cfg):
    """Submit every request at once (long prompts prefill in chunks while
    earlier requests already decode), stream the first, wait for the
    rest. Returns [(request, tokens)] in submission order."""
    import numpy as np

    rng = np.random.default_rng(2)
    reqs = []
    for i, n in enumerate(PROMPT_LENS):
        prompt = rng.integers(0, cfg.vocab_size, n).astype(np.int32)
        sampled = i % 2 == 1
        reqs.append(eng.submit(
            prompt, max_new_tokens=NEW_TOKENS,
            temperature=0.8 if sampled else 0.0,
            top_k=40 if sampled else 0, top_p=0.95 if sampled else 1.0))
    streamed = list(reqs[0].stream(timeout=900))
    out = [(r, r.result(timeout=900)) for r in reqs]
    check(streamed == out[0][1], "serve: stream() and result() disagree")
    for r, toks in out:
        check(len(toks) == NEW_TOKENS and r.finish_reason == "length",
              f"serve: request rid={r.rid} ended {r.finish_reason!r} "
              f"with {len(toks)} tokens, wanted {NEW_TOKENS} / 'length'")
        check(all(0 <= t < cfg.vocab_size for t in toks),
              f"serve: token out of range in rid={r.rid}")
    return out


def pool_slab_moves(hlo, pool_shape):
    """Instructions of a compiled paged program that materialise a
    layer's slab of the KV pool or copy the pool whole: anything that
    yields an array of the slab's shape ``(n_blocks, nh, bs, hd)``, and
    a pool-shaped ``copy`` (alone or in a fusion's name, as in
    ``copy_dynamic-update-slice_fusion``). The paged steps read and
    write the pool in place; any of these is a relapse."""
    dims = lambda shape: "[" + ",".join(str(int(d)) for d in shape) + "]"
    slab = dims(pool_shape[:1] + pool_shape[2:])
    pool = dims(pool_shape)
    found = []
    for line in hlo.splitlines():
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = \w+(\[[\d,]*\])\S* "
                     r"([\w\-]+)\(", line)
        if not m:
            continue
        name, shape, op = m.groups()
        if (shape == slab and op != "parameter") or (
                shape == pool and "copy" in (op, *re.split(r"[_.]", name))):
            found.append(f"{name} = {shape} {op}")
    return found


def check_no_pool_moves(hlo, pool, program):
    """Every array of a paged pool (``PagedKVCache.pool``, or a model's
    ``pool_spec``) against a compiled serve program: none is copied,
    and no layer's slab of one is cut out."""
    for i, arr in enumerate(pool):
        moves = pool_slab_moves(hlo, tuple(arr.shape))
        check(not moves, f"serve: {program} moves a layer's slab of pool "
                         f"array {i} {tuple(arr.shape)}, or the array "
                         f"whole: {moves[:4]}")


def latent_decode_program(clock, cfg=None, n_slots=32, n_blocks=6801,
                          block=64, width=128):
    """Compiled from shapes alone (no weights are made): the paged decode
    step of the latent-attention expert model, at one chip's share of
    ``sarvam_105b``, holds the Mosaic kernel and moves no slab of its
    latent pool."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.models import mla_decode_step_paged, mla_init, sarvam_105b

    cfg = cfg or sarvam_105b(n_layers=6, experts_held=32, vocab_size=65536,
                             seq_len=16384, dtype=jnp.bfloat16,
                             param_dtype=jnp.bfloat16)
    params = jax.eval_shape(lambda: mla_init(cfg, seed=0))
    pool = cfg.serving_model().pool_spec(cfg, n_blocks, block)
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    with clock.compiling():
        hlo = jax.jit(functools.partial(mla_decode_step_paged, cfg),
                      donate_argnums=(1,)).lower(
            params, pool, i32(n_slots, width), i32(n_slots),
            i32(n_slots)).compile().as_text()
    check(MOSAIC in hlo, "serve: the latent decode program holds no Mosaic "
                         "custom call")
    check(WRITER in hlo, "serve: the latent decode program holds no "
                         f"{WRITER} call")
    check_no_pool_moves(hlo, pool, "the latent decode program")


def phase_serve(cfg=None, n_slots=8):
    import jax.numpy as jnp

    from paddle_tpu.models import gpt_1p3b, gpt_init
    from paddle_tpu.serving import InferenceEngine

    clock = Clock()
    cfg = cfg or gpt_1p3b(dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    fb0 = fallbacks()
    with clock.running():
        params = gpt_init(cfg, seed=0)
    logits_err = decode_logits_check(cfg, params, clock)
    gc.collect()

    eng = InferenceEngine(cfg, params, n_slots=n_slots, block_size=BLOCK,
                          prefill_chunk=128, seed=0)
    try:
        with clock.compiling():
            # the width bucket the shortest prompt decodes in
            hlo = eng.lower_decode(
                table_width=PROMPT_LENS[0] // BLOCK + 1).compile().as_text()
        check(MOSAIC in hlo, "serve: the engine's decode program holds no "
                             "Mosaic custom call")
        check(WRITER in hlo, "serve: the engine's decode program holds no "
                             f"{WRITER} call")
        check_no_pool_moves(hlo, eng.cache.pool, "the decode program")
        t0 = time.perf_counter()
        cold = run_traffic(eng, cfg)
        cold_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        warm = run_traffic(eng, cfg)
        warm_s = time.perf_counter() - t0
    finally:
        eng.shutdown(drain=False, timeout=60)
    # every program the traffic needs was compiled in the cold pass, so
    # the cold pass's excess over the warm pass is compilation
    clock.compile_s += max(0.0, cold_s - warm_s)
    clock.run_s += warm_s + min(cold_s, warm_s)
    greedy = [i for i in range(len(PROMPT_LENS)) if i % 2 == 0]
    for i in greedy:
        check(cold[i][1] == warm[i][1],
              f"serve: greedy request {i} (prompt {PROMPT_LENS[i]}) is not "
              "token-identical when repeated")
    check(fallbacks() == fb0, "serve: a kernel entry fell back to jnp")
    latent_decode_program(clock)
    n_tok = len(PROMPT_LENS) * NEW_TOKENS
    return clock, {"layers": cfg.n_layers, "hidden": cfg.hidden,
                   "heads": cfg.n_heads, "depth_cut": False,
                   "requests_per_pass": len(PROMPT_LENS),
                   "prompt_lens": list(PROMPT_LENS),
                   "new_tokens": NEW_TOKENS, "cold_pass_s": round(cold_s, 2),
                   "warm_pass_s": round(warm_s, 2),
                   "warm_tokens": n_tok,
                   "greedy_repeat_identical": len(greedy),
                   "decode_logits_rel_err": round(logits_err, 5),
                   "tol": TOL_LOGITS, "mosaic_in_hlo": True,
                   "latent_decode_program": "compiled, no slab moved"}


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------

def run_kernel(name, clock, fn, ref_fn, args, tol, census):
    """Compile ``fn`` (a routed entry: on the chip it must lower to a
    Mosaic call), run it, and hold every output to ``ref_fn``'s."""
    import jax

    t0 = time.perf_counter()
    lowered = jax.jit(fn).lower(*args)
    check(MOSAIC in lowered.as_text(),
          f"kernels: {name} lowered without a Mosaic custom call")
    with clock.compiling():
        compiled = lowered.compile()
        ref = jax.jit(ref_fn)(*args)
    with clock.running():
        got = jax.block_until_ready(compiled(*args))
    got_l, ref_l = jax.tree_util.tree_leaves(got), \
        jax.tree_util.tree_leaves(ref)
    check(len(got_l) == len(ref_l), f"kernels: {name} output count")
    err = max(rel_err(g, r) for g, r in zip(got_l, ref_l))
    check(err <= tol, f"kernels: {name} differs from its reference by "
                      f"{err} (> {tol})")
    census[name] = {"status": "pass", "rel_err": float("%.3g" % err),
                    "tol": tol, "s": round(time.perf_counter() - t0, 2)}
    print(f"  kernel {name:<34} pass  err {err:.2e} (tol {tol:g})",
          flush=True)


def phase_kernels():
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, PartitionSpec as P

    # (by module path: paddle_tpu.ops re-exports functions under the
    # modules' own names)
    fa = importlib.import_module("paddle_tpu.ops.flash_attention")
    f8 = importlib.import_module("paddle_tpu.ops.fp8_matmul")
    fk = importlib.import_module("paddle_tpu.ops.fused_kernels")
    fo = importlib.import_module("paddle_tpu.ops.fused_optimizer")
    i8 = importlib.import_module("paddle_tpu.ops.int8_matmul")
    ma = importlib.import_module("paddle_tpu.ops.mla_attention")
    md = importlib.import_module("paddle_tpu.ops.moe_dispatch")
    pa = importlib.import_module("paddle_tpu.ops.paged_attention")
    pr = importlib.import_module("paddle_tpu.ops.power_retention")
    pw = importlib.import_module("paddle_tpu.ops.pool_write")
    rf = importlib.import_module("paddle_tpu.parallel.ring_flash")
    from paddle_tpu.parallel.mesh import AXES

    clock, census = Clock(), {}
    fb0 = fallbacks()
    rng = np.random.default_rng(3)
    bf, f32 = jnp.bfloat16, jnp.float32

    def normal(shape, dtype=bf, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, dtype)

    up = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda x: x.astype(f32) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, t)
    run = functools.partial(run_kernel, clock=clock, census=census)

    # -- attention: forward and backward in one program --------------------
    def attn_both(attn):
        def both(q, k, v, g):
            out, vjp = jax.vjp(attn, q, k, v)
            return (out,) + vjp(g.astype(out.dtype))
        return both

    def attn_ref(q, k, v):
        with jax.default_matmul_precision("highest"):
            return fa._attention_reference(q, k, v, True,
                                           q.shape[-1] ** -0.5)

    flash = functools.partial(fa.flash_attention_arrays, causal=True)
    for tag, shape in (("s512.d64", (4, 12, 512, 64)),       # bert_base
                       ("s2048.d128", (1, 16, 2048, 128)),   # gpt_1p3b
                       ("s1024.d96.pad", (1, 16, 1024, 96))):  # gpt_760m
        args = tuple(normal(shape) for _ in range(4))
        run("flash.fwd_bwd." + tag, fn=attn_both(flash),
            ref_fn=lambda *a: attn_both(attn_ref)(*up(a)), args=args,
            tol=TOL_BF16)

    # ring attention's per-hop path at degree 1: one device, one hop,
    # the hop's own block sizes (512 x 1024), custom forward and backward
    mesh1 = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1), AXES)
    spec = P(None, None, "sharding", None)
    ring = jax.shard_map(
        functools.partial(rf.ring_flash_attention, axis_name="sharding",
                          causal=True),
        mesh=mesh1, in_specs=(spec,) * 3, out_specs=spec)
    args = tuple(normal((1, 16, 2048, 128)) for _ in range(4))
    run("ring_flash.hop.s2048.d128", fn=attn_both(ring),
        ref_fn=lambda *a: attn_both(attn_ref)(*up(a)), args=args,
        tol=TOL_BF16)

    # -- paged decode -------------------------------------------------------
    def paged_case(B, nh, hd, W):
        n_blocks = B * W + 1
        q = normal((B, nh, hd))
        kb = normal((n_blocks, nh, BLOCK, hd))
        vb = normal((n_blocks, nh, BLOCK, hd))
        lengths = rng.integers(1, W * BLOCK + 1, B).astype(np.int32)
        lengths[0], lengths[-1] = W * BLOCK, 1        # full and one-token
        tables = 1 + rng.permutation(B * W).reshape(B, W).astype(np.int32)
        live = np.arange(W)[None, :] * BLOCK < lengths[:, None]
        return q, kb, vb, np.where(live, tables, 0).astype(np.int32), lengths

    def paged_ref(q, kb, vb, tables, lengths):
        with jax.default_matmul_precision("highest"):
            return pa._paged_attention_reference(
                *up((q, kb, vb)), tables, lengths, q.shape[-1] ** -0.5)

    for tag, dims in (("h16.d128", (8, 16, 128, 64)),
                      ("h12.d64", (8, 12, 64, 32))):
        run("paged_attention." + tag, fn=pa.paged_attention_arrays,
            ref_fn=paged_ref, args=paged_case(*dims), tol=TOL_BF16)

    # the serve cells' shapes: 32 slots, most of them holding no request
    # (length 0: no grid step, a row of zeros) between live ones of
    # mixed lengths, tables 128 wide, a layer of the whole pool
    def live_case(bs, W, n_blocks):
        lengths = np.zeros(32, np.int32)
        lengths[[2, 3, 11, 20, 30]] = (1, 20 * bs, W * bs, 93 * bs + 7,
                                       16 * bs)
        tables = rng.integers(1, n_blocks, (32, W)).astype(np.int32)
        live = np.arange(W)[None, :] * bs < lengths[:, None]
        return np.where(live, tables, 0).astype(np.int32), lengths

    def live_rows(out, lengths):
        return jnp.where((lengths > 0)[:, None, None], out, 0)

    tables, lengths = live_case(BLOCK, 128, 1025)
    run("paged_attention.cells.dead_lanes",
        fn=lambda q, kb, vb, t, n, li: pa.paged_attention_arrays(
            q, kb, vb, t, n, layer=li),
        ref_fn=lambda q, kb, vb, t, n, li: live_rows(
            paged_ref(q, kb[:, 1], vb[:, 1], t, n), n),
        args=(normal((32, 16, 128)), normal((1025, 2, 16, BLOCK, 128)),
              normal((1025, 2, 16, BLOCK, 128)), tables, lengths,
              jnp.int32(1)), tol=TOL_BF16)

    def mla_ref(ql, qr, pool, t, n, li):
        with jax.default_matmul_precision("highest"):
            return live_rows(ma._mla_decode_reference(
                *up((ql, qr, pool)), t, n, 0.07, li), n)

    tables, lengths = live_case(64, 128, 513)
    run("mla_latent_decode.cells.dead_lanes",
        fn=lambda ql, qr, pool, t, n, li: ma.mla_decode_arrays(
            ql, qr, pool, t, n, 0.07, li),
        ref_fn=mla_ref,
        args=(normal((32, 64, 512)), normal((32, 64, 64)),
              normal((513, 2, 64, 640)), tables, lengths, jnp.int32(1)),
        tol=TOL_BF16)

    # -- the decode tick's row writer at the serve cells' blocks: K and V
    # of 16 heads, and the latent row; the lanes of ``live_case`` (a
    # dead lane names the sink and writes nothing), then none live. The
    # reference is the composed loop with the sink put back: bit for bit
    def write_case(pool_shape, n_arrays, lengths):
        n_blocks, bs = pool_shape[0], pool_shape[-2]
        live = lengths > 0
        blk = np.where(live, rng.permutation(n_blocks - 1)[:32] + 1, 0)
        off = np.where(live, (lengths - 1) % bs, 0)
        row = pool_shape[2:-2] + pool_shape[-1:]
        return (tuple(normal(pool_shape) for _ in range(n_arrays)),
                tuple(normal((32,) + row) for _ in range(n_arrays)),
                blk.astype(np.int32), off.astype(np.int32), lengths,
                jnp.int32(1))

    def write_ref(pools, rows, blk, off, n, li):
        out = pw.write_rows_composed(pools, rows, blk, off, li)
        return tuple(o.at[0].set(p[0]) for o, p in zip(out, pools))

    for tag, shape, n_arrays in (("heads", (1025, 2, 16, BLOCK, 128), 2),
                                 ("latent", (513, 2, 64, 640), 1)):
        for lanes_tag, n in (("dead_lanes", lengths),
                             ("none_live", np.zeros_like(lengths))):
            run(f"pool_write_rows.cells.{tag}.{lanes_tag}",
                fn=lambda pools, rows, blk, off, n, li: pw.pool_write_rows(
                    pools, rows, blk, off, li, lanes=pw.live_lanes(n)),
                ref_fn=write_ref, args=write_case(shape, n_arrays, n),
                tol=0.0)

    # -- power retention at brumby_14b's heads: 40 query heads over 8 of
    # 128, states of (136, 9216) float32; 4 lanes of which one is dead
    # (its table names the sink), a 256-token chunk with a padded tail --
    def ret_case(T, seeded=64):
        def draw(T):
            return (normal((T, 40, 128), scale=0.3),
                    normal((T, 8, 128), scale=0.3), normal((T, 8, 128)),
                    -jnp.abs(normal((T, 8), f32, 0.5)))
        # states that a recurrence left (a normaliser is a sum of
        # squares), one a block, by the composed path
        pool = jnp.zeros((5, 2, 8, 136, 9216), f32)
        for blk in range(1, 5):
            _, pool = pr.retention_chunk(*draw(seeded), pool, blk, 1, 0,
                                         seeded, 1e-6, composed=True)
        return draw(T) + (pool,)

    lanes = jnp.asarray([3, 0, 1, 4], jnp.int32)
    run("power_retention_decode.cell.dead_lane",
        fn=lambda q, k, v, lg, pool: pr.retention_decode(
            q, k, v, lg, pool, lanes, lanes > 0, 1, 1e-6),
        ref_fn=lambda q, k, v, lg, pool: pr.retention_decode(
            q, k, v, lg, pool, lanes, lanes > 0, 1, 1e-6, composed=True),
        args=ret_case(4), tol=1e-4)
    for tag, start, n_true in (("first", 0, 256), ("padded_tail", 512, 77)):
        run("power_retention_chunk.c256." + tag,
            fn=lambda q, k, v, lg, pool, start=start, n=n_true:
            pr.retention_chunk(q, k, v, lg, pool, 2, 1, start, n, 1e-6),
            ref_fn=lambda q, k, v, lg, pool, start=start, n=n_true:
            pr.retention_chunk(q, k, v, lg, pool, 2, 1, start, n, 1e-6,
                               composed=True),
            args=ret_case(256), tol=TOL_BF16)

    # -- fused LN+MLP and add+LN (bert_base block shapes) -------------------
    H, M = 768, 3072
    x = normal((8, 512, H))
    mlp_args = (x, normal((H, M), scale=0.02), normal((M,), scale=0.02),
                normal((M, H), scale=0.02), normal((H,), scale=0.02),
                1.0 + normal((H,), f32, 0.1), normal((H,), f32, 0.1),
                normal((8, 512, H)))

    def mlp_both(mlp):
        def both(x, w1, b1, w2, b2, s, b, g):
            out, vjp = jax.vjp(mlp, x, w1, b1, w2, b2, s, b)
            return (out,) + vjp(g.astype(out.dtype))
        return both

    def mlp_ref(x, w1, b1, w2, b2, s, b):
        with jax.default_matmul_precision("highest"):
            return fk._ln_mlp_reference(x, s, b, w1, b1, w2, b2, None, None,
                                        "gelu", True, True, 1e-5)

    run("fused_ln_mlp.fwd_bwd",
        fn=mlp_both(lambda x, w1, b1, w2, b2, s, b: fk.fused_ln_mlp(
            x, w1, b1, w2, b2, ln_scale=s, ln_bias=b)),
        ref_fn=lambda *a: mlp_both(mlp_ref)(*up(a)), args=mlp_args,
        tol=TOL_BF16)

    def addln_both(addln):
        def both(x, y, s, b, g):
            out, vjp = jax.vjp(addln, x, y, s, b)
            return (out,) + vjp(g.astype(out.dtype))
        return both

    run("fused_add_layernorm.fwd_bwd",
        fn=addln_both(fk.fused_add_layernorm),
        ref_fn=lambda *a: addln_both(
            lambda x, y, s, b: fk._layer_norm_ref(x + y, s, b, 1e-5))(
                *up(a)),
        args=(x, normal((8, 512, H)), 1.0 + normal((H,), f32, 0.1),
              normal((H,), f32, 0.1), normal((8, 512, H))),
        tol=TOL_BF16)

    # -- fused optimizers: one bert_base leaf pair, odd tail ----------------
    n = H * M + H
    opt_args = (normal((n,), f32), normal((n,), f32, 0.01),
                normal((n,), f32, 0.01), jnp.abs(normal((n,), f32, 1e-4)))
    hyper = dict(b1=0.9, b2=0.999, eps=1e-8, wd=0.01)
    run("fused_adamw",
        fn=lambda p, g, m, v: fo.adamw_flat(p, g, m, v, 1e-3, 0.1, 0.001,
                                            **hyper),
        ref_fn=lambda p, g, m, v: fo._adamw_flat_ref(
            p, g, m, v, 1e-3, 0.1, 0.001, l2=0.0, eager_form=False, **hyper),
        args=opt_args, tol=TOL_F32)
    run("fused_lamb",
        fn=lambda p, g, m, v: fo.lamb_moments_flat(p, g, m, v, 0.1, 0.001,
                                                   **hyper),
        ref_fn=lambda p, g, m, v: fo._lamb_flat_ref(p, g, m, v, 0.1, 0.001,
                                                    **hyper),
        args=opt_args, tol=TOL_F32)

    # -- quantized matmuls --------------------------------------------------
    K, N = 2048, 8192                       # gpt_1p3b fc at 8 decode rows
    xq = jnp.asarray(rng.integers(-127, 128, (8, K)), jnp.int8)
    wq = jnp.asarray(rng.integers(-127, 128, (K, N)), jnp.int8)
    ws = jnp.abs(normal((N,), f32, 1e-3))
    run("int8_matmul",
        fn=lambda xq, wq, ws: i8.int8_matmul_arrays(xq, wq, ws, 0.01),
        ref_fn=lambda xq, wq, ws: i8._int8_matmul_ref(
            xq, wq, ws, jnp.float32(0.01), None, f32),
        args=(xq, wq, ws), tol=TOL_F32)
    e4 = jnp.float8_e4m3fn
    run("fp8_matmul",                       # bert_base fc at 512 rows
        fn=lambda a, b: f8.fp8_matmul_arrays(a, b, 0.5, 0.25),
        ref_fn=lambda a, b: f8._fp8_matmul_ref(
            a, b, jnp.float32(0.5), jnp.float32(0.25), None, f32),
        args=(normal((512, H), f32).astype(e4),
              normal((H, M), f32).astype(e4)), tol=TOL_F32)

    # -- MoE dispatch: 8 experts, top-2, hidden 512, 1024 tokens ------------
    T, Hm, slots = 1024, 512, 8 * 320
    src = rng.integers(-1, T, slots).astype(np.int32)
    run("moe_dispatch_gather", fn=md.moe_dispatch_gather,
        ref_fn=md._gather_reference, args=(normal((T, Hm)), src), tol=0.0)

    check(fallbacks() == fb0, "kernels: a kernel entry fell back to jnp")
    return clock, {"census": census, "families": len(census)}


# --------------------------------------------------------------------------
# train4
# --------------------------------------------------------------------------

def flash_call_shapes(hlo):
    """Operand shapes of every Mosaic call in a compiled module."""
    shapes = []
    for line in hlo.splitlines():
        if "custom-call(" in line and MOSAIC in line:
            shapes.append(re.findall(r"(?:bf16|f32)\[([\d,]+)\]", line))
    return shapes


def phase_train4(one_chip_loss, cfg=None, batch_size=32):
    import jax
    import numpy as np

    from paddle_tpu.models import bert_base_config
    from paddle_tpu.parallel import create_mesh

    clock = Clock()
    cfg = cfg or bert_base_config()
    batch = seeded_batch(cfg, batch_size)
    devs = jax.devices()[:4]
    out = {}
    for tag, dims in (("dp4", dict(dp=4)),
                      ("sh2_mp2", dict(dp=1, sharding=2, pp=1, mp=2))):
        mesh = create_mesh(devices=devs, **dims)
        step = build_step(cfg, mesh, zero=True)
        hlo = compile_step(step, batch, clock)
        with clock.compiling():
            loss = float(step(batch))
        with clock.running():
            loss2 = float(step(batch))
        check(np.isfinite(loss) and np.isfinite(loss2) and loss2 < loss,
              f"train4[{tag}]: losses {loss}, {loss2}")
        diff = abs(loss - one_chip_loss)
        check(diff <= TOL_LOSS_MESH,
              f"train4[{tag}]: first loss {loss} vs {one_chip_loss} on one "
              f"chip (|diff| {diff} > {TOL_LOSS_MESH})")

        leaves = jax.tree_util.tree_leaves(step.params)
        check(all(len(x.sharding.device_set) == 4 for x in leaves),
              f"train4[{tag}]: a parameter does not live on four devices")
        qkv = step.params["blocks"]["qkv_w"]
        m_qkv = step.opt_state["m"]["blocks"]["qkv_w"]
        shard = lambda a: a.addressable_shards[0].data.size  # noqa: E731
        mp, sh = mesh.shape["model"], mesh.shape["sharding"]
        check(shard(qkv) * mp == qkv.size,
              f"train4[{tag}]: qkv_w shard is not 1/{mp} of the leaf")
        check(shard(m_qkv) * mp * sh == m_qkv.size,
              f"train4[{tag}]: AdamW moment shard is not 1/{mp * sh}")

        mem = [d.memory_stats()["bytes_in_use"] for d in devs]
        check(max(mem) <= 2 * min(mem),
              f"train4[{tag}]: device memory is lopsided: {mem}")
        colls = {c: len(re.findall(r"\b%s(?:-start)?\(" % c, hlo))
                 for c in ("all-reduce", "all-gather", "reduce-scatter",
                           "all-to-all", "collective-permute")}
        check(colls["all-reduce"] > 0,
              f"train4[{tag}]: no all-reduce in the compiled step")
        if sh > 1:
            check(colls["all-gather"] > 0,
                  f"train4[{tag}]: ZeRO-sharded update but no all-gather")
        # the flash kernel must see its device's shard: (batch / (dp*sh))
        # * (heads / mp) rows, not the global batch * heads
        rows = (batch_size // (mesh.shape["data"] * sh)) \
            * (cfg.n_heads // mp)
        calls = flash_call_shapes(hlo)
        check(calls, f"train4[{tag}]: no Mosaic call in the compiled step")
        want = f"{rows},{cfg.seq_len},{cfg.head_dim}"
        whole = f"{batch_size * cfg.n_heads},{cfg.seq_len},{cfg.head_dim}"
        check(all(want in shapes and whole not in shapes
                  for shapes in calls),
              f"train4[{tag}]: a flash call's operands are not the "
              f"per-device shard [{want}]: {calls}")
        out[tag] = {"loss": round(loss, 4), "loss_diff_vs_one_chip":
                    round(diff, 5), "bytes_in_use": mem,
                    "qkv_w_shard": list(qkv.addressable_shards[0].data.shape),
                    "adam_m_qkv_shard":
                        list(m_qkv.addressable_shards[0].data.shape),
                    "collectives": colls, "flash_operand_rows": rows}
        del step
        gc.collect()
    out["tol"] = TOL_LOSS_MESH
    return clock, out


# --------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma list out of " + ",".join(PHASES))
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = set(phases) - set(PHASES)
    if unknown:
        ap.error(f"unknown phase(s) {sorted(unknown)}")
    if "train4" in phases and "train" not in phases[:phases.index("train4")]:
        ap.error("train4 compares with the one-chip loss: put train first")

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.stderr.write(
            f"chip_smoke: needs a TPU, but jax found platform "
            f"{dev.platform!r} ({dev.device_kind}); nothing was run\n")
        return 2

    from paddle_tpu.core import native
    from paddle_tpu.device import enable_compile_cache

    t_start = time.perf_counter()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(f"chip_smoke: platform={dev.platform} device_kind="
          f"{dev.device_kind!r} devices={device['count']} "
          f"jax={jax.__version__}")
    print(f"chip_smoke: native core loaded={native.NATIVE_AVAILABLE} "
          f"compile cache={enable_compile_cache()}", flush=True)

    report = {}
    one_chip_loss = None
    for name in phases:
        if name == "train4" and device["count"] < 4:
            print(f"[train4] not run: {device['count']} device(s), needs 4")
            report[name] = {"status": "not run", "devices": device["count"]}
            continue
        t0 = time.perf_counter()
        if name == "train4":
            clock, detail = phase_train4(one_chip_loss)
        else:
            clock, detail = {"train": phase_train, "serve": phase_serve,
                             "kernels": phase_kernels}[name]()
        if name == "train":
            one_chip_loss = detail["losses"][0]
        wall = time.perf_counter() - t0
        report[name] = {"status": "pass", "wall_s": round(wall, 1),
                        "compile_s": round(clock.compile_s, 1),
                        "run_s": round(clock.run_s, 1), **detail}
        print(f"[{name}] pass  wall {wall:.1f}s  compile "
              f"{clock.compile_s:.1f}s  run {clock.run_s:.1f}s", flush=True)

    print("chip_smoke report: " + json.dumps(
        {"phases": report, "total_s": round(time.perf_counter() - t_start, 1),
         "jax": jax.__version__, "native_core": native.NATIVE_AVAILABLE}))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
