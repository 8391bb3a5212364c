"""ISSUE 26 — the program read off its own trace.

- every scheduler turn of the engine is one span tree: the children
  nest in their ``serving.turn`` and carry its ``tick``;
- a request submitted WITHOUT a front end still yields its chain
  (``serving.queue_wait`` -> ``serving.admit_to_first`` ->
  ``serving.request_done``) under one ``rid``, and ``request_report``
  reads it;
- tracing off records nothing, and greedy tokens are identical on or off;
- ``monitor.trace.op_scopes`` labels forward, backward and optimizer on a
  CPU-compiled step; a traced ``DistributedTrainStep`` emits the table
  once at ``stop_tracing()``; a failing ``on_stop`` callback never raises;
- ``span()`` puts a ``jax.profiler.TraceAnnotation`` beside its event;
- ``serving_prefill_chunks`` is registered and incremented, a decode
  tick counts its live and its tabled blocks, and graftlint GL005/GL006
  stay clean.
"""
import collections
import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle  # noqa: F401 — jax/mesh bootstrap
from paddle_tpu import monitor
from paddle_tpu.models import gpt_init, gpt_loss, gpt_param_specs, gpt_tiny
from paddle_tpu.monitor import trace
from paddle_tpu.parallel import DistributedTrainStep, create_mesh
from paddle_tpu.serving import InferenceEngine

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CFG = gpt_tiny(dtype=jnp.float32, seq_len=128)
PARAMS = gpt_init(CFG, seed=26)
TURN = "serving.turn"
CHILDREN = {"serving.admit", "serving.prefill_chunk", "serving.first_token",
            "serving.decode_prep", "serving.decode_step", "serving.emit"}
CHAIN = ["serving.queue_wait", "serving.admit_to_first",
         "serving.request_done"]


def _prompt(n, seed):
    return np.random.default_rng(seed).integers(
        0, CFG.vocab_size, n).astype(np.int32)


@pytest.fixture(autouse=True)
def _clean():
    yield
    monitor.stop_tracing()


@pytest.fixture
def engine():
    engines = []

    def make(**kw):
        kw.setdefault("n_slots", 2)
        kw.setdefault("block_size", 8)
        kw.setdefault("prefill_chunk", 16)
        kw.setdefault("seed", 0)
        eng = InferenceEngine(CFG, PARAMS, **kw)
        engines.append(eng)
        return eng

    yield make
    for eng in engines:
        eng.shutdown(drain=False, timeout=30)


def _traced_run(eng, lengths=(40, 9, 33), new=5, sampling=()):
    """Run a few requests under tracing; returns the serving events.
    ``sampling``: submit arguments for the first requests (the rest are
    greedy)."""
    writer = monitor.start_tracing()
    try:
        reqs = [eng.submit(_prompt(n, i), max_new_tokens=new,
                           **(sampling[i] if i < len(sampling) else {}))
                for i, n in enumerate(lengths)]
        toks = [r.result(timeout=120) for r in reqs]
        time.sleep(0.05)    # let the scheduler close the last turn's span
    finally:
        monitor.stop_tracing()
    return [e for e in writer.events()
            if e.get("name", "").startswith("serving.")], reqs, toks


def _serving_report(events):
    """``tools/trace_report.py --section serving`` over ``events``."""
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(_ROOT, "tools", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.devnull, "w") as sink:
        return mod.serving_report(mod.aggregate(events), file=sink,
                                  events=events)


class TestTurnTree:
    @pytest.fixture(scope="class")
    def events(self):
        eng = InferenceEngine(CFG, PARAMS, n_slots=2, block_size=8,
                              prefill_chunk=16, seed=0)
        try:
            eng.submit(_prompt(20, 9), max_new_tokens=2).result(timeout=120)
            evs, _, _ = _traced_run(eng)
        finally:
            eng.shutdown(drain=False, timeout=30)
        return evs

    def test_every_kind_of_child_appears(self, events):
        names = {e["name"] for e in events}
        assert CHILDREN | {TURN, "serving.device_wait"} <= names

    @pytest.mark.parametrize("child", sorted(CHILDREN))
    def test_children_nest_in_their_turn_and_share_its_tick(self, events,
                                                            child):
        turns = {e["args"]["tick"]: e for e in events if e["name"] == TURN}
        assert len(turns) >= 3
        kids = [e for e in events if e["name"] == child]
        assert kids
        for e in kids:
            turn = turns[e["args"]["tick"]]
            # microsecond stamps are truncated: allow one on either side
            assert turn["ts"] - 1 <= e["ts"]
            assert e["ts"] + e["dur"] <= turn["ts"] + turn["dur"] + 1

    def test_device_wait_nests_in_decode_step(self, events):
        """Every tick is read once, by one ``serving.device_wait``. A
        turn that dispatches a tick reads inside its
        ``serving.decode_step`` (the tick before, when that one was
        still in flight); a turn that dispatches none, because its lanes'
        last tokens were in flight, reads in the turn itself."""
        turns = {e["args"]["tick"]: e for e in events if e["name"] == TURN}
        steps = {e["args"]["tick"]: e for e in events
                 if e["name"] == "serving.decode_step"}
        waits = [e for e in events if e["name"] == "serving.device_wait"]
        assert waits and len(waits) == len(steps)
        for e in waits:
            outer = steps.get(e["args"]["tick"], turns[e["args"]["tick"]])
            assert outer["ts"] - 1 <= e["ts"]
            assert e["ts"] + e["dur"] <= outer["ts"] + outer["dur"] + 1
        assert {s["args"]["ahead"] for s in steps.values()} == {0, 1}

    def test_turn_children_do_not_overlap(self, events):
        by_tick = collections.defaultdict(list)
        for e in events:
            if e["name"] in CHILDREN:
                by_tick[e["args"]["tick"]].append(e)
        for kids in by_tick.values():
            kids.sort(key=lambda e: e["ts"])
            for a, b in zip(kids, kids[1:]):
                assert a["ts"] + a["dur"] <= b["ts"] + 1


class TestRequestChain:
    def test_chain_under_one_rid_without_a_front_end(self, engine):
        events, reqs, _ = _traced_run(engine())
        for req in reqs:
            assert req.trace is None
            mine = sorted((e for e in events if e["name"] in CHAIN
                           and e["args"].get("rid") == req.rid),
                          key=lambda e: e["ts"])
            assert [e["name"] for e in mine] == CHAIN
            wait, first, done = mine
            assert "trace" not in first["args"]
            # submit -> admit ends where admit -> first token starts
            assert abs(wait["ts"] + wait["dur"] - first["ts"]) < 5e3
            assert first["ts"] + first["dur"] <= done["ts"] + 1
            assert first["args"]["chunks"] == -(-req.prompt.size // 16)
            assert done["args"]["tokens"] == 5
            assert done["args"]["reason"] == "length"

    def test_traced_request_keeps_its_trace_ids_on_the_chain(self, engine):
        eng = engine()
        ctx = monitor.mint_trace()
        writer = monitor.start_tracing()
        try:
            eng.submit(_prompt(20, 3), max_new_tokens=3,
                       trace=ctx).result(timeout=120)
        finally:
            monitor.stop_tracing()
        mine = [e for e in writer.events() if e.get("name") in CHAIN]
        assert [e["name"] for e in sorted(mine, key=lambda e: e["ts"])] \
            == CHAIN
        assert all(e["args"]["trace"] == ctx.trace_id for e in mine)

    def test_request_report_reads_the_rid_chain(self, engine):
        events, reqs, _ = _traced_run(engine())
        spec = importlib.util.spec_from_file_location(
            "trace_report", os.path.join(_ROOT, "tools", "trace_report.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        with open(os.devnull, "w") as sink:
            out = mod.request_report(events, file=sink, top=10)
        assert out["requests"] == len(reqs) == out["completed"]
        rows = {r["rid"]: r for r in out["slowest"]}
        assert set(rows) == {r.rid for r in reqs}
        for r in rows.values():
            assert r["trace"] is None and r["finish"] == "length"
            assert r["admit_to_first_ms"] > 0
            assert abs(r["lane_wait_ms"] + r["prefill_ms"] + r["decode_ms"]
                       - r["total_ms"]) < 0.01


class TestOffPath:
    def test_tracing_off_records_nothing(self, engine):
        writer = monitor.get_writer()
        writer.clear()
        engine().submit(_prompt(20, 1), max_new_tokens=4).result(timeout=120)
        assert len(writer) == 0

    def test_greedy_tokens_identical_on_and_off(self, engine):
        prompts = (40, 9, 33)
        base = [engine().submit(
            _prompt(n, i), max_new_tokens=5).result(timeout=120)
            for i, n in enumerate(prompts)]
        _, _, traced = _traced_run(engine(), lengths=prompts)
        assert traced == base

    def test_off_path_builds_no_args(self, engine):
        eng = engine()
        assert eng._tick_args() is None and eng._tick_args(rid=1) is None


class TestChunkCounter:
    def test_registered_and_incremented(self, engine):
        assert "serving_prefill_chunks" in monitor.DEFAULT_STATS
        before = monitor.stat_get("serving_prefill_chunks")
        engine().submit(_prompt(40, 2), max_new_tokens=2).result(timeout=120)
        assert monitor.stat_get("serving_prefill_chunks") - before == 3

    def test_decode_blocks_counted_on_the_span_and_in_the_registry(
            self, engine):
        """Each decode tick says how many of its tabled blocks are
        live: the span's two arguments, the two counters by the same
        amounts, and the serving report's share."""
        names = ("serving_decode_blocks_live",
                 "serving_decode_blocks_tabled")
        assert set(names) <= set(monitor.DEFAULT_STATS)
        before = [monitor.stat_get(n) for n in names]
        eng = engine(n_slots=4)
        events, _, _ = _traced_run(eng, lengths=(40, 9), new=4)
        ticks = [e["args"] for e in events
                 if e["name"] == "serving.decode_step"]
        assert ticks
        for a in ticks:
            # block 8: a slot of n tokens holds ceil(n / 8) blocks; the
            # tabled width is a power of two times all four slots
            assert 1 <= a["decode_blocks_live"] <= a["batch"] * 6
            width = a["decode_blocks_tabled"] // 4
            assert a["decode_blocks_tabled"] == 4 * width
            assert width & (width - 1) == 0
            assert a["decode_blocks_live"] <= a["batch"] * width
        after = [monitor.stat_get(n) for n in names]
        assert after[0] - before[0] == sum(
            a["decode_blocks_live"] for a in ticks)
        assert after[1] - before[1] == sum(
            a["decode_blocks_tabled"] for a in ticks)
        out = _serving_report(events)
        assert out["decode_blocks_live_share"] == pytest.approx(
            (after[0] - before[0]) / (after[1] - before[1]))

    def test_rows_written_counted_on_the_span_and_in_the_registry(
            self, engine):
        """Each decode tick says how many rows its writer puts into the
        paged pool (active lanes x layers) beside what a grid over every
        lane would put (slots x layers): the span's two arguments, the
        counter by the first, and the serving report's means a tick."""
        assert "serving_kv_rows_written" in monitor.DEFAULT_STATS
        before = monitor.stat_get("serving_kv_rows_written")
        eng = engine(n_slots=4)
        layers = eng.cache.pool[0].shape[1]
        events, _, _ = _traced_run(eng, lengths=(40, 9), new=4)
        ticks = [e["args"] for e in events
                 if e["name"] == "serving.decode_step"]
        assert ticks
        for a in ticks:
            assert a["kv_rows_written"] == a["batch"] * layers
            assert a["kv_rows_grid"] == 4 * layers
        written = sum(a["kv_rows_written"] for a in ticks)
        assert monitor.stat_get("serving_kv_rows_written") - before == written
        out = _serving_report(events)
        assert out["kv_rows_written_a_tick"] == pytest.approx(
            written / len(ticks))
        assert out["kv_rows_grid_a_tick"] == 4 * layers

    @pytest.mark.parametrize("sampling, paths", [
        ((), {"greedy"}),
        (({"temperature": 0.8, "top_k": 40, "top_p": 0.95},),
         {"greedy", "select"}),
        (({"temperature": 0.8, "top_p": 0.9}, {"temperature": 0.8,
                                               "top_k": 40}),
         {"greedy", "select", "sort"}),
    ])
    def test_sample_paths_counted_on_the_span_and_in_the_registry(
            self, engine, sampling, paths):
        """Each decode tick says which way its sampling goes (greedy,
        select or sort, by its rows' parameters): the span's argument,
        one of three counters, and the serving report's shares. The
        sampled requests are the short ones: once they leave, the tail
        of the run is greedy again."""
        order = ("greedy", "select", "sort")
        names = [f"serving_sample_ticks_{p}" for p in order]
        assert set(names) <= set(monitor.DEFAULT_STATS)
        before = [monitor.stat_get(n) for n in names]
        events, _, _ = _traced_run(engine(n_slots=4), lengths=(9, 12, 40),
                                   new=6, sampling=sampling)
        ticks = [e["args"]["sample_path"] for e in events
                 if e["name"] == "serving.decode_step"]
        counted = [monitor.stat_get(n) - b for n, b in zip(names, before)]
        assert counted == [ticks.count(p) for p in order]
        assert sum(counted) == len(ticks) > 0
        assert set(ticks) <= paths
        assert max(paths, key=order.index) in ticks
        out = _serving_report(events)
        for p, n in zip(order, counted):
            assert out[f"sample_ticks_{p}"] == n
            assert out[f"sample_{p}_share"] == pytest.approx(n / len(ticks))

    def test_ticks_ahead_synced_and_discarded_on_the_span_and_in_the_registry(
            self, engine):
        """Each decode tick says whether it left with the tick before
        still unread (the span's ``ahead``), and a tick whose lane
        results were thrown away says how many: three counters by the
        same amounts, and the serving report's three numbers."""
        names = ("serving_decode_ticks_ahead", "serving_decode_ticks_synced",
                 "serving_decode_lanes_discarded")
        assert set(names) <= set(monitor.DEFAULT_STATS)
        before = [monitor.stat_get(n) for n in names]
        events, _, _ = _traced_run(engine(n_slots=4), lengths=(9, 12, 40),
                                   new=6)
        ticks = [e["args"] for e in events
                 if e["name"] == "serving.decode_step"]
        counted = [monitor.stat_get(n) - b for n, b in zip(names, before)]
        assert counted[0] == sum(a["ahead"] for a in ticks) > 0
        assert counted[1] == len(ticks) - counted[0] >= 1
        assert counted[2] == sum(a.get("lanes_discarded", 0)
                                 for a in ticks) == 0
        out = _serving_report(events)
        assert [out["decode_ticks_ahead"], out["decode_ticks_synced"],
                out["decode_lanes_discarded"]] == counted

    def test_expert_reads_and_kernel_tiles_in_the_serving_report(self):
        """A model with an expert layer that holds a share of its experts:
        the report sums the held experts read and the grouped kernel's
        row tiles by kind of step, and gives their ratio, the weights'
        re-read factor (0 where the kernel did not run)."""
        def span(name, tick, reads, tiles):
            return {"name": name, "ph": "X", "ts": tick, "dur": 1,
                    "args": {"tick": tick, "moe_expert_reads": reads,
                             "moe_kernel_tiles": tiles}}
        events = [span("serving.prefill_chunk", 1, 150, 162),
                  span("serving.prefill_chunk", 2, 160, 170),
                  span("serving.decode_step", 3, 40, 40),
                  span("serving.decode_step", 4, 0, 0)]
        out = _serving_report(events)
        assert (out["chunk_moe_expert_reads"], out["chunk_moe_kernel_tiles"],
                out["decode_moe_expert_reads"],
                out["decode_moe_kernel_tiles"]) == (310, 332, 40, 40)
        assert out["chunk_moe_reread"] == pytest.approx(332 / 310)
        assert out["decode_moe_reread"] == 1.0
        off = _serving_report([span("serving.decode_step", 1, 12, 0)])
        assert off["decode_moe_reread"] == 0.0
        assert "chunk_moe_expert_reads" not in off
        # a model with no expert layer: nothing of the kind
        plain = _serving_report([dict(span("serving.decode_step", 1, 0, 0),
                                      args={"tick": 1})])
        assert not any(k.startswith(("decode_moe", "chunk_moe"))
                       for k in plain)

    def test_graftlint_gauges_clean(self):
        from paddle_tpu.analysis import run_lint

        findings = [f for f in run_lint([os.path.join(_ROOT, "paddle_tpu")])
                    if f.rule in ("GL005", "GL006")]
        assert findings == [], [f.format() for f in findings]

    def test_histogram_help_says_what_paged_mode_times(self):
        from paddle_tpu.monitor.stats import HISTOGRAM_HELP

        assert "DISPATCH" in HISTOGRAM_HELP["serving_prefill_chunk_ms"]
        assert "queued ahead" in HISTOGRAM_HELP["serving_decode_tick_ms"]


# -- op_scopes / on_stop / TraceAnnotation ----------------------------------

def _tiny_step(**kw):
    cfg = gpt_tiny()
    mesh = create_mesh(devices=jax.devices()[:1])
    step = DistributedTrainStep(
        lambda p, b: gpt_loss(cfg, p, b), gpt_init(cfg, 0),
        gpt_param_specs(cfg), optimizer="adamw", lr=1e-3, mesh=mesh, **kw)
    tok = np.zeros((2, 32), np.int32)
    return step, (tok, tok)


class TestOpScopes:
    @pytest.mark.parametrize("op_name, want", [
        ("jit(step)/jvp(mlp)/dot_general", "forward/mlp"),
        ("jit(step)/transpose(jvp(mlp))/dot_general", "backward/mlp"),
        ("jit(step)/transpose(jvp())/closed_call/checkpoint/"
         "rematted_computation/mlp/ln/mul", "backward/ln"),
        ("jit(step)/jvp(head_loss)/jit(log_softmax)/reduce_max",
         "forward/head_loss"),
        ("jit(step)/optimizer/grad_clip/mul", "optimizer/grad_clip"),
        ("jit(step)/optimizer/mul", "optimizer"),
        ("jit(step)/jvp(attn)/bhqd,bhkd->bhqk/dot_general", "forward/attn"),
        ("jit(step)/jvp(attn)/jit(_flash_forward)/flash_forward/"
         "pallas_call", "forward/flash_forward"),
        ("jit(step)/while/body/cond/branch_1_fun/add", "forward"),
    ])
    def test_label_of_an_op_name(self, op_name, want):
        hlo = (f'  %fusion.7 = f32[2]{{0}} fusion(%p), kind=kLoop, '
               f'metadata={{op_name="{op_name}" source_line=3}}\n')
        assert trace.op_scopes(hlo)["fusion.7"] == want

    def test_no_metadata_takes_its_first_users_label(self):
        hlo = ('  %f.1 = f32[2] fusion(%p.0), metadata={op_name='
               '"jit(s)/transpose(jvp(mlp))/mul"}\n'
               '  %copy-start.3 = (f32[2], f32[2]) copy-start(%f.1)\n'
               '  %copy-done.3 = f32[2] copy-done(%copy-start.3)\n'
               '  %lone.9 = f32[2] copy(%p.0)\n'
               '  ROOT %add.5 = f32[2] add(%copy-done.3, %f.1), '
               'metadata={op_name="jit(s)/optimizer/add"}\n')
        table = trace.op_scopes(hlo)
        assert table["copy-start.3"] == table["copy-done.3"] == "optimizer"
        assert table["f.1"] == "backward/mlp"
        assert "lone.9" not in table

    def test_cpu_compiled_step_has_all_three_phases(self):
        step, batch = _tiny_step(clip_norm=1.0)
        table = trace.op_scopes(step.lower(batch).compile().as_text())
        labels = collections.Counter(table.values())
        phases = {v.split("/")[0] for v in labels}
        assert phases == {"forward", "backward", "optimizer"}
        for want in ("forward/attn", "forward/mlp", "forward/ln",
                     "forward/embed", "forward/head_loss", "backward/attn",
                     "backward/mlp", "optimizer/grad_clip", "optimizer"):
            assert labels[want] > 0, (want, labels)

    def test_loss_scale_scope_sits_under_the_optimizer(self):
        step, batch = _tiny_step(dynamic_scale={"init_scale": 8.0})
        table = trace.op_scopes(step.lower(batch).compile().as_text())
        assert "optimizer/loss_scale" in set(table.values())

    @pytest.mark.parametrize("fn, scope", [
        ("_decode_paged_fn", "kv_pool"), ("_decode_paged_fn", "sampling"),
        ("_decode_paged_fn", "attn"), ("_chunk_fn", "kv_pool"),
        ("_chunk_fn", "mlp"), ("_chunk_fn", "embed")])
    def test_engine_programs_carry_scopes(self, engine, fn, scope):
        eng = engine()
        i32 = np.zeros(eng.n_slots, np.int32)
        if fn == "_decode_paged_fn":
            low = eng._program("decode", 4)[0].lower(
                eng._decode_params, eng.cache.kb, eng.cache.vb,
                np.zeros((eng.n_slots, 4), np.int32), i32, i32,
                eng._prev_toks, np.zeros(eng.n_slots, bool),
                eng._base_key, i32, i32, np.zeros(eng.n_slots, np.float32),
                i32, np.ones(eng.n_slots, np.float32), eng._mask_dev)
        else:
            low = eng._program("chunk", 16, 4)[0].lower(
                eng._params, eng.cache.kb, eng.cache.vb,
                np.zeros(4, np.int32), np.zeros((1, 16), np.int32),
                np.int32(0), np.int32(16))
        labels = set(trace.op_scopes(low.compile().as_text()).values())
        assert "forward/" + scope in labels, labels


class TestOnStop:
    def test_traced_step_emits_the_table_once(self):
        step, batch = _tiny_step()
        jax.block_until_ready(step(batch))
        writer = monitor.start_tracing()
        loss = step(batch)
        loss = step(batch)
        jax.block_until_ready(loss)
        monitor.stop_tracing()
        tables = [e for e in writer.events() if e["ph"] == "M"]
        assert len(tables) == 1 and tables[0]["name"] == "op_scopes"
        assert tables[0]["args"]["program"] == "jit_step"
        phases = {v.split("/")[0]
                  for v in tables[0]["args"]["scopes"].values()}
        assert phases == {"forward", "backward", "optimizer"}
        spans = [e for e in writer.events()
                 if e["name"] == "DistributedTrainStep.step"]
        assert len(spans) == 2
        # a second stop has nothing left to run; the next window asks again
        monitor.stop_tracing()
        assert len([e for e in writer.events() if e["ph"] == "M"]) == 1
        writer = monitor.start_tracing()
        jax.block_until_ready(step(batch))
        monitor.stop_tracing()
        assert len([e for e in writer.events() if e["ph"] == "M"]) == 1

    def test_the_table_costs_no_compile(self):
        import jax.monitoring

        step, batch = _tiny_step()
        jax.block_until_ready(step(batch))
        seen = []
        jax.monitoring.register_event_duration_secs_listener(
            lambda name, *a, **kw: seen.append(kw.get("fun_name"))
            if name.endswith("backend_compile_duration") else None)
        monitor.start_tracing()
        jax.block_until_ready(step(batch))
        mark = len(seen)
        monitor.stop_tracing()
        assert seen[mark:] == []

    def test_untraced_step_registers_nothing(self):
        step, batch = _tiny_step()
        jax.block_until_ready(step(batch))
        assert step._traced_batch is None
        writer = monitor.start_tracing()
        monitor.stop_tracing()
        assert [e for e in writer.events() if e["ph"] == "M"] == []

    def test_failing_callback_does_not_raise(self):
        ran = []

        def boom(writer):
            raise ValueError("no table today")

        writer = monitor.start_tracing()
        trace.on_stop(boom)
        trace.on_stop(lambda w: ran.append(w))
        assert monitor.stop_tracing() is writer      # nothing raised
        assert ran == [writer]
        notes = [e for e in writer.events() if e["ph"] == "i"]
        assert len(notes) == 1
        assert "ValueError" in notes[0]["name"]
        assert "no table today" in notes[0]["name"]
        assert not trace.is_tracing()

    def test_callback_runs_after_the_gate_is_off(self):
        seen = []
        monitor.start_tracing()
        trace.on_stop(lambda w: seen.append(trace.is_tracing()))
        monitor.stop_tracing()
        assert seen == [False]


class TestAnnotation:
    def test_span_enters_a_trace_annotation_only_when_tracing(self,
                                                               monkeypatch):
        entered = []

        class Note:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                entered.append(self.name)

            def __exit__(self, *exc):
                entered.append("/" + self.name)

        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Note)
        with trace.span("quiet"):
            pass
        assert entered == []
        writer = monitor.start_tracing()
        with trace.span("loud"):
            entered.append("body")
        monitor.stop_tracing()
        assert entered == ["loud", "body", "/loud"]
        assert [e["name"] for e in writer.events()] == ["loud"]

    def test_pallas_kernels_are_named(self):
        """Every ``pallas_call`` under ops/ bears a ``name=``, and the
        names the benchmark's roofline patterns look for are the ones
        those patterns match."""
        import ast
        import glob
        import re

        names = []
        for path in glob.glob(os.path.join(_ROOT, "paddle_tpu", "ops",
                                           "*.py")):
            with open(path) as f:
                tree = ast.parse(f.read())
            for node in ast.walk(tree):
                if isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "pallas_call":
                    kw = {k.arg: k.value for k in node.keywords}
                    assert "name" in kw, (path, node.lineno)
                    names.append(kw["name"].value)
        assert len(names) == 21 and len(set(names)) == 21
        for pattern, kernel in (("flash_forward", "flash_forward"),
                                ("ragged-dot(?!-metadata)",
                                 "ragged-dot-experts"),
                                ("flash_backward", "flash_backward"),
                                ("_paged_decode", "pallas_paged_decode"),
                                ("pool_write", "pool_write_rows"),
                                ("power_retention_decode",
                                 "power_retention_decode"),
                                ("power_retention_chunk",
                                 "power_retention_chunk"),
                                ("hybrid_diff_decode", "hybrid_diff_decode"),
                                ("selective_scan_chunk",
                                 "selective_scan_chunk")):
            assert [n for n in names if re.search(pattern, n)] == [kernel]


# -- the engine's own tables, and its idle -----------------------------------

def _mla():
    from paddle_tpu.models import mla_tiny
    from paddle_tpu.models.mla import mla_init

    cfg = mla_tiny()
    return cfg, mla_init(cfg, 0), {"n_blocks": 24, "prefix_cache": False}


def _retention():
    from paddle_tpu.models import retention_tiny
    from paddle_tpu.models.retention import retention_init

    cfg = retention_tiny()
    return cfg, retention_init(cfg, 0), {"n_blocks": 3, "prefill_chunk": 128}


MODELS = {"gpt": lambda: (CFG, PARAMS, {}), "mla": _mla,
          "retention": _retention}
# what each model's programs must label, beside forward/sampling
LABELS = {"gpt": {"forward/attn", "forward/mlp", "forward/kv_pool"},
          "mla": {"forward/attn", "forward/mlp", "forward/kv_pool",
                  "forward/router", "forward/experts"},
          "retention": {"forward/retention", "forward/mlp"}}


def _serve(eng, lengths=(20, 9), new=4):
    rng = np.random.default_rng(39)
    for n in lengths:
        eng.submit(rng.integers(0, eng.cfg.vocab_size, n).astype(np.int32),
                   max_new_tokens=new).result(timeout=300)


def _tables(events):
    return [e["args"] for e in events if e.get("ph") == "M"
            and e["name"] == "op_scopes"]


class TestEngineTables:
    """A traced window of a tiny engine of each model: one ``op_scopes``
    table per (program, signature) it ran, written at ``stop_tracing()``
    with no compiler run; nothing kept or registered with tracing off or
    with only the flight recorder armed; a second window asks again."""

    @pytest.fixture(scope="class", params=sorted(MODELS))
    def window(self, request):
        import jax.monitoring

        from paddle_tpu.monitor.flight import (arm_flight_recorder,
                                               disarm_flight_recorder)

        cfg, params, kw = MODELS[request.param]()
        kw = {"n_slots": 2, "block_size": 8, "prefill_chunk": 16,
              "seed": 0, **kw}
        eng = InferenceEngine(cfg, params, **kw)
        compiles = []

        def listen(name, *a, **k):
            if name.endswith("backend_compile_duration"):
                compiles.append(name)

        jax.monitoring.register_event_duration_secs_listener(listen)
        try:
            _serve(eng)                               # every program, once
            off = [(dict(eng._programs._kept), list(trace._on_stop))]
            arm_flight_recorder()
            try:
                _serve(eng)
            finally:
                disarm_flight_recorder()
            off.append((dict(eng._programs._kept), list(trace._on_stop)))
            runs = []
            for _ in range(2):
                writer = monitor.start_tracing()
                _serve(eng)
                mark = len(compiles)
                monitor.stop_tracing()
                runs.append((writer.events(), compiles[mark:]))
        finally:
            jax.monitoring.unregister_event_duration_listener(listen)
            eng.shutdown(drain=False, timeout=30)
        return {"kind": request.param, "off": off, "runs": runs,
                "bs": eng.block_size, "slots": eng.n_slots}

    def test_one_table_per_program_and_signature_it_ran(self, window):
        events, _ = window["runs"][0]
        got = [(t["program"], t["signature"]) for t in _tables(events)]
        assert len(got) == len(set(got))
        # a jit a signature, named for it: the profiler's name of its runs
        by = {}
        for program, sig in got:
            assert program.endswith("_" + sig)
            by.setdefault(program[:-len(sig) - 1], set()).add(sig)
        assert set(by) == {"jit__decode_paged_fn", "jit__chunk_fn"}
        widths = {e["args"]["decode_blocks_tabled"] // window["slots"]
                  for e in events if e["name"] == "serving.decode_step"}
        assert by["jit__decode_paged_fn"] == {f"w{w}" for w in widths}
        bs = window["bs"]
        chunks = {-(-e["args"]["chunk"] // bs) * bs for e in events
                  if e["name"] == "serving.prefill_chunk"}
        assert {int(s[1:].split("_")[0])
                for s in by["jit__chunk_fn"]} == chunks

    def test_labels_are_the_models_scopes(self, window):
        events, _ = window["runs"][0]
        labels = set().union(*(t["scopes"].values()
                               for t in _tables(events)))
        assert LABELS[window["kind"]] | {"forward/sampling"} <= labels
        # a kernel's own name never stands in for its caller's scope
        assert {lab.split("/", 1)[-1] for lab in labels if "/" in lab} \
            <= {"embed", "ln", "attn", "kv_pool", "retention", "mlp",
                "router", "experts", "head", "sampling"}

    def test_no_compile_while_the_tables_are_made(self, window):
        assert [c for _, c in window["runs"]] == [[], []]

    @pytest.mark.parametrize("how", ["tracing_off", "flight_only"])
    def test_nothing_kept_or_registered_untraced(self, window, how):
        kept, callbacks = window["off"][how == "flight_only"]
        assert kept == {} and callbacks == []

    def test_a_second_window_asks_again(self, window):
        (first, _), (second, _) = window["runs"]
        tables = [sorted((t["program"], t["signature"])
                         for t in _tables(evs)) for evs in (first, second)]
        assert tables[0] == tables[1] and tables[0]
        for evs in (first, second):
            eng = [e["args"] for e in evs if e.get("ph") == "M"
                   and e["name"] == "serving_engine"]
            assert len(eng) == 1 and eng[0]["tables"] == len(tables[0])
            assert "serving.idle" in eng[0]["spans"]
            assert 0 < eng[0]["seconds"] < 60
            own = [e for e in evs if e["name"] == "serving.op_scopes"]
            assert len(own) == 1 and own[0]["args"]["tables"] \
                == eng[0]["tables"]


class TestProgramLog:
    @pytest.mark.parametrize("arg, want", [
        (np.zeros(3, np.int32), "numpy"),
        (jnp.zeros(3), "uncommitted"),
        (jax.device_put(jnp.zeros(3), jax.devices()[0]), "committed"),
        (jnp.float32(1) + 1, "uncommitted")])
    def test_kept_as_a_lowering_must_see_it(self, arg, want):
        log = trace.ProgramLog()
        assert log.note("fn", (arg,), "s")
        assert not log.note("fn", (arg,), "s")            # one lookup
        (kept,) = log._kept["fn"][0]
        if want == "numpy":
            assert kept is arg
            return
        assert isinstance(kept, jax.ShapeDtypeStruct)
        assert (kept.shape, kept.dtype) == (arg.shape, arg.dtype)
        assert kept.weak_type == jax.typeof(arg).weak_type
        assert (kept.sharding is not None) == (want == "committed")

    @pytest.mark.parametrize("op_name, want", [
        ("jit(f)/while/body/closed_call/attn/jit(_paged_decode)/"
         "pallas_paged_decode/pallas_call", "forward/attn"),
        ("jit(f)/while/body/attn/kv_pool/pool_write_rows/pallas_call",
         "forward/kv_pool"),
        ("jit(f)/while/body/retention/power_retention_decode/pallas_call",
         "forward/retention"),
        ("jit(f)/flash_forward/pallas_call", "forward")])
    def test_a_kernel_takes_its_callers_scope(self, op_name, want):
        hlo = (f'  %k.1 = f32[2]{{0}} custom-call(%p), '
               f'metadata={{op_name="{op_name}"}}\n')
        known = ("attn", "kv_pool", "retention")
        assert trace.op_scopes(hlo, known)["k.1"] == want
        assert trace.op_scopes(hlo)["k.1"] \
            == "forward/" + op_name.split("/")[-2]


class TestIdleSpan:
    def test_one_span_a_stretch_none_while_a_request_is_open(self, engine):
        eng = engine()
        _serve(eng, lengths=(9,))
        time.sleep(0.1)
        writer = monitor.start_tracing()
        for n in (20, 9):
            _serve(eng, lengths=(n,))
            time.sleep(0.2)                 # four of the scheduler's waits
        monitor.stop_tracing()
        evs = writer.events()
        idle = sorted((e for e in evs if e["name"] == "serving.idle"),
                      key=lambda e: e["ts"])
        # before the first request, between the two, after the second:
        # the last one written at the stop, up to it
        assert len(idle) == 3
        assert all("args" not in e for e in idle)
        stop = [e for e in evs if e["name"] == "serving.op_scopes"][0]
        assert abs(idle[-1]["ts"] + idle[-1]["dur"] - stop["ts"]) <= 1
        assert idle[1]["dur"] >= 150e3 and idle[2]["dur"] >= 150e3
        opened = {e["args"]["rid"]: e["ts"] for e in evs
                  if e["name"] == "serving.queue_wait"}
        done = {e["args"]["rid"]: e["ts"] for e in evs
                if e["name"] == "serving.request_done"}
        assert len(opened) == len(done) == 2
        # the submit that queues a request ends the stretch (the
        # scheduler's wake-up is time with work), a few microseconds
        # after it stamps the request
        for rid, t0 in opened.items():
            for e in idle:
                assert e["ts"] + e["dur"] <= t0 + 50 or e["ts"] >= done[rid]

    @pytest.mark.parametrize("flight", [False, True])
    def test_none_with_tracing_off(self, engine, flight):
        from paddle_tpu.monitor.flight import (arm_flight_recorder,
                                               disarm_flight_recorder)

        eng = engine()
        writer = monitor.get_writer()
        writer.clear()
        rec = arm_flight_recorder() if flight else None
        try:
            for n in (20, 9):
                _serve(eng, lengths=(n,))
                time.sleep(0.12)
        finally:
            disarm_flight_recorder()
        assert [e for e in writer.events()
                if e["name"] == "serving.idle"] == []
        if flight:
            # the flight ring takes the stretches that ended while armed
            ring = [e for e in rec.events() if e["name"] == "serving.idle"]
            assert len(ring) >= 2
