"""fleet.auto hybrid-parallel planner (ISSUE 9).

Covers: planner legality/HBM-fit/explain on virtual 8-device meshes,
ZeRO-2/3 trajectory parity vs unsharded AdamW, 1F1B loss/grad identity to
the fill/drain schedule, sharded-optimizer checkpoint round-trip, the
`fleet.init(strategy={"auto": True})` + unmodified-hapi-script acceptance
path, planner gauges, the pipeline_report trace verdict, and the static
cleanliness of the planner package (graftlint + GL001 host-sync walk —
the cost model must be trace-build-time host code with no jit sinks).
"""
import io
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.distributed import fleet
from paddle_tpu.distributed.fleet import auto as fauto
from paddle_tpu.distributed.fleet.auto import (
    HardwareSpec, ModelStats, ShardedOptimizer, enumerate_plans)
from paddle_tpu.monitor import stats as mstats
from paddle_tpu.parallel.mesh import create_mesh, set_mesh
from paddle_tpu.parallel.pipeline import (pipeline_1f1b, pipeline_forward,
                                          stack_stages)
from paddle_tpu.parallel.train_step import DistributedTrainStep


@pytest.fixture(autouse=True)
def _cleanup():
    yield
    set_mesh(None)
    from paddle_tpu.distributed import env

    env.set_state(initialized=False, hcg=None, topology=None, mesh=None)
    fleet.fleet._strategy = None
    fleet.fleet._mesh = None
    fleet.fleet._hcg = None
    fleet.fleet._topology = None


def _stats(param_bytes=2 ** 22, layers=8, hidden=256, seq=64):
    n = param_bytes // 4
    return ModelStats(param_bytes=param_bytes, n_params=n,
                      layer_bytes=int(param_bytes * 0.9), layers=layers,
                      hidden=hidden, seq_len=seq)


class TestCostModel:
    def test_enumeration_legality(self):
        stats = _stats(layers=8)
        cands = enumerate_plans(8, 32, stats)
        assert cands
        for c in cands:
            assert c.dp * c.sharding * c.pp * c.mp == 8
            assert stats.layers % c.pp == 0
            assert 32 % (c.dp * c.sharding) == 0
            if c.pp > 1:
                assert c.n_micro >= c.pp
            else:
                assert c.n_micro == 1
            if c.zero > 0:
                assert c.sharding > 1
        # no TP annotations -> mp candidates excluded
        assert all(c.mp == 1 for c in cands)

    def test_constraints_pin(self):
        cands = enumerate_plans(8, 32, _stats(), constraints={"pp": 2})
        assert cands and all(c.pp == 2 for c in cands)

    def test_zero_shrinks_param_opt_hbm(self):
        stats = _stats()
        hw = HardwareSpec()
        base = fauto.estimate(
            fauto.PlanCandidate(dp=2, sharding=4, pp=1, mp=1, n_micro=1,
                                zero=0), stats, 32, hw)
        z3 = fauto.estimate(
            fauto.PlanCandidate(dp=2, sharding=4, pp=1, mp=1, n_micro=1,
                                zero=3), stats, 32, hw)
        po = lambda c: c.hbm_detail["params"] + c.hbm_detail["opt_state"]
        assert po(z3) == pytest.approx(po(base) / 4, rel=1e-6)
        # grads shard at level 2+
        assert z3.hbm_detail["grads"] == pytest.approx(
            base.hbm_detail["grads"] / 4, rel=1e-6)

    def test_bubble_formula(self):
        c = fauto.estimate(
            fauto.PlanCandidate(dp=1, sharding=1, pp=4, mp=1, n_micro=8,
                                zero=0), _stats(), 8, HardwareSpec())
        assert c.bubble_frac == pytest.approx(3 / 11)


class TestPlanner:
    def test_plan_picks_fitting_and_explains(self):
        stats = _stats(param_bytes=2 ** 22)
        # budget sized so unsharded pp=1 plans do NOT fit but ZeRO ones do
        hw = HardwareSpec(hbm_bytes=int(2 ** 22 * 2.2), hbm_fudge=1.0)
        mstats.PLAN_CANDIDATES_CONSIDERED.reset()
        plan = fauto.plan(stats=stats, global_batch=32, n_devices=8,
                          hardware=hw)
        assert plan.chosen.fits
        assert plan.zero >= 1 or plan.pp > 1  # something had to shrink HBM
        # explain prints a ranked table with the chosen row marked
        buf = io.StringIO()
        text = plan.explain(top=8, file=buf)
        assert "<== chosen" in text and "rank" in text
        assert buf.getvalue() == text + "\n"
        assert fauto.explain(top=8, file=io.StringIO()) == text  # module
        # gauges: both register (monitor.stats) and increment (planner)
        assert mstats.PLAN_CANDIDATES_CONSIDERED.get() == \
            len(plan.candidates) > 0
        assert mstats.ZERO_LEVEL.get() == plan.zero
        assert mstats.PIPELINE_BUBBLE_FRAC.get() == \
            int(plan.chosen.bubble_frac * 1e6)
        assert mstats.PLANNER_HBM_HEADROOM_BYTES.get() == \
            int(hw.hbm_bytes * hw.hbm_fudge) - plan.chosen.hbm_bytes

    def test_no_fit_raises_with_shortfall(self):
        with pytest.raises(ValueError, match="no plan fits"):
            fauto.plan(stats=_stats(param_bytes=2 ** 22), global_batch=32,
                       n_devices=8,
                       hardware=HardwareSpec(hbm_bytes=2 ** 12))

    def test_from_params_infers_layers_and_tp(self):
        params = {"blocks": {"w": jnp.zeros((6, 32, 32)),
                             "b": jnp.zeros((6, 32))},
                  "head": jnp.zeros((32, 16))}
        specs = {"blocks": {"w": P(None, None, "model"), "b": P()},
                 "head": P()}
        st = ModelStats.from_params(params, specs=specs)
        assert st.layers == 6
        assert st.layer_bytes == (6 * 32 * 32 + 6 * 32) * 4
        assert st.tp_bytes == 6 * 32 * 32 * 4
        assert st.n_params == 6 * 32 * 32 + 6 * 32 + 32 * 16


def _mlp_params(rng, d=16, h=32):
    return {"w1": jnp.asarray(rng.normal(size=(d, h)).astype("f4") * 0.2),
            "b1": jnp.zeros((h,), jnp.float32),
            "w2": jnp.asarray(rng.normal(size=(h, d)).astype("f4") * 0.2)}


def _mlp_loss(p, batch):
    x, y = batch
    hid = jnp.tanh(x @ p["w1"] + p["b1"])
    return jnp.mean((hid @ p["w2"] - y) ** 2)


class TestZeRO:
    def _run(self, zero, steps=50):
        rng = np.random.default_rng(0)
        params = _mlp_params(rng)
        specs = {k: P() for k in params}
        set_mesh(None)
        mesh = create_mesh(dp=2, sharding=4)
        opt = (ShardedOptimizer("adamw", level=zero, weight_decay=0.01)
               if zero else "adamw")
        step = DistributedTrainStep(_mlp_loss, params, specs, optimizer=opt,
                                    lr=1e-2, zero=zero, mesh=mesh,
                                    zero_min_size=1,
                                    opt_kwargs={"weight_decay": 0.01}
                                    if not zero else None)
        data = np.random.default_rng(7)
        for _ in range(steps):
            x = data.normal(size=(8, 16)).astype("f4")
            y = data.normal(size=(8, 16)).astype("f4")
            loss = step((jnp.asarray(x), jnp.asarray(y)))
        return step, float(loss)

    @staticmethod
    def _dev_bytes(step):
        tot = 0
        for a in (jax.tree_util.tree_leaves(step.params)
                  + jax.tree_util.tree_leaves(step.opt_state)):
            sh = a.addressable_shards[0].data
            tot += int(np.prod(sh.shape) or 1) * a.dtype.itemsize
        return tot

    def test_zero23_trajectory_matches_unsharded_adamw(self):
        s0, l0 = self._run(0)
        s2, l2 = self._run(2)
        s3, l3 = self._run(3)
        assert l0 == pytest.approx(l2, rel=1e-5) == pytest.approx(l3,
                                                                  rel=1e-5)
        for k in s0.params:
            np.testing.assert_allclose(np.asarray(s0.params[k]),
                                       np.asarray(s2.params[k]),
                                       rtol=1e-4, atol=1e-6, err_msg=k)
            np.testing.assert_allclose(np.asarray(s0.params[k]),
                                       np.asarray(s3.params[k]),
                                       rtol=1e-4, atol=1e-6, err_msg=k)

    def test_zero3_storage_fraction(self):
        s0, _ = self._run(0, steps=1)
        s3, _ = self._run(3, steps=1)
        frac = self._dev_bytes(s3) / self._dev_bytes(s0)
        # params+m+v all 1/4-sharded; count scalar stays replicated
        assert frac <= 0.40, frac
        assert s3.zero_level == 3
        # ZeRO levels annotate the specs: m/v and (level 3) params carry
        # the sharding axis
        m_specs = jax.tree_util.tree_leaves(
            s3.opt_specs["m"], is_leaf=lambda s: isinstance(s, P))
        assert any("sharding" in str(s) for s in m_specs)

    def test_zero2_grads_pinned_to_shard_layout(self):
        rng = np.random.default_rng(0)
        params = _mlp_params(rng)
        specs = {k: P() for k in params}
        set_mesh(None)
        mesh = create_mesh(dp=2, sharding=4)
        step = DistributedTrainStep(_mlp_loss, params, specs,
                                    optimizer="adamw", lr=1e-2, zero=2,
                                    mesh=mesh, zero_min_size=1)
        x = jnp.zeros((8, 16), jnp.float32)
        # the lowered module pins each gradient to the "sharding" axis —
        # the annotation that turns the grad reduction into a
        # reduce-scatter (TPU); CPU XLA legalizes the same annotation as
        # all-reduce + dynamic-slice
        low = step.lower((x, x)).as_text()
        pins = [ln for ln in low.splitlines()
                if "sharding_constraint" in ln and '"sharding"' in ln]
        assert len(pins) >= len(params), low[:2000]
        comp = step.lower((x, x)).compile().as_text()
        assert "reduce-scatter" in comp or (
            "all-reduce" in comp and "dynamic-slice" in comp)

    def test_sharded_optimizer_checkpoint_roundtrip(self, tmp_path):
        from paddle_tpu.framework.io import load, save

        s3, _ = self._run(3, steps=10)
        sd = s3.state_dict()
        path = os.path.join(str(tmp_path), "auto_ckpt.pdopt")
        save(sd, path)
        loaded = load(path)
        # restore into a FRESH differently-trained sharded step
        s3b, _ = self._run(3, steps=3)
        s3b.set_state_dict(loaded)
        assert s3b._step_count == 10
        for k in sd["params"]:
            np.testing.assert_allclose(np.asarray(s3b.params[k]),
                                       sd["params"][k], err_msg=k)
        np.testing.assert_allclose(np.asarray(s3b.opt_state["m"]["w1"]),
                                   sd["opt_state"]["m"]["w1"])
        # the restored step keeps training under its sharded layout, on
        # the same trajectory as the uninterrupted run
        data = np.random.default_rng(11)
        x = data.normal(size=(8, 16)).astype("f4")
        y = data.normal(size=(8, 16)).astype("f4")
        s3((jnp.asarray(x), jnp.asarray(y)))
        s3b((jnp.asarray(x), jnp.asarray(y)))
        for k in sd["params"]:
            np.testing.assert_allclose(np.asarray(s3b.params[k]),
                                       np.asarray(s3.params[k]),
                                       rtol=1e-6, err_msg=k)

    def test_sharded_optimizer_validation(self):
        with pytest.raises(ValueError, match="level"):
            ShardedOptimizer("adamw", level=5)
        with pytest.raises(ValueError, match="unknown optimizer"):
            ShardedOptimizer("adagrad")


class Test1F1B:
    def _setup(self, S=2, n=4, mb=2, H=8, L=4):
        rng = np.random.default_rng(0)
        sp = stack_stages(
            {"w": jnp.asarray(rng.normal(size=(L, H, H)).astype("f4") * .3),
             "b": jnp.asarray(rng.normal(size=(L, H)).astype("f4") * .1)}, S)
        hp = {"hw": jnp.asarray(rng.normal(size=(H, H)).astype("f4") * .3)}
        x = jnp.asarray(rng.normal(size=(n, mb, H)).astype("f4"))
        y = jnp.asarray(rng.normal(size=(n, mb, H)).astype("f4"))

        def stage_fn(p, h):
            for i in range(p["w"].shape[0]):
                h = jnp.tanh(h @ p["w"][i] + p["b"][i])
            return h

        def loss_head(hp, a, lab):
            return jnp.mean((a @ hp["hw"] - lab) ** 2)

        def ref_loss(sp, hp, x, y):
            ys = pipeline_forward(stage_fn, sp, x, S)
            return jnp.mean(jax.vmap(
                lambda o, t: loss_head(hp, o, t))(ys, y))

        return sp, hp, x, y, stage_fn, loss_head, ref_loss

    @pytest.mark.parametrize("S,n", [(2, 4), (4, 8)])
    def test_loss_and_grads_match_fill_drain(self, S, n):
        sp, hp, x, y, stage_fn, loss_head, ref_loss = self._setup(S=S, n=n)
        f1 = pipeline_1f1b(stage_fn, loss_head, S)
        set_mesh(None)
        mesh = create_mesh(dp=2, sharding=2, pp=2)
        with mesh:
            lr, (gsr, ghr) = jax.jit(jax.value_and_grad(
                ref_loss, argnums=(0, 1)))(sp, hp, x, y)
            l1, (gs1, gh1) = jax.jit(jax.value_and_grad(
                lambda a, b, c, d: f1(a, b, c, d),
                argnums=(0, 1)))(sp, hp, x, y)
        assert float(lr) == pytest.approx(float(l1), rel=1e-6)
        for k in gsr:
            np.testing.assert_allclose(np.asarray(gsr[k]),
                                       np.asarray(gs1[k]),
                                       rtol=1e-4, atol=1e-6, err_msg=k)
        np.testing.assert_allclose(np.asarray(ghr["hw"]),
                                   np.asarray(gh1["hw"]),
                                   rtol=1e-4, atol=1e-6)

    def test_input_cotangent_matches(self):
        sp, hp, x, y, stage_fn, loss_head, ref_loss = self._setup()
        f1 = pipeline_1f1b(stage_fn, loss_head, 2)
        gxr = jax.grad(ref_loss, argnums=2)(sp, hp, x, y)
        gx1 = jax.grad(lambda a, b, c, d: f1(a, b, c, d),
                       argnums=2)(sp, hp, x, y)
        np.testing.assert_allclose(np.asarray(gxr), np.asarray(gx1),
                                   rtol=1e-5, atol=1e-7)

    def test_engine_1f1b_schedule_loss_identical_to_fill_drain(self):
        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.meta_parallel.pp_layers import (
            LayerDesc, PipelineLayer)

        def mse(out, label):
            return paddle.mean((out - label) ** 2)

        def build(schedule):
            s = DistributedStrategy()
            s.hybrid_configs = {"dp_degree": 2, "mp_degree": 1,
                                "pp_degree": 2, "sharding_degree": 2}
            s.pipeline_configs = {"accumulate_steps": 4,
                                  "micro_batch_size": 1,
                                  "schedule": schedule}
            fleet.init(is_collective=True, strategy=s)
            paddle.seed(11)
            pipe = PipelineLayer(
                layers=[LayerDesc(paddle.nn.Linear, 8, 8)
                        for _ in range(4)],
                num_stages=2, loss_fn=mse)
            model = fleet.distributed_model(pipe)
            opt = fleet.distributed_optimizer(paddle.optimizer.SGD(
                learning_rate=0.05, parameters=model.parameters()))
            return pipe, model, opt

        rng = np.random.default_rng(3)
        data = [(rng.normal(size=(8, 8)).astype("f4"),
                 rng.normal(size=(8, 8)).astype("f4")) for _ in range(3)]
        pipe_a, model_a, opt_a = build("FThenB")
        losses_a = [float(model_a.train_batch(
            (paddle.to_tensor(x), paddle.to_tensor(y)), opt_a)._data)
            for x, y in data]
        set_mesh(None)
        pipe_b, model_b, opt_b = build("1F1B")
        losses_b = [float(model_b.train_batch(
            (paddle.to_tensor(x), paddle.to_tensor(y)), opt_b)._data)
            for x, y in data]
        np.testing.assert_allclose(losses_a, losses_b, rtol=1e-5,
                                   atol=1e-6)
        for (n1, p1), (n2, p2) in zip(pipe_a.named_parameters(),
                                      pipe_b.named_parameters()):
            np.testing.assert_allclose(np.asarray(p1._data),
                                       np.asarray(p2._data),
                                       rtol=1e-4, atol=1e-6, err_msg=n1)

    def test_needs_two_stages(self):
        with pytest.raises(ValueError, match="n_stages"):
            pipeline_1f1b(lambda p, h: h, lambda hp, a, y: a.sum(), 1)


class _Block(paddle.nn.Layer):
    def __init__(self, dim):
        super().__init__()
        self.fc = paddle.nn.Linear(dim, dim)

    def forward(self, x):
        return paddle.tanh(self.fc(x))


def _uniform_net(seed, dim=32, n=4):
    paddle.seed(seed)
    return paddle.nn.Sequential(*[_Block(dim) for _ in range(n)])


def _mse(out, label):
    return paddle.mean((out - label) ** 2)


class TestAutoHapi:
    """Acceptance: fleet.init(strategy={"auto": True}) + an unmodified
    hapi script trains under the planner-chosen (dp=2, sharding=2,
    pipe=2, mp=1) plan, loss/weights allclose to the single-device run."""

    def test_auto_hapi_matches_single_device(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(48, 32)).astype("f4")
        Y = rng.normal(size=(48, 32)).astype("f4")

        class DS:
            def __len__(self):
                return 48

            def __getitem__(self, i):
                return X[i], Y[i]

        # single-device eager reference
        ref = _uniform_net(3)
        opt_r = paddle.optimizer.SGD(learning_rate=0.05,
                                     parameters=ref.parameters())
        for i in range(6):
            x = paddle.to_tensor(X[i * 8:(i + 1) * 8])
            y = paddle.to_tensor(Y[i * 8:(i + 1) * 8])
            loss = _mse(ref(x), y)
            loss.backward()
            opt_r.step()
            opt_r.clear_grad()

        # unmodified hapi script, auto strategy (the slice operator pins
        # the pipeline depth and per-chip HBM; the planner chooses the
        # rest: dp/sharding split, ZeRO level, microbatches, schedule)
        fleet.init(is_collective=True, strategy={
            "auto": True,
            "auto_configs": {"pp": 2, "hbm_bytes_per_device": 26_000,
                             "zero_min_size": 1, "max_micro": 4}})
        net = _uniform_net(3)
        model = paddle.Model(net)
        opt = paddle.optimizer.SGD(learning_rate=0.05,
                                   parameters=net.parameters())
        model.prepare(optimizer=opt, loss=_mse)
        model.fit(DS(), epochs=1, batch_size=8, shuffle=False,
                  log_freq=100, verbose=0)

        plan = fauto.last_plan()
        assert (plan.dp, plan.sharding, plan.pp, plan.mp) == (2, 2, 2, 1)
        assert plan.zero >= 2
        assert plan.schedule == "1f1b"
        eng = model._train_step.engine
        assert eng is not None and eng.plan is plan
        assert eng.train_step.zero_level == plan.zero
        # planned mesh registered with the fleet facade
        assert dict(fleet.get_mesh().shape) == plan.mesh_dims

        for (n1, p1), (n2, p2) in zip(ref.named_parameters(),
                                      net.named_parameters()):
            np.testing.assert_allclose(np.asarray(p1._data),
                                       np.asarray(p2._data),
                                       rtol=2e-4, atol=2e-5, err_msg=n1)

    def test_auto_engine_without_global_batch_raises(self):
        from paddle_tpu.distributed.fleet.engine import FleetEngine

        fleet.init(is_collective=True, strategy={"auto": True})
        net = _uniform_net(5)
        opt = paddle.optimizer.SGD(learning_rate=0.1,
                                   parameters=net.parameters())
        with pytest.raises(ValueError, match="global batch"):
            FleetEngine(net, opt, fleet.fleet._strategy, loss_fn=_mse)


class TestPipelineReport:
    def test_tick_spans_and_report_verdict(self):
        import sys

        sys.path.insert(0, os.path.join(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__)))))
        from paddle_tpu.monitor import trace as mtrace
        from tools.trace_report import pipeline_report

        from paddle_tpu.distributed.fleet import DistributedStrategy
        from paddle_tpu.distributed.fleet.meta_parallel.pp_layers import (
            LayerDesc, PipelineLayer)

        s = DistributedStrategy()
        s.hybrid_configs = {"dp_degree": 2, "mp_degree": 1,
                            "pp_degree": 2, "sharding_degree": 2}
        s.pipeline_configs = {"accumulate_steps": 4, "micro_batch_size": 1,
                              "schedule": "1F1B"}
        fleet.init(is_collective=True, strategy=s)
        paddle.seed(7)
        pipe = PipelineLayer(
            layers=[LayerDesc(paddle.nn.Linear, 8, 8) for _ in range(4)],
            num_stages=2, loss_fn=_mse)
        model = fleet.distributed_model(pipe)
        opt = fleet.distributed_optimizer(paddle.optimizer.SGD(
            learning_rate=0.05, parameters=model.parameters()))
        writer = mtrace.start_tracing()
        try:
            rng = np.random.default_rng(0)
            x = rng.normal(size=(8, 8)).astype("f4")
            y = rng.normal(size=(8, 8)).astype("f4")
            model.train_batch((paddle.to_tensor(x), paddle.to_tensor(y)),
                              opt)
            events = list(writer._events)
        finally:
            mtrace.stop_tracing()
        ticks = [e for e in events if e["name"] == "pipeline.tick"]
        # 1F1B: T = n_micro + 2(S-1) = 4 + 2 = 6 ticks
        assert len(ticks) == 6
        buf = io.StringIO()
        out = pipeline_report(events, file=buf)
        assert out["schedule"] == "1f1b"
        # measured == predicted for the schedule that actually compiled
        assert out["measured_bubble_frac"] == pytest.approx(
            out["predicted_bubble_frac"], abs=1e-9)
        assert "matches the cost model" in out["verdict"]
        assert "Pipeline schedule" in buf.getvalue()

    def test_report_flags_deviation(self):
        from tools.trace_report import pipeline_report

        # spans claiming fill/drain occupancy but with half the budgeted
        # microbatches -> measured bubble far above the model's prediction
        events = [{"name": "pipeline.tick", "ph": "X", "ts": 0, "dur": 1,
                   "args": {"t": t, "busy": 1, "slots": 4, "stages": 4,
                            "n_micro": 16, "schedule": "fthenb"}}
                  for t in range(8)]
        out = pipeline_report(events, file=io.StringIO())
        assert "deviates" in out["verdict"]


class TestPlannerStatic:
    """Satellite: the planner package ships graftlint-clean and the cost
    model stays host-side (trace-build time only — no jit sinks for the
    GL001 host-sync walk to taint)."""

    AUTO_DIR = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "paddle_tpu", "distributed", "fleet",
        "auto")

    def test_graftlint_clean_no_new_suppressions(self):
        from paddle_tpu.analysis.lint import run_lint

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        findings = run_lint([self.AUTO_DIR], root=root)
        assert findings == [], [f.fingerprint() for f in findings]

    def test_gl001_walk_covers_planner_with_no_jit_sinks(self):
        from paddle_tpu.analysis.lint import build_project
        from paddle_tpu.analysis.hotpath import find_seeds

        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proj = build_project([self.AUTO_DIR], root=root)
        # the walk SEES the planner functions...
        mods = {m for (m, _f) in proj.functions}
        assert any(m.endswith("fleet/auto/planner.py") for m in mods)
        assert any(m.endswith("fleet/auto/cost_model.py") for m in mods)
        names = {f for (_m, f) in proj.functions}
        assert "plan" in names and "estimate" in names
        # ...and finds NO jit/pallas/shard_map/control-flow sinks in it:
        # the cost model runs at trace-build time on the host, so nothing
        # here may become traced code where a host sync would stall TPUs
        assert find_seeds(proj) == []
