"""Versioned StableHLO export + compiled-serve Predictor.

VERDICT r2 items 4/5: versioned export replacing cloudpickle (reference
ProgramDesc proto, framework.proto:234) and an AnalysisPredictor analog
(analysis_predictor.h:86) serving from a fresh process with no model code.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import static

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestVersionedExport:
    def test_round_trip_dynamic_batch(self, tmp_path):
        prefix = str(tmp_path / "model")
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [-1, 4], "float32")
            y = static.nn.fc(x, 8, activation="relu")
            z = paddle.sum(y)
        exe = static.Executor()
        want = exe.run(prog, feed={"x": np.ones((3, 4), np.float32)},
                       fetch_list=[y, z])

        static.save_inference_model(prefix, [x], [y, z], exe, program=prog)
        assert os.path.exists(prefix + ".pdmodel")
        meta = json.load(open(prefix + ".pdmeta.json"))
        assert meta["format_version"] == 1
        assert meta["feed_shapes"] == [[-1, 4]]

        prog2, feeds, fetches = static.load_inference_model(prefix, exe)
        assert feeds == ["x"]
        got = exe.run(prog2, feed={"x": np.ones((3, 4), np.float32)},
                      fetch_list=fetches)
        np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
        np.testing.assert_allclose(got[1], want[1], rtol=1e-5)
        # symbolic batch dim: a DIFFERENT batch size works from the same
        # artifact
        got5 = exe.run(prog2, feed={"x": np.ones((5, 4), np.float32)},
                       fetch_list=fetches)
        assert got5[0].shape == (5, 8)

    def test_format_version_check(self, tmp_path):
        prefix = str(tmp_path / "model")
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [2], "float32")
            y = x * 2
        exe = static.Executor()
        static.save_inference_model(prefix, [x], [y], exe, program=prog)
        # bump the stored version beyond the runtime's
        from paddle_tpu.static.export import MAGIC

        with open(prefix + ".pdmodel", "rb") as f:
            blob = f.read()
        with open(prefix + ".pdmodel", "wb") as f:
            f.write(MAGIC + (99).to_bytes(4, "little") + blob[len(MAGIC) + 4:])
        with pytest.raises(Exception, match="version"):
            static.load_inference_model(prefix, exe)

    def test_control_flow_model_round_trip(self, tmp_path):
        """A model containing While + Conditional survives export/load."""
        prefix = str(tmp_path / "cf")
        prog = static.Program()
        with static.program_guard(prog):
            x = static.data("x", [4], "float32")
            n = static.data("n", [], "int32")
            i0 = paddle.to_tensor(np.array(0, np.int32))
            _, acc = static.nn.while_loop(
                lambda i, acc: i < n,
                lambda i, acc: (i + 1, acc + x),
                [i0, x * 0])
            out = static.nn.cond(paddle.sum(acc) > 10.0,
                                 lambda: acc * 2, lambda: acc)
        exe = static.Executor()
        static.save_inference_model(prefix, [x, n], [out], exe, program=prog)

        prog2, feeds, fetches = static.load_inference_model(prefix, exe)
        xs = np.arange(4, dtype=np.float32)
        lo = exe.run(prog2, feed={"x": xs, "n": np.int32(1)},
                     fetch_list=fetches)[0]
        np.testing.assert_allclose(lo, xs)          # sum 6 < 10: unchanged
        hi = exe.run(prog2, feed={"x": xs, "n": np.int32(3)},
                     fetch_list=fetches)[0]
        np.testing.assert_allclose(hi, 6 * xs)      # sum 18 > 10: doubled

    def test_jit_save_layer_then_predict(self, tmp_path):
        prefix = str(tmp_path / "lay")
        paddle.seed(5)
        net = paddle.nn.Sequential(paddle.nn.Linear(6, 12), paddle.nn.ReLU(),
                                   paddle.nn.Linear(12, 3))
        want = net(paddle.to_tensor(np.ones((2, 6), np.float32))).numpy()
        paddle.jit.save(net, prefix,
                        input_spec=[static.InputSpec([-1, 6], "float32")])

        from paddle_tpu.inference import Predictor

        pred = Predictor(prefix)
        assert pred.get_input_names() == ["x0"]
        got = pred.run([np.ones((2, 6), np.float32)])[0]
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
        # different batch size through the symbolic dim
        got4 = pred.run([np.ones((4, 6), np.float32)])[0]
        assert got4.shape == (4, 3)

    def test_handle_names_validated_at_creation(self, tmp_path):
        """ISSUE 4 satellite: a bad handle name fails LOUDLY when the
        handle is created — not later as a cryptic KeyError inside
        copy_to_cpu."""
        prefix = str(tmp_path / "hv")
        net = paddle.nn.Linear(3, 2)
        paddle.jit.save(net, prefix,
                        input_spec=[static.InputSpec([-1, 3], "float32")])
        from paddle_tpu.inference import Predictor

        pred = Predictor(prefix)
        with pytest.raises(ValueError, match="get_input_names"):
            pred.get_input_handle("not_a_feed")
        with pytest.raises(ValueError, match="get_output_names"):
            pred.get_output_handle("fetch_99")
        # the real names still work end-to-end through the handles
        inp = pred.get_input_handle(pred.get_input_names()[0])
        inp.copy_from_cpu(np.ones((2, 3), np.float32))
        pred.run()
        out = pred.get_output_handle("fetch_0").copy_to_cpu()
        assert out.shape == (2, 2)


class TestPredictorFreshProcess:
    def test_gpt_tiny_served_without_model_code(self, tmp_path):
        """Export GPT-tiny, then serve it from a subprocess that imports
        ONLY paddle_tpu.inference + numpy (reference done-bar: predictor
        runs without the model-building python)."""
        import jax

        from paddle_tpu.models import gpt_tiny, gpt_init, gpt_forward
        from paddle_tpu.static.export import export_callable, write_artifacts

        cfg = gpt_tiny(use_flash=False)
        params = gpt_init(cfg, seed=0)
        leaves, treedef = jax.tree_util.tree_flatten(params)

        def fn(state_list, tokens):
            p = jax.tree_util.tree_unflatten(treedef, list(state_list))
            return gpt_forward(cfg, p, tokens)

        rng = np.random.default_rng(0)
        tokens = rng.integers(0, cfg.vocab_size, (2, cfg.seq_len)).astype(np.int32)
        want = np.asarray(fn(leaves, tokens))

        prefix = str(tmp_path / "gpt")
        data, st, meta = export_callable(fn, leaves, [tokens],
                                         feed_names=["tokens"])
        write_artifacts(prefix, data, st, meta)

        script = (
            "import sys; assert not any(m.startswith('paddle_tpu.models') "
            "for m in sys.modules), 'model code leaked'\n"
            "import numpy as np\n"
            "from paddle_tpu.inference import Predictor\n"
            f"p = Predictor({prefix!r})\n"
            f"tokens = np.load({str(tmp_path / 'tok.npy')!r})\n"
            "out = p.run([tokens])[0]\n"
            "assert not any(m.startswith('paddle_tpu.models') "
            "for m in sys.modules), 'predictor imported model code'\n"
            f"np.save({str(tmp_path / 'out.npy')!r}, out)\n"
        )
        np.save(str(tmp_path / "tok.npy"), tokens)
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run([sys.executable, "-c", script], env=env,
                              cwd=REPO, capture_output=True, text=True,
                              timeout=600)
        assert proc.returncode == 0, proc.stderr[-2000:]
        got = np.load(str(tmp_path / "out.npy"))
        # eager trace vs one fused compiled module: XLA fusion reorders
        # float ops, so small-magnitude logits drift a few 1e-3
        np.testing.assert_allclose(got, want, rtol=2e-2, atol=8e-3)
