"""The one module that touches the program under test: its builders,
its two entry points, its counters. The loss and the parameter specs of
a model come from its family (``benchmarks/families/<family>/``).
Everything measured or compared lives in the other modules of
``benchmarks/lib`` and in the families."""
import dataclasses
import importlib

from .weights import DTYPES


def build_config(config):
    """The program's configuration object from the file's builder call;
    refuses one whose sizes differ from the file's ``sizes``."""
    b = config["builder"]
    args = {k: DTYPES.get(v, v) if k in ("dtype", "param_dtype") else v
            for k, v in b.get("args", {}).items()}
    cfg = getattr(importlib.import_module(b["module"]), b["call"])(**args)
    sizes = config["sizes"]
    got = dataclasses.asdict(cfg)
    for k, want in sizes.items():
        if k == "init_std":          # the benchmark's own, its family's
            continue
        have = got[k]
        if k in ("dtype", "param_dtype"):
            want = DTYPES[want]
        if have != want:
            raise SystemExit(f"config {config['name']}: the program's "
                             f"{b['call']}() has {k}={have!r}, the file "
                             f"says {want!r}")
    return cfg


def build_train_step(cfg, params, step, family):
    """``DistributedTrainStep`` on a one-device mesh, as a user builds
    it. ``step``: the workload file's ``step`` group; ``family``: the
    configuration's, for the model's loss and parameter specs."""
    import jax

    from paddle_tpu.parallel import DistributedTrainStep, create_mesh

    mesh = create_mesh(devices=jax.devices()[:1])
    o = step["opt"]
    return DistributedTrainStep(
        family.train_loss(cfg), params, family.param_specs(cfg),
        optimizer=step["optimizer"], lr=step["lr"], zero=step["zero"],
        mesh=mesh, opt_kwargs={"beta1": o["beta1"], "beta2": o["beta2"],
                               "eps": o["eps"],
                               "weight_decay": o["weight_decay"]})


def build_engine(cfg, params, engine, seed):
    from paddle_tpu.serving import InferenceEngine

    return InferenceEngine(cfg, params, seed=int(seed) & 0x7FFFFFFF,
                           **engine)


def enable_compile_cache():
    import jax

    from paddle_tpu.device import enable_compile_cache as enable

    path = enable()
    # small programs too: set-up is then the same work in every run
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def stats_snapshot():
    """(counters, histograms) of the program's monitor registry."""
    from paddle_tpu.monitor import stats

    return stats.stat_snapshot(), stats.histogram_snapshot()


def stats_delta(before, after):
    from paddle_tpu.monitor import stats

    c0, h0 = before
    c1, h1 = after
    counters = {k: v - c0.get(k, 0) for k, v in c1.items()}
    hists = {k: stats.hist_delta(h0[k], h1[k]) for k in h1 if k in h0}
    return counters, hists


def gauge(name):
    from paddle_tpu.monitor import stats

    return stats.stat_get(name)
