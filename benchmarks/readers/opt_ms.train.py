"""``opt_ms.train``: device self time a step in phase ``optimizer``."""
from benchmarks.readers import phases


def read(ctx):
    return phases.read_phase(ctx, "optimizer")
