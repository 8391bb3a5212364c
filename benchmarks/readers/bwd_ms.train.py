"""``bwd_ms.train``: device self time a step in phase ``backward``."""
from benchmarks.readers import phases


def read(ctx):
    return phases.read_phase(ctx, "backward")
