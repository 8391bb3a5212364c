"""``tick_other_ms.serve``: device self time a run of the engine's decode
tick outside the three groups: ``ln``, instructions with no scope, and
unplaced ones (``serve_scopes``)."""
from benchmarks.readers import serve_scopes


def read(ctx):
    return serve_scopes.group_ms(ctx, serve_scopes.TICK, "other")
