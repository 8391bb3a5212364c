"""``tick_head_ms.serve``: device self time a run of the engine's decode
tick in the scopes ``embed``, ``head`` and ``sampling``
(``serve_scopes``)."""
from benchmarks.readers import serve_scopes


def read(ctx):
    return serve_scopes.group_ms(ctx, serve_scopes.TICK, "head")
