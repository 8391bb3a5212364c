"""``tick_ffn_ms.serve``: device self time a run of the engine's decode
tick in the scopes ``mlp``, ``router`` and ``experts``
(``serve_scopes``)."""
from benchmarks.readers import serve_scopes


def read(ctx):
    return serve_scopes.group_ms(ctx, serve_scopes.TICK, "ffn")
