"""``tick_ssm_ms.serve``: device self time a run of the engine's decode
tick in the scopes ``ssm`` and ``gmu`` (``serve_scopes``' tables). A
program without those scopes, or without op_scopes tables, reads
nothing."""
from benchmarks.readers import serve_scopes

SCOPES = ("ssm", "gmu")


def read(ctx):
    t = serve_scopes.tables(ctx).get(serve_scopes.TICK)
    if t is None or not any(s in t["scope_ms"] for s in SCOPES):
        return None
    return sum(t["scope_ms"].get(s, 0.0) for s in SCOPES)
