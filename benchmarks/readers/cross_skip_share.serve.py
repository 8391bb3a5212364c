"""``cross_skip_share.serve``: of the prompt rows the prefill chunks
ran in the traced sub-window, the share that stopped before the
cross-decoder: 1 - the spans' ``cross_rows`` over their ``chunk``. A
program whose chunk spans carry no ``cross_rows`` reads nothing."""
from benchmarks.lib import harness


def read(ctx):
    chunks = [e["args"] for e in ctx.program_events or ()
              if e.get("ph") == "X" and e["name"] == "serving.prefill_chunk"
              and "cross_rows" in (e.get("args") or {})]
    rows = sum(a["chunk"] for a in chunks)
    if not rows:
        return None
    cross = sum(a["cross_rows"] for a in chunks)
    harness.say(f"prefill: {len(chunks)} chunks of {rows} real rows, "
                f"{cross} of them through the cross-decoder")
    return 100.0 * (1.0 - cross / rows)
