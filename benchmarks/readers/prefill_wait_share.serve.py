"""``prefill_wait_share.serve``: of the time from admission to first
token, the share a request waits behind other requests' work.

Own work: the window's ``serving_prefill_chunks`` times the trace's
median device time of one chunk program. Admit-to-first-token time: the
sum of ``serving_first_token_ms`` less the sum of
``serving_queue_wait_ms`` (exact, from the histograms' ``sum``). A
program without the counter reads nothing."""
import re

from benchmarks.lib import harness

CHUNK = re.compile("_chunk_fn")


def read(ctx):
    chunks = ctx.stat_delta.get("serving_prefill_chunks")
    first = ctx.hist_delta.get("serving_first_token_ms")
    queue = ctx.hist_delta.get("serving_queue_wait_ms")
    if not chunks or not first or not queue or ctx.trace is None:
        return None
    durs = [e.dur / 1e6 for e in ctx.trace.modules.get(0, ())
            if CHUNK.search(e.name)]
    admit_to_first = first["sum"] - queue["sum"]
    if not durs or admit_to_first <= 0:
        return None
    own = chunks * harness.median(durs)
    harness.say(f"prefill: {chunks} chunks of {harness.median(durs):.2f} ms "
                f"= {own:.0f} ms of own work in {admit_to_first:.0f} ms "
                f"from admission to first token over {first['count']} "
                "requests")
    return 100.0 * (1.0 - own / admit_to_first)
