"""``decode_ahead_share.serve``: of the decode ticks dispatched in the
window, the share that left the host while the tick before was still in
flight (the counters ``serving_decode_ticks_ahead`` over it plus
``serving_decode_ticks_synced``). A program without the counters reads
nothing."""


def read(ctx):
    ahead = ctx.stat_delta.get("serving_decode_ticks_ahead")
    synced = ctx.stat_delta.get("serving_decode_ticks_synced")
    if ahead is None or synced is None or ahead + synced <= 0:
        return None
    return 100.0 * ahead / (ahead + synced)
