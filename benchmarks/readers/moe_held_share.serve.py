"""``moe_held_share.serve``: of the token-expert assignments the router
made in the window, the share that fell on experts held here (the
counters ``moe_assignments_held`` over ``moe_assignments_routed``). A
program without the counters reads nothing."""


def read(ctx):
    routed = ctx.stat_delta.get("moe_assignments_routed")
    held = ctx.stat_delta.get("moe_assignments_held")
    if not routed or held is None:
        return None
    return 100.0 * held / routed
