"""``retention_tick_share.serve``: how much of the decode programs'
device time the state kernel takes.

Over the traced sub-window: the device time of the
``power_retention_decode`` kernel's events (every layer of every tick)
over the device time of the runs of ``jit__decode_paged_fn`` on the
``XLA Modules`` line. The rest of a tick is the weights' stream through
the projections, the MLP and the head, and the sampler. A trace without
the kernel (a model that has none) reads nothing."""
import re

from benchmarks.lib import harness, xplane

KERNEL = r"power_retention_decode"
PROGRAM = re.compile(r"_decode_paged_fn")


def read(ctx):
    if ctx.trace is None:
        return None
    kernel = sum(e.dur for e in xplane.kernel_events(ctx.trace, KERNEL)) / 1e9
    ticks = [e.dur for e in ctx.trace.modules.get(0, ())
             if PROGRAM.search(e.name)]
    if kernel <= 0 or not ticks:
        return None
    total = sum(ticks) / 1e9
    harness.say(f"kernel {KERNEL}: {kernel:.6f} s of the {total:.6f} s of "
                f"{len(ticks)} decode programs")
    return 100.0 * kernel / total
