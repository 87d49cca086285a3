"""sim.device_idle_share: the share of the traced window, in %, in which
no operation ran on the chip (mean over chips), from the profiler's
device trace."""
from chipbench import trace


def read(ctx):
    tr = ctx.trace
    if not tr.devices or tr.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.mean_busy_s(tr) / tr.window_s)
