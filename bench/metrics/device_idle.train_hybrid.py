"""device_idle.train_hybrid: the share of the traced window in which no op
ran on the device, in %: 1 - (union of the device's op intervals /
window), from the profiler trace (``trace_reduce.reduce``), averaged over
the chips."""


def read(ctx: dict):
    trace = ctx.get("trace")
    if not trace:
        return None
    return 100.0 * trace["idle_share"]
