"""pallas_ms.train: device time per train step of the Pallas kernels the
fusion tier generates, in ms, from the profiler trace of the window: the
``tpu_custom_call`` ops that ``kernels/codegen.py`` names ``fused_...``."""

PREFIX = "%fused_"


def read(ctx: dict):
    trace, steps = ctx.get("trace"), ctx.get("steps")
    if not trace or not steps:
        return None
    kernels = [k for k in trace["kernels"] if k.rsplit("/", 1)[-1].startswith(PREFIX)]
    if not kernels:
        return None
    return 1e3 * sum(trace["op_s"][k] for k in kernels) / steps
