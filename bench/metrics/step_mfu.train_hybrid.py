"""step_mfu.train_hybrid: model FLOP/s of the window's train steps over the
chips' bf16 peak, in %.  FLOPs are the benchmark's own count for the
hybrid's forward and backward at the traffic's row length
(``counts_hybrid.granite4h_train_flops_per_token``); the time is the
window's, on the host clock."""

from bench import counts_hybrid


def read(ctx: dict):
    steps = ctx.get("steps")
    if not steps:
        return None
    per_token = counts_hybrid.granite4h_train_flops_per_token(ctx["config"], ctx["traffic"]["seq"])
    flops = per_token * ctx["tokens_per_step"] * steps
    return 100.0 * flops / ctx["window_s"] / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
