"""step_mfu.train: model FLOP/s of the window's train steps over the
chips' bf16 peak, in %.  FLOPs are the benchmark's own count for the
tanh-LM's forward and backward (``counts.tanhlm_train_flops_per_token``);
the time is the window's, on the host clock."""

from bench import counts


def read(ctx: dict):
    steps = ctx.get("steps")
    if not steps:
        return None
    flops = counts.tanhlm_train_flops_per_token(ctx["config"]) * ctx["tokens_per_step"] * steps
    return 100.0 * flops / ctx["window_s"] / (ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
