"""mamba_over_jax.train_hybrid: time of one Mamba-2 + MLP layer's
loss+gradient (the sum of its output, gradients of its input and its 13
weights) through the compiler, over that of ``jax.jit(jax.value_and_grad)``
of the reference's layer (base: the jax call), at the cell's batch and
row length; same inputs, default precision, the same number of calls
each ended by ``block_until_ready``; host clock, after the traced window."""


def read(ctx: dict):
    if "myia_layer_vag_s" not in ctx or "jax_layer_vag_s" not in ctx:
        return None
    return ctx["myia_layer_vag_s"] / ctx["jax_layer_vag_s"]
