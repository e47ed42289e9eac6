"""myia_over_jax.train_hybrid: time of the program's loss+gradient call
over that of ``jax.jit(jax.value_and_grad)`` of the reference's jnp
spelling of the same loss, each layer under ``jax.checkpoint`` (base: the
jax call); same inputs, default precision, the same number of calls each
ended by ``block_until_ready``; host clock, after the traced window."""


def read(ctx: dict):
    if "myia_vag_s" not in ctx or "jax_vag_s" not in ctx:
        return None
    return ctx["myia_vag_s"] / ctx["jax_vag_s"]
