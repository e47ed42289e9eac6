"""Sharding resolver tests on the production (abstract) meshes — no
devices needed: specs are checked structurally."""

import jax
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import get_config
from repro.distributed.sharding import batch_specs, make_rules, param_specs, tree_specs
from repro.models import init_params
from repro.optim import OptConfig, make_optimizer
from repro.parallel import MeshContext


def ctx_for(cfg, multi=False):
    mesh = (
        AbstractMesh((2, 16, 16), ("pod", "data", "model"))
        if multi
        else AbstractMesh((16, 16), ("data", "model"))
    )
    return MeshContext(mesh, make_rules(cfg))


def spec_map(cfg, ctx):
    p = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    specs = param_specs(cfg, p, ctx)
    flat = jax.tree_util.tree_flatten_with_path(p)[0]
    sleaves = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    out = {}
    for (path, leaf), s in zip(flat, sleaves):
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
        out[key] = (tuple(leaf.shape), s)
    return p, out


def find(out, suffix):
    hits = [(k, v) for k, v in out.items() if k.endswith(suffix)]
    assert hits, suffix
    return hits


class TestParamSpecs:
    def test_dense_gqa_specs(self):
        """deepseek: heads=56 not divisible by 16 → replicated; mlp
        sharded; embed dim FSDP-sharded on data (fsdp=True)."""
        cfg = get_config("deepseek-coder-33b")
        _, out = spec_map(cfg, ctx_for(cfg))
        for k, (shape, s) in find(out, "mixer/wq"):
            # (stack, D, H=56, hd): H % 16 != 0 → replicated, D → data (fsdp)
            assert s[-3] == "data" and s[-2] is None, (k, s)
        for k, (shape, s) in find(out, "ffn/wi"):
            assert s[-1] == "model", (k, s)  # d_ff 19200 % 16 == 0

    def test_vocab_sharding(self):
        cfg = get_config("internlm2-1.8b")
        _, out = spec_map(cfg, ctx_for(cfg))
        (k, (shape, s)) = find(out, "embed")[0]
        assert shape == (92544, 2048) and s[0] == "model"  # vocab % 16 == 0

    def test_moe_expert_parallel(self):
        """kimi: 384 experts % 16 == 0 → expert dim sharded."""
        cfg = get_config("kimi-k2-1t-a32b")
        _, out = spec_map(cfg, ctx_for(cfg))
        hits = [v for k, v in out.items() if k.endswith("ffn/wi") and "shared" not in k]
        for shape, s in hits:
            assert s[-3] == "model", (shape, s)  # (stack, E, D, F): E sharded

    def test_moe_fallback_grok(self):
        """grok: 8 experts on 16-way model axis → fall back to sharding F."""
        cfg = get_config("grok-1-314b")
        _, out = spec_map(cfg, ctx_for(cfg))
        hits = [v for k, v in out.items() if k.endswith("ffn/wi") and "shared" not in k]
        for shape, s in hits:
            e_axis, f_axis = s[-3], s[-1]
            assert e_axis is None and f_axis == "model", (shape, s)

    def test_mamba_specs(self):
        cfg = get_config("mamba2-370m")
        _, out = spec_map(cfg, ctx_for(cfg))
        for k, (shape, s) in find(out, "mixer/in_proj"):
            assert s[-1] == "model", (k, s)

    def test_every_leaf_has_valid_spec(self):
        """Divisibility invariant: every sharded dim divides its axis —
        across all 10 archs × both meshes."""
        from repro.configs import ARCHS

        for arch in ARCHS:
            cfg = get_config(arch)
            for multi in (False, True):
                ctx = ctx_for(cfg, multi)
                sizes = dict(ctx.mesh.shape)
                _, out = spec_map(cfg, ctx)
                for key, (shape, spec) in out.items():
                    for d, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
                        if ax is None:
                            continue
                        axes = ax if isinstance(ax, tuple) else (ax,)
                        n = 1
                        for a in axes:
                            n *= sizes[a]
                        assert d % n == 0, (arch, key, shape, spec)


class TestStateAndBatchSpecs:
    def test_optimizer_state_mirrors_params(self):
        cfg = get_config("internlm2-1.8b")
        ctx = ctx_for(cfg)
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        opt = make_optimizer(OptConfig())
        state = jax.eval_shape(lambda: opt.init(params))
        pspecs = param_specs(cfg, params, ctx)
        ospecs = tree_specs(pspecs, state, params)
        # m and v get exactly the parameter's spec
        assert ospecs["m"]["embed"] == pspecs["embed"]
        p_leaves = jax.tree_util.tree_leaves(pspecs, is_leaf=lambda x: isinstance(x, P))
        m_leaves = jax.tree_util.tree_leaves(ospecs["m"], is_leaf=lambda x: isinstance(x, P))
        assert p_leaves == m_leaves

    def test_adafactor_factored_state_replicated(self):
        cfg = get_config("kimi-k2-1t-a32b")
        ctx = ctx_for(cfg)
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        opt = make_optimizer(OptConfig(name="adafactor"))
        state = jax.eval_shape(lambda: opt.init(params))
        pspecs = param_specs(cfg, params, ctx)
        ospecs = tree_specs(pspecs, state, params)  # must not raise
        assert ospecs is not None

    def test_batch_specs_divisibility(self):
        cfg = get_config("internlm2-1.8b")
        ctx = ctx_for(cfg, multi=True)
        import jax.numpy as jnp

        big = {"tokens": jax.ShapeDtypeStruct((256, 128), jnp.int32)}
        small = {"token": jax.ShapeDtypeStruct((1,), jnp.int32)}
        assert batch_specs(ctx, big)["tokens"][0] == ("pod", "data")
        assert batch_specs(ctx, small)["token"] == P(None)
