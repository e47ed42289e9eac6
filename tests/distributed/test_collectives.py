"""Numeric collective tests on 8 forced host devices (subprocess — the
main pytest process has a locked 1-device backend)."""

import os
import subprocess
import sys
import textwrap


def run_with_devices(body: str, n: int = 8) -> str:
    script = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={n}"
        import sys
        sys.path.insert(0, {os.path.abspath('src')!r})
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import AxisType, PartitionSpec as P
        mesh = jax.make_mesh(({n},), ("d",), axis_types=(AxisType.Auto,))
        """
    ) + textwrap.dedent(body)
    res = subprocess.run(
        [sys.executable, "-c", script], capture_output=True, text=True, timeout=300
    )
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


class TestInt8AllReduce:
    def test_matches_exact_sum_within_quant_error(self):
        out = run_with_devices(
            """
            from repro.distributed.collectives import int8_allreduce
            xs = jax.random.normal(jax.random.PRNGKey(0), (8, 133))
            def f(x, e):
                o, err = int8_allreduce(x[0], "d", e[0])
                return o[None], err[None]
            sf = jax.shard_map(f, mesh=mesh, in_specs=(P("d", None),)*2,
                               out_specs=(P("d", None),)*2)
            out, err = sf(xs, jnp.zeros((8, 133), jnp.float32))
            expect = jnp.sum(xs, axis=0)
            rel = float(jnp.max(jnp.abs(out[0]-expect)) / jnp.max(jnp.abs(expect)))
            assert rel < 0.05, rel
            for i in range(8):
                np.testing.assert_allclose(np.asarray(out[i]), np.asarray(out[0]))
            print("REL", rel)
            """
        )
        assert "REL" in out

    def test_error_feedback_reduces_bias(self):
        """Accumulating EF makes the *average* reduced gradient unbiased:
        the mean over repeated reductions converges to the exact sum."""
        out = run_with_devices(
            """
            from repro.distributed.collectives import int8_allreduce
            xs = jax.random.normal(jax.random.PRNGKey(1), (8, 257)) * 0.1
            def f(x, e):
                o, err = int8_allreduce(x[0], "d", e[0])
                return o[None], err[None]
            sf = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P("d", None),)*2,
                               out_specs=(P("d", None),)*2))
            expect = np.asarray(jnp.sum(xs, axis=0))
            err = jnp.zeros((8, 257), jnp.float32)
            acc = np.zeros(257)
            N = 64
            for _ in range(N):
                o, err = sf(xs, err)
                acc += np.asarray(o[0])
            bias_ef = np.abs(acc / N - expect).mean()
            o1, _ = sf(xs, jnp.zeros_like(err))
            bias_1 = np.abs(np.asarray(o1[0]) - expect).mean()
            print("BIAS", bias_ef, bias_1)
            assert bias_ef < bias_1 * 0.6, (bias_ef, bias_1)
            """
        )
        assert "BIAS" in out


class TestRingMatmul:
    def test_matches_dense(self):
        run_with_devices(
            """
            from repro.distributed.collectives import ring_reduce_scatter_matmul
            X = jax.random.normal(jax.random.PRNGKey(1), (32, 64))
            W = jax.random.normal(jax.random.PRNGKey(2), (64, 16))
            sg = jax.shard_map(lambda x, w: ring_reduce_scatter_matmul(x, w, "d"),
                               mesh=mesh, in_specs=(P(None, "d"), P("d", None)),
                               out_specs=P("d", None))
            np.testing.assert_allclose(np.asarray(sg(X, W)), np.asarray(X @ W),
                                       rtol=2e-4, atol=2e-4)
            """
        )

    def test_various_shapes(self):
        run_with_devices(
            """
            from repro.distributed.collectives import ring_reduce_scatter_matmul
            for (m, K, N) in [(8, 32, 8), (64, 128, 32), (16, 64, 128)]:
                X = jax.random.normal(jax.random.PRNGKey(m), (m, K))
                W = jax.random.normal(jax.random.PRNGKey(K), (K, N))
                sg = jax.shard_map(lambda x, w: ring_reduce_scatter_matmul(x, w, "d"),
                                   mesh=mesh, in_specs=(P(None, "d"), P("d", None)),
                                   out_specs=P("d", None))
                np.testing.assert_allclose(np.asarray(sg(X, W)), np.asarray(X @ W),
                                           rtol=3e-4, atol=3e-4)
            """
        )


class TestShardedTrainStep:
    def test_two_by_four_mesh_train_step_runs(self):
        """A real sharded train step on a (2,4) host-device mesh: loss
        decreases and state shardings hold."""
        out = run_with_devices(
            """
            from repro.configs import get_config
            from repro.distributed import jit_train_step, make_rules, make_train_state_fn
            from repro.optim import OptConfig, make_optimizer
            from repro.parallel import mesh_context
            from repro.data import DataConfig, SyntheticLM
            mesh2 = jax.make_mesh(
                (2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2
            )
            cfg = get_config("internlm2-1.8b", reduced=True)
            # warmup_steps=1: the default 100-step warmup leaves lr ≈ 0 for
            # all 8 steps and the decrease assertion would ride on batch noise
            opt = make_optimizer(OptConfig(lr=1e-3, warmup_steps=1))
            ds = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=4))
            with mesh_context(mesh2, make_rules(cfg)) as ctx:
                init = make_train_state_fn(cfg, opt)
                state_sds = jax.eval_shape(init)
                batch0 = {k: jnp.asarray(v) for k, v in ds.batch(0).items()}
                step_jit, st_sh = jit_train_step(cfg, opt, ctx, state_sds, batch0)
                state = jax.tree.map(lambda x, s: jax.device_put(x, s), init(), st_sh)
                losses = []
                for i in range(8):
                    b = {k: jnp.asarray(v) for k, v in ds.batch(i).items()}
                    state, m = step_jit(state, b)
                    losses.append(float(m["loss"]))
            assert losses[-1] < losses[0], losses
            print("LOSSES", losses[0], losses[-1])
            """,
            n=8,
        )
        assert "LOSSES" in out
