"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \\
        --reduced --steps 200 --batch 8 --seq 256

Wires together every subsystem: config → model → sharded data pipeline →
optimizer → fault-tolerant runtime loop (checkpoint/restart, straggler
watchdog) → metrics.  On this CPU container use ``--reduced``; on a real
cluster drop it and point ``--mesh`` at the production topology.

``--compiler myia`` swaps the jax-AD train step for the Myia-compiled one
(``launch/myia_step.py``): the loss+adjoint is one graph through the
paper pipeline (parse → ST-AD → infer → optimize → fuse → lower), and
under ``--data-mesh``/``--model-mesh`` > 1 it executes as a per-shard
program under ``shard_map`` (the SPMD tier, ``repro.core.spmd``).  To
simulate a mesh on CPU, force host devices before launch:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
    PYTHONPATH=src python -m repro.launch.train --compiler myia \\
        --reduced --data-mesh 2 --model-mesh 2 --steps 20
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.data import DataConfig, SyntheticLM
from repro.distributed import (
    jit_train_step,
    make_rules,
    make_train_state_fn,
    make_train_step,
)
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh
from repro.optim import OptConfig, make_optimizer
from repro.parallel import mesh_context
from repro.runtime import TrainLoopConfig, train_loop


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b", choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--optimizer", default="adamw", choices=("adamw", "adafactor"))
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-mesh", type=int, default=1, help="data axis size (local devices)")
    ap.add_argument("--model-mesh", type=int, default=1)
    ap.add_argument(
        "--compiler",
        default="jax",
        choices=("jax", "myia"),
        help="jax: production jax-AD step; myia: the paper pipeline "
        "(optimized+fused graph, shard_map under a mesh)",
    )
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    ds = SyntheticLM(
        DataConfig(vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch)
    )

    use_mesh = args.data_mesh * args.model_mesh > 1
    mesh = make_local_mesh(args.data_mesh, args.model_mesh) if use_mesh else None

    if args.compiler == "myia":
        return _train_myia(args, cfg, ds, mesh)

    opt = make_optimizer(
        OptConfig(name=args.optimizer, lr=args.lr, warmup_steps=args.steps // 10,
                  total_steps=args.steps)
    )
    with mesh_context(mesh, make_rules(cfg)) as ctx:
        init_fn = make_train_state_fn(cfg, opt)
        if ctx is not None:
            state_sds = jax.eval_shape(init_fn)
            batch_sds = {
                k: jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32)
                for k in ("tokens", "labels")
            }
            step_jit, st_sh = jit_train_step(cfg, opt, ctx, state_sds, batch_sds)
            shardings = st_sh
        else:
            step_jit = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
            shardings = None

        t_start = time.monotonic()

        def on_step(step, metrics):
            if step % 10 == 0:
                print(
                    f"step {step:5d} loss {float(metrics['loss']):.4f} "
                    f"gnorm {float(metrics['gnorm']):.3f} "
                    f"({(time.monotonic()-t_start):.1f}s)"
                )

        result = train_loop(
            TrainLoopConfig(
                total_steps=args.steps,
                checkpoint_every=args.ckpt_every,
                checkpoint_dir=args.ckpt_dir,
            ),
            step_jit,
            init_fn,
            lambda s: {k: jnp.asarray(v) for k, v in ds.batch(s).items()},
            shardings=shardings,
            on_step=on_step,
        )

    first = np.mean(result.losses[:10]) if len(result.losses) >= 10 else result.losses[0]
    last = np.mean(result.losses[-10:])
    print(
        f"\ndone: {result.final_step} steps, loss {first:.4f} → {last:.4f}, "
        f"{result.restarts} restarts, {len(result.straggler_events)} straggler flags"
    )
    return 0


def _train_myia(args, cfg, ds, mesh) -> int:
    """The Myia-compiled e2e step: same train_loop, same checkpointing —
    the loss+adjoint runs through the paper pipeline, sharded under an
    active mesh, on the single-device tier otherwise."""
    from repro.launch.myia_step import MyiaLMDims, make_myia_train_step

    if args.optimizer != "adamw":  # adamw is the argparse default
        print(
            f"warning: --compiler myia uses plain SGD; --optimizer {args.optimizer} ignored"
        )

    dims = MyiaLMDims.from_config(cfg)
    step_fn, init_fn = make_myia_train_step(
        dims, args.batch, args.seq, lr=args.lr, fuse=True
    )

    t_start = time.monotonic()

    def on_step(step, metrics):
        if step % 10 == 0:
            print(
                f"step {step:5d} loss {float(metrics['loss']):.4f} "
                f"gnorm {float(metrics['gnorm']):.3f} "
                f"({(time.monotonic()-t_start):.1f}s)"
            )

    with mesh_context(mesh, {}):
        result = train_loop(
            TrainLoopConfig(
                total_steps=args.steps,
                checkpoint_every=args.ckpt_every,
                checkpoint_dir=args.ckpt_dir,
            ),
            step_fn,
            init_fn,
            lambda s: {k: jnp.asarray(v) for k, v in ds.batch(s).items()},
            on_step=on_step,
        )

    tier = "shard_map" if mesh is not None else "single-device"
    first = result.losses[0]
    last = np.mean(result.losses[-10:])
    print(
        f"\ndone [myia/{tier}]: {result.final_step} steps, "
        f"loss {first:.4f} → {last:.4f}, {result.restarts} restarts"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
