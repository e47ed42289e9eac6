"""A closed loop of train steps of the Granite 4.0-H hybrid through the
compiler's step function.

Everything is the ``train`` kind's (``kinds/train.py``): set-up, the
window, the check against the plain reference over the first steps.
What differs: the step is ``repro.launch.myia_hybrid.make_hybrid_train_step``
(``fuse=True``); each step's batch is made while the device runs the
step before it; set-up also keeps the attributes of the program's spans
(the ``ad.grad`` span's loop-adjoint counters); the reference's readings
start from weights no one else holds, since two sets of this model's
weights, its gradients and a row's activations do not fit one chip
together; and a traced run times, besides the whole loss+gradient
against ``jax.jit(jax.value_and_grad)`` of the reference, one Mamba-2 +
MLP layer's loss+gradient through the compiler against JAX's.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from bench.common import key_from_seed
from bench.kinds import train

gaps = train.gaps


class Kind(train.Kind):
    def build_step(self):
        """The program's train step: (state, batch) -> (state, metrics)."""
        from repro.launch.myia_hybrid import HybridDims, make_hybrid_train_step

        dims = HybridDims(self.spec.config)
        step_fn, _ = make_hybrid_train_step(
            dims, self.feed.batch_size, self.feed.seq, self.lr, fuse=True
        )
        return step_fn

    def __init__(self, spec) -> None:
        super().__init__(spec)
        #: (step, batch): the batch made while the device ran the step before
        self.prefetched: tuple | None = None

    def step(self):
        """One step, as ``train.Kind.step``, with the next step's batch made
        while the device runs this one (a training input pipeline's
        prefetch): the feed's host work, ~5 ms a step, otherwise idles the
        device, and its per-process speed moved whole runs by 1.7%."""
        i = self.next_step
        hit = self.prefetched is not None and self.prefetched[0] == i
        batch = self.prefetched[1] if hit else self.batch(i)
        with jax.profiler.TraceAnnotation("bench.step"):
            self.state, metrics = self.step_fn(self.state, batch)
        self.prefetched = (i + 1, self.batch(i + 1))
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready((self.state["params"], metrics["loss"]))
        self.next_step += 1
        return metrics["loss"]

    def setup(self) -> None:
        super().setup()
        self.readings["setup_span_attrs"] = [
            (e.name, dict(e.attrs)) for e in self.tracer.events if e.kind == "span"
        ]

    def reference_readings(self, rows: int | None = None, **kw) -> dict:
        """The reference's readings over the first steps' batches (their
        first ``rows`` rows only, where given: the half-batch fault), at
        the configuration's matmul precision unless ``kw`` says otherwise."""
        cfg = self.spec.config
        kw.setdefault("precision", cfg["matmul_precision"])
        batches = [
            (tokens[:rows], labels[:rows])
            for tokens, labels in map(self.feed.batch, range(self.first_steps))
        ]
        return self.spec.reference.sgd_readings(
            self.make_params(key_from_seed(self.spec.seed)), batches, self.lr, cfg=cfg, **kw
        )

    def extra(self) -> None:
        """Traced runs only: time the program's loss+gradient, and one
        Mamba-2 + MLP layer's, each against ``jax.jit(jax.value_and_grad)``
        of the reference's spelling (each layer under ``jax.checkpoint``),
        at default precision, on the same inputs."""
        from repro.core import api
        from repro.launch.myia_hybrid import HybridDims, build_mamba_layer_loss

        ref, cfg = self.spec.reference, self.spec.config
        B, S = self.feed.batch_size, self.feed.seq
        params = self.state["params"]
        batch = self.batch(self.next_step)
        n = len(params)

        def jnp_loss(*a):
            return ref.mean_loss(a[:n], a[n], a[n + 1], cfg=cfg)

        self._time(
            {
                "myia_vag_s": self.step_fn.vag,
                "jax_vag_s": jax.jit(jax.value_and_grad(jnp_loss, argnums=tuple(range(n)))),
            },
            (*params, batch["tokens"], batch["labels"]),
        )
        del jnp_loss

        layer = tuple(w[0] for w in params[2 : 2 + ref.N_MAMBA])
        h = jax.random.normal(key_from_seed(self.spec.seed), (B, S, cfg["hidden_size"]))
        wrt = tuple(range(1 + len(layer)))

        def jnp_layer(h, *w):
            return jnp.sum(ref.mamba_layer(cfg, h, w))

        myia_layer = api.value_and_grad(
            build_mamba_layer_loss(HybridDims(cfg), B, S),
            wrt=wrt,
            options=api.CompileOptions(fuse=True),
        )
        self._time(
            {
                "myia_layer_vag_s": myia_layer,
                "jax_layer_vag_s": jax.jit(jax.value_and_grad(jnp_layer, argnums=wrt)),
            },
            (h, *layer),
        )

    def _time(self, fns: dict, args: tuple) -> None:
        totals = dict.fromkeys(fns, 0.0)
        for fn in fns.values():
            jax.block_until_ready(fn(*args))
            jax.block_until_ready(fn(*args))
        for _ in range(train.TIMED_ROUNDS):
            for name, fn in fns.items():
                t0 = time.perf_counter()
                for _ in range(train.TIMED_CALLS):
                    jax.block_until_ready(fn(*args))
                totals[name] += time.perf_counter() - t0
        self.readings.update(totals)
