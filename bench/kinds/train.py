"""A closed loop of train steps through the compiler's step function.

Set-up builds the step (``repro.launch.myia_step.make_myia_train_step``,
``fuse=True``: parse, ST-AD, optimize, fuse into Pallas kernels, lower).
Its first call answers from a tier-0 compile that the window never runs,
so set-up makes that call once and throws its result away; it then
drives the step from the seed through the traffic's first steps, all on
the fully optimized program that the window runs.  The window then runs
the same object on, step after step, each ending in ``block_until_ready``.

The check follows the configuration's plain reference over the same first
steps, from the same weights and batches, and compares each step's loss,
the per-leaf norm of the first gradient as the update applied it, and the
per-leaf norm of the parameters' change after the first steps.
"""

from __future__ import annotations

import time

import numpy as np

import jax
import jax.numpy as jnp

from bench import compare
from bench.common import key_from_seed
from bench.traffic import TrainFeed

#: calls of each loss+gradient, per round, that ``myia_over_jax`` times
TIMED_CALLS = 10
TIMED_ROUNDS = 2


class Kind:
    def __init__(self, spec) -> None:
        from repro.obs.trace import Tracer

        self.spec = spec
        cfg = spec.config
        self.lr = float(cfg["optimizer"]["lr"])
        self.feed = TrainFeed(spec.traffic, cfg["vocab_size"], spec.seed)
        self.first_steps = int(spec.traffic["first_steps"])
        self.make_params = spec.reference.param_maker(cfg)
        self.tracer = Tracer()
        self.readings: dict = {"tokens_per_step": self.feed.tokens_per_step}
        self.next_step = 0

    # -- the program ---------------------------------------------------------
    def build_step(self):
        """The program's train step: (state, batch) -> (state, metrics)."""
        from repro.launch.myia_step import MyiaLMDims, make_myia_train_step

        cfg = self.spec.config
        dims = MyiaLMDims(cfg["vocab_size"], cfg["hidden_size"], cfg["intermediate_size"])
        step_fn, _ = make_myia_train_step(
            dims, self.feed.batch_size, self.feed.seq, self.lr, fuse=True
        )
        return step_fn

    def batch(self, step: int) -> dict:
        with jax.profiler.TraceAnnotation("bench.batch"):
            tokens, labels = self.feed.batch(step)
            return {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}

    def step(self):
        """One step of the window's own call and feed; returns its loss."""
        batch = self.batch(self.next_step)
        with jax.profiler.TraceAnnotation("bench.step"):
            self.state, metrics = self.step_fn(self.state, batch)
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready((self.state["params"], metrics["loss"]))
        self.next_step += 1
        return metrics["loss"]

    # -- phases --------------------------------------------------------------
    def setup(self) -> None:
        from repro.obs.trace import tracing

        key = key_from_seed(self.spec.seed)
        with tracing(self.tracer):
            self.step_fn = self.build_step()
            params0 = self.make_params(key)
            self.state = {"params": params0, "step": jnp.zeros((), jnp.int32)}
            with jax.profiler.TraceAnnotation("bench.tier0"):
                jax.block_until_ready(self.step_fn(self.state, self.batch(0)))
            losses = [float(self.step())]
            first = np.asarray(compare.leaf_norms(params0, self.state["params"])) / self.lr
            del params0
            while self.next_step < self.first_steps:
                losses.append(float(self.step()))
        change = np.asarray(compare.leaf_norms(self.state["params"], self.make_params(key)))
        self.program = {
            "losses": losses,
            "first_grad_norms": [float(x) for x in first],
            "change_norms": [float(x) for x in change],
        }
        self.readings["setup_spans"] = [
            (e.name, e.t0, e.t1) for e in self.tracer.events if e.kind == "span"
        ]

    def window(self, seconds: float) -> dict:
        losses, ends = [], []
        t0 = time.perf_counter()
        while True:
            losses.append(self.step())
            elapsed = time.perf_counter() - t0
            ends.append(elapsed)
            if elapsed >= seconds:
                break
        step_s = np.diff([0.0] + ends)
        failed = sum(not np.isfinite(float(x)) for x in losses)
        steps = len(losses)
        self.readings["steps"] = steps
        return {
            "metrics": {"train_tokens_per_s": steps * self.feed.tokens_per_step / elapsed},
            "attempted": steps,
            "failed": int(failed),
            "window_s": elapsed,
            "notes": f"step median {np.median(step_s) * 1e3:.2f} ms, slowest "
            f"{step_s.max() * 1e3:.2f} ms",
        }

    def extra(self) -> None:
        """Traced runs only: time the program's loss+gradient against
        ``jax.jit(jax.value_and_grad)`` of the reference's jnp spelling of
        the same loss, at default precision, on the same inputs."""
        ref = self.spec.reference

        def jnp_loss(emb, w1, w2, wout, tokens, labels):
            return ref.mean_loss((emb, w1, w2, wout), tokens, labels)

        batch = self.batch(self.next_step)
        args = (*self.state["params"], batch["tokens"], batch["labels"])
        fns = {
            "myia_vag_s": self.step_fn.vag,
            "jax_vag_s": jax.jit(jax.value_and_grad(jnp_loss, argnums=(0, 1, 2, 3))),
        }
        totals = dict.fromkeys(fns, 0.0)
        for fn in fns.values():
            jax.block_until_ready(fn(*args))
        for _ in range(TIMED_ROUNDS):
            for name, fn in fns.items():
                t0 = time.perf_counter()
                for _ in range(TIMED_CALLS):
                    jax.block_until_ready(fn(*args))
                totals[name] += time.perf_counter() - t0
        self.readings.update(totals)

    def release(self) -> None:
        del self.state, self.step_fn

    def reference_readings(self, rows: int | None = None, **kw) -> dict:
        """The reference's readings over the first steps' batches (their
        first ``rows`` rows only, where given: the half-batch fault), at the
        configuration's matmul precision unless ``kw`` says otherwise."""
        kw.setdefault("precision", self.spec.config["matmul_precision"])
        batches = [
            (tokens[:rows], labels[:rows])
            for tokens, labels in map(self.feed.batch, range(self.first_steps))
        ]
        params0 = self.make_params(key_from_seed(self.spec.seed))
        return self.spec.reference.sgd_readings(params0, batches, self.lr, **kw)

    def check(self) -> list[tuple[str, float, float]]:
        return gaps(self.program, self.reference_readings(), self.spec.limits)


def gaps(got: dict, want: dict, limits: dict) -> list[tuple[str, float, float]]:
    """The numbers compared, each with its limit."""
    keep = compare.kept_leaves(want["first_grad_norms"])
    return [
        ("loss_gap", compare.loss_gap(got["losses"], want["losses"]), limits["loss_gap"]),
        (
            "grad_norm_gap",
            compare.worst_leaf_gap(got["first_grad_norms"], want["first_grad_norms"], keep),
            limits["grad_norm_gap"],
        ),
        (
            "change_norm_gap",
            compare.worst_leaf_gap(got["change_norms"], want["change_norms"], keep),
            limits["change_norm_gap"],
        ),
    ]
