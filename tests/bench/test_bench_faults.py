"""The comparison that decides ``correct`` catches what it must: each
fault the train cell can have, planted under the timed path, and the
control (the reference in bfloat16 put in the program's place) come out
not correct.
The look for a chip is skipped here; the rest of each run is the
harness's own."""

import jax.numpy as jnp
import pytest

from bench_testing import rehearse, run

TRAIN = "myia-tanhlm.train"


def plant(monkeypatch, kind: str, make_overrides) -> None:
    """Load the ``kind`` module as the harness does, with its ``Kind``
    replaced by a subclass that ``make_overrides(base)`` breaks."""
    load = run.load_kind

    def load_planted(name):
        mod = load(name)
        if name == kind:
            mod.Kind = type("Planted", (mod.Kind,), make_overrides(mod.Kind))
        return mod

    monkeypatch.setattr(run, "load_kind", load_planted)


def unchanged_state(base):
    def build_step(self):
        real = base.build_step(self)

        def step(state, batch):
            _, metrics = real(state, batch)
            return state, metrics

        step.vag = real.vag
        return step

    return {"build_step": build_step}


def half_batch(base):
    def build_step(self):
        rows = self.feed.batch_size // 2
        self.feed.batch_size = rows
        try:
            real = base.build_step(self)
        finally:
            self.feed.batch_size = 2 * rows

        def step(state, batch):
            return real(state, {k: v[:rows] for k, v in batch.items()})

        step.vag = real.vag
        return step

    return {"build_step": build_step}


def bf16_train_step(base):
    def build_step(self):
        vag = self.spec.reference.make_value_and_grad(dtype=jnp.bfloat16, precision="default")
        lr = self.lr

        def step(state, batch):
            loss, grads = vag(state["params"], batch["tokens"], batch["labels"])
            params = tuple(p - lr * g for p, g in zip(state["params"], grads))
            return {"params": params, "step": state["step"] + 1}, {"loss": loss}

        return step

    return {"build_step": build_step}


def doubled_after_first_call(base):
    """The update moves every leaf twice as far, from the step's second
    call on: a fault only in the fully optimized program that the window
    runs, after the first call's tier-0 compile."""

    def build_step(self):
        real = base.build_step(self)
        calls = [0]

        def step(state, batch):
            calls[0] += 1
            new, metrics = real(state, batch)
            if calls[0] > 1:
                moved = tuple(2 * n - p for n, p in zip(new["params"], state["params"]))
                new = dict(new, params=moved)
            return new, metrics

        step.vag = real.vag
        return step

    return {"build_step": build_step}


def failing_checks(monkeypatch, capsys, tmp_path, fault) -> list[str]:
    plant(monkeypatch, "train", fault)
    rc, res, err = rehearse(monkeypatch, capsys, run, TRAIN, tmp_path)
    assert rc == 0, err
    assert res["correct"] is False, res["checks"]
    failing = [n for n, c in res["checks"].items() if c["value"] > c["limit"]]
    assert failing and all(f"[check] {n} " in err for n in failing)
    return failing


@pytest.mark.parametrize(
    "fault", [unchanged_state, half_batch, bf16_train_step], ids=lambda f: f.__name__
)
def test_fault_is_not_correct(monkeypatch, capsys, tmp_path, fault):
    failing_checks(monkeypatch, capsys, tmp_path, fault)


def test_first_gradient_is_read_from_the_timed_program(monkeypatch, capsys, tmp_path):
    failing = failing_checks(monkeypatch, capsys, tmp_path, doubled_after_first_call)
    assert "grad_norm_gap" in failing
