"""The program's own spans in the benchmark: the reader of
``setup_compile_s``, what a set-up records, and an untraced window that
arms nothing."""

import gc
import json
import os
import types

import pytest
from jax._src import monitoring

from bench_testing import ROOT, rehearse, run
from repro.obs import trace as obs_trace

DATA = os.path.join(ROOT, "tests", "bench", "data")
CELL = "myia-tanhlm.train"
READ = run.load_reader("setup_compile_s")


def _listeners() -> tuple:
    return (
        monitoring.get_scalar_listeners(),
        monitoring.get_event_time_span_listeners(),
        monitoring.get_event_listeners(),
    )


def test_setup_compile_s_is_the_union_of_the_programs_compile_spans():
    spans = [
        ("jit.trace", 0.0, 0.2),           # the harness's weights: in no program span
        ("jit.lower", 0.2, 0.9),
        ("jit.compile", 0.9, 1.0),
        ("parse", 1.0, 1.1),
        ("train.step", 1.2, 3.0),
        ("train.vag", 1.2, 3.0),
        ("xla.tier0_compile", 1.5, 2.5),
        ("jit.trace", 1.5, 1.7),           # inside the tier-0 compile
        ("jit.compile", 2.0, 2.5),         # inside it too
        ("host.gc", 3.5, 3.6),
        ("jit.compile", 3.5, 3.55),        # inside a collection only: not the program's
        ("train.step", 4.0, 9.0),
        ("jit.trace", 4.0, 4.5),
        ("jit.lower", 4.5, 5.0),
        ("jit.compile", 5.0, 8.0),
        ("host.gc", 6.0, 6.5),             # not a compile
        ("jit.compile", 9.5, 9.6),         # the harness's check, after the steps
        ("train.step", 10.0, None),        # still open: left out
        ("jit.compile", 10.0, 10.5),
    ]
    assert READ({"setup_spans": spans}) == pytest.approx(1.0 + 4.0)
    # the same set-up with a warm persistent cache: loads in place of compiles
    warm = [(n.replace("jit.compile", "jit.cache_load"), t0, t1) for n, t0, t1 in spans]
    assert READ({"setup_spans": warm}) == pytest.approx(1.0 + 4.0)


def test_setup_compile_s_reads_nothing_when_the_program_missed_the_cache():
    spans = [
        ("train.step", 0.0, 9.0),
        ("jit.cache_load", 1.0, 1.2),
        ("jit.compile", 2.0, 8.0),
        ("jit.cache_write", 7.9, 8.0),     # cold for one executable of the program
    ]
    assert READ({"setup_spans": spans}) is None
    # a miss of the harness's own compile, outside the program's spans, is not one
    harness = [("jit.compile", 10.0, 11.0), ("jit.cache_write", 10.9, 11.0)]
    assert READ({"setup_spans": spans[:2] + harness}) == pytest.approx(0.2)


def test_setup_compile_s_reads_nothing_without_the_jit_spans():
    # a program that records only its own tier-0 span, as one without the
    # compile listener does
    assert READ({"setup_spans": [("xla.tier0_compile", 0.0, 2.0)]}) is None
    assert READ({}) is None


@pytest.mark.parametrize("record,names", [
    ("train_setup_spans_v5e.json", {"jit.cache_load"}),
    ("train_setup_spans_cold_v5e.json", {"jit.cache_load", "jit.compile", "jit.cache_write"}),
])
def test_recorded_chip_setup_spans(record, names):
    """Set-up spans of traced runs on a TPU v5e (16 x 256 tokens), trimmed
    to the compile, pipeline and step spans, with a warm persistent cache
    and with one the program's loss+gradient missed: both set-up readers
    read what they read from the whole record, and the cold one reads no
    compile time."""
    with open(os.path.join(DATA, record)) as f:
        rec = json.load(f)
    ctx = {"setup_spans": [tuple(s) for s in rec["setup_spans"]]}
    if rec["setup_compile_s"] is None:
        assert READ(ctx) is None
    else:
        assert READ(ctx) == pytest.approx(rec["setup_compile_s"], rel=1e-9)
    assert run.load_reader("pipeline_s")(ctx) == pytest.approx(rec["pipeline_s"], rel=1e-9)
    found = {n for n, _, _ in rec["setup_spans"]}
    assert {"jit.trace", "jit.lower", "xla.tier0_compile", "train.step"} | names <= found
    assert "jit.cache_write" not in found - names


def _spying_kinds(seen: dict):
    """``run.load_kind`` whose kinds note what is armed as the window opens."""
    load_kind = run.load_kind

    def load(kind):
        base = load_kind(kind).Kind

        class Kind(base):
            def window(self, seconds):
                seen.update(
                    kind=self,
                    armed=obs_trace.active(),
                    gc_callbacks=list(gc.callbacks),
                    listeners=_listeners(),
                )
                return super().window(seconds)

        return types.SimpleNamespace(Kind=Kind)

    return load


@pytest.mark.parametrize("trace", [0, 1])
def test_the_window_arms_nothing_and_set_up_records_the_program_spans(
    monkeypatch, capsys, tmp_path, trace
):
    seen: dict = {}
    monkeypatch.setattr(run, "load_kind", _spying_kinds(seen))
    gc_before, listeners_before = list(gc.callbacks), _listeners()
    rc, res, err = rehearse(monkeypatch, capsys, run, CELL, tmp_path, trace=trace)
    assert rc == 0, err
    assert res["correct"] is True
    # the window runs with no tracer armed, no gc hook and no JAX listener
    assert seen["armed"] is None
    assert seen["gc_callbacks"] == gc_before and seen["listeners"] == listeners_before
    assert list(gc.callbacks) == gc_before and _listeners() == listeners_before
    # set-up's tracer holds the step, compile and gc spans, and dropped none
    tracer = seen["kind"].tracer
    assert tracer.dropped == 0
    names = {e.name for e in tracer.events}
    assert {"train.step", "train.vag", "train.update", "jit.trace", "jit.lower",
            "jit.compile", "xla.tier0_compile", "host.gc"} <= names
    if trace:
        assert res["metrics"]["setup_compile_s"]["value"] > 0
