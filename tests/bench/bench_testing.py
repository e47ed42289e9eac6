"""Drive ``bench/run.py`` in this process on the CPU, for the benchmark's
tests.  The look for a chip is replaced here, in the tests, and nowhere
in the harness: ``find_chips`` returns the CPU device and the peaks are
the v5e's, so the rest of a run goes exactly as on the chip."""

from __future__ import annotations

import json
import os
import sys

import jax

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import run, trace_reduce  # noqa: E402

SEED = 2**33 + 12345
TRAFFIC_TINY = {"train": {"batch": 4, "seq": 32}}
#: the contract's keys of a result line, in order (``breakdown`` traced only)
KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
KEYS_TRACED = ["correct", "attempted", "failed", "metrics", "device", "breakdown", "checks"]


def tiny_cell(load_cell, sizes=None):
    """``load_cell`` at the configuration's ``tiny`` widths (or ``sizes``)
    and a small batch: what a CPU test run can hold."""

    def load(name, bench=None):
        cell, config, traffic, limits = load_cell(name, bench)
        config = dict(config, **(config["tiny"] if sizes is None else sizes))
        traffic = dict(traffic, **TRAFFIC_TINY[traffic["kind"]])
        return cell, config, traffic, limits

    return load


def with_cpu_device(load):
    """The profiler's CPU trace has no TPU plane: stand the harness's own
    step annotations in for device ops, so the reduction has something
    to read."""

    def loaded(path):
        t = load(path)
        ops = [
            ['%fused_map0_cpu = f32[] custom-call(), custom_call_target="tpu_custom_call"', s, d]
            for n, s, d in t["host"]
            if n == "bench.step"
        ]
        return {"devices": {trace_reduce.DEVICE_PREFIX + "0": ops}, "host": t["host"]}

    return loaded


def rehearse(monkeypatch, capsys, run_mod, workload: str, cache_dir, *, trace: int = 0,
             seconds: float = 1.0, tiny: bool = True, sizes: dict | None = None,
             seed: int = SEED):
    """One run of ``workload`` through ``run_mod.main``; returns (exit
    code, result object or None, standard error).  The harness's cache
    directory is ``cache_dir``, and JAX's persistent compilation cache
    stays off, as the rest of the test suite expects."""
    monkeypatch.setattr(run_mod, "find_chips", lambda n: jax.devices()[:n])
    monkeypatch.setattr(run_mod, "enable_caches", lambda: None)
    monkeypatch.setattr(run_mod, "CACHE_DIR", str(cache_dir))
    monkeypatch.setattr(
        run_mod, "device_peaks", lambda device: run_mod.common.peaks_for("TPU v5 lite")
    )
    if tiny:
        monkeypatch.setattr(run_mod, "load_cell", tiny_cell(run_mod.load_cell, sizes))
    monkeypatch.setattr(trace_reduce, "load", with_cpu_device(trace_reduce.load))
    rc = run_mod.main(
        ["--workload", workload, "--seed", str(seed), "--seconds", str(seconds),
         "--trace", str(trace)]
    )
    out, err = capsys.readouterr()
    lines = out.strip().splitlines()
    return rc, (json.loads(lines[-1]) if rc == 0 and lines else None), err


__all__ = ["KEYS", "KEYS_TRACED", "ROOT", "SEED", "rehearse", "run"]
