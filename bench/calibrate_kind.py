"""``calibrate.py`` for a cell of any kind built on ``kinds/train.py``: read
the numbers that decide ``correct`` on many seeds in one process, the
program's, the bfloat16 control's and the half-batch fault's.

    python3 bench/calibrate_kind.py --workload <cell> --seeds 1,2,3 --control-seeds 1

The cell's kind (``bench/kinds/<kind>.py``) is loaded by name and must
offer ``Kind`` (with ``setup``, ``release`` and ``reference_readings``)
and ``gaps``, as ``kinds/train.py`` and ``kinds/train_hybrid.py`` do.
The program is built once and driven from each seed through its first
steps, as a run's set-up does.  Output: one JSON line per reading, as
``calibrate.py`` writes them.  Needs the chip.
"""

from __future__ import annotations

import argparse
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import run  # noqa: E402
from bench.calibrate import emit  # noqa: E402


def calibrate(mod, specs: dict, control: set) -> None:
    import jax.numpy as jnp

    step_fn = None
    for seed, spec in specs.items():
        k = mod.Kind(spec)
        if step_fn is not None:
            k.build_step = lambda: step_fn
        k.setup()
        step_fn = k.step_fn
        k.release()
        want = k.reference_readings()
        numbers = [(n, v) for n, v, _ in mod.gaps(k.program, want, spec.limits)]
        emit(seed, "program", numbers + [("program", k.program), ("reference", want)])
        if seed in control:
            got = k.reference_readings(dtype=jnp.bfloat16, precision="default")
            emit(seed, "control_bf16", [(n, v) for n, v, _ in mod.gaps(got, want, spec.limits)])
            got = k.reference_readings(rows=k.feed.batch_size // 2)
            emit(seed, "half_batch", [(n, v) for n, v, _ in mod.gaps(got, want, spec.limits)])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control-seeds", default="", help="comma-separated; a subset of --seeds")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    control = {int(s) for s in args.control_seeds.split(",") if s}
    bench = run.common.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell, config, traffic, limits = run.load_cell(args.workload, bench)
    devices = run.find_chips(int(cell["chips"]))
    os.makedirs(run.CACHE_DIR, exist_ok=True)
    run.enable_caches()
    reference = run.load_module(
        os.path.join(BENCH_DIR, "configs", config["reference"]), "bench_reference"
    )
    peaks = run.device_peaks(devices[0])
    specs = {
        seed: run.Spec(cell, config, traffic, limits, reference, seed, peaks, run.CACHE_DIR)
        for seed in seeds
    }
    calibrate(run.load_kind(traffic["kind"]), specs, control)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
