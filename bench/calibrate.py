"""Read the numbers that decide ``correct`` on many seeds in one process:
the program's, the control's and each fault's that the cell can have.
The limits in ``bench/cells/`` were set from its output (see PERF.md).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --control-seeds 1,2,3

The program is built once and driven from each seed through
its first steps, as a run's set-up does.  The control is the reference
computed in bfloat16 put in the program's place; the half-batch fault is
the reference over half of each batch's rows.  A step that leaves its
state unchanged reads 1 by construction and needs no run.

Each reading is one JSON line on standard output.  Needs the chip, like
``run.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(BENCH_DIR))
sys.path.insert(0, os.path.join(os.path.dirname(BENCH_DIR), "src"))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from bench import run  # noqa: E402


def emit(seed: int, who: str, numbers) -> None:
    print(json.dumps({"seed": seed, "who": who, **dict(numbers)}), flush=True)


def train(specs: dict, control: set) -> None:
    import jax.numpy as jnp

    mod = run.load_kind("train")
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
    if traffic["kind"] != "train":
        ap.error(f"no calibration for kind {traffic['kind']!r}")
    train(specs, control)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
