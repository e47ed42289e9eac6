"""Benchmark harness: one module per paper claim + the roofline reporter.

    PYTHONPATH=src python -m benchmarks.run            # all benches
    PYTHONPATH=src python -m benchmarks.run --only ad_overhead
    PYTHONPATH=src python -m benchmarks.run --quick    # reduced reps

Results land in ``artifacts/bench/<name>.json`` and a summary prints to
stdout.  The compile-time and AD-overhead rows are additionally written to
``BENCH_compile.json`` / ``BENCH_ad_overhead.json`` at the repo root so
successive PRs leave a perf trajectory to compare against (``--quick`` is
the cheap way to refresh them).  The roofline section only reports if the
dry-run artifacts exist (run ``python -m repro.launch.dryrun`` first)."""

from __future__ import annotations

import argparse
import json
import os
import random
import time

import numpy as np

#: repo-root trajectory files: bench name -> filename
TRAJECTORY = {
    "compile_time": "BENCH_compile.json",
    "ad_overhead": "BENCH_ad_overhead.json",
    "fusion": "BENCH_fusion.json",
    "spmd": "BENCH_spmd.json",
    "higher_order": "BENCH_higher_order.json",
    "serve": "BENCH_serve.json",
}


def _quick_selection(benches: dict) -> dict:
    """Narrow a ``--quick`` sweep to the benches whose module actually
    changed vs HEAD.  Only applies when *every* uncommitted change is a
    ``benchmarks/bench_*.py`` file — anything else (src/, run.py, configs)
    can shift any trajectory, so the full sweep runs.  This stops a
    serve-only bench edit from re-running the whole compile-time corpus."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "diff", "--name-only", "HEAD"],
            capture_output=True, text=True, check=True,
        ).stdout
    except Exception:
        return benches  # not a git checkout: run everything
    changed = {line.strip() for line in out.splitlines() if line.strip()}
    if not changed:
        return benches
    if any(
        not (c.startswith("benchmarks/bench_") and c.endswith(".py"))
        for c in changed
    ):
        return benches
    keep = {
        name: fn
        for name, fn in benches.items()
        if f"benchmarks/bench_{name}.py" in changed
    }
    if not keep:
        return benches
    skipped = sorted(set(benches) - set(keep))
    print(f"--quick: only {sorted(keep)} changed; skipping {skipped}")
    return keep


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument(
        "--quick",
        action="store_true",
        help="reduced reps; still refreshes the BENCH_*.json trajectory files",
    )
    ap.add_argument(
        "--trace",
        default=None,
        metavar="OUT.json",
        help="arm the tracer across the sweep and write a Chrome trace-event "
        "file (open in https://ui.perfetto.dev) covering every compile the "
        "benches trigger",
    )
    args = ap.parse_args(argv)

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()

    from . import (
        bench_ad_overhead,
        bench_compile_time,
        bench_fusion,
        bench_higher_order,
        bench_kernels,
        bench_opt_effectiveness,
        bench_serve,
        bench_spmd,
    )

    benches = {
        "ad_overhead": lambda: bench_ad_overhead.run(reps=5 if args.quick else 30),
        "opt_effectiveness": bench_opt_effectiveness.run,
        "compile_time": lambda: bench_compile_time.run(reps=10 if args.quick else 50),
        "fusion": lambda: bench_fusion.run(reps=10 if args.quick else 50),
        "spmd": lambda: bench_spmd.run(reps=10 if args.quick else 30),
        "higher_order": lambda: bench_higher_order.run(reps=10 if args.quick else 30),
        "serve": bench_serve.run,
        "kernels": bench_kernels.run,
    }
    if args.quick and not args.only:
        # kernels are the slow outlier and have no trajectory file
        benches.pop("kernels")
        benches = _quick_selection(benches)
    from repro.obs import trace as obs_trace

    tracer = obs_trace.Tracer() if args.trace else None
    os.makedirs("artifacts/bench", exist_ok=True)
    for name, fn in benches.items():
        if args.only and name != args.only:
            continue
        print(f"\n=== {name} ===")
        # Reseed the global RNGs per benchmark: trajectory diffs must be a
        # function of the code, not of which benches ran before this one
        # (--only vs the full sweep used to leave different global RNG
        # state, making BENCH json diffs ordering-dependent).
        random.seed(0)
        np.random.seed(0)
        t0 = time.perf_counter()
        with obs_trace.tracing(tracer):
            rows = fn()
        wall = time.perf_counter() - t0
        for row in rows:
            # ride-along provenance: how long the whole bench took, and
            # how heavy its instrumentation got (peak tracer occupancy) —
            # a trajectory diff can then tell "bench got slower" from
            # "tracing got heavier".  Not CI-gated (wall time is noisy).
            row["bench_wall_s"] = round(wall, 3)
            row["trace_buffer_peak"] = tracer.high_water if tracer else 0
        for row in rows:
            print("  ", row)
        with open(f"artifacts/bench/{name}.json", "w") as f:
            json.dump(rows, f, indent=1, default=str)
        if name in TRAJECTORY:
            with open(TRAJECTORY[name], "w") as f:
                json.dump(rows, f, indent=1, default=str)

    # roofline summary (from dry-run artifacts, if present)
    if (args.only in (None, "roofline")) and os.path.isdir("artifacts/dryrun"):
        import glob

        if glob.glob("artifacts/dryrun/*.json"):
            print("\n=== roofline (see EXPERIMENTS.md §Roofline for the analysis) ===")
            from . import roofline

            roofline.main(["--md", "artifacts/roofline.md"])
    if tracer is not None:
        tracer.write_chrome_trace(args.trace)
        print(
            f"\nwrote {len(tracer.events)} spans to {args.trace} "
            f"(open in https://ui.perfetto.dev)"
        )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
