#!/usr/bin/env python
"""The program's own spans over one run of the benchmark's train cell, and
the cost of an armed tracer: measurements the benchmark's readers do not
make yet, taken without changing the benchmark.

  python3 scripts/program_spans.py traced <seed> <seconds> <outdir> [--cpu-tiny]
  python3 scripts/program_spans.py cost <seed> <seconds> <pairs> <outdir> [--cpu-tiny]

``traced`` runs ``bench/run.py --trace 1`` on the cell in this process, with
a fresh ``Tracer`` armed over the measured window, and writes
``spans_<seed>.json`` (and prints it, less the set-up spans, on a line that
starts with ``SPANS``):

* ``step_host_ms_*``, ``vag_ms_mean``, ``update_ms_mean``: durations of the
  window's ``train.step`` / ``train.vag`` / ``train.update`` spans;
* ``gc_*``: the window's ``host.gc`` spans, per step and by generation;
* ``idle_by_host``: the first device's idle seconds in the window by what
  the host was doing.  Each idle gap is cut at every start and end of a
  host event inside it (the harness's ``bench.*`` annotations and the
  program's spans, which the armed tracer puts on the profiler's host
  plane); each piece is named by ``bench/trace_reduce._label`` at its
  midpoint, that is the innermost (shortest) host event open there, or
  ``bench.none``;
* ``longest_gaps``: the five longest gaps, each named by the same rule at
  its midpoint and split into its pieces;
* ``setup_compile_s`` as the benchmark reads it, and ``setup_compile_all_s``,
  the union of every ``jit.*`` and ``xla.tier0_compile`` span of set-up
  (the harness's own compiles included).

It also writes ``setup_spans_<seed>.json``: the set-up's compile, pipeline
and step spans, in seconds from the first one, with what the benchmark's
``setup_compile_s`` and ``pipeline_s`` read from them.

``cost`` sets the cell up once, then runs ``pairs`` pairs of windows of
``seconds`` each, disarmed and armed in turn (ABBA), with the profiler off,
and prints one ``COST`` line per window.

``--cpu-tiny`` runs either on the CPU at the configuration's tiny widths,
as the benchmark's tests do.
"""

from __future__ import annotations

import json
import os
import statistics
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (ROOT, os.path.join(ROOT, "src"), os.path.join(ROOT, "tests", "bench")):
    if path not in sys.path:
        sys.path.insert(0, path)

from bench import run, trace_reduce  # noqa: E402
from repro.obs import trace as T  # noqa: E402

CELL = "myia-tanhlm.train"
#: the set-up spans kept in ``setup_spans_<seed>.json``
KEPT = ("parse", "ad.grad", "compile_pipeline", "specialize")


def cpu_tiny(cache_dir: str) -> None:
    import jax
    from bench_testing import tiny_cell, with_cpu_device

    run.find_chips = lambda n: jax.devices()[:n]
    run.enable_caches = lambda: None
    run.device_peaks = lambda d: run.common.peaks_for("TPU v5 lite")
    run.load_cell = tiny_cell(run.load_cell)
    trace_reduce.load = with_cpu_device(trace_reduce.load)
    run.CACHE_DIR = cache_dir


def capture(cap: dict) -> None:
    """Keep the program's spans from the profiler's host plane, arm a fresh
    tracer over the window, and time the kind's set-up."""
    load = trace_reduce.load

    def loaded(path):
        from jax.profiler import ProfileData

        t = load(path)
        cap["trace"], cap["program"] = t, [
            [e.name, float(e.start_ns), float(e.duration_ns)]
            for plane in ProfileData.from_file(path).planes
            if plane.name == trace_reduce.HOST_PLANE
            for line in plane.lines
            for e in line.events
            if e.name in T.SPAN_NAMES
        ]
        return t

    trace_reduce.load = loaded
    load_kind = run.load_kind

    def spying(kind):
        base = load_kind(kind).Kind

        class Kind(base):
            def setup(self):
                t0 = time.monotonic()
                super().setup()
                cap["kind_setup_s"] = time.monotonic() - t0
                cap["setup_s"] = time.monotonic() - run.T_START
                cap["kind"] = self

            def window(self, seconds):
                cap["window_tracer"] = tracer = T.Tracer()
                with T.tracing(tracer):
                    return super().window(seconds)

        return types.SimpleNamespace(Kind=Kind)

    run.load_kind = spying


def pieces(host: list, a: float, b: float) -> list:
    """[a, b] cut at the host events' edges inside it, each piece named by
    the innermost host event open at its midpoint: (name, seconds)."""
    edges = sorted({a, b} | {x for _, s, d in host for x in (s, s + d) if a < x < b})
    return [
        (trace_reduce._label(host, (x + y) / 2), (y - x) * 1e-9)
        for x, y in zip(edges, edges[1:])
    ]


def gaps_of(trace: dict) -> list:
    """The first device's idle gaps in the window, in ns."""
    lo, hi = trace_reduce.window_of(trace)
    dev = sorted(trace["devices"])[0]
    busy = trace_reduce.union(
        [(a, b) for _, a, b in trace_reduce._clipped(trace["devices"][dev], lo, hi)]
    )
    edges = [lo] + [x for ab in busy for x in ab] + [hi]
    return [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2) if edges[i + 1] > edges[i]]


def setup_record(spans: list, seed: int) -> dict:
    """The set-up's compile, pipeline and step spans, from the first one."""
    kept = [
        (n, t0, t1) for n, t0, t1 in spans
        if t1 is not None and (n in KEPT or n.startswith(("jit.", "xla.", "train.")))
    ]
    origin = min(t0 for _, t0, _ in kept)
    kept = [(n, round(t0 - origin, 6), round(t1 - origin, 6)) for n, t0, t1 in kept]
    ctx = {"setup_spans": kept}
    return {
        "about": f"set-up spans of one traced run of {CELL} (seed {seed}), kept to the "
        "compile, pipeline and step spans; times in s from the first span",
        "setup_compile_s": run.load_reader("setup_compile_s")(ctx),
        "pipeline_s": run.load_reader("pipeline_s")(ctx),
        "setup_spans": [list(s) for s in kept],
    }


def analyze(cap: dict, seed: int) -> dict:
    trace, kind, tracer = cap["trace"], cap["kind"], cap["window_tracer"]
    host = trace["host"] + cap["program"]
    lo, hi = trace_reduce.window_of(trace)
    gaps = gaps_of(trace)
    idle_by: dict = {}
    for a, b in gaps:
        for name, s in pieces(host, a, b):
            idle_by[name] = idle_by.get(name, 0.0) + s
    gaps.sort(key=lambda ab: ab[0] - ab[1])
    steps = kind.readings["steps"]
    step_ms = [1e3 * e.dur_s for e in tracer.find("train.step")]
    gcs = tracer.find("host.gc")
    setup_spans = kind.readings["setup_spans"]
    compiles = [(t0, t1) for n, t0, t1 in setup_spans
                if t1 is not None and (n.startswith("jit.") or n == "xla.tier0_compile")]
    return {
        "seed": seed,
        "steps": steps,
        "window_s": (hi - lo) * 1e-9,
        "idle_s": sum((b - a) * 1e-9 for a, b in gaps),
        "idle_by_host": idle_by,
        "longest_gaps": [
            {"label": trace_reduce._label(host, (a + b) / 2), "s": (b - a) * 1e-9,
             "pieces": pieces(host, a, b)}
            for a, b in gaps[:5]
        ],
        "step_host_ms_mean": statistics.fmean(step_ms),
        "step_host_ms_median": statistics.median(step_ms),
        "step_host_ms_max": max(step_ms),
        "vag_ms_mean": 1e3 * sum(e.dur_s for e in tracer.find("train.vag")) / steps,
        "update_ms_mean": 1e3 * sum(e.dur_s for e in tracer.find("train.update")) / steps,
        "gc_ms_per_step": 1e3 * sum(e.dur_s for e in gcs) / steps,
        "gc_count": len(gcs),
        "gc_max_ms": 1e3 * max((e.dur_s for e in gcs), default=0.0),
        "gc_by_generation": {
            g: [sum(1 for e in gcs if e.attrs["generation"] == g),
                1e3 * sum(e.dur_s for e in gcs if e.attrs["generation"] == g)]
            for g in (0, 1, 2)
        },
        "window_compiles": [[e.name, e.attrs["fun_name"], e.dur_s] for e in tracer.events
                            if e.name in ("jit.compile", "jit.cache_load")],
        "window_dropped": tracer.dropped,
        "setup_compile_s": run.load_reader("setup_compile_s")({"setup_spans": setup_spans}),
        "setup_compile_all_s": sum(e - s for s, e in trace_reduce.union(compiles)),
        "setup_cache_writes": sum(1 for n, _, _ in setup_spans if n == "jit.cache_write"),
        "setup_gc_s": sum(t1 - t0 for n, t0, t1 in setup_spans if n == "host.gc"),
        "kind_setup_s": cap["kind_setup_s"],
        "setup_s": cap["setup_s"],
        "setup_dropped": kind.tracer.dropped,
    }


def traced(seed: int, seconds: float, outdir: str) -> int:
    cap: dict = {}
    capture(cap)
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", str(seconds),
                   "--trace", "1"])
    out = dict(analyze(cap, seed), rc=rc)
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, f"spans_{seed}.json"), "w") as f:
        json.dump(out, f)
    with open(os.path.join(outdir, f"setup_spans_{seed}.json"), "w") as f:
        json.dump(setup_record(cap["kind"].readings["setup_spans"], seed), f)
    print("SPANS " + json.dumps(out), flush=True)
    return rc


def cost(seed: int, seconds: float, pairs: int, outdir: str) -> int:
    """Windows on one set-up: disarmed, armed, armed, disarmed, ..."""
    import jax

    bench = run.common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic, limits = run.load_cell(CELL, bench)
    devices = run.find_chips(1)
    os.makedirs(run.CACHE_DIR, exist_ok=True)
    run.enable_caches()
    reference = run.load_module(
        os.path.join(run.BENCH_DIR, "configs", config["reference"]), "bench_reference"
    )
    spec = run.Spec(cell, config, traffic, limits, reference, seed,
                    run.device_peaks(devices[0]), run.CACHE_DIR)
    kind = run.load_kind(traffic["kind"]).Kind(spec)
    kind.setup()
    rows = []
    for i in range(pairs):
        for mode in (("off", "on") if i % 2 == 0 else ("on", "off")):
            tracer = T.Tracer() if mode == "on" else None
            with T.tracing(tracer):
                w = kind.window(seconds)
            rows.append({"pair": i, "mode": mode,
                         "tokens_per_s": w["metrics"]["train_tokens_per_s"],
                         "steps": w["attempted"], "events": len(tracer.events) if tracer else 0,
                         "dropped": tracer.dropped if tracer else 0, "notes": w["notes"]})
            print("COST " + json.dumps(rows[-1]), flush=True)
    jax.block_until_ready(kind.state["params"])
    os.makedirs(outdir, exist_ok=True)
    with open(os.path.join(outdir, f"cost_{seed}.json"), "w") as f:
        json.dump(rows, f)
    return 0


def main(argv: list[str]) -> int:
    if "--cpu-tiny" in argv:
        argv = [a for a in argv if a != "--cpu-tiny"]
        cpu_tiny(os.path.join(argv[-1], "cache"))
    if argv[0] == "traced":
        return traced(int(argv[1]), float(argv[2]), argv[3])
    return cost(int(argv[1]), float(argv[2]), int(argv[3]), argv[4])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
