"""Run one cell of the benchmark and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix, its limits, its kind of run
and its per-layer metrics are all found by name: ``BENCHMARK.json`` names
the cell's configuration file and traffic mix, ``bench/traffic/<mix>.json``
names the kind (``bench/kinds/<kind>.py``), ``bench/cells/<cell>.json``
holds the limits that decide ``correct``, and each per-layer metric is
read by ``bench/metrics/<metric>.py``.

A run loads the program and makes its weights from ``--seed`` (set-up,
reported as ``setup_s``), measures a window of ``--seconds``, reads the
device's peak memory, frees the program's state and compares what the
window produced with the configuration's plain reference.  ``--trace 1``
records a profiler trace of the window and reports the per-layer metrics
instead of the end-to-end ones.  Without a TPU, or with fewer chips than
the cell asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Any  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import common, trace_reduce  # noqa: E402

#: what building and running leave in the checkout (listed in .gitignore)
CACHE_DIR = os.path.join(ROOT, ".bench_cache")


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Spec:
    """Everything one run of one cell is given."""

    cell: dict
    config: dict
    traffic: dict
    limits: dict
    reference: Any
    seed: int
    peaks: dict
    cache_dir: str


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, bench: dict | None = None) -> tuple[dict, dict, dict, dict]:
    """(cell, configuration, traffic, limits) of the cell ``name``, found
    purely from ``BENCHMARK.json`` and the files it names."""
    bench = common.load_json(os.path.join(ROOT, "BENCHMARK.json")) if bench is None else bench
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = common.load_json(os.path.join(ROOT, configs[cell["config"]]["file"]))
    traffic = common.load_json(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json"))
    limits = common.load_json(os.path.join(BENCH_DIR, "cells", name + ".json"))
    return cell, config, traffic, limits


def load_kind(kind: str):
    return load_module(os.path.join(BENCH_DIR, "kinds", kind + ".py"), f"bench_kind_{kind}")


def load_reader(metric: str):
    path = os.path.join(BENCH_DIR, "metrics", metric + ".py")
    return load_module(path, "bench_metric_" + metric.replace(".", "_")).read


def find_chips(chips: int) -> list:
    """The first ``chips`` TPU devices; :class:`NoChip` where there are
    fewer, or none."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def device_peaks(device) -> dict:
    return common.peaks_for(device.device_kind)


def enable_caches() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (or where ``JAX_COMPILATION_CACHE_DIR`` says), caching every program."""
    import jax

    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Counts backend compiles (cache misses) while ``armed``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self) -> None:
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **kwargs) -> None:
        if self.armed and event == self.EVENT:
            self.count += 1


def per_layer_metrics(bench: dict, cell_name: str, ctx: dict) -> dict:
    """Each per-layer metric that names this cell (or names no cells),
    read by its own reader; a reader that finds nothing is left out."""
    out = {}
    for m in bench["per_layer"]:
        if cell_name not in m.get("workloads", [cell_name]):
            continue
        value = load_reader(m["name"])(ctx)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run_cell(args, bench: dict) -> dict:
    """One whole run; returns the result object."""
    import jax

    cell, config, traffic, limits = load_cell(args.workload, bench)
    devices = find_chips(int(cell["chips"]))
    peaks = device_peaks(devices[0])
    os.makedirs(CACHE_DIR, exist_ok=True)
    enable_caches()
    reference = load_module(
        os.path.join(BENCH_DIR, "configs", config["reference"]),
        "bench_reference_" + config["reference"].removesuffix(".py"),
    )
    spec = Spec(cell, config, traffic, limits, reference, args.seed, peaks, CACHE_DIR)
    kind = load_kind(traffic["kind"]).Kind(spec)
    compiles = CompileCounter()

    t_kind = time.monotonic()
    kind.setup()
    setup_s = time.monotonic() - T_START

    trace_dir = os.path.join(CACHE_DIR, "trace", args.workload)
    if args.trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles.armed = True
    try:
        with jax.profiler.TraceAnnotation(trace_reduce.WINDOW):
            window = kind.window(float(args.seconds))
    finally:
        compiles.armed = False
        if args.trace:
            jax.profiler.stop_trace()
    reduced = None
    if args.trace:
        xplane = trace_reduce.find_xplane(trace_dir)
        loaded = trace_reduce.load(xplane)
        if args.dump_trace:
            os.makedirs(args.dump_trace, exist_ok=True)
            with open(os.path.join(args.dump_trace, args.workload + ".events.json"), "w") as f:
                json.dump(loaded, f)
            with open(os.path.join(args.dump_trace, args.workload + ".summary.json"), "w") as f:
                json.dump(trace_reduce.summary(xplane), f, indent=1)
        reduced = trace_reduce.reduce(loaded)
        shutil.rmtree(trace_dir, ignore_errors=True)

    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    if args.trace:
        kind.extra()
    kind.release()
    gc.collect()
    t_check = time.monotonic()
    checks = kind.check()
    check_s = time.monotonic() - t_check

    correct = all(value <= limit for _, value, limit in checks)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": int(peak),
    }
    result: dict[str, Any] = {
        "correct": bool(correct),
        "attempted": int(window["attempted"]),
        "failed": int(window["failed"]),
    }
    if args.trace:
        ctx = {
            "config": config,
            "traffic": traffic,
            "peaks": peaks,
            "chips": len(devices),
            "window_s": window["window_s"],
            "trace": reduced,
            **kind.readings,
        }
        result["metrics"] = per_layer_metrics(bench, args.workload, ctx)
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        result["device"] = device
        result["breakdown"] = reduced["breakdown"]
    else:
        metrics = dict(window["metrics"], setup_s=setup_s)
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        result["metrics"] = {k: {"value": float(v), "unit": units[k]} for k, v in metrics.items()}
        result["device"] = device
    result["checks"] = {name: {"value": value, "limit": limit} for name, value, limit in checks}
    print(
        f"[bench] {args.workload}: setup {setup_s:.3f} s (to the kind's set-up "
        f"{t_kind - T_START:.3f} s), window {window['window_s']:.3f} s, "
        f"compiles in window {compiles.count}, check {check_s:.3f} s; {window['notes']}",
        file=sys.stderr,
    )
    for name, value, limit in checks:
        verdict = "ok" if value <= limit else "FAILED"
        print(f"[check] {name} {value!r} limit {limit!r} {verdict}", file=sys.stderr)
    sys.stderr.flush()
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True, help="a workload name in BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True, help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default=None,
                    help="with --trace 1: also write the trace's events and a summary here")
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"bench: the program is not in this checkout ({src}/repro)", file=sys.stderr)
        return 2
    if src not in sys.path:
        sys.path.insert(0, src)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    bench = common.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    try:
        result = run_cell(args, bench)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
