"""Reduce a profiler trace to device busy and idle time, per-op device
time and the breakdown that the result line carries.

:func:`load` reads the ``.xplane.pb`` the JAX profiler writes into plain
lists of ``(name, start_ns, duration_ns)``: the op events of each device
and the harness's own host annotations (``bench.*``).  :func:`reduce`
works on those lists alone, so a recorded trace kept as JSON checks it.
Busy time is the union of the op intervals, never their sum.
"""

from __future__ import annotations

import bisect
import glob
import os

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
HOST_PLANE = "/host:CPU"
ANNOTATION_PREFIX = "bench."
WINDOW = "bench.window"


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*", "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, found {paths}")
    return paths[0]


def load(path: str) -> dict:
    """``{"devices": {plane: [event]}, "modules": {plane: [event]}, "host":
    [event]}``, where an event is ``[name, start_ns, duration_ns]``: each
    device's ops, the compiled programs they ran in, and the harness's
    annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[str, list] = {}
    modules: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                events = [[e.name, float(e.start_ns), float(e.duration_ns)] for e in line.events]
                if line.name == OPS_LINE:
                    devices[plane.name] = events
                elif line.name == MODULES_LINE:
                    modules[plane.name] = events
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(
                    [e.name, float(e.start_ns), float(e.duration_ns)]
                    for e in line.events
                    if e.name.startswith(ANNOTATION_PREFIX)
                )
    return {"devices": devices, "modules": modules, "host": host}


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_name(event_name: str) -> str:
    """An op event is named by its HLO instruction's text; its name is the
    part before `` = `` (``%fusion.6``, ``%fused_map1_unreduce_eq_cast.1``)."""
    return event_name.split(" = ", 1)[0]


def _module_at(modules: list, starts: list, t: float) -> str | None:
    """The compiled program running at ``t`` (instruction names are only
    unique within one)."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t <= modules[i][1] + modules[i][2]:
        return modules[i][0]
    return None


def window_of(trace: dict) -> tuple[float, float]:
    """The harness's measured window, from its ``bench.window`` annotation."""
    spans = [(s, s + d) for n, s, d in trace["host"] if n == WINDOW]
    if not spans:
        raise ValueError("the trace holds no bench.window annotation")
    return min(s for s, _ in spans), max(e for _, e in spans)


def _clipped(events: list, lo: float, hi: float):
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            yield name, a, b


def _label(host: list, t: float) -> str:
    """The innermost harness annotation open at ``t``."""
    best = None
    for name, s, d in host:
        if name != WINDOW and s <= t <= s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "bench.none"


def reduce(trace: dict, top: int = 10) -> dict:
    """Busy and idle time of the measured window, averaged over devices,
    device time per op (named ``<program>/<instruction>`` where the trace
    holds the programs), the names of the Pallas kernels
    (``tpu_custom_call``) among them, and the breakdown: the ``top`` ops
    by device time and the ``top`` longest idle gaps of the first device,
    each named by what the harness was doing then."""
    lo, hi = window_of(trace)
    devices = sorted(trace["devices"])
    if not devices:
        raise ValueError("the trace holds no device op events")
    op_s: dict[str, float] = {}
    kernels: set[str] = set()
    busy_ns = 0.0
    gaps: list[tuple[float, float]] = []
    for k, dev in enumerate(devices):
        spans = []
        modules = sorted(trace.get("modules", {}).get(dev, []), key=lambda m: m[1])
        starts = [m[1] for m in modules]
        for text, a, b in _clipped(trace["devices"][dev], lo, hi):
            name = op_name(text)
            module = _module_at(modules, starts, a)
            if module is not None:
                name = f"{module}/{name}"
            op_s[name] = op_s.get(name, 0.0) + (b - a) * 1e-9 / len(devices)
            if KERNEL_TARGET in text:
                kernels.add(name)
            spans.append((a, b))
        merged = union(spans)
        busy_ns += sum(b - a for a, b in merged)
        if k == 0:
            edges = [lo] + [x for ab in merged for x in ab] + [hi]
            gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)]
            gaps = [(a, b) for a, b in gaps if b > a]
    window_s = (hi - lo) * 1e-9
    busy_s = busy_ns * 1e-9 / len(devices)
    gaps.sort(key=lambda ab: ab[0] - ab[1])
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "op_s": op_s,
        "kernels": sorted(kernels),
        "breakdown": {
            "device_ops": sorted(op_s.items(), key=lambda kv: -kv[1])[:top],
            "idle_gaps": [
                [_label(trace["host"], (a + b) / 2), (b - a) * 1e-9] for a, b in gaps[:top]
            ],
        },
    }


def summary(path: str, per_line: int = 12) -> dict:
    """What a trace holds, for reading one by hand: each plane's lines with
    their event counts and most frequent event names."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        for line in plane.lines:
            counts: dict[str, int] = {}
            n = 0
            for e in line.events:
                counts[e.name] = counts.get(e.name, 0) + 1
                n += 1
            common = sorted(counts.items(), key=lambda kv: -kv[1])[:per_line]
            lines[line.name] = {"events": n, "names": common}
        out[plane.name] = lines
    return out
