"""The benchmark's yardstick on the CPU: trace reduction, FLOP and byte
counts, the peaks table and the comparisons.  Nothing here touches a TPU
topology."""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import common, compare, counts, trace_reduce  # noqa: E402
from bench.run import load_reader  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
DEV = "/device:TPU:0"
KERNEL = '%fused_map0_tanh = f32[8,128] custom-call(), custom_call_target="tpu_custom_call"'


def _trace(ops, host, window=(0.0, 1000.0)):
    lo, hi = window
    return {
        "devices": {DEV: [list(o) for o in ops]},
        "host": [["bench.window", lo, hi - lo]] + [list(h) for h in host],
    }


def test_busy_is_a_union_not_a_sum():
    # two overlapping ops and one nested inside them: busy 0..300 and 500..600
    t = _trace(
        [("a", 0, 200), ("b", 100, 200), ("c", 150, 10), ("d", 500, 100)],
        [],
    )
    r = trace_reduce.reduce(t)
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["idle_share"] == pytest.approx(0.6)
    # per-op time is each op's own, overlaps included
    assert r["op_s"]["b"] == pytest.approx(200e-9)


def test_ops_are_clipped_to_the_window():
    t = _trace([("a", -100, 200), ("b", 900, 300)], [])
    r = trace_reduce.reduce(t)
    assert r["busy_s"] == pytest.approx(200e-9)
    assert r["op_s"] == {"a": pytest.approx(100e-9), "b": pytest.approx(100e-9)}


def test_idle_gaps_are_named_by_the_innermost_annotation():
    t = _trace(
        [("a", 0, 100), ("b", 400, 100), ("c", 550, 100)],
        [["bench.step", 0, 600], ["bench.batch", 120, 250], ["bench.wait", 500, 60]],
    )
    gaps = trace_reduce.reduce(t)["breakdown"]["idle_gaps"]
    # 100..400 (batch), 650..1000 (none open), 500..550 (wait)
    assert [g[0] for g in gaps] == ["bench.none", "bench.batch", "bench.wait"]
    assert gaps[0][1] == pytest.approx(350e-9)
    assert gaps[1][1] == pytest.approx(300e-9)


def test_ops_are_named_within_their_program():
    t = _trace([(KERNEL, 0, 100), ("%fusion.1 = ...", 150, 50), ("%fusion.1 = ...", 400, 50)], [])
    t["modules"] = {DEV: [["jit_step(1)", 0, 200], ["jit_update(2)", 390, 100]]}
    r = trace_reduce.reduce(t)
    assert r["op_s"] == {
        "jit_step(1)/%fused_map0_tanh": pytest.approx(100e-9),
        "jit_step(1)/%fusion.1": pytest.approx(50e-9),
        "jit_update(2)/%fusion.1": pytest.approx(50e-9),
    }
    assert r["kernels"] == ["jit_step(1)/%fused_map0_tanh"]
    assert load_reader("pallas_ms.train")({"trace": r, "steps": 1}) == pytest.approx(1e-4)


def test_breakdown_keeps_ten_entries_each():
    ops = [(f"op{i}", 10 * i, 5) for i in range(30)]
    r = trace_reduce.reduce(_trace(ops, []))
    assert len(r["breakdown"]["device_ops"]) == 10
    assert len(r["breakdown"]["idle_gaps"]) == 10


def test_a_trace_without_window_or_devices_is_refused():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": {DEV: [["a", 0, 1]]}, "host": []})
    with pytest.raises(ValueError):
        trace_reduce.reduce({"devices": {}, "host": [["bench.window", 0, 1]]})


def test_recorded_chip_trace():
    """A slice of a train-cell trace recorded on one v5e: the window holds
    the generated ``fused_`` kernels, and the reduction's numbers are the
    ones worked out by hand from the same events."""
    with open(os.path.join(DATA, "train_trace_v5e.json")) as f:
        rec = json.load(f)
    r = trace_reduce.reduce(rec["trace"])
    assert r["window_s"] == pytest.approx(rec["expected"]["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(rec["expected"]["busy_s"], rel=1e-9)
    assert 0.0 < r["idle_share"] < 1.0
    fused = [k for k in r["kernels"] if k.rsplit("/", 1)[-1].startswith("%fused_")]
    assert len(fused) == rec["expected"]["fused_kernels"]
    pallas = load_reader("pallas_ms.train")({"trace": r, "steps": rec["expected"]["steps"]})
    assert pallas == pytest.approx(rec["expected"]["pallas_ms"], rel=1e-9)


def test_pallas_reader_counts_generated_kernels_only():
    # an XLA fusion whose text names a fused computation is no kernel
    xla = "%fusion.6 = f32[8,128] fusion(), kind=kOutput, calls=%fused_computation.11"
    r = trace_reduce.reduce(_trace([(xla, 0, 100)], []))
    assert r["kernels"] == [] and list(r["op_s"]) == ["%fusion.6"]
    assert load_reader("pallas_ms.train")({"trace": r, "steps": 3}) is None
    r = trace_reduce.reduce(_trace([(KERNEL, 0, 100), (xla, 100, 50)], []))
    assert r["kernels"] == ["%fused_map0_tanh"]
    assert load_reader("pallas_ms.train")({"trace": r, "steps": 2}) == pytest.approx(5e-5)


def test_flop_counts_by_hand():
    cfg = {"vocab_size": 10, "hidden_size": 4, "intermediate_size": 8}
    # 6 (4*8 + 8*4 + 4*10) = 624
    assert counts.tanhlm_train_flops_per_token(cfg) == 624
    # the real widths: 1.338 GFLOP per token
    real = common.load_json(os.path.join(ROOT, "bench/configs/myia-tanhlm.json"))
    assert counts.tanhlm_train_flops_per_token(real) == 1_338_507_264


def test_peaks_are_keyed_by_device_kind():
    v5e = common.peaks_for("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["source"]
    with pytest.raises(KeyError):
        common.peaks_for("TPU v9 imaginary")


def test_comparisons():
    assert compare.loss_gap([1.0, 2.2], [1.0, 2.0]) == pytest.approx(0.1)
    # leaf 2's reference gradient is under a thousandth of the median's
    keep = compare.kept_leaves([1.0, 2.0, 1e-6, 3.0])
    assert keep == [0, 1, 3]
    # leaf 0 is measured against the median (2.0), not its own norm
    assert compare.worst_leaf_gap([1.1, 2.0, 5.0, 3.0], [1.0, 2.0, 1e-6, 3.0], keep) == (
        pytest.approx(0.05)
    )
