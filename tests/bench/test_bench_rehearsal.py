"""CPU rehearsal of the benchmark: each kind's set-up, window and output
check at the configurations' tiny widths, the result line's keys, the
refusals without a chip or without the program, and a cell, a
configuration and a per-layer metric added by files alone."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from bench_testing import KEYS, KEYS_TRACED, ROOT, rehearse, run

BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
E2E = {m["name"]: m for m in BENCH["end_to_end"]}


def _cell_metrics(cell: str, table: list) -> set:
    return {m["name"] for m in table if cell in m.get("workloads", [cell])}


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_untraced_run_reports_the_end_to_end_metrics(monkeypatch, capsys, tmp_path, cell):
    rc, res, err = rehearse(monkeypatch, capsys, run, cell, tmp_path)
    assert rc == 0, err
    assert list(res) == KEYS
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == _cell_metrics(cell, BENCH["end_to_end"])
    for name, m in res["metrics"].items():
        assert m["unit"] == E2E[name]["unit"] and m["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    # every number compared is printed beside its limit, last on stderr
    tail = err.strip().splitlines()[-len(res["checks"]):]
    for line, (name, c) in zip(tail, res["checks"].items()):
        assert line.startswith(f"[check] {name} ") and f"limit {c['limit']!r}" in line


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_traced_run_reports_the_per_layer_metrics(monkeypatch, capsys, tmp_path, cell):
    rc, res, err = rehearse(monkeypatch, capsys, run, cell, tmp_path, trace=1)
    assert rc == 0, err
    assert list(res) == KEYS_TRACED
    assert res["correct"] is True, res["checks"]
    assert set(res["metrics"]) == _cell_metrics(cell, BENCH["per_layer"])
    assert res["device"]["busy_s"] > 0 and res["device"]["window_s"] > 0
    bd = res["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_no_tpu_exits_nonzero_and_prints_no_result(capsys):
    rc = run.main(["--workload", BENCH["workloads"][0]["name"], "--seed", "1",
                   "--seconds", "1", "--trace", "0"])
    out, err = capsys.readouterr()
    assert rc != 0 and out == ""
    assert "no TPU" in err


def test_unknown_device_kind_is_an_error():
    class Device:
        device_kind = "TPU v9 imaginary"

    with pytest.raises(KeyError):
        run.device_peaks(Device())


def _copy_benchmark(dest) -> None:
    for path in BENCH["paths"]:
        shutil.copytree(os.path.join(ROOT, path), os.path.join(dest, path),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dest)


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    _copy_benchmark(tmp_path)
    p = subprocess.run(
        [sys.executable, *BENCH["command"][1:], "--workload", BENCH["workloads"][0]["name"],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert p.returncode != 0 and p.stdout == ""


NEW_READER = '''"""steps_seen.tiny: train steps in the window (a test's metric)."""


def read(ctx):
    return ctx.get("steps")
'''


def test_a_cell_config_and_metric_are_added_by_files_alone(monkeypatch, capsys, tmp_path):
    """A copy of the benchmark gains a configuration, a traffic mix, a
    cell and a per-layer metric as new files and new entries only; the
    copy's harness runs the new cell and reports the new metric."""
    _copy_benchmark(tmp_path)
    os.symlink(os.path.join(ROOT, "src"), tmp_path / "src")
    b = tmp_path / "bench"
    cfg = json.load(open(b / "configs" / "myia-tanhlm.json"))
    cfg.update(name="tiny-tanhlm", vocab_size=256, hidden_size=32, intermediate_size=64)
    (b / "configs" / "tiny-tanhlm.json").write_text(json.dumps(cfg))
    traffic = json.load(open(b / "traffic" / "train_b16_s256.json"))
    traffic.update(batch=2, seq=16)
    (b / "traffic" / "train_b2_s16.json").write_text(json.dumps(traffic))
    (b / "cells" / "tiny-tanhlm.train.json").write_text(
        (b / "cells" / "myia-tanhlm.train.json").read_text()
    )
    (b / "metrics" / "steps_seen.tiny.py").write_text(NEW_READER)
    bench = json.load(open(tmp_path / "BENCHMARK.json"))
    bench["configs"].append({"name": "tiny-tanhlm", "source": "https://example.org/tiny",
                             "file": "bench/configs/tiny-tanhlm.json", "reduced": [],
                             "why": "a test"})
    bench["workloads"].append({"name": "tiny-tanhlm.train", "config": "tiny-tanhlm",
                               "traffic": "train_b2_s16", "chips": 1, "why": "a test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "myia-tanhlm.train" in m.get("workloads", []):
            m["workloads"].append("tiny-tanhlm.train")
    bench["per_layer"].append({"name": "steps_seen.tiny", "unit": "steps", "better": "higher",
                               "source": "host_clock", "layer": "model step",
                               "moves": "train_tokens_per_s",
                               "workloads": ["tiny-tanhlm.train"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    copy = run.load_module(str(b / "run.py"), "bench_run_copy")
    assert copy.ROOT == str(tmp_path)
    rc, res, err = rehearse(monkeypatch, capsys, copy, "tiny-tanhlm.train",
                            tmp_path / "cache", trace=1, tiny=False)
    assert rc == 0, err
    assert res["correct"] is True
    assert res["metrics"]["steps_seen.tiny"]["value"] == res["attempted"]
