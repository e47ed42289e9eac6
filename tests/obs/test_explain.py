"""Tests for the compile-decision explain layer (``repro.obs.explain``).

The acceptance contract: ``explain`` over the whole VM-fallback corpus
(``benchmarks.bench_compile_time._fallback_corpus`` — the 11 programs
spanning straight-line, higher-order AD, loops, defunctionalized HOFs)
yields a *structured* verdict for every node and cluster — reason objects
with a ``kind``, never bare strings — and the report JSON-round-trips
exactly.  IR dumps are deterministic and diffable.
"""

import json
import os

import jax.numpy as jnp
import pytest

from benchmarks.bench_compile_time import _fallback_corpus
from repro.core import parse_function
from repro.core.api import CompileOptions, grad
from repro.core.primitives import pad_zeros_axis as _pad
from repro.core.primitives import reduce_sum as _rsum
from repro.core.primitives import slice_axis as _slice
from repro.core.primitives import tanh as _tanh
from repro.obs.explain import ExplainReport, explain_graph, format_graph


def _loss(w1, w2, x):
    h = _tanh(x @ w1)
    return _rsum(_tanh(h @ w2), None, False)


ARGS = (
    jnp.ones((8, 8), jnp.float32) * 0.1,
    jnp.ones((8, 8), jnp.float32) * 0.1,
    jnp.ones((4, 8), jnp.float32),
)


def _assert_reason(obj, ctx):
    assert isinstance(obj, dict), f"{ctx}: reason must be a dict, got {obj!r}"
    assert isinstance(obj.get("kind"), str) and obj["kind"], f"{ctx}: {obj!r}"
    assert "detail" in obj, f"{ctx}: reason without detail: {obj!r}"


def _assert_structured(rep: ExplainReport, ctx: str) -> None:
    fus = rep["fusion"]
    if not fus["enabled"]:
        _assert_reason(fus["reason"], f"{ctx}/fusion-disabled")
    else:
        for c in fus["clusters"]:
            assert c["verdict"] in ("emitted", "declined"), f"{ctx}: {c}"
            if c["verdict"] == "declined":
                _assert_reason(c["reason"], f"{ctx}/cluster{c['cluster']}")
        for n in fus["nodes"]:
            assert n["decision"] in ("fused", "unfused"), f"{ctx}: {n}"
            if n["decision"] == "unfused":
                _assert_reason(n["reason"], f"{ctx}/node {n['node']}")
            else:
                assert isinstance(n["cluster"], int), f"{ctx}: {n}"
    sh = rep["sharding"]
    assert sh["verdict"] in ("unsharded", "sharded", "fallback-single-device")
    if sh["verdict"] != "sharded":
        _assert_reason(sh["reason"], f"{ctx}/sharding")
    for tier in rep["cache"]:
        assert tier["tier"] in ("graph", "exec")
        assert tier["verdict"] in (
            "graph-hit", "miss", "exec-hit", "cold", "unkeyable", "disabled"
        ), f"{ctx}: {tier}"
        if tier["verdict"] == "unkeyable":
            _assert_reason(tier["reason"], f"{ctx}/cache")
    for lp in rep["loops"]:
        assert lp["loop"] in ("while_loop", "scan_loop"), f"{ctx}: {lp}"
        assert isinstance(lp["slots"], int) and lp["checkpoint_policy"]
    fb = rep["fallback"]
    assert isinstance(fb["lowers"], bool)
    for r in fb["reasons"]:
        _assert_reason(r, f"{ctx}/fallback")


@pytest.mark.parametrize(
    "name,g,args", _fallback_corpus(), ids=[n for n, _, _ in _fallback_corpus()]
)
def test_corpus_reports_are_structured_and_round_trip(name, g, args):
    rep = explain_graph(g, args, CompileOptions(fuse=True), name=name)
    _assert_structured(rep, name)
    rt = ExplainReport.from_json(rep.to_json())
    assert rt.as_dict() == rep.as_dict(), f"{name}: JSON round trip diverged"
    assert rep["program"] == name
    assert rep["ir_stages"][0] == "input" and rep["ir_stages"][-1] == "final"
    assert rep.summary()  # renders without raising


def test_loop_corpus_programs_report_checkpoint_policy():
    corpus = {n: (g, a) for n, g, a in _fallback_corpus()}
    g, args = corpus["grad_while_pow"]
    rep = explain_graph(g, args, CompileOptions())
    assert rep["loops"], "loop adjoint program reported no loops"
    row = rep["loops"][0]
    assert row["loop"] == "while_loop"
    assert row["slots"] >= 1


def _tree(x, n):
    if n <= 1:
        return x
    return _tree(x * 2.0, n - 1) + _tree(x * 0.5, n - 2)


def test_vm_fallback_program_reports_reasons():
    """Tree recursion is not loop-shaped: it survives optimization as
    residual graph calls and the report must say so, structurally."""
    rep = explain_graph(
        parse_function(_tree), (jnp.float32(2.0), 3), CompileOptions()
    )
    fb = rep["fallback"]
    assert not fb["lowers"] and fb["reasons"], "tree recursion should stay on the VM"
    kinds = {r["kind"] for r in fb["reasons"]}
    assert "recursion-shape" in kinds or "higher-order-residual" in kinds
    for r in fb["reasons"]:
        _assert_reason(r, "tree")


def test_backend_vm_forces_fallback_reason():
    rep = explain_graph(
        parse_function(_loss), ARGS, CompileOptions(backend="vm")
    )
    assert not rep["fallback"]["lowers"]
    assert any(r["kind"] == "backend-vm" for r in rep["fallback"]["reasons"])


def test_myia_function_explain_resolves_transforms():
    df = grad(_loss, (0, 1), options=CompileOptions(fuse=True))
    rep = df.explain(*ARGS)
    _assert_structured(rep, "grad(_loss)")
    fus = rep["fusion"]
    assert fus["enabled"] and fus["clusters"], "grad MLP produced no clusters"
    assert any(c["verdict"] == "emitted" for c in fus["clusters"])
    emitted = [c for c in fus["clusters"] if c["verdict"] == "emitted"]
    assert all(c["bytes_moved"] > 0 for c in emitted)
    # the loss's scalar cotangent is unreduce-d into the first adjoint
    # kernel: broadcast in VMEM, not expanded by the wrapper
    assert [(c["broadcast_in_kernel"], c["broadcast_materialized"]) for c in emitted] == [
        (1, 0), (0, 0)
    ]
    fused = [n for n in fus["nodes"] if n["decision"] == "fused"]
    assert fused, "no node actually joined a cluster"


def _shifted(x):
    up = _pad(x, 1, 1, 0)
    return _tanh(_slice(up, 1, 0, 16)) * _slice(up, 1, 1, 17) + 1.0


@pytest.mark.parametrize("budget", [None, 40 * 1024], ids=["fits", "over_budget"])
def test_window_members_reported(monkeypatch, budget):
    """The explain report counts a kernel's windows per cluster; the pad
    and slices it reads in VMEM are fused, and when their whole axis does
    not fit the budget they are unfused with that reason."""
    from repro.kernels import codegen

    if budget is not None:
        monkeypatch.setattr(codegen, "_VMEM_BUDGET", budget)
    x = jnp.ones((2, 16, 1024), jnp.float32)
    rep = explain_graph(parse_function(_shifted), (x,), CompileOptions(fuse=True))
    _assert_structured(rep, "_shifted")
    (c,) = rep["fusion"]["clusters"]
    windows = [n for n in rep["fusion"]["nodes"] if n["op"] in ("pad_zeros_axis", "slice_axis")]
    assert sorted(n["op"] for n in windows) == ["pad_zeros_axis", "slice_axis", "slice_axis"]
    assert c["verdict"] == "emitted"
    if budget is None:
        assert (c["window_in_kernel"], c["window_materialized"]) == (2, 0)
        assert all(n["decision"] == "fused" for n in windows)
    else:
        # the (1, 16, 128) windowed block needs 48 KiB, the cluster
        # without its windows (8, 128) rows in 36 KiB
        assert (c["window_in_kernel"], c["window_materialized"]) == (0, 2)
        assert all(n["reason"]["kind"] == "exceeds-vmem-budget" for n in windows)


def test_signature_and_phases_recorded():
    df = grad(_loss, 0, options=CompileOptions())
    rep = df.explain(*ARGS)
    assert rep["signature"] is not None and len(rep["signature"]) == 3
    phases = rep["phases_ms"]
    assert "compile_pipeline" in phases and "explain.report" in phases


def test_dump_ir_stage_files_are_diffable(tmp_path):
    df = grad(_loss, (0, 1), options=CompileOptions(fuse=True))
    d1, d2 = tmp_path / "a", tmp_path / "b"
    r1 = df.explain(*ARGS, dump_ir=str(d1))
    r2 = df.explain(*ARGS, dump_ir=str(d2))
    assert r1["ir_stages"] == r2["ir_stages"]
    files1 = sorted(os.listdir(d1))
    assert files1 == sorted(os.listdir(d2))
    assert files1[0] == "00-input.ir"
    for f in files1:
        t1 = (d1 / f).read_text()
        assert t1 == (d2 / f).read_text(), f"{f} not deterministic"
        assert t1.startswith("graph ")
    # the final stage differs from the input: the pipeline did something
    assert (d1 / files1[0]).read_text() != (d1 / files1[-1]).read_text()


def test_format_graph_is_parse_stable():
    """Two parses of the same source print identical IR text — node ids
    differ, topological names don't (the dump_ir diffability property)."""
    t1 = format_graph(parse_function(_loss))
    t2 = format_graph(parse_function(_loss))
    assert t1 == t2


def test_cache_tiers_disabled_without_caches():
    rep = explain_graph(parse_function(_loss), ARGS, CompileOptions())
    verdicts = {t["tier"]: t["verdict"] for t in rep["cache"]}
    assert verdicts == {"graph": "disabled", "exec": "disabled"}


def test_cache_tier_verdicts_cold_then_warm(tmp_path):
    from repro.core.jax_backend import ProgramCache

    pc = ProgramCache(str(tmp_path))
    opts = CompileOptions(fuse=True, program_cache=pc, graph_cache=pc)
    df = grad(_loss, (0, 1), options=opts)
    cold = {t["tier"]: t for t in df.explain(*ARGS)["cache"]}
    assert cold["graph"]["verdict"] == "miss"
    assert cold["exec"]["verdict"] == "cold"
    df(*ARGS)  # warm both tiers through a real call
    warm = {t["tier"]: t for t in df.explain(*ARGS)["cache"]}
    assert warm["graph"]["verdict"] == "graph-hit"
    assert warm["exec"]["verdict"] == "exec-hit"
    assert warm["exec"]["key"] == cold["exec"]["key"], "explain key drifted"


def test_cache_probe_is_read_only(tmp_path):
    """The exec-tier verdict must not perturb the stats it reports on."""
    from repro.core.jax_backend import ProgramCache

    pc = ProgramCache(str(tmp_path))
    df = grad(_loss, 0, options=CompileOptions(program_cache=pc))
    df(*ARGS)
    before = pc.stats.as_dict()
    df.explain(*ARGS)
    after = pc.stats.as_dict()
    assert after["hits"] == before["hits"] and after["misses"] == before["misses"]


def test_fusion_disabled_reason():
    rep = explain_graph(parse_function(_loss), ARGS, CompileOptions(fuse=False))
    fus = rep["fusion"]
    assert not fus["enabled"]
    assert fus["reason"]["kind"] == "fusion-disabled"


def test_report_is_plain_json_data():
    """No objects leak into the report: json.dumps succeeds and every
    reason everywhere is a dict (spot-checked by _assert_structured, but
    this pins the whole tree)."""
    df = grad(_loss, (0, 1), options=CompileOptions(fuse=True))
    rep = df.explain(*ARGS)
    text = json.dumps(rep.as_dict(), sort_keys=True)
    assert json.loads(text) == rep.as_dict()
