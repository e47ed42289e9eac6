#!/usr/bin/env bash
# CI entry point: lint → tier-1 tests → serve smoke + chaos corpus →
# quick benchmarks → bench gate.
#
#   scripts/ci.sh                 # everything (the CI "full" job)
#   SKIP_SLOW=1 SKIP_BENCH=1 scripts/ci.sh   # the CI "fast" job (minutes)
#
# The quick benchmark run rewrites the repo-root BENCH_*.json trajectory
# files (compile time, AD overhead, fusion, spmd) and scripts/check_bench.py
# diffs them against the committed trajectory — >25% regression in compile
# time, AD overhead ratio, or fused/sharded launch counts fails the build.
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

# Run an inline python script through a real temp file instead of stdin:
# parse_function needs inspect.getsource, which cannot read a `python -`
# heredoc (OSError: could not get source code).
pyfile() {
  local tmp rc=0
  tmp="$(mktemp "${TMPDIR:-/tmp}/ci-inline-XXXXXX.py")"
  cat > "$tmp"
  python "$tmp" || rc=$?
  rm -f "$tmp"
  return "$rc"
}

echo "== lint (ruff via pyproject; in-repo fallback when unavailable) =="
python scripts/lint.py

echo "== tier-1 tests (fast suite) =="
python -m pytest -x -q -m "not slow"

echo "== serve engine smoke (tmpdir AOT cache: cold run compiles, warm run hits) =="
python - <<'PY'
import tempfile
import jax, numpy as np
from repro.core.jax_backend import ProgramCache
from repro.serve import ServeEngine, ServeLMDims, init_serve_params

dims = ServeLMDims(vocab=48, d_model=8, d_hidden=16)
params = init_serve_params(dims, jax.random.PRNGKey(0))
with tempfile.TemporaryDirectory(prefix="ci-progcache-") as d:
    outs = []
    for leg in ("cold", "warm"):
        cache = ProgramCache(d)
        eng = ServeEngine(dims, params, n_slots=2, min_bucket=16, program_cache=cache)
        rng = np.random.default_rng(0)
        rids = [eng.submit(rng.integers(0, dims.vocab, n).tolist(), m)
                for n, m in [(5, 6), (9, 4)]]
        res = eng.run()
        outs.append({r: res[r]["tokens"] for r in rids})
        print(f"  {leg}: {cache.stats.as_dict()}")
        if leg == "cold":
            assert cache.stats.misses > 0 and cache.stats.puts > 0
        else:
            assert cache.stats.hits > 0, "warm run found no cached programs"
            assert cache.stats.misses == 0 and cache.stats.xla_compiles == 0
    assert outs[0] == outs[1], "warm serve diverged from cold serve"
print("  serve smoke OK")
PY

echo "== graph-cache smoke (cold run optimizes + stores, warm run skips optimize) =="
pyfile <<'PY'
import tempfile
import jax.numpy as jnp
from repro.core import build_grad_graph, parse_function
from repro.core.api import CompileOptions, compile_pipeline
from repro.core.infer import abstract_of_value
from repro.core.jax_backend import ProgramCache
from repro.core.primitives import reduce_sum as _rsum, tanh as _tanh
from repro.core.serialize import dumps
from repro.obs import trace as obs_trace

def _loss(w, x):
    h = _tanh(x @ w)
    return _rsum(h * h, None, False)

g = build_grad_graph(build_grad_graph(parse_function(_loss), 0), 0)
ex = tuple(abstract_of_value(a) for a in
           (jnp.ones((4, 4), jnp.float32), jnp.ones((2, 4), jnp.float32)))
with tempfile.TemporaryDirectory(prefix="ci-graphcache-") as d:
    pc = ProgramCache(d)
    opts = CompileOptions(graph_cache=pc)
    cold = compile_pipeline(g, ex, options=opts)
    assert pc.stats.graph_misses == 1 and pc.stats.graph_puts == 1, pc.stats.as_dict()
    tr = obs_trace.Tracer()
    with obs_trace.tracing(tr):
        warm = compile_pipeline(g, ex, options=opts)
    assert pc.stats.graph_hits == 1, pc.stats.as_dict()
    phases = tr.phase_totals_ms("compile_pipeline")
    assert "optimize" not in phases, f"warm run still optimized: {phases}"
    assert dumps(warm, names=False) == dumps(cold, names=False)
    print(f"  graph-cache smoke OK (warm phases: {sorted(phases)})")
PY

echo "== explain + profile smoke (every job: reports stay structured, profiler stays armed) =="
pyfile <<'PY'
import jax.numpy as jnp
from repro.core.api import CompileOptions, grad
from repro.core.lowering import lower_graph
from repro.core.primitives import reduce_sum as _rsum, tanh as _tanh
from repro.obs import Profiler, profiling
from repro.obs.explain import ExplainReport

def _loss(w1, w2, x):
    h = _tanh(x @ w1)
    return _rsum(_tanh(h @ w2), None, False)

df = grad(_loss, (0, 1), options=CompileOptions(fuse=True))
args = (jnp.ones((8, 8), jnp.float32) * 0.1,
        jnp.ones((8, 8), jnp.float32) * 0.1,
        jnp.ones((4, 8), jnp.float32))

# explain: every cluster and every node carries a structured verdict,
# and the report survives a JSON round trip
rep = df.explain(*args)
rt = ExplainReport.from_json(rep.to_json())
assert rt.as_dict() == rep.as_dict(), "explain report not JSON-round-trippable"
fus = rep["fusion"]
assert fus["enabled"] and fus["clusters"], "grad corpus program produced no clusters"
for c in fus["clusters"]:
    assert c["verdict"] in ("emitted", "declined"), c
for n in fus["nodes"]:
    assert n["decision"] in ("fused", "unfused"), n
    if n["decision"] == "unfused":
        assert isinstance(n.get("reason"), dict) and "kind" in n["reason"], n

# profile: armed eager run of the instrumented fused lowering of the same
# optimized graph lands on the roofline scale
fn = lower_graph(df.optimized_graph(*args), fuse=True, profile=True)
fn(*args)  # warm: trace the kernels outside the profiled window
prof = Profiler()
with profiling(prof):
    for _ in range(3):
        fn(*args)
assert prof.sites, "armed profiler recorded no launches"
agg = prof.aggregate()
fr = agg["roofline_fraction"]
assert fr is not None and 0.0 < fr <= 1.0, f"roofline_fraction out of range: {fr}"
print(f"  explain+profile smoke OK ({len(fus['clusters'])} clusters, "
      f"{agg['calls']} launches, roofline_fraction {fr})")
PY

echo "== chaos corpus (deterministic fault injection, fixed seed) =="
# part of every job, fast included: the chaos tests use explicit
# fire-at-step fault plans (seed 0xC0FFEE feeds only the garbage bytes),
# so this run is deterministic — a flake here is a real robustness bug.
python -m pytest -q -m "not slow" tests/serve/test_chaos.py

if [ "${SKIP_SLOW:-0}" != "1" ]; then
  echo "== slow suite (multi-device subprocess corpus) =="
  python -m pytest -x -q -m slow
fi

if [ "${SKIP_BENCH:-0}" != "1" ]; then
  echo "== quick benchmarks (BENCH_*.json trajectories) =="
  python -m benchmarks.run --quick
  echo "== bench regression gate =="
  python scripts/check_bench.py
  echo "== traced bench (Perfetto trace uploaded via artifacts/bench/) =="
  # one traced --quick rerun of the higher-order bench: the trace lands in
  # artifacts/bench/ which ci.yml already uploads, so every full CI run
  # leaves a loadable compile-pipeline profile next to the BENCH numbers
  python -m benchmarks.run --quick --only higher_order \
    --trace artifacts/bench/trace_higher_order.json
  echo "== runtime profile artifact (per-launch attribution + counter tracks) =="
  # armed profiler over the fused MLP adjoint: the attribution JSON and a
  # Perfetto trace with GB/s counter tracks land in artifacts/bench/,
  # which ci.yml uploads — every full run leaves a runtime profile next
  # to the compile profile above
  python - <<'PY'
import json, os
import jax
from repro.core import build_grad_graph, parse_function
from repro.core.api import compile_pipeline
from repro.core.infer import abstract_of_value
from repro.core.lowering import lower_graph
from benchmarks.bench_fusion import _two_layer
from repro.obs import Profiler, Tracer, profiling

k = jax.random.PRNGKey
args = (jax.random.normal(k(0), (256, 256)) * 0.1,
        jax.random.normal(k(1), (256, 256)) * 0.1,
        jax.random.normal(k(2), (32, 256)))
g = compile_pipeline(build_grad_graph(parse_function(_two_layer), (0, 1)),
                     tuple(abstract_of_value(a) for a in args))
fn = lower_graph(g, fuse=True, profile=True)
jax.block_until_ready(fn(*args))  # warm
prof = Profiler()
with profiling(prof):
    for _ in range(10):
        fn(*args)
os.makedirs("artifacts/bench", exist_ok=True)
with open("artifacts/bench/profile_fusion.json", "w") as f:
    json.dump(prof.as_dict(), f, indent=1)
tr = Tracer()
prof.export_counters(tr)
tr.write_chrome_trace("artifacts/bench/trace_profile_fusion.json")
print(prof.attribution_table(top=10))
print("  wrote artifacts/bench/profile_fusion.json + trace_profile_fusion.json")
PY
fi
