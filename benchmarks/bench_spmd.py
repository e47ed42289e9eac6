"""SPMD tier benchmark: launch counts and step time per mesh size.

For the MLP adjoint (the paper's running example) compiled through the
shard-aware tier, report — per mesh shape (1×1, 2×1, 2×2) —

* the **partition**: kernel launches of the per-shard program before and
  after fusion (collectives included; clusters never span one) and the
  collective counts the propagation pass inserted (psum / pmax /
  all_gather / shard_slice),
* **wall clock**: median jitted step time of the fused sharded program
  under ``shard_map`` vs the single-device unfused oracle, and the
  allclose check against that oracle (``max_rel_err``).

Every mesh is simulated per-row in a subprocess pinned to the CPU
(``JAX_PLATFORMS=cpu``) with ``--xla_force_host_platform_device_count``:
the rows say ``platform: cpu``, and a worker never reaches for an
accelerator the parent process may already hold.
Rows land in ``BENCH_spmd.json`` via ``benchmarks/run.py`` so successive
PRs leave a trajectory; ``scripts/check_bench.py`` gates launch-count
regressions in CI.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import textwrap

_WORKER = textwrap.dedent(
    """
    import sys
    sys.path.insert(0, %(src)r)
    import json, time
    import jax, numpy as np

    import repro.core.primitives as P
    from repro.core import build_grad_graph, parse_function
    from repro.core.api import compile_pipeline
    from repro.core.infer import abstract_of_value
    from repro.core.jax_backend import compile_graph_spmd
    from repro.core.lowering import lower_graph
    from repro.launch.mesh import make_local_mesh

    MESH = %(mesh)r
    REPS = %(reps)d

    def _two_layer(w1, w2, x):
        h = P.tanh(x @ w1)
        return P.reduce_sum(P.tanh(h @ w2), (0, 1), False)

    k = jax.random.PRNGKey
    d, b = 64, 32
    w1 = jax.random.normal(k(0), (d, d)) * 0.1
    w2 = jax.random.normal(k(1), (d, d)) * 0.1
    x = jax.random.normal(k(2), (b, d))
    args = (w1, w2, x)
    in_specs = (None, None, ("data",))

    g = compile_pipeline(
        build_grad_graph(parse_function(_two_layer), (0, 1)),
        tuple(abstract_of_value(a) for a in args),
    )
    oracle = jax.jit(lower_graph(g))
    ref = oracle(*args)

    def median_us(fn):
        r = fn(*args)
        jax.block_until_ready(r)  # compile outside the timer
        ts = []
        for _ in range(REPS):
            t0 = time.perf_counter()
            r = fn(*args)
            jax.block_until_ready(r)
            ts.append((time.perf_counter() - t0) * 1e6)
        ts.sort()
        return ts[len(ts) // 2]

    run = compile_graph_spmd(g, make_local_mesh(*MESH), in_specs, fuse=True)
    got = run(*args)
    rel = max(
        float(np.max(np.abs(np.asarray(a) - np.asarray(b)))
              / (np.max(np.abs(np.asarray(b))) + 1e-30))
        for a, b in zip(got, ref)
    )
    plan = run.fn.__fusion_plan__
    stats = run.sharded.stats
    row = {
        "workload": "mlp_adjoint_dp",
        "mesh": "x".join(map(str, MESH)),
        "platform": jax.devices()[0].platform,
        "devices": %(ndev)d,
        "launches_unfused": plan.launches_before,
        "launches_fused": plan.launches_after,
        "n_clusters": len(plan.clusters),
        "n_psum": stats["psum"],
        "n_pmax": stats["pmax"],
        "n_all_gather": stats["all_gather"],
        "n_shard_slice": stats["shard_slice"],
        "oracle_us": round(median_us(oracle), 1),
        "spmd_fused_us": round(median_us(run), 1),
        "max_rel_err": float(f"{rel:.2e}"),
    }
    print("ROW " + json.dumps(row))
    """
)

_MESHES = (((1, 1), 1), ((2, 1), 2), ((2, 2), 4))


def run(reps: int = 30) -> list[dict]:
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    rows = []
    for mesh, ndev in _MESHES:
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            XLA_FLAGS=f"--xla_force_host_platform_device_count={ndev}",
        )
        script = _WORKER % {"ndev": ndev, "src": src, "mesh": mesh, "reps": reps}
        with tempfile.NamedTemporaryFile("w", suffix="_bench_spmd.py", delete=False) as f:
            f.write(script)
            path = f.name
        try:
            res = subprocess.run(
                [sys.executable, path],
                capture_output=True,
                text=True,
                timeout=600,
                env=env,
            )
        finally:
            os.unlink(path)
        if res.returncode != 0:  # pragma: no cover - surfaced to the console
            raise RuntimeError(
                f"bench_spmd worker (mesh {mesh}) failed:\n{res.stderr[-2000:]}"
            )
        for line in res.stdout.splitlines():
            if line.startswith("ROW "):
                rows.append(json.loads(line[4:]))
    return rows


if __name__ == "__main__":
    for row in run(reps=10):
        print(row)
