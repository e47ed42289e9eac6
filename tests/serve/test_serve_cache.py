"""AOT program cache under the serve engine: durable compiled programs.

* In-process cold→warm over one tmpdir cache: the warm engine answers
  every specialization from disk (hits > 0, zero XLA compiles) and
  serves identical tokens — this is the CI fast-job smoke.
* Subprocess cold→warm: a genuine process restart replays serialized
  executables with **zero recompilations** (the ISSUE 5 acceptance
  criterion), pinned via the cache counters.
"""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core.jax_backend import ProgramCache
from repro.serve import ServeEngine, ServeLMDims, init_serve_params

_SRC = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "src")
)

DIMS = ServeLMDims(vocab=48, d_model=8, d_hidden=16)
PARAMS = init_serve_params(DIMS, jax.random.PRNGKey(0))


def _workload(engine):
    rng = np.random.default_rng(0)
    rids = [
        engine.submit(rng.integers(0, DIMS.vocab, n).tolist(), m)
        for n, m in [(5, 6), (9, 4), (3, 8)]
    ]
    return rids, engine.run()


def test_cold_then_warm_in_process(tmp_path):
    cold_cache = ProgramCache(str(tmp_path))
    cold = ServeEngine(DIMS, PARAMS, n_slots=2, min_bucket=16, program_cache=cold_cache)
    _rids, cold_res = _workload(cold)
    assert cold_cache.stats.misses > 0
    assert cold_cache.stats.puts == cold_cache.stats.misses
    assert cold_cache.stats.hits == 0

    warm_cache = ProgramCache(str(tmp_path))
    warm = ServeEngine(DIMS, PARAMS, n_slots=2, min_bucket=16, program_cache=warm_cache)
    _rids2, warm_res = _workload(warm)
    assert warm_cache.stats.hits > 0
    assert warm_cache.stats.misses == 0
    assert warm_cache.stats.xla_compiles == 0  # answered purely from disk
    assert warm_cache.stats.exec_loads == warm_cache.stats.hits
    for rid in cold_res:
        assert warm_res[rid]["tokens"] == cold_res[rid]["tokens"]


def test_aot_runner_survives_tracer_args_after_eager_call(tmp_path):
    """The specialization key cannot tell a concrete array from a
    same-shaped tracer: a MyiaFunction called eagerly first (caching the
    AOT runner) and then under an outer jit must not hand the compiled
    executable tracer arguments — it falls back to an ordinary jit."""
    import jax
    import jax.numpy as jnp
    from repro.core import P, api

    def f(x, w):
        return P.reduce_sum(P.tanh(x @ w), None, False)

    g = api.myia(f, options=api.CompileOptions(program_cache=ProgramCache(str(tmp_path))))
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.ones((8, 8), jnp.float32) * 0.1
    eager = g(x, w)  # caches the AOT runner for this signature
    assert getattr(g.specialize((x, w)), "aot", False)
    traced = jax.jit(lambda x_, w_: g(x_, w_) * 2.0)(x, w)
    np.testing.assert_allclose(
        np.asarray(traced), np.asarray(eager) * 2.0, rtol=1e-6
    )


def test_other_device_kind_misses(tmp_path, monkeypatch):
    """An executable compiled for one device kind is never loaded on
    another: the kind is part of the key, so the lookup misses and the
    program compiles afresh."""
    from repro.core import api
    from repro.core import jax_backend

    f = _tiny_fn()
    x = np.ones((4, 8), np.float32)
    w = np.full((8, 8), 0.1, np.float32)
    opts = api.CompileOptions(program_cache=ProgramCache(str(tmp_path)))
    api.myia(f, options=opts)(x, w)  # cold: compiles and persists

    platform, kind = jax_backend._device_identity()
    warm = ProgramCache(str(tmp_path))
    api.myia(f, options=api.CompileOptions(program_cache=warm))(x, w)
    assert (warm.stats.hits, warm.stats.misses) == (1, 0)

    monkeypatch.setattr(
        jax_backend, "_device_identity", lambda: (platform, kind + " (other)")
    )
    other = ProgramCache(str(tmp_path))
    api.myia(f, options=api.CompileOptions(program_cache=other))(x, w)
    assert (other.stats.hits, other.stats.misses) == (0, 1)
    assert other.stats.xla_compiles == 1


def test_cache_spills_when_over_capacity(tmp_path):
    cache = ProgramCache(str(tmp_path), max_entries=1)
    engine = ServeEngine(DIMS, PARAMS, n_slots=2, min_bucket=16, program_cache=cache)
    rng = np.random.default_rng(0)
    engine.submit(rng.integers(0, DIMS.vocab, 4).tolist(), 4)    # 16-bucket
    engine.submit(rng.integers(0, DIMS.vocab, 20).tolist(), 8)   # 32-bucket
    engine.run()
    assert cache.stats.puts >= 2
    assert cache.stats.spills >= 1
    files = [n for n in os.listdir(tmp_path) if n.endswith(".pkl")]
    assert len(files) == 1


def _tiny_fn():
    from repro.core import P

    def f(x, w):
        return P.reduce_sum(P.tanh(x @ w), None, False)

    return f


def test_truncated_entry_quarantined_on_load(tmp_path):
    """A truncated entry file is classified corrupt, renamed aside
    (``*.quarantined``) so it is never re-read, and recompiled around —
    the caller sees a plain miss, never an exception."""
    import jax.numpy as jnp
    from repro.core import api

    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.full((8, 8), 0.1, jnp.float32)
    cache = ProgramCache(str(tmp_path))
    mf = api.myia(_tiny_fn(), options=api.CompileOptions(program_cache=cache))
    want = np.asarray(mf(x, w))
    (entry,) = [n for n in os.listdir(tmp_path) if n.endswith(".pkl")]
    with open(tmp_path / entry, "r+b") as f:
        f.truncate(16)

    cache2 = ProgramCache(str(tmp_path))
    mf2 = api.myia(_tiny_fn(), options=api.CompileOptions(program_cache=cache2))
    got = np.asarray(mf2(x, w))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert cache2.stats.corrupt_entries == 1
    assert cache2.stats.quarantined == 1
    assert cache2.stats.hits == 0 and cache2.stats.misses == 1
    names = set(os.listdir(tmp_path))
    assert entry + ".quarantined" in names  # renamed aside …
    assert entry in names  # … and the key re-written fresh by the miss

    cache3 = ProgramCache(str(tmp_path))
    mf3 = api.myia(_tiny_fn(), options=api.CompileOptions(program_cache=cache3))
    np.testing.assert_allclose(np.asarray(mf3(x, w)), want, rtol=1e-6)
    assert cache3.stats.hits == 1  # the re-written entry answers
    assert cache3.stats.corrupt_entries == 0  # quarantine was never re-read


_RACE_SCRIPT = textwrap.dedent(
    """
    import json, os, sys
    import jax.numpy as jnp
    from repro.core import P, api
    from repro.core.jax_backend import ProgramCache

    cachedir, iters = sys.argv[1], int(sys.argv[2])
    cache = ProgramCache(cachedir)

    def f(x, w):
        return P.reduce_sum(P.tanh(x @ w), None, False)

    mf = api.myia(f, options=api.CompileOptions(program_cache=cache))
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.full((8, 8), 0.1, jnp.float32)
    key = None
    for _ in range(iters):
        # churn the one shared key: unlink, then re-specialize (miss ->
        # compile -> atomic _write), racing the sibling process's
        # reads/writes of the same file
        if key is not None:
            try:
                os.unlink(os.path.join(cachedir, key + ".pkl"))
            except FileNotFoundError:
                pass
        mf._specializations.clear()
        runner = mf.specialize((x, w))
        key = getattr(runner, "cache_key", None)
        assert key is not None, "specialization left the AOT tier"
        float(runner(x, w))  # and the program actually runs
    print(json.dumps(cache.stats.as_dict()))
    """
)


@pytest.mark.slow
def test_concurrent_same_key_writers_last_writer_wins(tmp_path):
    """Two processes churn the SAME cache key concurrently (unlink +
    re-write through ``_write``'s tmpfile + atomic rename).  Torn reads
    would surface as ``corrupt_entries``/``quarantined`` in either
    process; the survivor entry must be a clean, loadable last-writer
    artifact."""
    script = tmp_path / "race.py"
    script.write_text(_RACE_SCRIPT)
    cachedir = tmp_path / "cache"
    cachedir.mkdir()
    env = dict(os.environ, PYTHONPATH=_SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(cachedir), "12"],
            stdout=subprocess.PIPE,
            stderr=subprocess.PIPE,
            text=True,
            env=env,
        )
        for _ in range(2)
    ]
    stats = []
    for p in procs:
        out, err = p.communicate(timeout=600)
        assert p.returncode == 0, err
        stats.append(json.loads(out.strip().splitlines()[-1]))
    for s in stats:
        # atomic rename ⇒ no reader ever saw a half-written entry
        assert s["corrupt_entries"] == 0, s
        assert s["quarantined"] == 0, s
        assert s["puts"] > 0, s
    # no tmpfile leaks, and exactly the one (last-written) entry survives
    names = os.listdir(cachedir)
    assert not [n for n in names if n.endswith(".tmp")], names
    assert len([n for n in names if n.endswith(".pkl")]) == 1, names

    import jax.numpy as jnp
    from repro.core import api

    cache = ProgramCache(str(cachedir))
    mf = api.myia(_tiny_fn(), options=api.CompileOptions(program_cache=cache))
    x = jnp.ones((4, 8), jnp.float32)
    w = jnp.full((8, 8), 0.1, jnp.float32)
    val = float(mf(x, w))
    assert cache.stats.hits == 1 and cache.stats.corrupt_entries == 0
    assert np.isfinite(val)


_SUBPROCESS_SCRIPT = textwrap.dedent(
    """
    import json, sys
    import jax, numpy as np
    from repro.core.jax_backend import ProgramCache
    from repro.serve import ServeEngine, ServeLMDims, init_serve_params

    dims = ServeLMDims(vocab=48, d_model=8, d_hidden=16)
    params = init_serve_params(dims, jax.random.PRNGKey(0))
    cache = ProgramCache(sys.argv[1])
    engine = ServeEngine(dims, params, n_slots=2, min_bucket=16, program_cache=cache)
    rng = np.random.default_rng(0)
    rids = [
        engine.submit(rng.integers(0, dims.vocab, n).tolist(), m)
        for n, m in [(5, 6), (9, 4), (3, 8)]
    ]
    results = engine.run()
    print(json.dumps({
        "stats": cache.stats.as_dict(),
        "engine": engine.stats(),
        "tokens": {str(r): results[r]["tokens"] for r in rids},
    }))
    """
)


@pytest.mark.slow
def test_warm_process_restart_zero_recompilations(tmp_path):
    """The acceptance criterion: the same workload in a fresh process hits
    the persistent cache for every specialization and performs zero XLA
    compilations, serving identical tokens."""
    script = tmp_path / "serve_once.py"
    script.write_text(_SUBPROCESS_SCRIPT)
    cachedir = tmp_path / "cache"
    env = dict(os.environ, PYTHONPATH=_SRC + os.pathsep + os.environ.get("PYTHONPATH", ""))
    runs = []
    for _ in range(2):
        res = subprocess.run(
            [sys.executable, str(script), str(cachedir)],
            capture_output=True,
            text=True,
            env=env,
        )
        assert res.returncode == 0, res.stderr
        runs.append(json.loads(res.stdout.strip().splitlines()[-1]))
    cold, warm = runs
    assert cold["stats"]["misses"] == cold["engine"]["total_compilations"]
    assert cold["stats"]["xla_compiles"] > 0
    # warm restart: every lookup hits, nothing compiles
    assert warm["stats"]["misses"] == 0
    assert warm["stats"]["xla_compiles"] == 0
    assert warm["stats"]["hits"] == cold["stats"]["misses"]
    assert warm["stats"]["exec_loads"] == warm["stats"]["hits"]
    assert warm["tokens"] == cold["tokens"]
