"""Unit tests for the runtime profiler (``repro.obs.profile``).

The load-bearing contracts:

* disarmed overhead is STRUCTURALLY zero — ``probe()`` returns the shared
  ``NULL_PROBE`` singleton (identity-pinned, like ``NULL_SPAN``) and the
  default lowering emits byte-identical source to ``profile=True``'s
  absence,
* armed, every launch lands once with a wall time and a bytes estimate,
  and the derived roofline fraction clamps to (0, 1],
* tracer arguments pass through ``call_profiled`` untimed, so an armed
  profiler never corrupts a jit trace.
"""

import threading

import jax
import jax.numpy as jnp
import pytest

from repro.obs import Tracer
from repro.obs import profile as obs_profile
from repro.obs.profile import NULL_PROBE, Profiler, call_profiled, probe, profiling


def test_disarmed_probe_is_null_singleton():
    """The structural-zero-overhead contract: disarmed, probe() returns
    the ONE shared singleton — same identity every call, no allocation."""
    assert obs_profile.active() is None
    assert probe("x", "opaque", 128) is NULL_PROBE
    assert probe("y", "fused", 0) is NULL_PROBE
    with probe("z") as p:
        assert p is NULL_PROBE


def test_disarmed_call_profiled_is_passthrough():
    calls = []

    def fn(a, b):
        calls.append((a, b))
        return a + b

    assert obs_profile.active() is None
    assert call_profiled(fn, "add:v0", "opaque", 8, 1, 2) == 3
    assert calls == [(1, 2)]


def test_profiling_arms_and_restores():
    prof = Profiler()
    assert obs_profile.active() is None
    with profiling(prof):
        assert obs_profile.active() is prof
        with profiling(None):  # None nests as a no-op
            assert obs_profile.active() is prof
    assert obs_profile.active() is None


def test_armed_call_profiled_records_launch():
    prof = Profiler()
    with profiling(prof):
        out = call_profiled(lambda x: x * 2, "mul:v0", "opaque", 64, jnp.ones(4))
    assert out.shape == (4,)
    site = prof.sites[("mul:v0", "opaque")]
    assert site.calls == 1 and site.nbytes == 64 and site.total_s > 0.0


def test_tracer_args_pass_through_untimed():
    """An armed profiler under an outer jit trace must not record (it
    would measure trace time) nor block on tracers."""
    prof = Profiler()

    def f(x):
        return call_profiled(jnp.tanh, "tanh:v0", "opaque", 32, x)

    with profiling(prof):
        jax.jit(f)(jnp.ones(4))
    assert ("tanh:v0", "opaque") not in prof.sites


def test_roofline_fraction_clamps_to_one():
    prof = Profiler(peak_gbps=10.0)
    assert prof.roofline_fraction(None) is None
    assert prof.roofline_fraction(0.0) is None
    assert prof.roofline_fraction(5.0) == pytest.approx(0.5)
    # a site beating the model (cache-resident CPU) saturates at 1.0
    assert prof.roofline_fraction(1e6) == 1.0


def test_rows_and_aggregate():
    prof = Profiler(peak_gbps=100.0)
    prof.record("a", "fused", 0.001, 1_000_000)  # 1 GB/s
    prof.record("a", "fused", 0.001, 1_000_000)
    prof.record("b", "opaque", 0.003, 0)  # no byte estimate
    rows = prof.rows()
    assert [r["name"] for r in rows] == ["b", "a"]  # hottest first
    a = rows[1]
    assert a["calls"] == 2
    assert a["achieved_gbps"] == pytest.approx(1.0, rel=1e-3)
    assert a["roofline_fraction"] == pytest.approx(0.01, rel=1e-3)
    b = rows[0]
    assert b["achieved_gbps"] is None and b["roofline_fraction"] is None
    agg = prof.aggregate("fused")
    assert agg["calls"] == 2 and agg["total_bytes"] == 2_000_000
    assert prof.aggregate()["calls"] == 3


def test_sample_ring_bounded_and_counted():
    prof = Profiler(max_samples=3)
    for i in range(5):
        prof.record(f"s{i}", "opaque", 0.001, 10)
    assert len(prof.samples) == 3
    assert prof.dropped_samples == 2
    assert prof.as_dict()["dropped_samples"] == 2


def test_export_counters_emits_counter_events():
    prof = Profiler()
    prof.record("k", "fused", 0.001, 1_000_000)
    tr = Tracer()
    n = prof.export_counters(tr)
    assert n == 2  # launch_ms + gbps series
    kinds = {e.name for e in tr.events}
    assert kinds == {"profile.launch_ms", "profile.gbps.k"}
    ct = tr.chrome_trace()
    cs = [e for e in ct["traceEvents"] if e["ph"] == "C"]
    assert len(cs) == 2 and all("value" in e["args"] for e in cs)


def test_attribution_table_renders_total_row():
    prof = Profiler()
    prof.record("hot", "fused", 0.002, 4096)
    table = prof.attribution_table()
    assert "hot" in table and "TOTAL" in table and "roofline" in table


def test_record_is_thread_safe():
    prof = Profiler(max_samples=10_000)

    def worker():
        for _ in range(500):
            prof.record("shared", "opaque", 0.0001, 8)

    threads = [threading.Thread(target=worker) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert prof.sites[("shared", "opaque")].calls == 2000


def test_default_lowering_source_is_byte_identical():
    """profile=False (the production default) must emit byte-identical
    generated source to the pre-profiler lowering: the hook only exists
    in the source when explicitly requested."""
    from repro.core import P, parse_function
    from repro.core.api import compile_pipeline
    from repro.core.infer import abstract_of_value
    from repro.core.lowering import lower_graph

    def f(x):
        return P.tanh(x) * x

    g = compile_pipeline(
        parse_function(f), (abstract_of_value(jnp.ones((4, 4))),)
    )
    plain = lower_graph(g)
    default = lower_graph(g, profile=False)
    instrumented = lower_graph(g, profile=True)
    assert plain.__lowered_source__ == default.__lowered_source__
    assert "_prof(" not in plain.__lowered_source__
    assert "_prof(" in instrumented.__lowered_source__


def test_instrumented_lowering_matches_and_records():
    from repro.core import P, parse_function
    from repro.core.api import compile_pipeline
    from repro.core.infer import abstract_of_value
    from repro.core.lowering import lower_graph
    import numpy as np

    def f(x):
        return P.reduce_sum(P.tanh(x) * x, (0, 1), False)

    x = jnp.asarray(np.linspace(-1, 1, 16, dtype=np.float32).reshape(4, 4))
    g = compile_pipeline(parse_function(f), (abstract_of_value(x),))
    plain = lower_graph(g)
    inst = lower_graph(g, profile=True)
    prof = Profiler()
    with profiling(prof):
        got = inst(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(plain(x)), rtol=1e-6)
    assert prof.sites, "no launches recorded"
    assert all(k in ("opaque", "loop", "collective") for (_, k) in prof.sites)
    # bytes estimates come from the inferred abstracts: nonzero here
    assert any(s.nbytes > 0 for s in prof.sites.values())


def test_fused_kernel_self_times():
    """A FusedKernel records itself (kind="fused") when armed — and the
    bytes_moved estimate covers cluster inputs + root output."""
    from repro.core import P, build_grad_graph, parse_function
    from repro.core.api import compile_pipeline
    from repro.core.infer import abstract_of_value
    from repro.core.lowering import lower_graph

    def two_layer(w1, w2, x):
        h = P.tanh(x @ w1)
        return P.reduce_sum(P.tanh(h @ w2), (0, 1), False)

    args = (jnp.ones((8, 8)), jnp.ones((8, 8)), jnp.ones((4, 8)))
    g = compile_pipeline(
        build_grad_graph(parse_function(two_layer), (0, 1)),
        tuple(abstract_of_value(a) for a in args),
    )
    fn = lower_graph(g, fuse=True, profile=True)
    assert fn.__fused_kernels__, "workload fused nothing"
    assert all(k.bytes_moved > 0 for k in fn.__fused_kernels__)
    prof = Profiler()
    with profiling(prof):
        fn(*args)
    fused_sites = [s for (_, kind), s in prof.sites.items() if kind == "fused"]
    assert len(fused_sites) == len(fn.__fused_kernels__)

