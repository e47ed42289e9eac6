"""Tracer contract: nesting, exception safety, Chrome export, bounded
buffer, and — the load-bearing one — zero work on the disarmed path."""

import json

import pytest

from repro.obs import trace as T


def test_nested_spans_depth_and_order():
    tr = T.Tracer()
    with T.tracing(tr):
        with T.span("outer", k=1):
            with T.span("inner_a"):
                pass
            with T.span("inner_b"):
                pass
    # children close before the parent → buffer order is close order
    names = [e.name for e in tr.events]
    assert names == ["inner_a", "inner_b", "outer"]
    by_name = {e.name: e for e in tr.events}
    assert by_name["outer"].depth == 0
    assert by_name["inner_a"].depth == by_name["inner_b"].depth == 1
    assert by_name["outer"].attrs == {"k": 1}
    # intervals nest
    assert by_name["outer"].t0 <= by_name["inner_a"].t0
    assert by_name["inner_b"].t1 <= by_name["outer"].t1


def test_phase_totals_direct_children_only():
    tr = T.Tracer()
    with T.tracing(tr):
        with T.span("root"):
            with T.span("phase_a"):
                with T.span("sub"):  # depth 2: excluded from the breakdown
                    pass
            with T.span("phase_b"):
                pass
    totals = tr.phase_totals_ms("root")
    assert set(totals) == {"phase_a", "phase_b"}
    root = tr.find("root")[0]
    assert sum(totals.values()) <= root.dur_s * 1e3 + 1e-6


def test_span_exception_safety():
    tr = T.Tracer()
    with T.tracing(tr):
        with pytest.raises(ValueError):
            with T.span("boom"):
                raise ValueError("x")
    assert T.active() is None, "tracing() must disarm on raise"
    (rec,) = tr.events
    assert rec.name == "boom"
    assert rec.t1 is not None, "record must close on raise"
    assert rec.attrs["error"] == "ValueError"


def test_set_attrs_mid_span():
    tr = T.Tracer()
    with T.tracing(tr):
        with T.span("s") as sp:
            sp.set(count=7)
    assert tr.events[0].attrs["count"] == 7


def test_mark_with_explicit_timestamp():
    tr = T.Tracer()
    with T.tracing(tr):
        T.mark("evt", ts=123.456, rid=9)
    (rec,) = tr.events
    assert rec.kind == "mark"
    assert rec.t0 == rec.t1 == 123.456
    assert rec.attrs["rid"] == 9


def test_chrome_trace_round_trip(tmp_path):
    tr = T.Tracer()
    with T.tracing(tr):
        with T.span("compile_pipeline", graph="g"):
            with T.span("optimize"):
                pass
        T.mark("serve.submit", rid=0)
    path = tmp_path / "trace.json"
    tr.write_chrome_trace(str(path))
    doc = json.loads(path.read_text())  # must be valid JSON end to end
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert len(evs) == 3
    by_name = {e["name"]: e for e in evs}
    x = by_name["optimize"]
    assert x["ph"] == "X" and x["dur"] >= 0 and x["ts"] >= 0
    i = by_name["serve.submit"]
    assert i["ph"] == "i" and i["cat"] == "serve" and i["args"]["rid"] == 0
    # timestamps are rebased: the earliest event opens at t=0
    assert min(e["ts"] for e in evs) == 0


def test_bounded_buffer_drops_and_high_water():
    tr = T.Tracer(max_events=3)
    with T.tracing(tr):
        for i in range(5):
            with T.span(f"s{i}"):
                pass
    assert len(tr.events) == 3
    assert tr.dropped == 2
    assert tr.high_water == 3
    assert tr.chrome_trace()["otherData"]["dropped"] == 2


def test_disarmed_overhead_is_one_global_read():
    # the production state: no tracer armed.  span() must return the
    # SHARED singleton — no allocation, no clock read, no buffer append —
    # and mark() must be a no-op.  Structural identity (not timing) pins
    # the fast path deterministically.
    assert T.active() is None
    s1 = T.span("anything", big_attr="ignored")
    s2 = T.span("other")
    assert s1 is T.NULL_SPAN and s2 is T.NULL_SPAN
    # the train step's spans take the same path
    for name in ("train.step", "train.vag", "train.update"):
        assert T.span(name) is T.NULL_SPAN
    with s1:
        s1.set(x=1)  # all no-ops
    assert s1.dur_s == 0.0
    T.mark("nothing", rid=1)
    # and a disarmed block leaves zero residue in a later-armed tracer
    tr = T.Tracer()
    with T.tracing(tr):
        pass
    assert tr.events == [] and tr.high_water == 0


def test_tracing_none_is_passthrough():
    tr = T.Tracer()
    with T.tracing(tr):
        with T.tracing(None):  # optional-tracer call sites: keep ambient
            with T.span("kept"):
                pass
    assert [e.name for e in tr.events] == ["kept"]


def test_total_s_and_summary():
    tr = T.Tracer()
    with T.tracing(tr):
        for _ in range(3):
            with T.span("opt.rules"):
                pass
    assert tr.total_s("opt.rules") >= 0
    text = tr.phase_summary()
    assert "opt.rules" in text and "count" in text


def test_counter_events_export_as_counter_tracks():
    tr = T.Tracer()
    with T.tracing(tr):
        tr.counter("profile.gbps.k0", 12.5, ts=1.0)
        tr.counter("profile.launch_ms", 0.8, ts=1.0, site="k0")
    assert all(e.kind == "counter" for e in tr.events)
    doc = tr.chrome_trace()
    cs = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    assert len(cs) == 2
    # counter args carry exactly the series value (Perfetto stacks args)
    assert {e["args"]["value"] for e in cs} == {12.5, 0.8}
    # counters are samples, not phases: excluded from span aggregation
    assert tr.phase_totals_ms() == {}


def test_span_name_registry_covers_instrumented_sources():
    """Every span()/mark() literal in src/ and benchmarks/ appears in the
    trace.py registry — same AST check scripts/lint.py enforces, run here
    through the lint helpers so the contract fails in BOTH gates."""
    import importlib.util
    import pathlib

    root = pathlib.Path(__file__).resolve().parents[2]
    spec = importlib.util.spec_from_file_location(
        "repro_lint", root / "scripts" / "lint.py"
    )
    lint = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(lint)
    # the AST-parsed registries agree with the imported constants
    assert lint._registry_names("SPAN_NAMES") == set(T.SPAN_NAMES)
    assert lint._registry_names("MARK_NAMES") == set(T.MARK_NAMES)
    assert lint._span_registry_check() == 0


def test_registry_contains_pipeline_and_profiler_names():
    for name in ("compile_pipeline", "optimize", "fuse.partition", "explain.report"):
        assert name in T.SPAN_NAMES
    # the spans the armed tracer records from JAX's events and gc, too
    assert set(T.JAX_SPAN_EVENTS.values()) | {"host.gc"} <= T.SPAN_NAMES
    for name in ("serve.submit", "serve.terminal"):
        assert name in T.MARK_NAMES


# ---------------------------------------------------------------------------
# The profiler bridge, JAX's compile events and gc pauses
# ---------------------------------------------------------------------------


def _hooks_installed() -> tuple:
    import gc

    from jax._src import monitoring

    return (
        list(gc.callbacks),
        monitoring.get_scalar_listeners(),
        monitoring.get_event_time_span_listeners(),
        monitoring.get_event_listeners(),
    )


def _host_events(log_dir) -> list[str]:
    """Names of the events on the profiler's ``/host:CPU`` plane."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(str(log_dir / "plugins" / "profile" / "*" / "*.xplane.pb"))
    data = ProfileData.from_file(path)
    return [
        e.name
        for plane in data.planes
        if plane.name == "/host:CPU"
        for line in plane.lines
        for e in line.events
    ]


def test_armed_spans_are_on_the_profiler_timeline(tmp_path):
    import gc

    import jax

    tr = T.Tracer()
    with jax.profiler.trace(str(tmp_path)):
        with T.span("train.update"):  # disarmed: never on the timeline
            pass
        with T.tracing(tr):
            with T.span("train.step"):
                with T.span("train.vag"):
                    pass
            gc.collect()
    names = _host_events(tmp_path)
    assert {"train.step", "train.vag", "host.gc"} <= set(names)
    assert "train.update" not in names


def test_fresh_jit_records_compile_spans_inside_the_enclosing_span():
    import jax
    import jax.numpy as jnp

    def fresh(x):  # a new function object: nothing cached for it
        return jnp.tanh(x) * 3.0 + 1.0

    tr = T.Tracer()
    with T.tracing(tr):
        with T.span("train.vag"):
            jax.block_until_ready(jax.jit(fresh)(jnp.ones((4, 4))))
    (outer,) = tr.find("train.vag")
    for name in ("jit.trace", "jit.lower", "jit.compile"):
        mine = [e for e in tr.find(name) if "fresh" in e.attrs["fun_name"]]
        assert len(mine) == 1, name
        (rec,) = mine
        assert outer.t0 <= rec.t0 <= rec.t1 <= outer.t1
        assert rec.depth == outer.depth + 1
    # no persistent cache here: compiled, with no lookup and no load
    assert not [e for e in tr.events if e.name in ("jit.cache_load", "jit.cache_write")]


def test_disarm_restores_the_hooks_and_records_nothing():
    import gc

    import jax
    import jax.numpy as jnp

    before = _hooks_installed()
    tr = T.Tracer()
    with T.tracing(tr):
        with T.tracing(T.Tracer()):  # nested: installed once
            assert len(gc.callbacks) == len(before[0]) + 1
        assert len(gc.callbacks) == len(before[0]) + 1
    assert _hooks_installed() == before
    n = len(tr.events)

    def later(x):
        return jnp.cos(x) - 2.0

    jax.block_until_ready(jax.jit(later)(jnp.ones(3)))
    gc.collect()
    assert len(tr.events) == n


def test_hooks_are_removed_when_the_block_raises():
    before = _hooks_installed()
    with pytest.raises(RuntimeError):
        with T.tracing(T.Tracer()):
            raise RuntimeError("x")
    assert _hooks_installed() == before


def test_gc_collect_records_a_host_gc_span():
    import gc

    tr = T.Tracer()
    with T.tracing(tr):
        with T.span("train.step"):
            gc.collect()
    (step,) = tr.find("train.step")
    gcs = [e for e in tr.find("host.gc") if e.attrs["generation"] == 2]
    assert gcs, [e.attrs for e in tr.find("host.gc")]
    rec = gcs[-1]
    assert step.t0 <= rec.t0 <= rec.t1 <= step.t1
    assert rec.depth == step.depth + 1 and rec.attrs["collected"] >= 0


@pytest.mark.parametrize("event,name", [
    ("/jax/compilation_cache/cache_hits", "jit.cache_load"),
    ("/jax/compilation_cache/cache_misses", "jit.compile"),
])
def test_cache_events_mark_the_compile_that_encloses_them(event, name):
    import time

    from jax import monitoring

    compile_event = "/jax/core/compile/backend_compile_duration"
    tr = T.Tracer()
    with T.tracing(tr):
        with T.span("train.vag"):
            for _ in range(2):
                start = time.time()
                monitoring.record_event(event)
                monitoring.record_event_time_span(
                    compile_event, start, time.time(), fun_name="jit(f)"
                )
            # a compile with no cache event in it: no cache in use
            start = time.time()
            monitoring.record_event_time_span(compile_event, start, time.time(), fun_name="g")
    compiles = [e for e in tr.events if e.name in ("jit.compile", "jit.cache_load")]
    assert [(e.name, e.attrs["fun_name"]) for e in compiles] == [
        (name, "jit(f)"), (name, "jit(f)"), ("jit.compile", "g")
    ]
    writes = tr.find("jit.cache_write")
    if name == "jit.cache_load":
        assert writes == []
    else:  # the write that follows the miss nests in its compile, to its end
        assert len(writes) == 2
        for write, comp in zip(writes, compiles):
            assert comp.t0 <= write.t0 <= write.t1 == comp.t1
            assert write.depth == comp.depth + 1 and write.attrs["fun_name"] == "jit(f)"
    (vag,) = tr.find("train.vag")
    assert all(e.depth == vag.depth + 1 for e in compiles)


def test_blocks_exiting_out_of_order_leave_nothing_armed():
    """Two threads' blocks exit in the order they armed, not the reverse:
    the one still open stays armed, and the last exit disarms all."""
    before = _hooks_installed()
    first, second = T.Tracer(), T.Tracer()
    a, b = T.tracing(first), T.tracing(second)
    a.__enter__()
    b.__enter__()
    assert T.active() is second
    a.__exit__(None, None, None)
    assert T.active() is second and _hooks_installed() != before
    b.__exit__(None, None, None)
    assert T.active() is None and _hooks_installed() == before


def test_train_step_spans_nest():
    import jax
    import jax.numpy as jnp

    from repro.launch.myia_step import MyiaLMDims, make_myia_train_step

    dims = MyiaLMDims(32, 8, 16)
    step_fn, init_fn = make_myia_train_step(dims, 2, 4, 0.1, fuse=False)
    state = init_fn(jax.random.PRNGKey(1))
    batch = {"tokens": jnp.zeros((2, 4), jnp.int32), "labels": jnp.ones((2, 4), jnp.int32)}
    tr = T.Tracer()
    with T.tracing(tr):
        state, _ = step_fn(state, batch)
    (step,) = tr.find("train.step")
    for name in ("train.vag", "train.update"):
        (child,) = tr.find(name)
        assert step.t0 <= child.t0 <= child.t1 <= step.t1
        assert child.depth == step.depth + 1
    assert int(state["step"]) == 1
    # the first call compiled inside the loss+gradient span
    (vag,) = tr.find("train.vag")
    assert any(vag.t0 <= e.t0 <= e.t1 <= vag.t1 for e in tr.find("jit.compile"))


def test_concurrent_append_exact_drop_accounting():
    """N threads hammering a bounded buffer: len(events) + dropped must
    equal the exact number of records offered, and high_water equals the
    cap — no lost updates under the append lock."""
    import threading

    cap = 100
    tr = T.Tracer(max_events=cap)
    per_thread, n_threads = 200, 8
    barrier = threading.Barrier(n_threads)

    def worker(tid):
        barrier.wait()
        for i in range(per_thread):
            tr.mark(f"m{tid}.{i}", {"i": i})

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    offered = per_thread * n_threads
    assert len(tr.events) == cap
    assert tr.dropped == offered - cap
    assert tr.high_water == cap


def test_concurrent_spans_under_capacity_lose_nothing():
    import threading

    tr = T.Tracer(max_events=10_000)
    n_threads, per_thread = 8, 100

    def worker():
        with T.tracing(tr):
            for _ in range(per_thread):
                with T.span("concurrent"):
                    pass

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.find("concurrent")) == n_threads * per_thread
    assert tr.dropped == 0
    # the armed tracer also records the collections that ran meanwhile
    assert tr.high_water == len(tr.events) == n_threads * per_thread + len(tr.find("host.gc"))
    # however the threads' blocks interleaved, the last exit disarmed
    assert T.active() is None

