"""The hybrid cell's ``broadcast_materialized`` reader on hand-made span
attributes: the sum over the ``lower`` spans, nothing where the program
records no such counter."""

from bench_testing import run


def _read(ctx):
    return run.load_reader("broadcast_materialized.train_hybrid")(ctx)


def test_sums_the_lower_spans_loop_bodies_included():
    spans = [
        ("parse", {"fn": "f"}),
        ("lower", {"graph": "body", "fuse": True, "broadcast_in_kernel": 4,
                   "broadcast_materialized": 3}),
        ("lower", {"graph": "step", "fuse": True, "broadcast_in_kernel": 1,
                   "broadcast_materialized": 0}),
        ("ad.grad", {"graph": "f", "broadcast_materialized": 99}),
    ]
    assert _read({"setup_span_attrs": spans}) == 3


def test_zero_is_a_reading():
    spans = [("lower", {"graph": "step", "broadcast_materialized": 0})]
    assert _read({"setup_span_attrs": spans}) == 0


def test_reads_nothing_without_the_counter():
    assert _read({"setup_span_attrs": [("lower", {"graph": "step", "fuse": False})]}) is None
    assert _read({"setup_span_attrs": []}) is None
    assert _read({}) is None
