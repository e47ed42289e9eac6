"""pipeline_s: seconds the Myia pipeline took while set-up compiled the
cell's programs: the union of the ``parse``, ``ad.grad`` and
``compile_pipeline`` spans and what nests in them, less any ``xla.*``
span inside them.  Source: the program's own spans (``obs.trace``)."""

from bench.trace_reduce import union

NAMES = ("parse", "ad.grad", "compile_pipeline")


def _overlap(a: list, b: list) -> float:
    return sum(max(0.0, min(e1, e2) - max(s1, s2)) for s1, e1 in a for s2, e2 in b)


def read(ctx: dict):
    spans = [(n, t0, t1) for n, t0, t1 in ctx.get("setup_spans", ()) if t1 is not None]
    pipeline = union([(t0, t1) for n, t0, t1 in spans if n in NAMES])
    if not pipeline:
        return None
    xla = union([(t0, t1) for n, t0, t1 in spans if n.startswith("xla.")])
    return sum(e - s for s, e in pipeline) - _overlap(pipeline, xla)
