"""broadcast_materialized.train_hybrid: operands that set-up's generated
kernels still take expanded to their body shape in HBM, rather than at
their own size broadcast per block in VMEM: the sum of the
``broadcast_materialized`` attribute of the ``lower`` spans, one span per
lowered graph, loop bodies included.  Source: the program's own spans
(``obs.trace``); a program whose ``lower`` span carries no such attribute
reads nothing."""


def read(ctx: dict):
    counts = [
        attrs["broadcast_materialized"]
        for name, attrs in ctx.get("setup_span_attrs", ())
        if name == "lower" and "broadcast_materialized" in attrs
    ]
    if not counts:
        return None
    return sum(counts)
