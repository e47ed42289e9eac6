"""saved_carry_gb.train_hybrid: gigabytes (1e9 bytes) of the saved-carry
stacks of the loop adjoints that set-up's AD transforms built, from
static shapes: the sum of the ``saved_carry_bytes`` attribute of the
``ad.grad`` spans.  A loop nested in another loop's step counts once,
though its stack lives once per outer iteration of the backward pass.
Source: the program's own spans (``obs.trace``); a program whose
``ad.grad`` span carries no such attribute reads nothing."""


def read(ctx: dict):
    counts = [
        attrs["saved_carry_bytes"]
        for name, attrs in ctx.get("setup_span_attrs", ())
        if name == "ad.grad" and "saved_carry_bytes" in attrs
    ]
    if not counts:
        return None
    return sum(counts) / 1e9
