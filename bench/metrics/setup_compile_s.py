"""setup_compile_s: seconds set-up spends compiling the program's own
executables, or loading them from the persistent cache: the union of the
``jit.trace``, ``jit.lower``, ``jit.compile``, ``jit.cache_load`` and
``xla.tier0_compile`` spans that nest in another of the program's spans
(a train step, a specialization), so the harness's own compiles (weights,
checks) are left out.

It reads one state of the persistent cache: every executable of the
program loaded from it (a warm cache; the benchmark runs with the cache
on), or no cache in use at all.  A set-up that compiled one of the
program's executables and wrote it to the cache (a ``jit.cache_write``
span) found the cache cold and reads nothing, as does a program without
the ``jit.*`` spans.  Source: the program's own spans (``obs.trace``,
whose armed tracer turns JAX's compile events into the ``jit.*`` spans)."""

from bench.trace_reduce import union

COMPILES = ("jit.trace", "jit.lower", "jit.compile", "jit.cache_load", "xla.tier0_compile")
WRITE = "jit.cache_write"


def read(ctx: dict):
    spans = [(n, t0, t1) for n, t0, t1 in ctx.get("setup_spans", ()) if t1 is not None]
    if not any(n.startswith("jit.") for n, _, _ in spans):
        return None
    owners = [(t0, t1) for n, t0, t1 in spans if n not in COMPILES + (WRITE, "host.gc")]
    mine = [
        (n, t0, t1)
        for n, t0, t1 in spans
        if n in COMPILES + (WRITE,) and any(a <= t0 and t1 <= b for a, b in owners)
    ]
    if any(n == WRITE for n, _, _ in mine):
        return None
    return sum(e - s for s, e in union([(t0, t1) for _, t0, t1 in mine]))
