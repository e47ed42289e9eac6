"""Public API of the Myia-style toolchain (paper §4).

* ``@myia`` — compile a pure-Python-subset function through the pipeline:
  parse → (AD transform) → inline → infer (call-site specialization on the
  actual argument types/shapes, §4.2) → worklist-optimize (§4.3) → execute.
  First-order graphs are *lowered directly* to a straight-line callable
  (``repro.core.lowering``); the first call answers from a cheap tier-0
  XLA compile of it, and subsequent calls use the fully optimized
  ``jax.jit`` executable.  Graphs with residual recursion / higher-order
  calls fall back to the reference VM, traced once under ``jax.jit``.
  See ``docs/pipeline.md``.
* ``grad`` / ``value_and_grad`` / ``vjp`` — the ST AD transforms of §3.2.
  ``grad`` is also a *macro*: used inside ``@myia`` code it expands at parse
  time (paper Figure 1: "After the grad macro is expanded …").

Compile configuration
---------------------

All four entry points (and ``compile_pipeline``) take a single frozen
:class:`CompileOptions` carrying the full tier set::

    opts = CompileOptions(fuse=True, program_cache=cache,
                          checkpoint_policy="auto")
    f  = myia(fn, options=opts)
    df = grad(fn, options=opts)          # same tiers — full parity

``options=None`` means ``CompileOptions()``.  ``MyiaFunction.options``
holds the object the function was built with.

``grad``/``value_and_grad``/``vjp`` of a program containing loops or
recursion defer the AD transform to specialization time: the primal runs
the full pipeline (so parsed loops become ``while_loop``/``scan_loop``
primitives) *before* the adjoint is built, which is what lets grad-of-loop
programs compile VM-free instead of leaving residual ▶-closures.
"""

from __future__ import annotations

import dataclasses
import functools
import hashlib
from typing import Any, Callable

import jax
import numpy as np

from repro.obs import trace as obs_trace

from .ad import (
    _needs_loop_pipeline,
    build_grad_graph,
    build_value_and_grad_graph,
    build_vjp_graph,
)
from .infer import InferenceError, abstract_of_value, infer
from .ir import Constant, Graph, clone_graph
from .lowering import try_lower
from .opt import OptStats, count_nodes, optimize
from .parser import MyiaSyntaxError, parse_function
from .values import is_array_like
from .vm import VM

__all__ = [
    "myia",
    "grad",
    "value_and_grad",
    "vjp",
    "MyiaFunction",
    "CompileOptions",
    "compile_pipeline",
]


@dataclasses.dataclass(frozen=True)
class CompileOptions:
    """One immutable object carrying every compile tier configuration.

    Every entry point accepts ``options=CompileOptions(...)`` and threads
    it whole, so each tier (fusion, patterns, SPMD, AOT cache, tracing,
    loop-adjoint checkpointing) is reachable from *all* of
    ``myia``/``grad``/``value_and_grad``/``vjp``.

    ===================  ==========  =============================================
    field                default     tier it arms
    ===================  ==========  =============================================
    ``backend``          ``"jax"``   lowered/jit execution (``"vm"``: reference)
    ``opt``              ``True``    the worklist optimizer (§4.3)
    ``fuse``             ``False``   fusion clusters → generated Pallas kernels
    ``patterns``         ``False``   kernel-pattern rewrites (rmsnorm/attention)
    ``in_specs``         ``None``    SPMD partitioning (under a mesh context)
    ``program_cache``    ``None``    AOT executable tier (``ProgramCache``)
    ``graph_cache``      ``None``    optimized-graph tier (skips optimize warm)
    ``trace``            ``None``    observability (``Tracer`` spans)
    ``checkpoint_policy``  ``"auto"``  loop-adjoint memory/recompute point
    ===================  ==========  =============================================

    ``graph_cache`` and ``program_cache`` usually point at the *same*
    :class:`~repro.core.jax_backend.ProgramCache` object — the two tiers
    key and store independently (``<key>.graph.json`` vs ``<key>.pkl``),
    see ``docs/architecture.md`` ("Cache-tier anatomy").
    """

    #: execution backend: "jax" (lowered/jit tiers) or "vm" (reference)
    backend: str = "jax"
    #: run the optimizer (False: parse-and-execute, debugging only)
    opt: bool = True
    #: fusion tier — clustered regions run as generated Pallas kernels
    fuse: bool = False
    #: kernel-pattern rewrites (rmsnorm / attention → Pallas prims)
    patterns: bool = False
    #: SPMD tier — per-argument sharding specs (active under a mesh)
    in_specs: tuple | None = None
    #: AOT tier — a ProgramCache making compiled specializations durable
    program_cache: Any = None
    #: optimized-graph tier — a ProgramCache (usually the same object as
    #: ``program_cache``) consulted *before* the optimizer runs: a hit
    #: deserializes the stored post-optimize graph and skips the
    #: optimize + closure-elim pipeline phases entirely
    graph_cache: Any = None
    #: observability tier — a Tracer armed for every specialization
    trace: Any = None
    #: loop-adjoint carry recording: "auto" / "save_all" / "recompute"
    #: or an int slot count (see ``repro.core.ad._CHECKPOINT_SLOTS``)
    checkpoint_policy: str | int = "auto"


#: XLA options for the throwaway first-call executable (tiered compilation):
#: skip backend optimizations and expensive LLVM passes — on CPU this
#: roughly halves time-to-first-result for straight-line lowered graphs,
#: and the executable is discarded once the full-opt jit takes over.
_TIER0_COMPILER_OPTIONS = {
    "xla_backend_optimization_level": 0,
    "xla_llvm_disable_expensive_passes": True,
}


def _content_key(a: Any) -> tuple:
    """Hashable content-capturing key for an unhashable static argument.

    The whole value is baked into the specialized runner, so two statics
    may share a cache slot only if their *contents* agree — ``repr`` is not
    enough (numpy elides arrays > 1000 elements with ``...``)."""
    if isinstance(a, (list, tuple)):
        return (type(a).__name__, tuple(_content_key(e) for e in a))
    if isinstance(a, dict):
        return (
            "dict",
            tuple(
                (_content_key(k), _content_key(v))
                for k, v in sorted(a.items(), key=lambda kv: repr(kv[0]))
            ),
        )
    if is_array_like(a) or isinstance(a, np.generic):
        arr = np.asarray(a)
        return ("arrval", arr.shape, str(arr.dtype), hashlib.sha1(arr.tobytes()).hexdigest())
    try:
        hash(a)
        return ("val", type(a).__name__, a)
    except TypeError:
        return ("repr", type(a).__name__, repr(a))


def compile_pipeline(
    graph: Graph,
    example_args: tuple | None = None,
    *,
    infer_types: bool = True,
    engine: str = "worklist",
    stats: OptStats | None = None,
    loops: bool = True,
    options: CompileOptions | None = None,
    snapshot: Callable[[str, Graph], None] | None = None,
) -> Graph:
    """inline → infer → optimize → loop-lower, on a private clone of
    ``graph``.

    ``options`` (a :class:`CompileOptions`, default ``CompileOptions()``)
    supplies ``opt``, ``patterns`` and ``graph_cache`` — the same object
    the entry points thread — while the pipeline-internal knobs
    (``engine``, ``stats``, ``infer_types``, ``loops``) stay explicit
    kwargs.  ``engine`` / ``stats`` are forwarded to
    :func:`repro.core.opt.optimize` (all optimize calls share the one
    stats object).  ``options.patterns`` additionally enables the
    kernel-pattern rules of the fusion tier (rmsnorm / softmax-attention
    subgraphs rewritten to the hand-written Pallas primitives registered
    in ``repro.kernels.ops``) in the shape-directed pass.  ``loops=True``
    (the closure-elimination tier) rewrites residual tail-recursive
    families into ``while_loop`` / ``scan_loop`` primitive applies
    (``repro.core.closure``) so parsed loops lower instead of falling
    back to the VM; when ``stats`` is given, any remaining fallback
    reasons land in ``stats.fallback_reasons`` (structured, see
    ``FallbackReason``).

    ``snapshot`` (the explain layer's IR-dump hook) is called as
    ``snapshot(stage, graph)`` after each pipeline stage — ``cloned`` /
    ``optimized`` / ``shape_opt`` / ``loop_lowered`` / ``final``, or
    ``graph_cache_hit`` when the optimized-graph tier answers.  None (the
    default) costs nothing.
    """
    if options is None:
        options = CompileOptions()
    opt, patterns, gcache = options.opt, options.patterns, options.graph_cache
    # every phase below opens a span (see docs/observability.md for the
    # taxonomy); disarmed, span() is a single global None-check
    with obs_trace.span("compile_pipeline", graph=graph.name):
        gkey = None
        if gcache is not None and opt and infer_types and example_args is not None:
            # optimized-graph tier: key the PRE-optimization graph × abstract
            # signature × optimizer config; a hit deserializes the stored
            # post-optimize post-closure-elim graph and skips both expensive
            # phases, falling through to infer → lower → XLA below
            from .serialize import SerializeError

            hit = None
            with obs_trace.span("cache.graph_lookup", graph=graph.name) as sp:
                try:
                    gkey = gcache.graph_key(
                        graph, example_args,
                        opt=opt, patterns=patterns, loops=loops, engine=engine,
                    )
                except SerializeError:
                    sp.set(verdict="unkeyable")  # exotic constants: full pipeline
                else:
                    hit = gcache.load_graph(gkey)
                    sp.set(verdict="hit" if hit is not None else "miss")
            if hit is not None:
                try:
                    infer(hit, *example_args)  # re-derive abstracts (cheap)
                except InferenceError:
                    pass
                if stats is not None:
                    from .closure import analyze_blockers

                    with obs_trace.span("closure.analyze_blockers"):
                        stats.fallback_reasons = [
                            r.as_dict() for r in analyze_blockers(hit)
                        ]
                if snapshot is not None:
                    snapshot("graph_cache_hit", hit)
                    snapshot("final", hit)
                return hit
        with obs_trace.span("clone"):
            g = clone_graph(graph)
        if snapshot is not None:
            snapshot("cloned", g)
        if not opt:
            if snapshot is not None:
                snapshot("final", g)
            return g
        optimize(g, engine=engine, stats=stats)  # structural pass (no abstracts)
        if snapshot is not None:
            snapshot("optimized", g)
        if infer_types and example_args is not None:
            try:
                infer(g, *example_args)
            except InferenceError:
                pass  # dynamic program: shape-directed rules simply won't fire
            # shape-directed pass (kernel patterns need inferred shapes)
            optimize(g, engine=engine, stats=stats, patterns=patterns)
            if snapshot is not None:
                snapshot("shape_opt", g)
            if loops:
                from .closure import lower_loops

                report = lower_loops(g, stats=stats)
                if report.lowered:
                    # the rewrite leaves dead families and foldable glue; the
                    # cleanup pass also optimizes *inside* the loop subgraphs
                    optimize(g, engine=engine, stats=stats, patterns=patterns)
                if snapshot is not None:
                    snapshot("loop_lowered", g)
        if gkey is not None:
            with obs_trace.span("cache.graph_write", graph=graph.name):
                gcache.store_graph(gkey, g)
        if stats is not None:
            from .closure import analyze_blockers

            with obs_trace.span("closure.analyze_blockers"):
                stats.fallback_reasons = [r.as_dict() for r in analyze_blockers(g)]
        if snapshot is not None:
            snapshot("final", g)
        return g


def _apply_transform(
    g: Graph, t: tuple, example: tuple | None, policy: str | int
) -> Graph:
    """Apply one pending AD stage.  ``example`` lets the builders run the
    primal through the full pipeline first (loops lower before J), which
    is what makes grad-of-loop adjoints closed first-order graphs."""
    kind = t[0]
    if kind == "grad":
        return build_grad_graph(
            g, t[1], example_args=example, checkpoint_policy=policy
        )
    if kind == "vag":
        return build_value_and_grad_graph(
            g, t[1], example_args=example, checkpoint_policy=policy
        )
    if kind == "vjp":
        return build_vjp_graph(g, example_args=example, checkpoint_policy=policy)
    raise ValueError(f"unknown transform {t!r}")


class MyiaFunction:
    """A function compiled through the Myia pipeline, specialized and cached
    per call signature (the paper's call-site specialization)."""

    def __init__(
        self,
        fn: Callable | None = None,
        graph: Graph | None = None,
        *,
        options: CompileOptions | None = None,
        name: str | None = None,
        transforms: tuple = (),
    ) -> None:
        if fn is None and graph is None:
            raise ValueError("need fn or graph")
        self._fn = fn
        self._graph = graph
        #: the :class:`CompileOptions` — the single source of truth for
        #: every tier:
        #:
        #: * ``program_cache`` — AOT tier: a ProgramCache makes compiled
        #:   specializations durable (``jit(...).lower().compile()`` +
        #:   serialized executable), so a warm process skips XLA entirely.
        #: * ``fuse`` / ``patterns`` — fusion tier: clustered regions run
        #:   as generated Pallas kernels (docs/fusion.md).
        #: * ``in_specs`` — SPMD tier: per-argument sharding specs; active
        #:   only under a concrete mesh context, inert otherwise.
        #: * ``trace`` — observability tier: a Tracer armed for the
        #:   dynamic extent of every specialization.
        #: * ``checkpoint_policy`` — loop-adjoint carry recording (used
        #:   when pending AD ``transforms`` resolve at specialization).
        self.options = options if options is not None else CompileOptions()
        #: pending AD transforms, applied at specialization time *after*
        #: the primal has run the loop-lowering pipeline: a tuple of
        #: ``("grad", wrt)`` / ``("vag", wrt)`` / ``("vjp",)`` stages.
        #: Empty for plain ``@myia`` functions and for AD of straight-line
        #: programs (those build their adjoint graph eagerly).
        self.transforms = tuple(transforms)
        self._resolved: dict[tuple, Graph] = {}
        self._specializations: dict[tuple, Callable] = {}
        self.__name__ = name or (fn.__name__ if fn is not None else graph.name)
        if fn is not None:
            functools.update_wrapper(self, fn, updated=())

    # -- graph access ---------------------------------------------------
    @property
    def graph(self) -> Graph:
        if self._graph is None:
            self._graph = parse_function(self._fn)
        return self._graph

    def __myia_graph_factory__(self) -> Graph:
        return self.graph

    # -- pending AD transforms -------------------------------------------
    def _resolved_graph(self, example: tuple | None) -> Graph:
        """The graph to compile: the primal with any pending AD transforms
        applied.  ``example`` is the full abstract signature of *this*
        function; each trailing ``vjp`` stage consumes one argument (the
        output cotangent), so the primal's own signature is the prefix."""
        if not self.transforms:
            return self.graph
        n_vjp = sum(1 for t in self.transforms if t[0] == "vjp")
        base_ex = example[: len(example) - n_vjp] if example is not None else None
        key = ("resolved", base_ex)
        hit = self._resolved.get(key)
        if hit is not None:
            return hit
        g = self.graph
        ex = base_ex
        for t in self.transforms:
            g = _apply_transform(g, t, ex, self.options.checkpoint_policy)
            # downstream stages differentiate the adjoint graph itself;
            # its signature matches the primal's (grad) so ex carries over
        self._resolved[key] = g
        return g

    # -- compilation ------------------------------------------------------
    def _sigkey(self, args: tuple) -> tuple:
        out = []
        for a in args:
            if is_array_like(a) or isinstance(a, np.generic):
                out.append(("arr", np.shape(a), np.dtype(a.dtype) if hasattr(a, "dtype") else None))
            elif isinstance(a, tuple):
                out.append(("tup", self._sigkey(a)))
            else:
                try:
                    hash(a)
                except TypeError:
                    # unhashable static (list, dict, …): its *content* is
                    # baked into the specialization, so the key must capture
                    # content — repr() truncates large arrays and collides
                    out.append(("val", type(a).__name__, _content_key(a)))
                else:
                    out.append(("val", type(a).__name__, a))
        return tuple(out)

    def _active_mesh(self):
        """The concrete mesh the SPMD tier should target, or None.

        None when no ``in_specs`` were configured, no mesh context is
        active, or the context's mesh is abstract (spec-resolution tests).
        A trivial 1×1 mesh still takes the spmd path — that identity with
        the single-device tier is pinned by tests."""
        if self.options.in_specs is None or self.options.backend != "jax":
            return None
        from repro.parallel import current_mesh_context

        ctx = current_mesh_context()
        if ctx is None or not isinstance(ctx.mesh, jax.sharding.Mesh):
            return None
        return ctx.mesh

    def specialize(self, args: tuple) -> Callable:
        o = self.options
        if o.fuse:
            # fused runners bake the kernel mode in at trace time (the
            # FusedKernel dispatch runs under jit), so a mode switch must
            # select a different specialization, not reuse a stale trace
            from repro.kernels.ops import get_kernel_mode

            mode = get_kernel_mode()
        else:
            mode = None
        mesh = self._active_mesh()
        # key by shape AND device identity: a same-shape mesh over different
        # devices must not reuse a runner closed over the old mesh (same
        # identity rule the AOT cache key uses)
        from .jax_backend import mesh_descriptor

        meshkey = mesh_descriptor(mesh)
        key = (o.backend, o.fuse, o.patterns, mode, meshkey, self._sigkey(args))
        hit = self._specializations.get(key)
        if hit is not None:
            return hit
        with obs_trace.tracing(o.trace), obs_trace.span(
            "specialize", graph=self.__name__, fuse=o.fuse
        ):
            try:
                example = tuple(abstract_of_value(a) for a in args)
            except InferenceError:
                example = None  # e.g. a list static: skip inference, VM handles it
            base = self._resolved_graph(example) if self.transforms else self.graph
            g = compile_pipeline(base, example, options=o)
            runner = None
            if mesh is not None:
                runner = self._make_spmd_runner(g, args, mesh)
            if runner is None:
                runner = self._make_runner(g, args)
            self._specializations[key] = runner
            return runner

    def _make_spmd_runner(self, g: Graph, example_args: tuple, mesh) -> Callable | None:
        """Sharded runner, or None → automatic single-device fallback (graph
        not first-order / non-array arguments / propagation failure)."""
        from .jax_backend import compile_graph_spmd
        from .spmd import SpmdError

        if not all(is_array_like(a) for a in example_args):
            return None
        try:
            return compile_graph_spmd(g, mesh, self.options.in_specs, fuse=self.options.fuse)
        except SpmdError:
            return None

    def _make_runner(self, g: Graph, example_args: tuple) -> Callable:
        o = self.options
        if o.backend == "vm":
            def runner(*args):
                return VM().call(g, args)

            runner.lowered = False
            return runner
        # jax backend: arrays are dynamic (traced), everything else static.
        dyn_idx = [i for i, a in enumerate(example_args) if is_array_like(a)]
        static = {i: a for i, a in enumerate(example_args) if i not in set(dyn_idx)}
        lowered = try_lower(g, fuse=o.fuse)

        if (
            o.program_cache is not None
            and lowered is not None
            and len(dyn_idx) == len(example_args)
            and not any(isinstance(a, jax.core.Tracer) for a in example_args)
        ):
            # (tracer args mean we're specializing under an outer jit trace
            # — an AOT executable cannot be invoked there; use the jit tier)
            # AOT tier: durable compiled artifact, answered from the
            # persistent cache when this program was compiled before (by
            # this process or any earlier one)
            from .jax_backend import CompileFailed
            from .serialize import SerializeError

            try:
                aot = o.program_cache.load_or_compile(
                    g, example_args, fuse=o.fuse, lowered_fn=lowered
                )
            except SerializeError:
                pass  # not durable (exotic constants): ordinary tiers
            except CompileFailed:
                # bottom rung of the degraded-mode ladder: XLA would not
                # compile this specialization even after bounded retries
                # (docs/serving.md).  The reference VM evaluates the same
                # optimized graph eagerly — no XLA on the critical path,
                # slow but correct — and the downgrade is counted so a
                # serving fleet can alarm on it.
                o.program_cache.stats.vm_fallbacks += 1

                def runner(*args):
                    return VM().call(g, args)

                runner.lowered = False
                runner.degraded = "vm_oracle"
                return runner
            else:
                # the specialization key cannot tell a concrete array from
                # a same-shaped tracer, so this runner may later be handed
                # tracer args (the MyiaFunction called under an outer
                # jit/grad) — an AOT executable rejects those; route them
                # to a lazily-built ordinary jit of the same lowered fn
                state: dict[str, Any] = {}

                def runner(*args):
                    if any(isinstance(a, jax.core.Tracer) for a in args):
                        jitted = state.get("jit")
                        if jitted is None:
                            jitted = state["jit"] = jax.jit(lowered)
                        return jitted(*args)
                    return aot(*args)

                runner.lowered = True
                runner.aot = True
                runner.cache_key = aot.cache_key
                return runner

        def assemble(arrs) -> tuple:
            full: list[Any] = [None] * (len(arrs) + len(static))
            for i, v in static.items():
                full[i] = v
            for i, v in zip(dyn_idx, arrs):
                full[i] = v
            return tuple(full)

        if lowered is not None:
            def run(*arrs):
                return lowered(*assemble(arrs))
        else:
            # residual graph values (recursion, higher-order calls): the VM
            # evaluates, and jit traces *through* the interpreter.
            def run(*arrs):
                return VM().call(g, assemble(arrs))

        jitted = jax.jit(run)

        if lowered is not None:
            # Tiered compilation (only possible because the program is a
            # straight-line lowered function, not an interpreter trace):
            # the first concrete call compiles at a low XLA optimization
            # level — a fraction of the full-opt compile time on CPU — and
            # answers from that; the second call onwards uses the fully
            # optimized ``jax.jit`` executable.  Both the CPU and the TPU
            # compilers accept the tier-0 options, so a failure here is a
            # real compile error and propagates.
            state = {"calls": 0}

            def runner(*args):
                arrs = [args[i] for i in dyn_idx]
                state["calls"] += 1
                if state["calls"] == 1 and not any(
                    isinstance(a, jax.core.Tracer) for a in arrs
                ):
                    with obs_trace.span("xla.tier0_compile"):
                        fast = jitted.lower(*arrs).compile(
                            compiler_options=_TIER0_COMPILER_OPTIONS
                        )
                    return fast(*arrs)
                return jitted(*arrs)
        else:
            def runner(*args):
                return jitted(*[args[i] for i in dyn_idx])

        runner.lowered = lowered is not None
        runner.jitted = jitted
        return runner

    def __call__(self, *args: Any) -> Any:
        return self.specialize(args)(*args)

    # -- introspection (benchmarks / tests) --------------------------------
    def explain(self, *example_args: Any, dump_ir: str | None = None):
        """A structured compile report for this function at
        ``example_args``'s signature: per-cluster fusion verdicts, per-node
        decisions with reasons, sharding specs, cache-tier verdicts,
        checkpoint policies and residual VM-fallback reasons — see
        :class:`repro.obs.explain.ExplainReport`.  ``dump_ir="dir/"``
        additionally writes diffable per-stage IR text dumps."""
        from repro.obs.explain import explain_function

        return explain_function(self, example_args, dump_ir=dump_ir)

    def optimized_graph(self, *args: Any) -> Graph:
        example = tuple(abstract_of_value(a) for a in args)
        base = self._resolved_graph(example) if self.transforms else self.graph
        return compile_pipeline(base, example, options=self.options)

    def node_count(self, *args: Any, optimized: bool = True) -> int:
        g = self.optimized_graph(*args) if optimized else self.graph
        return count_nodes(g)


def myia(
    fn: Callable | None = None,
    *,
    options: CompileOptions | None = None,
):
    """Decorator: compile ``fn`` (pure Python subset) through the pipeline.

    Tier configuration arrives as one ``options=CompileOptions(...)``
    (``None``: ``CompileOptions()``):

    * ``fuse=True`` turns on the fusion tier (clustered regions run as
      generated Pallas kernels); ``patterns=True`` additionally rewrites
      kernel-shaped subgraphs (rmsnorm, softmax-attention core) to the
      hand-written Pallas primitives.  Both default off: the unfused
      straight-line lowering remains the bit-exact reference.
    * ``in_specs`` (one sharding spec per argument) arms the SPMD tier:
      under an active concrete mesh context the optimized+fused graph is
      partitioned per-shard and executed under ``shard_map``; with no
      mesh active the single-device tiers run unchanged.
    * ``program_cache`` (a :class:`repro.core.jax_backend.ProgramCache`)
      arms the AOT tier: all-array specializations of lowerable graphs
      are compiled ahead of time and persisted, so a warm process reloads
      the XLA executable instead of recompiling (see docs/serving.md).
    * ``trace`` (a :class:`repro.obs.Tracer`) arms the observability
      tier: every specialization compiles with the tracer armed, so
      pipeline phases, inline waves and XLA compiles land in its buffer.
    """

    def wrap(f: Callable) -> MyiaFunction:
        return MyiaFunction(f, options=options)

    return wrap(fn) if fn is not None else wrap


# ---------------------------------------------------------------------------
# AD entry points (callable API + in-language macros)
# ---------------------------------------------------------------------------


def _as_graph(fn: Any) -> Graph:
    if isinstance(fn, Graph):
        return fn
    if isinstance(fn, MyiaFunction):
        return fn.graph
    return parse_function(fn)


def _macro_expand_grad(parser, block, ast_args):
    if len(ast_args) < 1:
        raise MyiaSyntaxError("grad() takes a function argument")
    fn_node = parser.expr(block, ast_args[0])
    if not (isinstance(fn_node, Constant) and isinstance(fn_node.value, Graph)):
        raise MyiaSyntaxError("grad() macro requires a statically-known function")
    wrt: int | tuple = 0
    if len(ast_args) > 1:
        import ast as _ast

        a1 = ast_args[1]
        if isinstance(a1, _ast.Constant):
            wrt = a1.value
        elif isinstance(a1, _ast.Tuple):
            wrt = tuple(e.value for e in a1.elts)
        else:
            raise MyiaSyntaxError("grad() wrt must be a literal")
    return Constant(build_grad_graph(fn_node.value, wrt))


def _macro_expand_vag(parser, block, ast_args):
    fn_node = parser.expr(block, ast_args[0])
    if not (isinstance(fn_node, Constant) and isinstance(fn_node.value, Graph)):
        raise MyiaSyntaxError("value_and_grad() macro requires a statically-known function")
    return Constant(build_value_and_grad_graph(fn_node.value))


def _transform_entry(
    fn: Any, transform: tuple, options: CompileOptions | None
) -> MyiaFunction:
    """Shared construction path of the AD entry points.

    Straight-line primals build their adjoint graph eagerly (back-compat:
    ``grad(f).graph`` is the adjoint, and the grad *macro* path stays
    parse-time).  Primals containing loops or recursion defer the
    transform to specialization (``MyiaFunction.transforms``), so the
    primal runs the loop-lowering pipeline — with the concrete signature
    — before J sees it; that ordering is what keeps grad-of-loop programs
    off the VM.  Chaining (``grad(grad(f))``) extends the pending tuple."""
    opts = options if options is not None else CompileOptions()
    if isinstance(fn, MyiaFunction) and fn.transforms:
        return MyiaFunction(
            fn=fn._fn, graph=fn._graph, options=opts,
            transforms=fn.transforms + (transform,),
            name=f"{transform[0]}_{fn.__name__}",
        )
    primal = _as_graph(fn)
    if _needs_loop_pipeline(primal):
        return MyiaFunction(
            graph=primal, options=opts, transforms=(transform,),
            name=f"{transform[0]}_{primal.name}",
        )
    g = _apply_transform(primal, transform, None, opts.checkpoint_policy)
    return MyiaFunction(graph=g, options=opts, name=g.name)


def grad(
    fn: Any,
    wrt: int | tuple[int, ...] = 0,
    *,
    options: CompileOptions | None = None,
):
    """Reverse-mode gradient of a scalar-output function (paper §3.2).

    The adjoint takes the same arguments as ``fn``, so every tier in
    ``options`` (SPMD ``in_specs``, the AOT ``program_cache``, ``trace``)
    carries over unchanged — full parity with ``myia``."""
    return _transform_entry(fn, ("grad", wrt), options)


def value_and_grad(
    fn: Any,
    wrt: int | tuple[int, ...] = 0,
    *,
    options: CompileOptions | None = None,
):
    return _transform_entry(fn, ("vag", wrt), options)


def vjp(fn: Any, *, options: CompileOptions | None = None):
    return _transform_entry(fn, ("vjp",), options)


grad.__is_myia_macro__ = True
grad.__myia_macro_expand__ = _macro_expand_grad
value_and_grad.__is_myia_macro__ = True
value_and_grad.__myia_macro_expand__ = _macro_expand_vag
