"""JAX backend: execute IR graphs under ``jax.jit``.

The paper compiled "the straight-line parts of the graph using TVM"; the
TPU-idiomatic equivalent is to hand XLA the whole optimized graph as one
straight-line program.  Two routes produce that program:

* **Direct lowering** (the fast path): ``repro.core.lowering`` emits the
  optimized first-order graph as generated Python source — one assignment
  per apply node in topological order over the primitives' ``jnp``
  implementations.  ``jax.jit`` traces that straight-line function with
  *zero* interpreter machinery in the way, and the same callable can also
  run eagerly (no XLA compile on the critical path of the first call).
* **VM trace** (the fallback): when residual graph values survive
  optimization *and* closure elimination — non-tail recursion, nested
  loops, closures selected by ``switch`` on traced values — the reference
  VM evaluates the graph and ``jax.jit`` traces *through* the
  interpreter.  Interpreter overhead is paid once at trace time (contrast
  with the OO baseline, which pays it per call).

``compile_graph`` picks automatically: lowering when
``lowering_blockers(graph)`` is empty, VM otherwise.

Data-dependent control flow: the closure-elimination tier
(``repro.core.closure``) rewrites tail-recursive families — parsed
``while``/``for`` loops, defunctionalized higher-order recursion — into
``while_loop``/``scan_loop`` primitive applies, which the lowering emits
as ``jax.lax.while_loop``/``jax.lax.scan`` with recursively-lowered
cond/step/exit callables: traced-value loop bounds compile instead of
punting to the VM.  See the fallback matrix in ``docs/pipeline.md`` for
the shapes that genuinely still need the interpreter.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import tempfile
from typing import Any, Callable, Sequence

import jax

from repro.obs import trace as obs_trace

from .ir import Graph
from .lowering import lower_graph, lowering_blockers, try_lower
from .serialize import (
    FORMAT_VERSION,
    SerializeError,
    deserialize_graph,
    dumps,
    loads,
    serialize_graph,
    structural_hash,
)
from .spmd import SpmdError, shard_graph
from .vm import VM

__all__ = [
    "CacheStats",
    "CompileFailed",
    "ProgramCache",
    "abstract_value_signature",
    "compile_graph",
    "compile_graph_spmd",
    "trace_graph",
    "lower_graph",
    "lowering_blockers",
]


class CompileFailed(Exception):
    """XLA compilation failed after the cache's bounded retries.

    The next rung of the degraded-mode ladder (``api.MyiaFunction``)
    catches this and falls back to the VM oracle — slow, but alive and
    correct (see docs/serving.md, "Failure modes & degraded operation").
    """


def _fault_hooks():
    """The serving fault-injection hooks, or None outside chaos runs.

    Imported lazily: ``repro.serve`` depends on ``repro.core``, so a
    module-level import here would be circular — and the cache/compile
    paths are cold enough that the cached-module lookup is free."""
    try:
        from repro.serve import faults
    except ImportError:  # pragma: no cover - serve tier absent
        return None
    return faults


def trace_graph(graph: Graph) -> Callable:
    """A plain callable evaluating the graph via the VM (traceable by jax)."""

    def run(*args: Any) -> Any:
        return VM().call(graph, tuple(args))

    run.__name__ = f"myia_{graph.name}"
    return run


def compile_graph(
    graph: Graph,
    *,
    jit: bool = True,
    donate_argnums=(),
    lower: bool = True,
    fuse: bool = False,
) -> Callable:
    """Compile ``graph`` to a callable.

    Straight-line first-order graphs are lowered directly (no VM in the
    trace); anything with residual graph values falls back to tracing the
    VM.  ``fuse=True`` selects the fusion tier: clustered regions execute
    as generated Pallas kernels (``repro.core.fusion`` +
    ``repro.kernels.codegen``), mode-selected by ``set_kernel_mode``.  The
    returned callable carries ``.lowered`` (bool) and ``.fn`` (the
    un-jitted callable) for introspection.
    """
    fn = try_lower(graph, fuse=fuse) if lower else None
    lowered = fn is not None
    if fn is None:
        fn = trace_graph(graph)

    if jit and fuse and lowered:
        # FusedKernel dispatch reads set_kernel_mode at TRACE time, so one
        # jit executable pins one mode — keep one jit per mode observed,
        # and the documented flip-and-rerun flow retraces instead of
        # silently replaying the old mode's executable.
        by_mode: dict[str, Callable] = {}

        def runner(*args: Any) -> Any:
            from repro.kernels.ops import get_kernel_mode

            mode = get_kernel_mode()
            jitted = by_mode.get(mode)
            if jitted is None:
                jitted = by_mode[mode] = jax.jit(fn, donate_argnums=donate_argnums)
            return jitted(*args)

        out = None
    else:
        out = jax.jit(fn, donate_argnums=donate_argnums) if jit else fn

        def runner(*args: Any) -> Any:
            return out(*args)

    runner.__name__ = f"myia_{graph.name}"
    runner.lowered = lowered
    runner.fn = fn
    runner.jitted = out if jit else None
    return runner


def compile_graph_spmd(
    graph: Graph,
    mesh,
    in_specs: Sequence[Any],
    *,
    jit: bool = True,
    fuse: bool = False,
) -> Callable:
    """Compile ``graph`` to a sharded callable over ``mesh`` (SPMD tier).

    The sharding propagation pass (``repro.core.spmd``) turns the
    optimized global graph into a per-shard program — collectives at the
    resharding points, shape constants localized — which lowers through
    the ordinary straight-line path (optionally fused into generated
    Pallas kernels; clusters never span a collective) and executes under
    ``jax.shard_map``.  Inputs arrive as *global* arrays; shard_map
    splits them per ``in_specs`` and reassembles global outputs.

    Raises :class:`SpmdError` when the graph cannot be sharded (residual
    recursion / higher-order calls, non-array parameters) — callers fall
    back to the single-device tier.
    """
    mesh_axes = dict(mesh.shape)
    sharded = shard_graph(graph, in_specs, mesh_axes)
    fn = try_lower(sharded.graph, fuse=fuse)
    if fn is None:  # pragma: no cover - shard_graph already validated
        raise SpmdError(f"per-shard program of {graph.name} failed to lower")

    def wrap() -> Callable:
        return jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=sharded.in_partition,
            out_specs=sharded.out_partition,
            check_vma=False,
        )

    if jit and fuse:
        # FusedKernel dispatch reads set_kernel_mode at TRACE time (see
        # compile_graph): keep one jit executable per observed mode
        by_mode: dict[str, Callable] = {}

        def runner(*args: Any) -> Any:
            from repro.kernels.ops import get_kernel_mode

            mode = get_kernel_mode()
            jitted = by_mode.get(mode)
            if jitted is None:
                jitted = by_mode[mode] = jax.jit(wrap())
            return jitted(*args)

        out = None
    else:
        out = jax.jit(wrap()) if jit else wrap()

        def runner(*args: Any) -> Any:
            return out(*args)

    runner.__name__ = f"myia_spmd_{graph.name}"
    runner.lowered = True
    runner.spmd = True
    runner.fn = fn
    runner.jitted = out if jit else None
    runner.sharded = sharded
    runner.plan = sharded.plan
    return runner


# ---------------------------------------------------------------------------
# Persistent AOT program cache
# ---------------------------------------------------------------------------


class CacheStats:
    """Counters from one :class:`ProgramCache` (surfaced like ``OptStats``).

    * ``hits`` / ``misses`` — cache-key lookups that found / did not find a
      durable entry,
    * ``exec_loads`` — hits answered by deserializing the stored XLA
      executable (zero recompilation: neither the pipeline's lowering nor
      XLA ran),
    * ``xla_compiles`` — actual ``.lower().compile()`` invocations this
      process performed (a warm restart of the same workload must keep
      this at 0 — pinned by the serve subprocess test),
    * ``puts`` / ``spills`` — entries written / evicted (LRU by mtime when
      over ``max_entries``),
    * ``errors`` — every degradation event, in aggregate (never fatal:
      the cache degrades to recompiling), classified further as:

      - ``corrupt_entries`` — entries whose payload would not decode
        (truncated/garbage pickle, undeserializable graph).  Each is
        **quarantined** (renamed to ``*.quarantined``, counted in
        ``quarantined``) so it is never re-read and never fatal,
      - ``io_errors`` — OS-level read/write failures (permissions, disk
        full, vanished files): the *file system* misbehaving, as opposed
        to the *bytes* being wrong,
      - the remainder of ``errors`` is benign degradation: foreign/stale
        executable blobs rebuilt from the graph payload, non-durable
        graphs served from memory only.

    * ``compile_retries`` / ``vm_fallbacks`` — the degraded-mode ladder:
      failed XLA compiles retried (bounded by ``max_compile_retries``),
      and specializations that exhausted retries and were handed to the
      VM oracle by ``api.MyiaFunction`` (see docs/serving.md).
    * ``graph_hits`` / ``graph_misses`` / ``graph_puts`` — the
      optimized-graph tier (``graph_key``/``load_graph``/``store_graph``):
      lookups of the *pre-optimization* key that found / did not find a
      stored post-optimize graph, and entries written.  A graph hit skips
      the optimize + closure-elim phases of ``compile_pipeline`` entirely.
    """

    __slots__ = (
        "hits", "misses", "exec_loads", "xla_compiles", "puts", "spills",
        "errors", "corrupt_entries", "io_errors", "quarantined",
        "compile_retries", "vm_fallbacks",
        "graph_hits", "graph_misses", "graph_puts",
    )

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        self.exec_loads = 0
        self.xla_compiles = 0
        self.puts = 0
        self.spills = 0
        self.errors = 0
        self.corrupt_entries = 0
        self.io_errors = 0
        self.quarantined = 0
        self.compile_retries = 0
        self.vm_fallbacks = 0
        self.graph_hits = 0
        self.graph_misses = 0
        self.graph_puts = 0

    @property
    def hit_rate(self) -> float:
        total = self.hits + self.misses
        return self.hits / total if total else 0.0

    @property
    def graph_hit_rate(self) -> float:
        total = self.graph_hits + self.graph_misses
        return self.graph_hits / total if total else 0.0

    def as_dict(self) -> dict:
        return {
            "hits": self.hits,
            "misses": self.misses,
            "exec_loads": self.exec_loads,
            "xla_compiles": self.xla_compiles,
            "puts": self.puts,
            "spills": self.spills,
            "errors": self.errors,
            "corrupt_entries": self.corrupt_entries,
            "io_errors": self.io_errors,
            "quarantined": self.quarantined,
            "compile_retries": self.compile_retries,
            "vm_fallbacks": self.vm_fallbacks,
            "graph_hits": self.graph_hits,
            "graph_misses": self.graph_misses,
            "graph_puts": self.graph_puts,
            "hit_rate": round(self.hit_rate, 4),
            "graph_hit_rate": round(self.graph_hit_rate, 4),
        }

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"CacheStats({self.as_dict()!r})"


def mesh_descriptor(mesh: Any) -> tuple | None:
    """Canonical identity of a concrete mesh: axis sizes + device ids.
    The single definition shared by the specialization key
    (``api.MyiaFunction``) and the AOT cache key — a same-shape mesh over
    different devices must never collide."""
    if mesh is None:
        return None
    return (
        tuple(sorted(mesh.shape.items())),
        tuple(int(d.id) for d in mesh.devices.flat),
    )


def abstract_signature(example_args: Sequence[Any]) -> str:
    """Canonical string for the argument avals — the signature component of
    the cache key.  Only array arguments are supported (the AOT cache holds
    straight-line array programs; statics are baked into the graph)."""
    parts = []
    for a in example_args:
        if isinstance(a, jax.ShapeDtypeStruct):
            dt, shp = a.dtype, a.shape
        elif hasattr(a, "dtype") and hasattr(a, "shape"):
            dt, shp = a.dtype, a.shape
        else:
            raise SerializeError(f"non-array argument {type(a).__name__} in AOT signature")
        parts.append(f"{jax.numpy.dtype(dt).str}{list(shp)}")
    return ";".join(parts)


def abstract_value_signature(abstracts: Sequence[Any]) -> str:
    """Canonical string for a tuple of *inference* abstract values
    (``repro.core.infer.AScalar``/``AArray``/``ATuple``) — the signature
    component of the optimized-graph cache key.

    Known scalar *values* are part of the signature: constant propagation
    bakes them into the optimized graph, so two calls differing only in a
    static scalar must occupy different buckets.  Anything that cannot be
    canonically rendered (functions, environments, opaque statics) raises
    :class:`SerializeError` — the caller skips the graph tier."""
    from .infer import ANY, AArray, AScalar, ATuple

    def part(a: Any) -> str:
        if isinstance(a, AArray):
            return f"{a.dtype.str}{list(a.shape)}"
        if isinstance(a, ATuple):
            return "(" + ",".join(part(e) for e in a.elements) + ")"
        if isinstance(a, AScalar):
            if a.value is ANY:
                return f"{a.kind}:?"
            if a.value is None or isinstance(a.value, (bool, int, float, str)):
                return f"{a.kind}:{a.value!r}"
            raise SerializeError(
                f"opaque static value {type(a.value).__name__} in graph-cache signature"
            )
        raise SerializeError(
            f"non-durable abstract {type(a).__name__} in graph-cache signature"
        )

    return ";".join(part(a) for a in abstracts)


def _device_identity() -> tuple[str, str]:
    """``(platform, device_kind)`` of the default device: an executable
    compiled for one TPU generation must never be loaded on another."""
    d = jax.devices()[0]
    return d.platform, d.device_kind


def _avals(example_args: Sequence[Any]) -> tuple:
    return tuple(
        a if isinstance(a, jax.ShapeDtypeStruct) else jax.ShapeDtypeStruct(a.shape, a.dtype)
        for a in example_args
    )


class ProgramCache:
    """Persistent two-tier cache of compiled programs, keyed on *what the
    program is* rather than which process built it.

    **Executable tier** (``key``/``load_or_compile``, ``<key>.pkl``)::

        structural graph hash × abstract signature × fuse/kernel-mode ×
        mesh descriptor × (jax version, serialize format, backend platform,
        device kind)

    Each entry stores the serialized optimized graph (``repro.core.
    serialize``) and, best-effort, the serialized XLA executable
    (``jax.experimental.serialize_executable``).  A warm process finds the
    entry, reloads the executable, and serves with **zero recompilations**;
    if the executable blob is incompatible (different machine/jaxlib) the
    stored graph is re-lowered and recompiled — never wrong, at worst slow.

    **Optimized-graph tier** (``graph_key``/``load_graph``/``store_graph``,
    ``<key>.graph.json``)::

        loose structural hash of the PRE-optimization graph ×
        abstract-value signature × opt/patterns/loops/engine config ×
        serialize format version

    The value is the canonical JSON of the post-optimize post-closure-elim
    graph, so a new specialization of a known family deserializes it and
    skips the optimize + closure-elim pipeline phases entirely (falling
    through to infer → lower → XLA, where the executable tier takes over).
    Reads are lock-free: writers publish complete entries atomically
    (``mkstemp`` + ``os.replace``), so concurrent distinct-key builds never
    block each other and same-key racers each land a valid entry with one
    survivor.  Counters are surfaced on ``.stats`` like ``OptStats``.
    """

    def __init__(
        self, path: str, *, max_entries: int = 256, max_compile_retries: int = 1
    ) -> None:
        self.path = os.path.abspath(path)
        self.max_entries = max_entries
        #: bounded retry for failed XLA compiles (rung 2 of the ladder);
        #: past it, :class:`CompileFailed` hands the caller to the VM rung
        self.max_compile_retries = max_compile_retries
        self.stats = CacheStats()
        os.makedirs(self.path, exist_ok=True)

    # -- keys --------------------------------------------------------------
    def key(
        self,
        graph: Graph,
        example_args: Sequence[Any],
        *,
        fuse: bool = False,
        kernel_mode: str | None = None,
        mesh: Any = None,
    ) -> str:
        if kernel_mode is None:
            from repro.kernels.ops import get_kernel_mode

            kernel_mode = get_kernel_mode()
        meshdesc = mesh_descriptor(mesh)
        payload = {
            "graph": structural_hash(graph),
            "sig": abstract_signature(example_args),
            "fuse": bool(fuse),
            "kernel_mode": kernel_mode,
            "mesh": meshdesc,
            "jax": jax.__version__,
            "format": FORMAT_VERSION,
            "device": _device_identity(),
        }
        return hashlib.sha256(
            json.dumps(payload, sort_keys=True, separators=(",", ":")).encode()
        ).hexdigest()

    def _file(self, key: str) -> str:
        return os.path.join(self.path, key + ".pkl")

    def probe(self, key: str) -> bool:
        """True when a durable executable entry exists for ``key``.

        Read-only: no stats mutation, no entry load — the explain layer's
        cache-tier verdict must not perturb the hit counters it reports."""
        return os.path.exists(self._file(key))

    def probe_graph(self, key: str) -> bool:
        """``probe`` for the optimized-graph tier (same read-only contract)."""
        return os.path.exists(self._graph_file(key))

    # -- optimized-graph tier ----------------------------------------------
    def graph_key(
        self,
        graph: Graph,
        abstracts: Sequence[Any],
        *,
        opt: bool = True,
        patterns: bool = False,
        loops: bool = True,
        engine: str = "worklist",
    ) -> str:
        """Cache key of the *pre-optimization* ``graph`` at an abstract
        signature, under one optimizer configuration.

        Raises :class:`SerializeError` when the graph or signature cannot
        be canonically keyed (runtime-only constants beyond symbolic keys
        and empty envs, opaque statics) — callers skip the tier.
        """
        payload = {
            "graph": structural_hash(graph, loose=True),
            "sig": abstract_value_signature(abstracts),
            "opt": bool(opt),
            "patterns": bool(patterns),
            "loops": bool(loops),
            "engine": engine,
            "format": FORMAT_VERSION,
        }
        return hashlib.sha256(
            json.dumps(payload, sort_keys=True, separators=(",", ":")).encode()
        ).hexdigest()

    def _graph_file(self, key: str) -> str:
        return os.path.join(self.path, key + ".graph.json")

    def load_graph(self, key: str) -> Graph | None:
        """The stored post-optimize graph for ``key``, or None.

        The read path takes no lock: writers only ever publish complete
        entries via ``os.replace``, so a reader sees either no file or a
        whole one — concurrent builders of distinct keys never serialize
        behind each other, and a corrupt entry (torn by an unclean shutdown)
        is quarantined, not fatal."""
        fpath = self._graph_file(key)
        try:
            with open(fpath, "r", encoding="utf-8") as f:
                text = f.read()
        except FileNotFoundError:
            self.stats.graph_misses += 1
            return None
        except OSError:
            self.stats.graph_misses += 1
            self.stats.io_errors += 1
            self.stats.errors += 1
            return None
        try:
            g = loads(text)
        except Exception:
            self._quarantine(fpath)
            self.stats.graph_misses += 1
            return None
        self.stats.graph_hits += 1
        try:
            os.utime(fpath)  # LRU touch
        except OSError:
            pass
        return g

    def store_graph(self, key: str, graph: Graph) -> bool:
        """Persist a post-optimize ``graph`` under ``key`` (atomic publish).

        Best-effort: a non-durable graph (residual runtime values) or a
        failing write degrades to not caching — never to an error."""
        try:
            text = dumps(graph)
        except SerializeError:
            self.stats.errors += 1
            return False
        tmp = None
        try:
            fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".tmp")
            with os.fdopen(fd, "w", encoding="utf-8") as f:
                f.write(text)
            os.replace(tmp, self._graph_file(key))
            self.stats.graph_puts += 1
        except OSError:
            self.stats.errors += 1
            self.stats.io_errors += 1
            if tmp is not None:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
            return False
        self._evict(".graph.json")
        return True

    # -- main entry point --------------------------------------------------
    def load_or_compile(
        self,
        graph: Graph,
        example_args: Sequence[Any],
        *,
        fuse: bool = False,
        lowered_fn: Callable | None = None,
        mesh: Any = None,
    ) -> Callable:
        """An AOT-compiled callable for ``graph`` at ``example_args``'s
        avals, answered from disk when possible.

        Raises :class:`SerializeError` when the graph/arguments cannot be
        made durable (VM-fallback graphs, non-array args) — callers fall
        back to the ordinary jit tiers.
        """
        key = self.key(graph, example_args, fuse=fuse, mesh=mesh)
        avals = _avals(example_args)
        with obs_trace.span("cache.lookup", graph=graph.name) as sp:
            entry = self._read(key)
            if entry is not None:
                runner = self._from_entry(entry, avals, fuse=fuse, fpath=self._file(key))
                if runner is not None:
                    self.stats.hits += 1
                    sp.set(verdict="hit")
                    runner.cache_key = key
                    return runner
            self.stats.misses += 1
            sp.set(verdict="miss")
        # miss: compile fresh from the live graph and persist
        fn = lowered_fn if lowered_fn is not None else try_lower(graph, fuse=fuse)
        if fn is None:
            raise SerializeError(f"graph {graph.name} does not lower (VM fallback)")
        compiled = self._compile(fn, avals, tag=f"fresh:{graph.name}")
        with obs_trace.span("cache.write", graph=graph.name):
            self._write(key, graph, compiled)
        runner = _aot_runner(compiled)
        runner.cache_key = key
        return runner

    # -- internals ---------------------------------------------------------
    def _compile(self, fn: Callable, avals: tuple, *, tag: str) -> Any:
        """One XLA compile, with bounded retry (rung 2 of the ladder).

        Transient compile failures (injected by the chaos harness; OOM /
        backend flakes in the wild) are retried up to
        ``max_compile_retries`` times; a persistent failure raises
        :class:`CompileFailed` so the caller can take the VM rung."""
        fh = _fault_hooks()
        last: Exception | None = None
        for attempt in range(self.max_compile_retries + 1):
            if attempt:
                self.stats.compile_retries += 1
            try:
                if fh is not None:
                    fh.on_compile(tag)
                with obs_trace.span("xla.compile", tag=tag, attempt=attempt):
                    compiled = jax.jit(fn).lower(*avals).compile()
            except Exception as e:
                last = e
                continue
            self.stats.xla_compiles += 1
            return compiled
        raise CompileFailed(
            f"XLA compile of {tag} failed after "
            f"{self.max_compile_retries + 1} attempts"
        ) from last

    def _quarantine(self, fpath: str) -> None:
        """Rename a corrupt entry aside: ``<key>.pkl.quarantined`` no
        longer matches the ``.pkl`` suffix, so it is never re-read (and
        never re-written over — the key's next ``_write`` creates a
        fresh ``.pkl``).  Quarantine failure degrades to deletion; both
        paths leave the cache consistent and the process alive."""
        self.stats.corrupt_entries += 1
        self.stats.errors += 1
        try:
            os.replace(fpath, fpath + ".quarantined")
            self.stats.quarantined += 1
        except OSError:
            try:
                os.unlink(fpath)
                self.stats.quarantined += 1
            except OSError:
                self.stats.io_errors += 1

    def _read(self, key: str) -> dict | None:
        fpath = self._file(key)
        if not os.path.exists(fpath):
            return None
        fh = _fault_hooks()
        if fh is not None:
            fh.on_cache_read(fpath)
        try:
            with open(fpath, "rb") as f:
                entry = pickle.load(f)
        except OSError:
            self.stats.io_errors += 1
            self.stats.errors += 1
            return None
        except Exception:
            # truncated / garbage bytes: the entry itself is poison
            self._quarantine(fpath)
            return None
        if not isinstance(entry, dict) or "graph" not in entry:
            self._quarantine(fpath)  # decoded, but not a cache entry
            return None
        try:
            os.utime(fpath)  # LRU touch
        except OSError:
            self.stats.io_errors += 1
        return entry

    def _from_entry(
        self, entry: dict, avals: tuple, *, fuse: bool, fpath: str | None = None
    ) -> Callable | None:
        if entry.get("exec") is not None:
            try:
                from jax.experimental import serialize_executable

                compiled = serialize_executable.deserialize_and_load(
                    entry["exec"], entry["in_tree"], entry["out_tree"]
                )
                self.stats.exec_loads += 1
                return _aot_runner(compiled)
            except Exception:
                self.stats.errors += 1  # foreign/stale executable: rebuild
        try:
            g = deserialize_graph(entry["graph"])
        except Exception:
            # exec blob unusable AND graph payload undecodable: corrupt
            if fpath is not None:
                self._quarantine(fpath)
            else:
                self.stats.corrupt_entries += 1
                self.stats.errors += 1
            return None
        try:
            fn = try_lower(g, fuse=fuse)
            if fn is None:
                return None
            return _aot_runner(self._compile(fn, avals, tag=f"entry:{g.name}"))
        except CompileFailed:
            raise
        except Exception:
            self.stats.errors += 1
            return None

    def _write(self, key: str, graph: Graph, compiled: Any) -> None:
        try:
            payload = serialize_graph(graph)
        except SerializeError:
            self.stats.errors += 1
            return  # graph not durable: serve from memory only
        blob = in_tree = out_tree = None
        try:
            from jax.experimental import serialize_executable

            blob, in_tree, out_tree = serialize_executable.serialize(compiled)
        except Exception:
            self.stats.errors += 1  # entry still useful: graph-level reuse
        entry = {"graph": payload, "exec": blob, "in_tree": in_tree, "out_tree": out_tree}
        tmp = None
        try:
            fd, tmp = tempfile.mkstemp(dir=self.path, suffix=".tmp")
            with os.fdopen(fd, "wb") as f:
                pickle.dump(entry, f)
            os.replace(tmp, self._file(key))
            self.stats.puts += 1
        except Exception as e:
            # disk full / permissions / unpicklable tree — the write layer,
            # not the entry bytes
            self.stats.errors += 1
            if isinstance(e, OSError):
                self.stats.io_errors += 1
            if tmp is not None:  # don't leak .tmp files into the cache dir
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
            return
        self._evict()

    def _evict(self, suffix: str = ".pkl") -> None:
        """Bound one tier's entry count (LRU by mtime).  Tiers evict
        independently: a burst of graph-tier puts never spills executables."""
        try:
            files = [
                os.path.join(self.path, n)
                for n in os.listdir(self.path)
                if n.endswith(suffix)
            ]
            if len(files) <= self.max_entries:
                return
            files.sort(key=os.path.getmtime)
            for f in files[: len(files) - self.max_entries]:
                os.remove(f)
                self.stats.spills += 1
        except OSError:
            self.stats.errors += 1
            self.stats.io_errors += 1


def _aot_runner(compiled: Any) -> Callable:
    def runner(*args: Any) -> Any:
        return compiled(*args)

    runner.lowered = True
    runner.aot = True
    runner.compiled = compiled
    return runner
