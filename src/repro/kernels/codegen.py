"""Pallas kernel code generation for fusion clusters.

The partitioner (``repro.core.fusion``) hands this module legal clusters;
``emit_cluster`` turns each one into a :class:`FusedKernel` — a callable
with three interchangeable execution paths, selected by the *same*
``set_kernel_mode`` switch the hand-written kernels use:

* ``"ref"`` / ``"chunked"``  — the **pure-jnp oracle**: exactly the same
  primitive ``impl`` calls, in the same order, as the unfused lowering
  would emit.  This path is bit-identical to the unfused program by
  construction and is what CPU test/serving traffic executes.
* ``"pallas_interpret"``     — the generated Pallas kernel run by the
  Pallas interpreter (correctness validation on CPU; every op inside the
  kernel is the same jnp call the oracle makes, so blocked map kernels
  remain bit-identical).
* ``"pallas"``               — the compiled Pallas TPU kernel.

Kernel shape strategy (blocks are legal for the TPU's Mosaic compiler
and sized to a VMEM budget, :data:`_VMEM_BUDGET`):

* **map clusters** (elementwise root): every operand enters the kernel
  at its own size — read at the body's rank after ``unreduce``'s keepdims
  expansion and numpy left-padding, so each of its axes is the body's or
  1 — and the kernel broadcasts it to the block in VMEM; a broadcast
  member (``unreduce`` / ``broadcast_to``) runs in the kernel.  The
  kernel's **frame** is the 2-D ``(R, C) = (prod(S[:-1]), S[-1])`` when
  the operands are all full, rows ``(R, 1)``, lanes ``(1, C)`` or
  scalars, and the body's own axes otherwise (see ``_frame``).  The
  frame's last two axes are the **tile**, blocked
  ``(br, bc)``: a block dimension is either the whole array dimension or
  a multiple of the tiling — ``br`` of the narrowest dtype's sublane count
  (8 rows of a 32-bit type, 16 of a 16-bit one, 32 of an 8-bit one),
  ``bc`` of the 128-lane width — so edge blocks may be partial, which
  elementwise bodies tolerate.  Whole rows are kept when 8 of them fit
  the budget; wider rows are blocked in columns too.  When whole tiles
  fit, the block takes as many as fit over the leading axes (innermost
  first, an axis whole or by its largest divisor that fits).  An
  operand's block has extent 1, at block index 0, on each axis it is 1
  along.  The grid runs the leading axes along which the largest
  in-kernel broadcast repeats fastest, so its block index holds between
  consecutive steps and Pallas does not fetch it again.  A bool operand
  enters as Pallas passes bools to Mosaic, int32 at its own size; a
  value narrower than 32 bits that is 1 on both tile axes enters widened
  to a lane, which Mosaic can broadcast.
* **windows**: a map cluster's window members (``slice_axis`` along a
  non-lane axis, and the ``pad_zeros_axis`` beneath it; see
  ``repro.core.fusion``) run in the kernel as they are, on a block of
  their **source**, which enters once at its own shape and whole along
  the window axis.  The frame is then the body's own axes and every
  block holds each window axis whole (whole rows, lanes blocked, when
  it is the frame's second-to-last).  Since such a block cannot shrink
  to pay for temporaries, the VMEM plan counts what Mosaic keeps in
  scoped VMEM, the blocks of the operands spanning the tile and of the
  output double-buffered, and one temporary per window.  When no block
  fits, ``emit_cluster`` emits the cluster without its windows: the
  slices stay outside, as the partitioner would leave them without the
  rule.
* **reduce clusters** (reduction root): one whole-array block (no grid)
  — the kernel computes the elementwise body and applies the reduction
  primitive with its static axes, so the floating-point reduction order
  is identical to the unfused lowering's.  Rank-0/1 results are staged
  through a 2-D output block and reshaped by the wrapper.  A reduce
  cluster whose whole-array block does not fit the VMEM budget is
  declined.

``emit_cluster`` *declines* (returns None) clusters it cannot express —
non-array external inputs, rank-0 bodies, blocks over the VMEM budget —
and the lowering falls back to the per-node jnp path for exactly that
cluster, never the whole graph.  Non-array constants (a ``cast``'s
dtype, python scalars) stay closure-bound in the kernel body; only array
values become kernel operands.

Generated source (kernel + wrapper + oracle) is kept on the result as
``FusedKernel.source`` — tests exec it and ``docs/fusion.md`` shows one.
"""

from __future__ import annotations

import math
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core.fusion import Cluster, DeclineReason, classify, window_axis
from repro.core.infer import AArray
from repro.core.ir import Apply, Constant, Node
from repro.core.primitives import _norm_axes
from repro.obs import profile as obs_profile
from .ops import get_kernel_mode

__all__ = ["FusedKernel", "emit_cluster", "emit_cluster_explained"]

#: VMEM bytes a generated kernel plans for: half of the TPU v5e's 16 MiB
#: scoped-VMEM default, leaving the rest to Mosaic's own scratch
_VMEM_BUDGET = 8 * 1024 * 1024
#: lane width: the minor block dimension is a multiple of it (or whole)
_LANE = 128

_counter = [0]


class FusedKernel:
    """One generated kernel: callable (mode-dispatching), with the oracle
    and both Pallas variants exposed for differential testing."""

    __slots__ = (
        "name",
        "source",
        "n_nodes",
        "kind",
        "body_shape",
        "out_shape",
        "bytes_moved",
        "broadcast_in_kernel",
        "broadcast_materialized",
        "window_in_kernel",
        "window_materialized",
        "cluster",
        "oracle",
        "pallas_interpret",
        "pallas_compiled",
    )

    def __init__(
        self,
        name: str,
        source: str,
        n_nodes: int,
        kind: str,
        body_shape: tuple,
        out_shape: tuple,
        oracle: Callable,
        pallas_interpret: Callable,
        pallas_compiled: Callable,
        bytes_moved: int = 0,
        broadcast_in_kernel: int = 0,
        broadcast_materialized: int = 0,
        window_in_kernel: int = 0,
        window_materialized: int = 0,
        cluster: Cluster | None = None,
    ) -> None:
        self.name = name
        self.source = source
        self.n_nodes = n_nodes
        self.kind = kind
        self.body_shape = body_shape
        self.out_shape = out_shape
        #: HBM traffic per launch: each kernel operand at the size the
        #: kernel reads it (a broadcast one at its own size times the
        #: passes the grid makes over it, a materialized one at the
        #: body's) + the root output — what the runtime profiler divides
        #: wall time into for achieved-GB/s / roofline_fraction
        self.bytes_moved = bytes_moved
        #: operands broadcast to the block in VMEM by the kernel itself
        self.broadcast_in_kernel = broadcast_in_kernel
        #: operands the wrapper still expands to the body shape in HBM
        self.broadcast_materialized = broadcast_materialized
        #: window slices the kernel forms in VMEM from their source
        self.window_in_kernel = window_in_kernel
        #: body-shaped non-lane slices that reach the kernel from outside,
        #: materialized in HBM
        self.window_materialized = window_materialized
        #: the cluster the kernel computes: the one it was asked for, or
        #: that cluster without its windows when they did not fit
        self.cluster = cluster
        self.oracle = oracle
        self.pallas_interpret = pallas_interpret
        self.pallas_compiled = pallas_compiled

    def __call__(self, *args: Any) -> Any:
        mode = get_kernel_mode()
        if mode == "pallas_interpret":
            fn = self.pallas_interpret
        elif mode == "pallas":
            fn = self.pallas_compiled
        else:
            fn = self.oracle  # "ref" / "chunked"
        # runtime profiler hook: disarmed this is one module-global read
        # (the structural-zero-overhead contract); armed + concrete args,
        # the launch is timed to completion and attributed per kernel
        prof = obs_profile._ACTIVE
        if prof is None or any(isinstance(a, jax.core.Tracer) for a in args):
            return fn(*args)
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(*args))
        prof.record(self.name, "fused", time.perf_counter() - t0, self.bytes_moved)
        return out

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return f"<FusedKernel {self.name} {self.kind} n={self.n_nodes}>"


def _literal(value: Any) -> str | None:
    """Source literal for embeddable static values (mirrors
    ``lowering._literal``: exact types only, so numpy scalars stay
    closure-bound and dtype promotion is untouched)."""
    if value is None:
        return "None"
    t = type(value)
    if t is bool or t is str or t is int:
        return repr(value)
    if t is float:
        return repr(value) if math.isfinite(value) else None
    if t is tuple:
        elts = [_literal(v) for v in value]
        if any(e is None for e in elts):
            return None
        inner = ", ".join(elts)
        return f"({inner},)" if len(elts) == 1 else f"({inner})"
    return None


def _sublanes(itemsize: int) -> int:
    """Row tiling of a dtype: 8 rows of 32-bit values, 16 of 16-bit, 32
    of 8-bit (a bool is stored as one byte)."""
    return max(8, 32 // itemsize)


def _map_block(R: int, C: int, sub: int, elem_bytes: int) -> tuple[int, int] | None:
    """``(br, bc)`` for an ``(R, C)`` map body whose every element costs
    ``elem_bytes`` of VMEM, or None when even one minimal tile does not
    fit.  Each block dimension is the whole dimension or a multiple of
    its tiling (``sub`` rows, ``_LANE`` columns)."""
    cap = _VMEM_BUDGET // elem_bytes  # elements per block
    if R * C <= cap:
        return R, C
    if min(R, sub) * C <= cap:
        bc = C  # whole rows fit: block rows only
    else:
        bc = (cap // sub) // _LANE * _LANE
        if bc == 0:
            return None
    br = (cap // bc) // sub * sub
    if br == 0:
        return None
    return min(br, R), bc


def _abstract_nbytes(ab: Any) -> int:
    """Bytes of one array abstract (0 for non-arrays/unknown)."""
    if isinstance(ab, AArray):
        n = 1
        for d in ab.shape:
            n *= int(d)
        return n * np.dtype(ab.dtype).itemsize
    return 0


def _broadcast_input_shape(n: Apply, data_shape: tuple) -> tuple:
    """The shape a broadcast member's data input has as the member
    broadcasts it: ``unreduce`` with ``keepdims=False`` first re-inserts
    the reduced axes as 1s (``broadcast_to`` takes it as it is)."""
    shape = list(data_shape)
    if n.fn.value.name == "unreduce":
        shp, axes, keepdims = (a.value for a in n.args[1:])
        if not keepdims:
            for ax in _norm_axes(axes, len(shp)):
                shape.insert(ax, 1)
    return tuple(shape)


def _frame(body_shape: tuple, padded: list[tuple]) -> tuple[tuple, list[tuple]]:
    """The map kernel's frame and each operand's shape in it: the 2-D
    ``(R, C) = (prod(S[:-1]), S[-1])`` when every operand is whole or 1
    along all the leading axes at once (full, a row, a lane or a scalar),
    else the body's own axes: a reshape that merged leading axes there
    would let XLA lay the operand's producer out otherwise than the
    kernel's row-major operand and add a relayout copy between them (it
    did for the attention's scores on a v5e)."""
    lead = body_shape[:-1]
    if all(p[:-1] == lead or set(p[:-1]) <= {1} for p in padded):
        return (math.prod(lead), body_shape[-1]), [(math.prod(p[:-1]), p[-1]) for p in padded]
    return body_shape, padded


def _lead_block(dims: tuple, room: int) -> tuple:
    """Block extents over a frame's leading axes when ``room`` whole tiles
    fit the budget: from the innermost axis outwards each axis is taken
    whole while it fits, then the largest divisor of the next that fits,
    and every axis further out at 1."""
    out = []
    for d in reversed(dims):
        b = next(k for k in range(max(1, min(d, room)), 0, -1) if d % k == 0)
        out.append(b)
        room = room // d if b == d else 1
    return tuple(reversed(out))


def _memref_itemsize(dtype) -> int:
    """Bytes an element of ``dtype`` takes as a kernel operand: Mosaic
    holds a bool memref as int32 (Pallas converts it at the boundary)."""
    dtype = np.dtype(dtype)
    return 4 if dtype == np.bool_ else dtype.itemsize


def emit_cluster(cluster: Cluster) -> FusedKernel | None:
    """Generate the fused kernel for ``cluster`` or decline with None."""
    kernel, _reason = emit_cluster_explained(cluster)
    return kernel


def emit_cluster_explained(
    cluster: Cluster,
) -> tuple[FusedKernel | None, DeclineReason | None]:
    """``(kernel, None)`` on success, ``(None, DeclineReason)`` when the
    generator cannot express the cluster — the structured verdict the
    explain layer reports per cluster."""
    got = _emit_cluster_impl(cluster)
    if not isinstance(got, FusedKernel) and cluster.windows:
        # the windows' whole axes did not fit: the slices stay outside
        got = _emit_cluster_impl(cluster.without_windows())
    if isinstance(got, FusedKernel):
        return got, None
    return None, got


def _emit_cluster_impl(cluster: Cluster) -> FusedKernel | DeclineReason:
    body_shape = tuple(cluster.body_shape)
    out_shape = tuple(cluster.out_shape)
    out_dtype = cluster.out_dtype
    if out_dtype is None or len(body_shape) == 0:
        return DeclineReason(
            DeclineReason.EMPTY_BODY,
            "cluster has no output dtype or a rank-0 body; no kernel to win",
        )

    # -- name & classify members ------------------------------------------
    members = {n._id for n in cluster.order}
    pre: list[Apply] = []  # broadcast members: run in the wrapper
    body: list[Apply] = []  # elementwise members (+ reduction root)
    for n in cluster.order:
        (pre if classify(n) == "broadcast" else body).append(n)
    # window members (the slices and a pad beneath them) run in the kernel
    # as they are, on a block that holds their axis whole: the source under
    # each (a cluster input) and the axis it shifts along
    windows: list[tuple[Node, int]] = []
    for n in cluster.windows:
        src = n.args[0]
        if src._id in members:
            src = src.args[0]
        windows.append((src, window_axis(n)))
    if not body or body[-1] is not cluster.root:
        return DeclineReason(
            DeclineReason.CODEGEN,
            "root is not the last body node (single-output ordering)",
        )

    env: dict[str, Any] = {"jnp": jnp, "jax": jax, "pl": pl}
    prim_names: dict[int, str] = {}

    def bind_prim(prim) -> str:
        name = prim_names.get(id(prim))
        if name is None:
            name = f"_prim_{prim.name}_{len(prim_names)}"
            prim_names[id(prim)] = name
            env[name] = prim.impl
        return name

    # -- operand discovery -------------------------------------------------
    # names for: cluster inputs (a{i}), bound constants (_const_{k}),
    # pre-member results (p{k}), body values (v{k})
    input_name: dict[int, str] = {}
    for i, node in enumerate(cluster.inputs):
        if not isinstance(node.abstract, AArray):
            # non-array input: the jnp path keeps this cluster
            return DeclineReason(
                DeclineReason.NOT_ARRAY,
                f"cluster input {i} has no array abstract",
            )
        input_name[node._id] = f"a{i}"

    def ext_ref(node: Node) -> str | None:
        """Name/literal for a non-member node, or None if unsupported."""
        got = input_name.get(node._id)
        if got is not None:
            return got
        if isinstance(node, Constant):
            lit = _literal(node.value)
            if lit is not None:
                return lit
            name = f"_const_{len(env)}"
            env[name] = node.value
            input_name[node._id] = name
            return name
        return None

    def ext_shape(node: Node) -> tuple | None:
        if isinstance(node.abstract, AArray):
            return node.abstract.shape
        if isinstance(node, Constant):  # an array constant (see operand_for)
            return tuple(int(d) for d in np.shape(node.value))
        return None

    # a map cluster's broadcast member runs in the kernel: its data input is
    # the operand, and the kernel broadcasts it to the block in VMEM (member
    # id -> (data ref, the shape the member broadcasts)); a reduce
    # cluster's runs in the wrapper
    in_kernel: dict[int, tuple[str, tuple]] = {}
    pre_name: dict[int, str] = {}
    pre_lines: list[str] = []
    for n in pre:
        args = []
        for a in n.args:
            if a._id in members:
                return DeclineReason(
                    DeclineReason.CODEGEN,
                    "broadcast member consumes a kernel-body value",
                )
            r = ext_ref(a)
            if r is None:
                return DeclineReason(
                    DeclineReason.CODEGEN,
                    f"unsupported external reference feeding {n.fn.value.name}",
                )
            args.append(r)
        if cluster.kind == "map":
            in_kernel[n._id] = (args[0], _broadcast_input_shape(n, ext_shape(n.args[0])))
            continue
        k = len(pre_name)
        pre_name[n._id] = f"p{k}"
        pre_lines.append(
            f"    p{k} = {bind_prim(n.fn.value)}({', '.join(args)})  # {n.fn.value.name} (pre)"
        )

    # kernel operands: every value entering the elementwise body, as
    # (slot, wrapper ref, own shape, dtype, whether the wrapper computes
    # it at the body shape: a reduce cluster's broadcast member)
    found: list[tuple[str, str, tuple, Any, bool]] = []
    operand_slot: dict[int, str] = {}

    def operand_for(a: Node) -> str | None:
        slot = operand_slot.get(a._id)
        if slot is not None:
            return slot
        wide = False
        if a._id in in_kernel:
            r, shape = in_kernel[a._id]
            dtype = a.abstract.dtype
        elif a._id in pre_name:
            r, shape, dtype = pre_name[a._id], body_shape, a.abstract.dtype
            wide = True
        else:
            r = ext_ref(a)
            if r is None or (
                isinstance(a, Constant)
                and not isinstance(a.value, (np.ndarray, jax.Array))
            ):
                # literal or non-array static (a cast's dtype, a numpy
                # scalar): embedded in the kernel body, not an operand
                return r
            shape = ext_shape(a)
            if shape is None:
                return None
            dtype = a.abstract.dtype if isinstance(a.abstract, AArray) else a.value.dtype
        slot = f"x{len(found)}"
        operand_slot[a._id] = slot
        found.append((slot, r, tuple(int(d) for d in shape), dtype, wide))
        return slot

    body_lines: list[str] = []
    vname: dict[int, str] = {}
    red_root = cluster.kind == "reduce"
    for k, n in enumerate(body):
        rendered = []
        is_root_reduction = red_root and n is cluster.root
        for j, a in enumerate(n.args):
            if a._id in vname:
                rendered.append(vname[a._id])
                continue
            if is_root_reduction and j > 0:
                # static reduction config (axes / shape / keepdims)
                assert isinstance(a, Constant)
                r = ext_ref(a)
            else:
                r = operand_for(a)
            if r is None:
                return DeclineReason(
                    DeclineReason.CODEGEN,
                    f"unsupported operand feeding {n.fn.value.name}",
                )
            rendered.append(r)
        vname[n._id] = f"v{k}"
        body_lines.append(
            f"v{k} = {bind_prim(n.fn.value)}({', '.join(rendered)})  # {n.fn.value.name}"
        )
    root_v = vname[cluster.root._id]

    # -- shapes & VMEM plan -------------------------------------------------
    out2 = (1, max(int(np.prod(out_shape)), 1)) if len(out_shape) < 2 else None
    sizes = [
        np.dtype(n.abstract.dtype).itemsize
        for n in cluster.order
        if isinstance(n.abstract, AArray)
    ]
    widest, narrowest = max(sizes), min(sizes)
    # each operand as (slot, wrapper expr, shape it enters with, kind,
    # itemsize), of one kind: "full" (at the kernel's frame), "small" (at
    # its own size, broadcast by the kernel per block in VMEM), "source"
    # (under windows, at its own size, whole along the window axes) or
    # "wide" (a reduce cluster's, expanded to the body shape in HBM by the
    # wrapper)
    operands: list[tuple[str, str, tuple, str, int]] = []
    if cluster.kind == "map":
        # each operand's shape left-padded to the body's rank (numpy's
        # broadcasting): every axis is then the body's or 1
        nd = len(body_shape)
        padded = [(1,) * (nd - len(shape)) + shape for _, _, shape, _, _ in found]
        sources = {operand_slot[src._id] for src, _ in windows}
        # a window shifts along the body's own axes: no 2-D frame then
        frame, shapes = (body_shape, padded) if windows else _frame(body_shape, padded)
        for (slot, r, own, dtype, _), shape in zip(found, shapes):
            if shape == frame:
                expr, kind = (r if own == frame else f"jnp.reshape({r}, {frame})"), "full"
            elif slot in sources:
                expr, kind = r, "source"
            else:
                expr, kind = f"jnp.reshape({r}, {shape})", "small"
                if shape[-2:] == (1, 1) and np.dtype(dtype).itemsize < 4:
                    # Mosaic cannot broadcast a packed (sub-32-bit) value
                    # across sublanes and lanes at once: the wrapper widens
                    # it to a lane
                    shape = shape[:-1] + (frame[-1],)
                    expr = f"jnp.broadcast_to({expr}, {shape})"
            operands.append((slot, expr, shape, kind, _memref_itemsize(dtype)))
    else:
        for slot, r, own, dtype, wide in found:
            if wide or own != body_shape:
                expr = r if own == body_shape else f"jnp.broadcast_to({r}, {body_shape})"
                operands.append((slot, expr, body_shape, "wide", _memref_itemsize(dtype)))
            else:
                operands.append((slot, r, body_shape, "full", _memref_itemsize(dtype)))
    kinds = [op[3] for op in operands]
    n_small = kinds.count("small")
    if cluster.kind == "map":
        tile = frame[-2:]
        sub = _sublanes(narrowest)
        # the block holds each window axis whole
        waxes = {axis for _, axis in windows}
        if windows:
            # so it cannot shrink to pay for temporaries: it plans what
            # Mosaic keeps in scoped VMEM, the blocks of the operands
            # spanning the tile (a source at its whole extent) and of the
            # output double-buffered, and one temporary per window
            whole = math.prod(frame[a] for a in waxes)
            n_buf = 1 + sum(
                -(-math.prod(op[2][a] for a in waxes) // whole)
                for op in operands
                if op[2][-2:] == tile or op[3] == "source"
            )
            elem_bytes = widest * (2 * n_buf + len(windows))
        else:
            # operands spanning the whole tile (the frame's last two axes)
            # and the output double-buffered, plus one temporary per body
            # node and per in-kernel broadcast
            n_tile = sum(op[2][-2:] == tile for op in operands)
            elem_bytes = widest * (2 * (n_tile + 1) + len(body) + n_small)
        # each element of a tile costs this much with the leading window
        # axes held whole
        per_tile = elem_bytes * math.prod(frame[a] for a in waxes if a < nd - 2)
        if nd - 2 in waxes:
            cap = _VMEM_BUDGET // per_tile
            R, C = tile
            got = (R, C) if R * C <= cap else (
                (R, cap // R // _LANE * _LANE) if R * _LANE <= cap else None
            )
        else:
            got = _map_block(*tile, sub, per_tile)
        if got is None:
            return DeclineReason(
                DeclineReason.VMEM,
                f"no ({sub}, {_LANE})-aligned block of the "
                f"{tile[0]}x{tile[1]} tile fits {_VMEM_BUDGET} bytes of VMEM",
            )
        room = 1
        if got == tile:
            # whole tiles (each at its sublane- and lane-padded size): the
            # block takes as many as fit over the leading axes
            padded_tile = -(-got[0] // sub) * sub * (-(-got[1] // _LANE) * _LANE)
            room = _VMEM_BUDGET // per_tile // padded_tile
        outer = _lead_block(tuple(1 if a in waxes else d for a, d in enumerate(frame[:-2])), room)
        block = (*(frame[a] if a in waxes else b for a, b in enumerate(outer)), *got)
        grid = [-(-f // b) for f, b in zip(frame, block)]
        lead = range(len(frame) - 2)
        names = [f"g{a}" for a in lead] + ["i", "j"]
        # the leading axes along which the largest in-kernel broadcast
        # repeats vary fastest: its block index then holds between
        # consecutive grid steps, and a held block is not fetched again
        big = max(
            (op for op in operands if op[3] == "small"),
            key=lambda op: math.prod(op[2]) * op[4],
            default=None,
        )
        rep = [a for a in lead if big is not None and big[2][a] == 1 and grid[a] > 1]
        order = [a for a in lead if a not in rep] + [len(frame) - 2, len(frame) - 1] + rep
        params = ", ".join(names[a] for a in order)
    else:
        need = widest * int(np.prod(body_shape)) * (len(operands) + len(body))
        if need > _VMEM_BUDGET:
            return DeclineReason(
                DeclineReason.VMEM,
                f"whole-array reduce block of {list(body_shape)} needs ~{need} "
                f"bytes of VMEM, over the {_VMEM_BUDGET}-byte budget",
            )

    # HBM traffic: each operand as the kernel reads it, + the output.  A
    # block is fetched again only when its block index changes between
    # consecutive grid steps, so an operand is read whole once, and once
    # more for each step of a grid axis it does not span (it is 1 there)
    # outside the innermost axis it does span
    bytes_moved = _abstract_nbytes(cluster.root.abstract)
    for _, _, shape, _, itemsize in operands:
        n = int(np.prod(shape))
        if cluster.kind == "map":
            spans = [shape[a] > 1 and grid[a] > 1 for a in order]
            if any(spans):
                inner = len(spans) - 1 - spans[::-1].index(True)
                n *= math.prod(grid[a] for a, s in zip(order[:inner], spans) if not s)
        bytes_moved += n * itemsize

    _counter[0] += 1
    name = f"fused_{cluster.kind}{_counter[0]}_" + "_".join(
        dict.fromkeys(n.fn.value.name for n in cluster.order)
    )
    env["_out_dtype"] = np.dtype(out_dtype)

    # -- source ------------------------------------------------------------
    nargs = ", ".join(f"a{i}" for i in range(len(cluster.inputs)))
    krefs = ", ".join(f"{op[0]}_ref" for op in operands)
    lines = [f"def _kernel({krefs}{', ' if krefs else ''}o_ref):"]
    for slot, _, _, kind, _ in operands:
        if kind == "small":
            lines.append(f"    {slot} = jnp.broadcast_to({slot}_ref[...], {block})")
        else:
            lines.append(f"    {slot} = {slot}_ref[...]")
    lines += [f"    {l}" for l in body_lines]
    if cluster.kind == "map":
        lines.append(f"    o_ref[...] = {root_v}")
    else:
        # whole-array block: operands arrive at body_shape already; the
        # reduction's static axes were rendered into the body line itself
        lines.append(f"    o_ref[...] = jnp.reshape({root_v}, {out2 or out_shape})")
    lines.append("")

    # wrapper: prepare operands (each in its frame shape, or a reduce
    # cluster's at the body shape), call pallas, restore shape
    lines.append("def _make(interpret):")
    lines.append(f"    def {name}({nargs}):")
    for pl_line in pre_lines:
        lines.append("    " + pl_line)
    if cluster.kind == "map":
        spec = f"pl.BlockSpec({block}, lambda {params}: ({', '.join(names)}))"
    call_args, in_specs = [], []
    for slot, expr, shape, kind, _ in operands:
        if kind == "small":
            # its block spans its 1-long axes whole (block index 0) and
            # follows the grid along the others
            ext = tuple(b if d > 1 else 1 for d, b in zip(shape, block))
            idx = ", ".join(n if d > 1 else "0" for d, n in zip(shape, names))
            in_specs.append(f"pl.BlockSpec({ext}, lambda {params}: ({idx}))")
        elif kind == "source":
            # whole along the window axes, with the grid along the others
            ext = tuple(d if a in waxes else b for a, (d, b) in enumerate(zip(shape, block)))
            idx = ", ".join("0" if a in waxes else n for a, n in enumerate(names))
            in_specs.append(f"pl.BlockSpec({ext}, lambda {params}: ({idx}))")
        elif cluster.kind == "map":
            in_specs.append(spec)
        lines.append(f"        {slot} = {expr}")
        call_args.append(slot)
    if cluster.kind == "map":
        lines += [
            "        out = pl.pallas_call(",
            "            _kernel,",
            f"            grid={tuple(grid[a] for a in order)},",
            "            in_specs=[" + ", ".join(in_specs) + "],",
            f"            out_specs={spec},",
            f"            out_shape=jax.ShapeDtypeStruct({frame}, _out_dtype),",
            "            interpret=interpret,",
            f"            name={name!r},",
            f"        )({', '.join(call_args)})",
            f"        return jnp.reshape(out, {out_shape})",
        ]
    else:
        lines += [
            "        out = pl.pallas_call(",
            "            _kernel,",
            f"            out_shape=jax.ShapeDtypeStruct({out2 or out_shape}, _out_dtype),",
            "            interpret=interpret,",
            f"            name={name!r},",
            f"        )({', '.join(call_args)})",
            f"        return jnp.reshape(out, {out_shape})",
        ]
    lines.append(f"    return {name}")
    lines.append("")

    # oracle: the exact unfused computation (impl call per member, original
    # shapes, no broadcasts inserted) — bit-identical to direct lowering
    lines.append(f"def _oracle({nargs}):")
    ovname: dict[int, str] = {}
    for n in cluster.order:
        rendered = []
        for a in n.args:
            if a._id in ovname:
                rendered.append(ovname[a._id])
            else:
                rendered.append(ext_ref(a))
        ovname[n._id] = f"w{len(ovname)}"
        lines.append(
            f"    {ovname[n._id]} = {bind_prim(n.fn.value)}"
            f"({', '.join(rendered)})  # {n.fn.value.name}"
        )
    lines.append(f"    return {ovname[cluster.root._id]}")
    source = "\n".join(lines) + "\n"

    namespace = dict(env)
    try:
        exec(compile(source, f"<myia-fused:{name}>", "exec"), namespace)
    except SyntaxError:  # pragma: no cover - codegen bug guard
        return DeclineReason(
            DeclineReason.CODEGEN, "generated source failed to compile"
        )
    oracle = namespace["_oracle"]
    interp = namespace["_make"](True)
    compiled = namespace["_make"](False)
    for fn in (oracle, interp, compiled):
        fn.__fused_source__ = source
    return FusedKernel(
        name=name,
        source=source,
        n_nodes=len(cluster.order),
        kind=cluster.kind,
        body_shape=body_shape,
        out_shape=out_shape,
        oracle=oracle,
        pallas_interpret=interp,
        pallas_compiled=compiled,
        bytes_moved=bytes_moved,
        broadcast_in_kernel=n_small,
        broadcast_materialized=kinds.count("wide"),
        window_in_kernel=len(windows),
        window_materialized=sum(
            cluster.kind == "map"
            and window_axis(i) is not None
            and i.fn.value.name == "slice_axis"
            and i.abstract.shape == body_shape
            for i in cluster.inputs
        ),
        cluster=cluster,
    )
