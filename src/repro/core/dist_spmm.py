"""Distributed SpMM execution in JAX via ``shard_map`` (paper §5-§6).

Two executors over a 1-D row-partitioned ``C = A @ B``:

* ``flat_spmm``      — single-tier schedule implementing the planner's
  strategy ('block' / 'col' / 'row' / 'joint'): paper Fig. 1.
* ``hier_spmm``      — two-tier (group, local) schedule implementing
  paper Alg. 1 / Fig. 6(f): inter-group B fetch ∥ intra-group C
  pre-aggregation, then inter-group C transfer ∥ intra-group B
  distribution. Collectives live on *disjoint mesh axes* so XLA's
  latency-hiding scheduler can overlap the complementary stages.

All buffer shapes are static (padded by the offline planner), so both
executors jit/lower cleanly — the same property the multi-pod dry-run
relies on.

Communication schedules are pluggable (core.comm_schedule): the default
``single`` schedule is the paper-style one max-padded ``all_to_all`` per
part; a ``bucketed`` CommSchedule replaces it with statically-unrolled
ppermute rounds whose slot sizes track per-shift demand, cutting the
executed padded bytes toward the planner's analytic volume on skewed
patterns. Pass ``schedule=`` to ``flat_exec_arrays`` /
``hier_exec_arrays``; the executors read it from the plan's static
metadata, so ``flat_spmm`` / ``hier_spmm`` calls are unchanged.

Local compute is pluggable too (core.local_backend): each exec plan
carries the planner's sparse pieces prepared in one or more backend
layouts (COO with a degree-bucketed row fold, Pallas ELL/BSR blocks,
...), and the executors take ``backend="coo"|"bsr"`` per call. Neither
the backend nor the pack/aggregate kernels touch the communication
schedule — the collectives in the lowered HLO are identical whichever
backend computes the local pieces.

The send-buffer pack and the received-partials aggregation go through
``kernels.ops`` (``pack_rows_op`` / ``scatter_add_rows_exec_op``): the
Pallas gather / sorted-scatter kernels on TPU (interpret mode when
``REPRO_PALLAS_INTERPRET=1``), the pure-jnp oracles elsewhere — all
numerically interchangeable.

Execution is staged or ROUND-PIPELINED (``overlap=True``): bucketed
plans carry per-round consumable layouts (segment colp/rowp pieces +
per-round aggregation maps, prepared host-side), and the overlapped
bodies consume each round's received slab the moment it lands — segment
compute depends only on its own collective-permute, so XLA's async
collective scheduling hides round k+1's wire behind round k's MXU/VPU
work. The hierarchical overlap additionally interleaves the Stage I
inter-group B fetch with shift-0 own-group compute and departs each
group shift's C transfer straight out of its own reduce-scatter (paper
Alg. 1 / Fig. 6(f)). Overlap changes only WHEN work executes: the
collective-permute operands are identical to the staged schedule's, and
C is bit-identical (the per-round accumulation replays the staged
per-element addition chains exactly — see core.local_backend's
cumulative-prefix contract).

Every executor runs under ``jax.named_scope("shiro.spmm")``, so that a
profile attributes its device ops, forward and backward, through the
HLO's ``op_name`` metadata. Inside every ``shard_map`` body each step
runs under a scope of its own, named once in ``core.trace``: ``pack``
(``pack_rows_op``, the gather of B rows into the send buffer),
``exchange`` (the all_to_all, ppermute, psum_scatter and all_gather
calls, ``_exchange_segments`` included, with the slicing and relayout of
their buffers), ``compute`` (the backend's diag, colp and rowp pieces)
and ``aggregate`` (``scatter_add_rows_exec_op``). A profile reads them
as ``shiro.spmm/.../<step>``. The scopes are metadata only: the compiled
program is unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..compat import all_to_all, ppermute, psum_scatter, shard_map
from ..kernels.ops import (
    pack_rows_op, prepare_sorted_scatter, scatter_add_rows_exec_op,
)
from .comm_schedule import (
    CommSchedule, flat_schedule_layout, hier_schedule_layout, ordered_spans,
    single_round_hier_schedule, single_round_schedule, span_cuts,
)
from .hierarchy import HierPlan, hier_piece_csrs
from .local_backend import (
    LocalSpmmBackend, backend_compute_segment, backend_prepare_segments,
    coo_spmm_local, get_backend,
)
from .planner import SpmmPlan, local_piece_csrs
from .trace import AGGREGATE, COMPUTE, EXCHANGE, PACK

__all__ = [
    "BackendSpec",
    "FlatExecPlan",
    "HierExecPlan",
    "ReplicatedExecPlan",
    "flat_exec_arrays",
    "hier_exec_arrays",
    "replicated_exec_arrays",
    "flat_spmm",
    "hier_spmm",
    "replicated_spmm",
    "coo_spmm_local",
]

BackendSpec = Union[str, LocalSpmmBackend]

# the named scope around every executor (see the module docstring)
SCOPE = "shiro.spmm"

# piece name -> backend-native arrays, all with leading [P, ...] (flat) or
# [G, L, ...] (hier) axes so they shard over the mesh like any other leaf
Pieces = Dict[str, Dict[str, jax.Array]]

# static per-shift segment descriptors: ((shift, offset, slot), ...)
Segments = Tuple[Tuple[int, int, int], ...]


def _prepare_pieces(
    piece_csrs: Dict[str, list],
    backends: Sequence[BackendSpec],
) -> Tuple[Dict[str, Pieces], Dict[str, LocalSpmmBackend]]:
    """Run every requested backend's host-side prepare over the pieces."""
    prepared: Dict[str, Pieces] = {}
    resolved: Dict[str, LocalSpmmBackend] = {}
    for spec in backends:
        be = get_backend(spec)
        if be.name in resolved:
            raise ValueError(f"duplicate backend {be.name!r}")
        resolved[be.name] = be
        prepared[be.name] = {k: be.prepare(v) for k, v in piece_csrs.items()}
    if not resolved:
        raise ValueError("at least one backend is required")
    return prepared, resolved


def _stack_sorted_scatter(tgt_rows: np.ndarray
                          ) -> Tuple[np.ndarray, np.ndarray]:
    """Per-process sorted-scatter prep, stacked on the leading axis.

    ``tgt_rows`` is [P, S] (-1 pads). Returns (perm [P, S] int32,
    meta [P, S+1] int32) ready to ride into the shard_map body as device
    args for ``scatter_add_rows_exec_op``.
    """
    perms, metas = [], []
    for p in range(tgt_rows.shape[0]):
        perm, meta = prepare_sorted_scatter(tgt_rows[p])
        perms.append(perm)
        metas.append(meta)
    return np.stack(perms), np.stack(metas)


class _ExecPlanBase:
    """Shared backend-resolution logic for the two exec-plan pytrees."""

    def resolve_backend(self, backend: Optional[BackendSpec]
                        ) -> Tuple[LocalSpmmBackend, Dict[str, jax.Array]]:
        if backend is None:
            be = self.meta["backends"][self.meta["default_backend"]]
        elif isinstance(backend, str):
            # the plan's own instances win over the global registry, so a
            # custom backend passed to *_exec_arrays stays addressable by
            # its name even when it was never register_backend()-ed
            be = self.meta["backends"].get(backend) or get_backend(backend)
        else:
            be = backend
        # the selected backend must match a prepared layout
        if be.name not in self.pieces:
            raise ValueError(
                f"backend {be.name!r} has no prepared pieces in this plan; "
                f"rebuild with *_exec_arrays(plan, backends=(..., {be.name!r}))"
            )
        return be, self.pieces[be.name]

    @property
    def backends(self) -> Tuple[str, ...]:
        return tuple(self.pieces)

    @property
    def schedule(self) -> CommSchedule:
        return self.meta["schedule"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FlatExecPlan(_ExecPlanBase):
    """Stacked per-process device arrays for the flat executor.

    ``pieces[backend][piece]`` holds the backend-native arrays for the
    three local-compute pieces ('diag', 'colp', 'rowp'), leading axis P.
    ``b_send_idx`` / ``c_recv_rows`` follow the active schedule's layout:
    [P, P, max_b] / [P, P, max_c] for the single all_to_all round,
    [P, R_b] / [P, R_c] flat segment spaces for a bucketed schedule.
    ``agg_perm`` / ``agg_meta`` are the host-prepared sorted-scatter maps
    consumed by the Pallas aggregation kernel. Bucketed plans additionally
    carry per-round consumables: ``pieces[backend]["colp@i"]`` /
    ``["rowp@i"]`` (segment layouts for round-pipelined compute, see
    ``local_backend.backend_prepare_segments``) and ``seg_agg``
    (``perm@i`` / ``meta@i`` per-round sorted-scatter maps).
    """

    pieces: Dict[str, Pieces]
    b_send_idx: jax.Array  # int32, -1 pad
    c_recv_rows: jax.Array  # int32, -1 pad
    agg_perm: jax.Array  # [P, S] int32
    agg_meta: jax.Array  # [P, S+1] int32
    seg_agg: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    meta: dict = dataclasses.field(metadata=dict(static=True), default_factory=dict)

    @property
    def P(self) -> int:
        return self.meta["P"]

    @property
    def max_b(self) -> int:
        return self.meta["max_b"]

    @property
    def max_c(self) -> int:
        return self.meta["max_c"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HierExecPlan(_ExecPlanBase):
    """Stacked per-process device arrays for the hierarchical executor.

    All leading [P, ...] arrays are reshaped to [G, L, ...] so they shard
    over the ('g', 'l') mesh axes. Layouts follow the active inter-group
    schedule exactly as in ``FlatExecPlan``.
    """

    pieces: Dict[str, Pieces]
    b_group_send_idx: jax.Array
    c_recv_rows: jax.Array
    agg_perm: jax.Array
    agg_meta: jax.Array
    seg_agg: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    meta: dict = dataclasses.field(metadata=dict(static=True), default_factory=dict)

    @property
    def G(self) -> int:
        return self.meta["G"]

    @property
    def L(self) -> int:
        return self.meta["L"]

    @property
    def max_bg(self) -> int:
        return self.meta["max_bg"]

    @property
    def max_cg(self) -> int:
        return self.meta["max_cg"]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class ReplicatedExecPlan(_ExecPlanBase):
    """Stacked per-device arrays for the replicated (1.5D) executor.

    All leading axes are [c, s, ...] (lane-major: device (r, g) = linear
    r·s + g) so they shard over the ('r', 'x') mesh. The static metadata
    carries the pre-flattened round descriptors (``b_rounds`` /
    ``c_rounds``): per round the per-lane shifts, the shared slot
    ceiling, its offset in the R_b / R_c segment space, and the
    participating lanes.
    """

    pieces: Dict[str, Pieces]
    b_send_idx: jax.Array  # [c, s, R_b] int32, -1 pad
    c_recv_rows: jax.Array  # [c, s, R_c] int32, -1 pad
    agg_perm: jax.Array  # [c, s, R_c] int32
    agg_meta: jax.Array  # [c, s, R_c+1] int32
    seg_agg: Dict[str, jax.Array] = dataclasses.field(default_factory=dict)
    meta: dict = dataclasses.field(metadata=dict(static=True), default_factory=dict)

    @property
    def c(self) -> int:
        return self.meta["c"]

    @property
    def s(self) -> int:
        return self.meta["s"]


# ---------------------------------------------------------------------------
# host-side array builders
# ---------------------------------------------------------------------------


def _uniform_m_local(bounds) -> int:
    m_locals = {b[1] - b[0] for b in bounds}
    if len(m_locals) != 1:
        raise ValueError("row blocks must be equal-sized; pad M to P|M first")
    return int(next(iter(m_locals)))


def _segments_static(off: Dict[int, Tuple[int, int]],
                     skip_shift0: bool = True) -> Segments:
    """Freeze a {shift: (offset, slot)} map into static metadata."""
    items = [(d, o, s) for d, (o, s) in off.items()
             if not (skip_shift0 and d == 0)]
    return tuple(sorted(items, key=lambda t: t[1]))


def flat_exec_arrays(plan: SpmmPlan,
                     backends: Sequence[BackendSpec] = ("coo",),
                     schedule: Optional[CommSchedule] = None,
                     overlap_layouts: bool = True
                     ) -> FlatExecPlan:
    """Convert an offline SpmmPlan into stacked device arrays.

    ``backends`` selects which local-compute layouts to prepare; the
    executor picks among them per call (``flat_spmm(..., backend=...)``).
    ``schedule`` selects the communication realization: ``None`` (or a
    ``kind="single"`` CommSchedule) keeps the one max-padded all_to_all
    per part; a bucketed CommSchedule (core.comm_schedule.
    build_comm_schedule) switches to per-shift ppermute rounds and
    re-lays the colp/rowp pieces into the bucketed index spaces.
    ``overlap_layouts=False`` skips the per-round consumables (a second
    copy of the colp/rowp layouts per backend + per-round scatter maps)
    when the caller knows execution stays staged — ``compile_spmm``
    passes its autotuned decision here.
    """
    m_local = _uniform_m_local(plan.bounds)
    if schedule is None or schedule.kind == "single":
        sched = schedule or single_round_schedule(plan)
        pieces, resolved = _prepare_pieces(local_piece_csrs(plan), backends)
        c_recv = plan.c_send_rows.transpose(1, 0, 2)  # [P(dst), P(src), max_c]
        perm, meta_arr = _stack_sorted_scatter(
            c_recv.reshape(plan.P, -1))
        return FlatExecPlan(
            pieces=pieces,
            b_send_idx=jnp.asarray(plan.b_send_idx),
            c_recv_rows=jnp.asarray(c_recv),
            agg_perm=jnp.asarray(perm),
            agg_meta=jnp.asarray(meta_arr),
            meta=dict(P=plan.P, max_b=plan.max_b, max_c=plan.max_c,
                      m_local=m_local, backends=resolved,
                      default_backend=next(iter(resolved)),
                      schedule=sched),
        )

    layout = flat_schedule_layout(plan, schedule)
    piece_csrs = {"diag": list(plan.a_diag), "colp": layout.colp,
                  "rowp": layout.rowp}
    pieces, resolved = _prepare_pieces(piece_csrs, backends)
    perm, meta_arr = _stack_sorted_scatter(layout.c_recv_rows)

    # per-round consumables for the overlapped executor: segment colp
    # layouts over the cumulative receive prefix, per-round rowp row
    # slices, and per-round aggregation maps
    b_spans = ordered_spans(layout.off_b)
    c_spans = ordered_spans(layout.off_c)
    seg_agg: Dict[str, jax.Array] = {}
    if overlap_layouts:
        for name, be in resolved.items():
            for i, seg in enumerate(
                    backend_prepare_segments(be, layout.colp,
                                             span_cuts(b_spans))):
                pieces[name][f"colp@{i}"] = seg
            for i, (_, off, slot) in enumerate(c_spans):
                pieces[name][f"rowp@{i}"] = be.prepare(
                    [csr.row_block(off, off + slot) for csr in layout.rowp])
        for i, (_, off, slot) in enumerate(c_spans):
            sp, sm = _stack_sorted_scatter(
                layout.c_recv_rows[:, off:off + slot])
            seg_agg[f"perm@{i}"] = jnp.asarray(sp)
            seg_agg[f"meta@{i}"] = jnp.asarray(sm)

    return FlatExecPlan(
        pieces=pieces,
        b_send_idx=jnp.asarray(layout.b_send_idx),
        c_recv_rows=jnp.asarray(layout.c_recv_rows),
        agg_perm=jnp.asarray(perm),
        agg_meta=jnp.asarray(meta_arr),
        seg_agg=seg_agg,
        meta=dict(P=plan.P, max_b=plan.max_b, max_c=plan.max_c,
                  m_local=m_local, backends=resolved,
                  default_backend=next(iter(resolved)),
                  schedule=schedule,
                  b_segments=b_spans,
                  c_segments=c_spans,
                  overlap_ready=overlap_layouts,
                  R_b=layout.R_b, R_c=layout.R_c),
    )


def hier_exec_arrays(hier: HierPlan,
                     backends: Sequence[BackendSpec] = ("coo",),
                     schedule: Optional[CommSchedule] = None,
                     overlap_layouts: bool = True
                     ) -> HierExecPlan:
    """Convert a HierPlan into stacked device arrays for the (g,l) mesh.

    ``schedule`` buckets the INTER-GROUP collectives (see
    core.comm_schedule.build_hier_comm_schedule); the intra-group
    psum_scatter / all_gather keep their uniform layouts either way.
    ``overlap_layouts`` as in ``flat_exec_arrays``.
    """
    base = hier.base
    G, L = hier.G, hier.L
    m_local = _uniform_m_local(base.bounds)

    if schedule is None or schedule.kind == "single":
        sched = schedule or single_round_hier_schedule(hier)
        pieces, resolved = _prepare_pieces(hier_piece_csrs(hier), backends)
        pieces = jax.tree_util.tree_map(
            lambda x: x.reshape((G, L) + x.shape[1:]), pieces)
        c_recv = hier.c_group_rows.transpose(1, 0, 2)  # [P(dst), G(src), max_cg]
        perm, meta_arr = _stack_sorted_scatter(
            c_recv.reshape(base.P, -1))
        return HierExecPlan(
            pieces=pieces,
            b_group_send_idx=jnp.asarray(
                hier.b_group_send_idx.reshape(G, L, G, hier.max_bg)),
            c_recv_rows=jnp.asarray(
                c_recv.reshape(G, L, G, hier.max_cg)),
            agg_perm=jnp.asarray(perm.reshape(G, L, -1)),
            agg_meta=jnp.asarray(meta_arr.reshape(G, L, -1)),
            meta=dict(G=G, L=L, max_bg=hier.max_bg, max_cg=hier.max_cg,
                      m_local=m_local, backends=resolved,
                      default_backend=next(iter(resolved)),
                      schedule=sched),
        )

    layout = hier_schedule_layout(hier, schedule)
    piece_csrs = {"diag": list(base.a_diag), "colp": layout.colp,
                  "rowp": layout.rowp}
    pieces, resolved = _prepare_pieces(piece_csrs, backends)

    # per-round consumables over the SEGMENT-MAJOR gathered space (the
    # shift-0 own-group segment is ordinal 0 when present): colp segment
    # layouts cut at the gathered cumulative boundaries, and per-round
    # aggregation maps over the inter-group C receive segments
    bg_all = ordered_spans(layout.off_bg)
    cg_all = ordered_spans(layout.off_cg)
    if overlap_layouts:
        gathered_cuts = tuple(L * (off + slot) for _, off, slot in bg_all)
        for name, be in resolved.items():
            for i, seg in enumerate(
                    backend_prepare_segments(be, layout.colp,
                                             gathered_cuts)):
                pieces[name][f"colp@{i}"] = seg
    pieces = jax.tree_util.tree_map(
        lambda x: x.reshape((G, L) + x.shape[1:]), pieces)
    perm, meta_arr = _stack_sorted_scatter(layout.c_recv_rows)
    seg_agg: Dict[str, jax.Array] = {}
    if overlap_layouts:
        for i, (_, off, slot) in enumerate(cg_all):
            sp, sm = _stack_sorted_scatter(
                layout.c_recv_rows[:, off:off + slot])
            seg_agg[f"perm@{i}"] = jnp.asarray(sp.reshape(G, L, -1))
            seg_agg[f"meta@{i}"] = jnp.asarray(sm.reshape(G, L, -1))
    local_b = layout.off_bg.get(0)
    local_c = layout.off_cg.get(0)
    return HierExecPlan(
        pieces=pieces,
        b_group_send_idx=jnp.asarray(
            layout.b_send_idx.reshape(G, L, layout.R_bg)),
        c_recv_rows=jnp.asarray(
            layout.c_recv_rows.reshape(G, L, layout.R_cg)),
        agg_perm=jnp.asarray(perm.reshape(G, L, -1)),
        agg_meta=jnp.asarray(meta_arr.reshape(G, L, -1)),
        seg_agg=seg_agg,
        meta=dict(G=G, L=L, max_bg=hier.max_bg, max_cg=hier.max_cg,
                  m_local=m_local, backends=resolved,
                  default_backend=next(iter(resolved)),
                  schedule=schedule,
                  bg_segments=_segments_static(layout.off_bg),
                  cg_segments=_segments_static(layout.off_cg),
                  bg_all=bg_all, cg_all=cg_all,
                  overlap_ready=overlap_layouts,
                  local_b=local_b, local_c=local_c,
                  R_bg=layout.R_bg, R_cg=layout.R_cg),
    )


def replicated_exec_arrays(rp,
                           backends: Sequence[BackendSpec] = ("coo",),
                           schedule=None) -> ReplicatedExecPlan:
    """Convert a ``planner.ReplicatedPlan`` into stacked device arrays.

    ``schedule`` is a ``comm_schedule.ReplicatedSchedule`` (built from
    the plan when None). The replicated executor is staged-only: the
    lane rounds are few by construction (ceil((s-1)/c) shifts per lane)
    and the reduce-scatter already serializes the tail, so there is no
    per-round consumable axis here.
    """
    from .comm_schedule import (
        build_replicated_schedule, replicated_schedule_layout,
    )

    sched = schedule or build_replicated_schedule(rp)
    layout = replicated_schedule_layout(rp, sched)
    c, s = rp.c, rp.s
    m_local = _uniform_m_local(rp.base.bounds)
    if m_local % c:
        raise ValueError(
            f"replicate={c} needs c | m_local for the tiled replica "
            f"reduce-scatter (m_local={m_local}); pad M or pick another c")
    piece_csrs = {"diag": layout.diag, "colp": layout.colp,
                  "rowp": layout.rowp}
    pieces, resolved = _prepare_pieces(piece_csrs, backends)
    pieces = jax.tree_util.tree_map(
        lambda x: x.reshape((c, s) + x.shape[1:]), pieces)
    perm, meta_arr = _stack_sorted_scatter(
        layout.c_recv_rows.reshape(c * s, layout.R_c))
    b_rounds = tuple((rnd.shifts, rnd.slot_b, rnd.off_b, rnd.b_lanes)
                     for rnd in sched.rounds if rnd.b_lanes)
    c_rounds = tuple((rnd.shifts, rnd.slot_c, rnd.off_c, rnd.c_lanes)
                     for rnd in sched.rounds if rnd.c_lanes)
    return ReplicatedExecPlan(
        pieces=pieces,
        b_send_idx=jnp.asarray(layout.b_send_idx),
        c_recv_rows=jnp.asarray(layout.c_recv_rows),
        agg_perm=jnp.asarray(perm.reshape(c, s, -1)),
        agg_meta=jnp.asarray(meta_arr.reshape(c, s, -1)),
        meta=dict(c=c, s=s, m_local=m_local, backends=resolved,
                  default_backend=next(iter(resolved)),
                  schedule=sched, b_rounds=b_rounds, c_rounds=c_rounds,
                  R_b=layout.R_b, R_c=layout.R_c),
    )


# ---------------------------------------------------------------------------
# bucketed round execution (shared by both executors)
# ---------------------------------------------------------------------------


def _shift_perm(P_: int, d: int) -> List[Tuple[int, int]]:
    return [(q, (q + d) % P_) for q in range(P_)]


def _exchange_segments(segments: Segments, axis: str, P_: int, total: int,
                       n: int, dtype, fetch,
                       local: Optional[Tuple[int, int]] = None) -> jax.Array:
    """Run one ppermute per segment and rebuild the flat receive space.

    ``fetch(d, off, slot)`` produces the [slot, N] send buffer for shift
    ``d`` (a static slice of the packed send space, or of the
    pre-aggregated hier tiles). Segment (d, off, slot) comes back — from
    src ``(me - d) % P`` — at the same offset, so send and receive share
    one layout. ``local`` is the hier shift-0 (own group) segment:
    fetched straight into the receive space, never touching the wire.
    Degenerate empty schedules yield the all-padding [total, N] zeros.
    """
    parts: List[Tuple[int, jax.Array]] = []
    if local is not None:
        off, slot = local
        parts.append((off, fetch(0, off, slot)))
    for d, off, slot in segments:
        parts.append((off, ppermute(fetch(d, off, slot), axis,
                                    _shift_perm(P_, d))))
    if not parts:
        return jnp.zeros((total, n), dtype)
    parts.sort(key=lambda t: t[0])
    out = jnp.concatenate([seg for _, seg in parts], axis=0)
    if out.shape[0] < total:  # trailing dummy slot (degenerate empty plan)
        out = jnp.concatenate(
            [out, jnp.zeros((total - out.shape[0], n), dtype)], axis=0)
    return out


def _slice_fetch(buf: jax.Array):
    """fetch() over a packed send buffer sharing the receive layout."""
    return lambda d, off, slot: jax.lax.slice_in_dim(buf, off, off + slot)


# ---------------------------------------------------------------------------
# flat executor (paper §5 / Fig. 1)
# ---------------------------------------------------------------------------


def flat_spmm(plan: FlatExecPlan, b_global: jax.Array, mesh: Mesh,
              axis: str = "x",
              backend: Optional[BackendSpec] = None,
              overlap: bool = False) -> jax.Array:
    """Execute ``C = A @ B`` with the flat SHIRO schedule on ``mesh[axis]``.

    ``b_global``: [K, N] dense matrix, row-sharded over ``axis``.
    ``backend`` selects the local-compute substrate among the layouts the
    plan was built with (default: the plan's first backend). The
    communication realization (single all_to_all round vs bucketed
    ppermute rounds) was fixed at ``flat_exec_arrays`` time.
    ``overlap=True`` switches a bucketed plan to the round-pipelined
    executor: identical collective-permutes, bit-identical C, but each
    round's segment compute depends only on its own permute so the
    compiler can hide round k+1's wire behind round k's work (single-
    round plans have no rounds to pipeline and fall back to staged).
    Returns C [M, N] row-sharded the same way.
    """
    m_local = plan.meta["m_local"]
    P_ = plan.P
    be, pieces = plan.resolve_backend(backend)
    sched = plan.schedule

    if sched.kind == "single":
        def body(pieces, b_send_idx, c_recv_rows, agg_perm, agg_meta,
                 seg_agg, b_loc):
            pieces = jax.tree_util.tree_map(lambda x: x[0], pieces)
            b_send_idx = b_send_idx[0]
            c_recv_rows = c_recv_rows[0]
            agg_perm, agg_meta = agg_perm[0], agg_meta[0]
            n = b_loc.shape[1]

            # ① pack + exchange B rows (column-based comm, Fig. 1(b))
            with jax.named_scope(PACK):
                send_b = pack_rows_op(b_loc, b_send_idx)  # [P, max_b, N]
            with jax.named_scope(EXCHANGE):
                recv_b = all_to_all(send_b, axis, 0, 0, tiled=False)

            # ② remote computation (row-based, Fig. 1(c)): partial C rows
            #    for every other process, against the LOCAL B block.
            with jax.named_scope(COMPUTE):
                partials = be.compute(pieces["rowp"], b_loc,
                                      P_ * plan.max_c)  # [P*max_c, N]
            with jax.named_scope(EXCHANGE):
                send_c = partials.reshape(P_, plan.max_c, n)
                recv_c = all_to_all(send_c, axis, 0, 0, tiled=False)

            # ③ local compute: diagonal + column-covered remote nonzeros
            with jax.named_scope(COMPUTE):
                c = be.compute(pieces["diag"], b_loc, m_local)
                recv_b_flat = recv_b.reshape(P_ * plan.max_b, n)
                c = c + be.compute(pieces["colp"], recv_b_flat, m_local)

            # ④ result aggregation: scatter received partial C rows
            with jax.named_scope(AGGREGATE):
                return scatter_add_rows_exec_op(
                    c, recv_c.reshape(P_ * plan.max_c, n),
                    c_recv_rows.reshape(-1), agg_perm, agg_meta)
    elif not overlap:
        b_segments: Segments = plan.meta["b_segments"]
        c_segments: Segments = plan.meta["c_segments"]
        R_b, R_c = plan.meta["R_b"], plan.meta["R_c"]

        def body(pieces, b_send_idx, c_recv_rows, agg_perm, agg_meta,
                 seg_agg, b_loc):
            pieces = jax.tree_util.tree_map(lambda x: x[0], pieces)
            b_send_idx = b_send_idx[0]
            c_recv_rows = c_recv_rows[0]
            agg_perm, agg_meta = agg_perm[0], agg_meta[0]

            n = b_loc.shape[1]

            # ① pack once, then one ppermute per scheduled shift — each
            #   padded only to its round's slot ceiling
            with jax.named_scope(PACK):
                send_b = pack_rows_op(b_loc, b_send_idx)  # [R_b, N]
            with jax.named_scope(EXCHANGE):
                recv_b = _exchange_segments(b_segments, axis, P_, R_b, n,
                                            b_loc.dtype, _slice_fetch(send_b))

            # ② partial C rows, computed straight into the bucketed
            #   send space, then exchanged shift by shift
            with jax.named_scope(COMPUTE):
                partials = be.compute(pieces["rowp"], b_loc, R_c)  # [R_c, N]
            with jax.named_scope(EXCHANGE):
                recv_c = _exchange_segments(c_segments, axis, P_, R_c, n,
                                            b_loc.dtype, _slice_fetch(partials))

            # ③ local compute against the bucketed receive space
            with jax.named_scope(COMPUTE):
                c = be.compute(pieces["diag"], b_loc, m_local)
                c = c + be.compute(pieces["colp"], recv_b, m_local)

            # ④ aggregation of received partials
            with jax.named_scope(AGGREGATE):
                return scatter_add_rows_exec_op(
                    c, recv_c, c_recv_rows, agg_perm, agg_meta)
    else:
        if not plan.meta.get("overlap_ready"):
            raise ValueError(
                "overlap=True needs the per-round consumable layouts; "
                "rebuild with flat_exec_arrays(..., overlap_layouts=True)")
        b_segments = plan.meta["b_segments"]
        c_segments = plan.meta["c_segments"]

        def body(pieces, b_send_idx, c_recv_rows, agg_perm, agg_meta,
                 seg_agg, b_loc):
            pieces = jax.tree_util.tree_map(lambda x: x[0], pieces)
            b_send_idx = b_send_idx[0]
            c_recv_rows = c_recv_rows[0]
            seg_agg = {k: v[0] for k, v in seg_agg.items()}
            n = b_loc.shape[1]

            # ① pack once; every B round is issued up front — the
            #   unrolled permutes are mutually independent, so the async
            #   collective scheduler keeps round k+1 on the wire while
            #   round k's segment compute (step ④) runs
            with jax.named_scope(PACK):
                send_b = pack_rows_op(b_loc, b_send_idx)  # [R_b, N]
            with jax.named_scope(EXCHANGE):
                recv_b = [ppermute(jax.lax.slice_in_dim(send_b, off, off + slot),
                                   axis, _shift_perm(P_, d))
                          for d, off, slot in b_segments]

            # ② per-round partial-C compute feeding its own round's wire:
            #   round i's permute departs after only ITS rowp slice ran
            recv_c = []
            for i, (d, off, slot) in enumerate(c_segments):
                with jax.named_scope(COMPUTE):
                    part = be.compute(pieces[f"rowp@{i}"], b_loc, slot)
                with jax.named_scope(EXCHANGE):
                    recv_c.append(ppermute(part, axis, _shift_perm(P_, d)))

            with jax.named_scope(COMPUTE):
                # ③ diagonal block while the first rounds fly
                c = be.compute(pieces["diag"], b_loc, m_local)

                # ④ consume B rounds as they land: cumulative receive prefix
                #   + segment-accumulating compute (bit-identical to staged)
                colp_acc = jnp.zeros((m_local, n), b_loc.dtype)
                prefix = None
                for i, seg in enumerate(recv_b):
                    prefix = seg if prefix is None else jnp.concatenate(
                        [prefix, seg], axis=0)
                    colp_acc = backend_compute_segment(
                        be, pieces[f"colp@{i}"], prefix, colp_acc)
                c = c + colp_acc

            # ⑤ per-round aggregation of received partials
            with jax.named_scope(AGGREGATE):
                for i, (d, off, slot) in enumerate(c_segments):
                    c = scatter_add_rows_exec_op(
                        c, recv_c[i],
                        jax.lax.slice_in_dim(c_recv_rows, off, off + slot),
                        seg_agg[f"perm@{i}"], seg_agg[f"meta@{i}"])
            return c

    fn = shard_map(body, mesh=mesh,
                   in_specs=(P(axis),) * 7,
                   out_specs=P(axis))
    with jax.named_scope(SCOPE):
        return fn(pieces, plan.b_send_idx, plan.c_recv_rows,
                  plan.agg_perm, plan.agg_meta, plan.seg_agg, b_global)


# ---------------------------------------------------------------------------
# hierarchical executor (paper §6 / Alg. 1)
# ---------------------------------------------------------------------------


def hier_spmm(plan: HierExecPlan, b_global: jax.Array, mesh: Mesh,
              group_axis: str = "g", local_axis: str = "l",
              backend: Optional[BackendSpec] = None,
              overlap: bool = False) -> jax.Array:
    """Two-tier SHIRO schedule on a (group, local) mesh.

    Program order follows paper Alg. 1; the two stages use disjoint axes
    (inter ↔ ``group_axis``, intra ↔ ``local_axis``) so the compiler can
    overlap them (Fig. 6(f)). ``backend`` selects the local-compute
    substrate exactly as in ``flat_spmm``; a bucketed schedule (fixed at
    ``hier_exec_arrays`` time) replaces the two inter-group all_to_alls
    with per-group-shift ppermute rounds and serves own-group traffic
    with a local slice. ``overlap=True`` round-pipelines a bucketed
    plan: the shift-0 own-group segment computes while the inter-group
    fetch rounds fly, each group shift's C transfer departs straight out
    of its own intra-group reduce-scatter, and every received slab is
    consumed the moment it lands — same collective-permutes,
    bit-identical C.
    """
    m_local = plan.meta["m_local"]
    G, L = plan.G, plan.L
    max_bg, max_cg = plan.max_bg, plan.max_cg
    be, pieces = plan.resolve_backend(backend)
    sched = plan.schedule

    if sched.kind == "single":
        def body(pieces, b_group_send_idx, c_recv_rows, agg_perm, agg_meta,
                 seg_agg, b_loc):
            pieces = jax.tree_util.tree_map(lambda x: x[0, 0], pieces)
            b_group_send_idx = b_group_send_idx[0, 0]
            c_recv_rows = c_recv_rows[0, 0]
            agg_perm, agg_meta = agg_perm[0, 0], agg_meta[0, 0]
            n = b_loc.shape[1]

            # Stage I.① (inter-group, column-based): ship de-duplicated B
            # rows once per destination group. Pairs (g, l) <-> (g', l).
            with jax.named_scope(PACK):
                send_bg = pack_rows_op(b_loc, b_group_send_idx)  # [G, max_bg, N]
            with jax.named_scope(EXCHANGE):
                recv_bg = all_to_all(send_bg, group_axis, 0, 0, tiled=False)

            # Stage I.① (intra-group, row-based): compute partials and
            # pre-aggregate within the source group via reduce-scatter;
            # each member ends up owning the aggregates for destinations
            # that share its local rank (the "representative" of Fig. 6(e)).
            with jax.named_scope(COMPUTE):
                partials = be.compute(pieces["rowp"], b_loc,
                                      G * L * max_cg)  # [(gd,ld,slot), N]
            with jax.named_scope(EXCHANGE):
                partials = partials.reshape(G, L * max_cg, n)
                agg = psum_scatter(partials, local_axis,
                                   scatter_dimension=1, tiled=True)
                # agg: [G(dst), max_cg, N] — aggregated partials for dests
                # sharing my local rank.

                # Stage II.② (inter-group, row-based): aggregated C rows
                # cross the slow tier once per source group.
                recv_cg = all_to_all(agg, group_axis, 0, 0, tiled=False)

                # Stage II.② (intra-group, column-based): distribute
                # fetched B rows inside the destination group.
                all_bg = jax.lax.all_gather(recv_bg, local_axis, axis=0,
                                            tiled=False)
                # all_bg: [L(src), G(src), max_bg, N]

            # local compute
            with jax.named_scope(COMPUTE):
                c = be.compute(pieces["diag"], b_loc, m_local)
                bg_flat = all_bg.reshape(L * G * max_bg, n)
                c = c + be.compute(pieces["colp"], bg_flat, m_local)

            # result aggregation of row-based partials
            with jax.named_scope(AGGREGATE):
                c = scatter_add_rows_exec_op(
                    c, recv_cg.reshape(G * max_cg, n),
                    c_recv_rows.reshape(-1), agg_perm, agg_meta)
            return c[None]
    elif not overlap:
        bg_segments: Segments = plan.meta["bg_segments"]
        cg_segments: Segments = plan.meta["cg_segments"]
        bg_all: Segments = plan.meta["bg_all"]
        local_b = plan.meta["local_b"]
        local_c = plan.meta["local_c"]
        R_bg, R_cg = plan.meta["R_bg"], plan.meta["R_cg"]

        def body(pieces, b_group_send_idx, c_recv_rows, agg_perm, agg_meta,
                 seg_agg, b_loc):
            pieces = jax.tree_util.tree_map(lambda x: x[0, 0], pieces)
            b_send_flat = b_group_send_idx[0, 0]
            c_recv_flat = c_recv_rows[0, 0]
            agg_perm, agg_meta = agg_perm[0, 0], agg_meta[0, 0]
            n = b_loc.shape[1]

            # Stage I.① inter-group B fetch, one ppermute per group shift;
            # shift 0 (own group) is a wire-free local slice
            with jax.named_scope(PACK):
                send_bg = pack_rows_op(b_loc, b_send_flat)  # [R_bg, N]
            with jax.named_scope(EXCHANGE):
                recv_bg = _exchange_segments(bg_segments, group_axis, G, R_bg,
                                             n, b_loc.dtype,
                                             _slice_fetch(send_bg),
                                             local=local_b)

            # Stage I.① intra-group pre-aggregation (unchanged): rowp rows
            # are laid out shift-major — (dg·L + ld)·max_cg + slot — so
            # the aggregated tile for group shift dg sits at agg[dg]
            with jax.named_scope(COMPUTE):
                partials = be.compute(pieces["rowp"], b_loc, G * L * max_cg)
            with jax.named_scope(EXCHANGE):
                partials = partials.reshape(G, L * max_cg, n)
                agg = psum_scatter(partials, local_axis,
                                   scatter_dimension=1, tiled=True)
                # agg: [G(shift), max_cg, N]

                # Stage II.② inter-group C transfer, bucketed per shift:
                # the send buffer for shift dg is the pre-aggregated tile
                # agg[dg]
                recv_cg = _exchange_segments(
                    cg_segments, group_axis, G, R_cg, n, b_loc.dtype,
                    lambda dg, off, slot: jax.lax.slice_in_dim(agg[dg], 0, slot),
                    local=local_c)

                # Stage II.② intra-group B distribution; the gathered
                # buffer is re-laid SEGMENT-major ([L·off, L·(off+slot))
                # per group shift) to match the colp index space — the
                # order the overlapped executor consumes segments in, so
                # both paths accumulate identically
                all_bg = jax.lax.all_gather(recv_bg, local_axis, axis=0,
                                            tiled=False)  # [L, R_bg, N]
                gparts = [all_bg[:, off:off + slot, :].reshape(L * slot, n)
                          for _, off, slot in bg_all]
                gathered = (jnp.concatenate(gparts, axis=0) if gparts
                            else jnp.zeros((L * R_bg, n), b_loc.dtype))

            with jax.named_scope(COMPUTE):
                c = be.compute(pieces["diag"], b_loc, m_local)
                c = c + be.compute(pieces["colp"], gathered, m_local)
            with jax.named_scope(AGGREGATE):
                c = scatter_add_rows_exec_op(
                    c, recv_cg, c_recv_flat, agg_perm, agg_meta)
            return c[None]
    else:
        if not plan.meta.get("overlap_ready"):
            raise ValueError(
                "overlap=True needs the per-round consumable layouts; "
                "rebuild with hier_exec_arrays(..., overlap_layouts=True)")
        bg_all = plan.meta["bg_all"]
        cg_all = plan.meta["cg_all"]

        def body(pieces, b_group_send_idx, c_recv_rows, agg_perm, agg_meta,
                 seg_agg, b_loc):
            pieces = jax.tree_util.tree_map(lambda x: x[0, 0], pieces)
            b_send_flat = b_group_send_idx[0, 0]
            c_recv_flat = c_recv_rows[0, 0]
            seg_agg = {k: v[0, 0] for k, v in seg_agg.items()}
            n = b_loc.shape[1]

            # Stage I.① inter-group B fetch, issued round by round; the
            # shift-0 own-group segment never touches the wire
            with jax.named_scope(PACK):
                send_bg = pack_rows_op(b_loc, b_send_flat)  # [R_bg, N]
            b_segs = []
            with jax.named_scope(EXCHANGE):
                for dg, off, slot in bg_all:
                    seg = jax.lax.slice_in_dim(send_bg, off, off + slot)
                    if dg != 0:
                        seg = ppermute(seg, group_axis, _shift_perm(G, dg))
                    b_segs.append(seg)

            # Stage I.① intra-group pre-aggregation, one reduce-scatter
            # per consumed group shift — round dg's inter-group C
            # transfer departs as soon as ITS tile is aggregated, while
            # the remaining shifts are still reducing (Alg. 1's
            # "inter-group ∥ intra-group" made explicit in dataflow)
            with jax.named_scope(COMPUTE):
                partials = be.compute(pieces["rowp"], b_loc, G * L * max_cg)
            c_segs = []
            with jax.named_scope(EXCHANGE):
                partials = partials.reshape(G, L * max_cg, n)
                for dg, off, slot in cg_all:
                    agg_dg = psum_scatter(partials[dg], local_axis,
                                          scatter_dimension=0, tiled=True)
                    seg = jax.lax.slice_in_dim(agg_dg, 0, slot)
                    if dg != 0:
                        seg = ppermute(seg, group_axis, _shift_perm(G, dg))
                    c_segs.append(seg)

            # Stage II: own-group compute first (overlaps the in-flight
            # fetch rounds), then consume each gathered slab as it lands
            with jax.named_scope(COMPUTE):
                c = be.compute(pieces["diag"], b_loc, m_local)
                colp_acc = jnp.zeros((m_local, n), b_loc.dtype)
            prefix = None
            for i, seg in enumerate(b_segs):
                with jax.named_scope(EXCHANGE):
                    gathered = jax.lax.all_gather(
                        seg, local_axis, axis=0, tiled=False)
                    gathered = gathered.reshape(-1, n)  # [L·slot, N]
                with jax.named_scope(COMPUTE):
                    prefix = gathered if prefix is None else jnp.concatenate(
                        [prefix, gathered], axis=0)
                    colp_acc = backend_compute_segment(
                        be, pieces[f"colp@{i}"], prefix, colp_acc)
            with jax.named_scope(COMPUTE):
                c = c + colp_acc

            # per-round aggregation of the inter-group partials
            with jax.named_scope(AGGREGATE):
                for i, (dg, off, slot) in enumerate(cg_all):
                    c = scatter_add_rows_exec_op(
                        c, c_segs[i],
                        jax.lax.slice_in_dim(c_recv_flat, off, off + slot),
                        seg_agg[f"perm@{i}"], seg_agg[f"meta@{i}"])
            return c[None]

    gl = P(group_axis, local_axis)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(gl,) * 6 + (P((group_axis, local_axis)),),
                   out_specs=gl)
    with jax.named_scope(SCOPE):
        out = fn(pieces, plan.b_group_send_idx, plan.c_recv_rows,
                 plan.agg_perm, plan.agg_meta, plan.seg_agg, b_global)
        return out.reshape(-1, b_global.shape[1])


# ---------------------------------------------------------------------------
# replicated executor (1.5D: c lanes + replica-axis reduce-scatter)
# ---------------------------------------------------------------------------


def replicated_spmm(plan: ReplicatedExecPlan, b_global: jax.Array,
                    mesh: Mesh, replica_axis: str = "r", axis: str = "x",
                    backend: Optional[BackendSpec] = None,
                    overlap: bool = False) -> jax.Array:
    """Execute ``C = A @ B`` on a (c, s) replica × shard mesh.

    ``b_global``: [K, N] dense matrix, row-sharded over ``axis`` ONLY —
    every lane holds a full s-way shard (the c-fold B replication).
    Per round, every participating lane runs ITS OWN shift's
    collective-permute concurrently in one static ppermute over the
    joint (replica, shard) axes; lanes outside the permutation receive
    zeros, and their pieces carry no nonzeros in the segment. After the
    lane-local compute + aggregation, the per-lane partial C blocks are
    summed and scattered over ``replica_axis`` (``compat.psum_scatter``)
    — the inter-lane traffic replication buys down to one dense
    ``(c-1)/c``-sized block per device. Returns C [M, N] row-sharded
    over (shard, replica) so global row order is preserved.
    """
    if overlap:
        raise ValueError(
            "the replicated executor is staged-only; overlap composes "
            "with replicate=1 tiers (flat/hier) instead")
    m_local = plan.meta["m_local"]
    c_, s_ = plan.c, plan.s
    R_b, R_c = plan.meta["R_b"], plan.meta["R_c"]
    b_rounds = plan.meta["b_rounds"]
    c_rounds = plan.meta["c_rounds"]
    be, pieces = plan.resolve_backend(backend)
    axes = (replica_axis, axis)

    def _lane_perm(shifts, lanes):
        # lane r's shift d pairs device (r, g) with (r, (g + d) % s):
        # disjoint per-lane cycles, one static collective
        return [(r * s_ + g, r * s_ + (g + shifts[r]) % s_)
                for r in lanes for g in range(s_)]

    def _exchange(rounds, buf, total, n, dtype):
        parts = []
        for shifts, slot, off, lanes in rounds:
            seg = jax.lax.slice_in_dim(buf, off, off + slot)
            parts.append((off, ppermute(seg, axes,
                                        _lane_perm(shifts, lanes))))
        if not parts:
            return jnp.zeros((total, n), dtype)
        parts.sort(key=lambda t: t[0])
        out = jnp.concatenate([seg for _, seg in parts], axis=0)
        if out.shape[0] < total:
            out = jnp.concatenate(
                [out, jnp.zeros((total - out.shape[0], n), dtype)], axis=0)
        return out

    def body(pieces, b_send_idx, c_recv_rows, agg_perm, agg_meta,
             seg_agg, b_loc):
        pieces = jax.tree_util.tree_map(lambda x: x[0, 0], pieces)
        b_send_idx = b_send_idx[0, 0]
        c_recv_rows = c_recv_rows[0, 0]
        agg_perm, agg_meta = agg_perm[0, 0], agg_meta[0, 0]
        n = b_loc.shape[1]

        # ① pack + lane-exchange B rows, one joint ppermute per round
        with jax.named_scope(PACK):
            send_b = pack_rows_op(b_loc, b_send_idx)  # [R_b, N]
        with jax.named_scope(EXCHANGE):
            recv_b = _exchange(b_rounds, send_b, R_b, n, b_loc.dtype)

        # ② partial C rows for this lane's shifts, exchanged per round
        with jax.named_scope(COMPUTE):
            partials = be.compute(pieces["rowp"], b_loc, R_c)  # [R_c, N]
        with jax.named_scope(EXCHANGE):
            recv_c = _exchange(c_rounds, partials, R_c, n, b_loc.dtype)

        # ③ lane-local compute: diagonal (lane 0 only, by construction)
        #   + this lane's column-covered nonzeros
        with jax.named_scope(COMPUTE):
            c = be.compute(pieces["diag"], b_loc, m_local)
            c = c + be.compute(pieces["colp"], recv_b, m_local)

        # ④ aggregate received partials, then sum + scatter the lanes'
        #   C blocks over the replica axis
        with jax.named_scope(AGGREGATE):
            c = scatter_add_rows_exec_op(
                c, recv_c, c_recv_rows, agg_perm, agg_meta)
        with jax.named_scope(EXCHANGE):
            return psum_scatter(c, replica_axis, scatter_dimension=0,
                                tiled=True)  # [m_local / c, N]

    rx = P(replica_axis, axis)
    fn = shard_map(body, mesh=mesh,
                   in_specs=(rx,) * 6 + (P(axis),),
                   out_specs=P((axis, replica_axis)))
    with jax.named_scope(SCOPE):
        return fn(pieces, plan.b_send_idx, plan.c_recv_rows,
                  plan.agg_perm, plan.agg_meta, plan.seg_agg, b_global)
