"""One front door: ``compile_spmm`` — an autotuned, cacheable DistSpmm handle.

SHIRO's pitch is that the *framework* picks the near-optimal communication
strategy. The low-level surface (``build_plan`` → ``build_hier_plan`` →
``flat_exec_arrays``/``hier_exec_arrays`` → ``flat_spmm``/``hier_spmm``)
exposes every knob but makes the caller assemble the pipeline by hand — and
in practice nobody turns the knobs. This module owns the whole pipeline
behind a single prepared handle:

    cfg = SpmmConfig(backends=("coo", "bsr"), hier="auto", schedule="auto")
    h   = compile_spmm(a, mesh, cfg)      # plan + autotune + prepare, once
    c   = h(b)                            # cached AOT executable per shape
    h.stats()                             # what it decided, and why
    h.save("plan.shiro")                  # ship the preprocessed plan
    h2  = DistSpmm.load("plan.shiro", mesh)   # no MWVC re-run per process

Autotune decision procedure (all offline, α-β model from ``comm_model``):

1. ``build_plan(a, P, strategy, pad_to)`` — the flat SHIRO plan (MWVC).
2. flat vs hierarchical: ``hier="auto"`` takes the topology's intrinsic
   (G, L) tiers (two-axis mesh shape, hosts × local devices) — falling
   back to a ``net.group_size`` divisor sweep on structureless
   substrates — and keeps the hierarchical executor iff
   ``modeled_time_hier`` beats ``modeled_time`` at ``n_dense_hint`` dense
   columns; an explicit ``(G, L)`` forces it; ``None`` stays flat.
3. schedule: ``"auto"`` sweeps K = 1..k_max bucketed ppermute schedules
   against the single max-padded all_to_all (``choose_schedule`` /
   ``choose_hier_schedule``); ``"single"`` keeps the paper-style round;
   an int K forces that bucketing.
4. execution mode: ``overlap="auto"`` keeps the round-pipelined executor
   iff ``modeled_time_overlap`` (Σ_k max(comm_k, comp_k)) beats the
   staged comm+comp total for the chosen schedule; the sweep in step 3
   co-optimizes K with the mode. The decision lands in ``h.stats()``
   (``overlap`` + both modeled times) and in BENCH records.
5. every backend in ``backends`` gets its layout prepared once; calls pick
   among them (``h(b, backend="bsr")``).

The handle memoizes jitted executables keyed by ``(n_cols, dtype,
backend)`` so repeated serving calls never re-lower; inside an outer
``jax.jit`` (e.g. a training step) it transparently falls back to the
traceable executor path instead. ``save``/``load`` serialize only the
host-side plan (NumPy) — device arrays and executables are rebuilt
deterministically on load, so a serving fleet ships preprocessed plans
instead of re-running MWVC per process.

Drop to the low-level layer when you need a custom communication schedule
object, a mesh the handle's axis conventions don't cover, or per-call
control of exec-plan internals — the handle composes exactly those
functions and nothing else.

Lifecycle lives one layer up: ``compile_spmm`` is the thin one-rung form
of ``core.session.SpmmSession`` (P-ladders for elastic resizes,
drift-triggered replans with warm hot-swaps, ladder bundle save/load),
and every entry point here names its execution substrate through
``distributed.topology.Topology`` (``Topology | Mesh | int | None`` are
all accepted and normalized by ``Topology.resolve``).
"""
from __future__ import annotations

import dataclasses
import os
import pickle
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..distributed.topology import Topology
from ..launch.hlo_analysis import executable_memory, strip_metadata
from ..robustness import faults, guards
from .comm_model import (
    NetworkSpec, choose_fused_schedule, choose_hier_fused_schedule,
    choose_hier_schedule, choose_schedule,
    modeled_time, modeled_time_fused_schedule, modeled_time_hier,
    modeled_time_hier_fused_schedule, modeled_time_hier_overlap,
    modeled_time_hier_schedule, modeled_time_hier_staged,
    modeled_time_overlap, modeled_time_replicated, modeled_time_schedule,
    modeled_time_staged, replicated_device_bytes,
)
from .comm_schedule import (
    CommSchedule, ReplicatedSchedule, build_comm_schedule,
    build_hier_comm_schedule, build_replicated_schedule,
    single_round_hier_schedule, single_round_schedule,
)
from .dist_sddmm import (
    EDGE_FNS, flat_fused, flat_sddmm, hier_fused, hier_sddmm,
)
from .dist_spmm import (
    BackendSpec, FlatExecPlan, HierExecPlan, ReplicatedExecPlan,
    flat_exec_arrays, flat_spmm, hier_exec_arrays, hier_spmm,
    replicated_exec_arrays, replicated_spmm,
)
from .hierarchy import HierPlan, build_hier_plan
from .local_backend import fold_counts, get_backend
from .planner import SpmmPlan, Strategy, build_plan, replicate_plan
from .sparse import CSRMatrix, PatternSnapshot
from .trace import span

__all__ = [
    "SpmmConfig",
    "DistSpmm",
    "compile_spmm",
    "compile_sddmm",
    "compile_fused",
    "make_spmm_fn",
    "register_lowering_hook",
    "unregister_lowering_hook",
]

_SCHEDULE_POLICIES = ("auto", "single")
_KERNELS = ("spmm", "sddmm", "fused")
# per-call ``edge=`` default: "not passed" (fall back to the config's edge)
_UNSET = object()
_SAVE_FORMAT = "shiro.DistSpmm"
# v1: PR 3 (no pattern snapshot). v2: adds the planned-pattern snapshot
# (drift detection) and records the planning topology. v3: the schedule
# slot may carry a ReplicatedSchedule (1.5D rung — the plan slot then
# holds the s-shard base plan, P = schedule.P). Loaders reject anything
# they don't know how to rebuild — see ``DistSpmm.load``.
_SAVE_VERSION = 3
_KNOWN_VERSIONS = (1, 2, 3)

# hooks called as hook(handle, key) each time the handle lowers+compiles a
# NEW executable — tests count cache behavior here. Keys are
# (n_cols, dtype_name, backend) for spmm calls and "sddmm"/"fused"-tagged
# tuples for the sibling kernels (see ``DistSpmm._executable`` et al.).
_LOWERING_HOOKS: List[Callable[["DistSpmm", Tuple[Any, ...]], None]] = []


def register_lowering_hook(fn: Callable) -> Callable:
    """Install a callback fired on every fresh executable lowering."""
    _LOWERING_HOOKS.append(fn)
    return fn


def unregister_lowering_hook(fn: Callable) -> None:
    _LOWERING_HOOKS.remove(fn)


@dataclasses.dataclass(frozen=True)
class SpmmConfig:
    """Everything ``compile_spmm`` needs beyond the matrix and the mesh.

    ``strategy``       planner cover strategy ('block'|'col'|'row'|'joint').
    ``kernel``         which kernel family calls run by default:
                       ``"spmm"`` (C = A @ B), ``"sddmm"`` (sampled
                       dense-dense: values = A ⊙ (X Yᵀ) on A's pattern)
                       or ``"fused"`` (FusedMM:
                       C = edge(A ⊙ (X Yᵀ)) @ B through ONE
                       communication phase). All three share the same
                       plan/schedule; per-call selectable like
                       ``backend=``: ``h(x, y, b, kernel="fused")``.
                       Non-spmm kernels always execute staged
                       (``overlap`` does not apply) and skip B-buffer
                       donation.
    ``edge``           zero-preserving edge nonlinearity applied to the
                       sampled values before the SpMM phase of
                       ``"sddmm"``/``"fused"`` calls — a name from
                       ``dist_sddmm.EDGE_FNS`` (e.g. ``"leaky_relu"``
                       for GAT-style attention) or None (identity).
    ``hier``           None = flat executor; ``(G, L)`` forces the two-tier
                       executor; ``"auto"`` derives (G, L) from
                       ``net.group_size`` and keeps it iff the α-β model
                       says it wins.
    ``backends``       local-compute layouts to prepare (names or
                       LocalSpmmBackend instances); calls select per-call.
    ``default_backend`` name used when ``h(b)`` gets no ``backend=``
                       (default: the first entry of ``backends``).
    ``schedule``       ``"auto"`` = model-picked (single vs bucketed
                       K=1..k_max); ``"single"`` = the paper-style
                       max-padded all_to_all; an int K forces a K-class
                       bucketed schedule.
    ``overlap``        ``"auto"`` (default) = round-pipelined execution
                       iff ``modeled_time_overlap`` beats the staged
                       comm+comp total for the chosen plan; ``True``
                       forces overlapped execution on bucketed
                       schedules; ``False`` keeps staged execution.
                       Single-round schedules have no rounds to
                       pipeline and always execute staged.
    ``net``            two-tier NetworkSpec the autotuner scores against;
                       ``"auto"`` (default) derives it from the topology's
                       structure (``Topology.network()`` — multi-host
                       fleets and two-axis meshes carry their own tiers;
                       flat substrates keep the paper's TSUBAME-like
                       model network, bit-compatible with the old fixed
                       default).
    ``pad_to``         slot-count rounding forwarded to ``build_plan``.
    ``n_dense_hint``   dense column count the offline model evaluates at
                       (the handle itself serves any N).
    ``k_max``          upper bound of the schedule-K sweep under "auto".
    ``drift_threshold`` sparsity-pattern Jaccard distance above which a
                       live operand no longer matches the planned
                       snapshot — ``SpmmSession.maybe_replan`` re-plans
                       past it, and ``h.stats()["drift"]`` reports the
                       last measured value either way.
    ``donate``         donate the B operand buffer to the executable so
                       XLA reuses its allocation for receive slabs / the
                       C accumulator (C bit-identical either way). Only
                       applied when the operand is square (C then has
                       B's exact row count, so the alias is always
                       usable); the handle copies B defensively when a
                       caller's on-sharding device array would otherwise
                       be consumed.
    ``measure``        timed candidate profiling on top of the α-β model:
                       ``True`` profiles the model's top
                       ``profile_topk`` candidates with real executions,
                       ``False`` stays model-only, ``"auto"`` (default)
                       measures iff an autotune cache directory is
                       configured (env ``REPRO_AUTOTUNE_CACHE``).
                       ``REPRO_MEASURE=0``/``1`` overrides either way.
                       See ``core.autotune``.
    ``memory_budget``  per-device byte budget; ``SpmmSession.build``
                       skips ladder rungs whose estimated (or measured)
                       executable allocation exceeds it.
    ``profile_topk``   how many model-ranked candidates to time-profile.
    ``profile_iters``  timed runs per candidate (median is kept).
    ``profile_warmup`` discarded warmup runs per candidate.
    ``replicate``      1.5D replication factor ``c``: B is replicated
                       across ``c`` lanes of ``s = P/c`` shards, each
                       lane covers a disjoint subset of the nonzero
                       shifts, and the partial C is reduce-scattered
                       over the replica axis. ``1`` (default) keeps the
                       flat/hier executors untouched; an int ``c > 1``
                       forces a c-lane plan (raising if P, the row
                       blocks or the B partition don't divide);
                       ``"auto"`` sweeps feasible c ∈ {2, 4, 8} under
                       ``memory_budget`` and keeps the winner iff
                       ``modeled_time_replicated`` beats the chosen
                       flat/hier time. Only ``kernel="spmm"``; c > 1
                       executes staged (no ``overlap``).
    ``check``          serving-path guardrails (``robustness.guards``):
                       ``"auto"`` (default) validates B's shape/dtype
                       with actionable errors before XLA sees the
                       mismatch, validates the sparse operand's values
                       are finite at plan/replan time, and runs a cheap
                       SAMPLED ``isfinite`` sweep over each served C —
                       raising ``NumericalFault`` naming the first bad
                       element/call. The sweep reduces each shard on its
                       own device; the host reads back a few scalars per
                       shard, never C. ``"full"``/``True`` sweeps every C
                       element; ``False`` disables all of it
                       (bit-identical to the unguarded path).
    """

    strategy: Strategy = "joint"
    kernel: str = "spmm"
    edge: Optional[str] = None
    hier: Union[str, Tuple[int, int], None] = None
    backends: Tuple[BackendSpec, ...] = ("coo",)
    default_backend: Optional[str] = None
    schedule: Union[str, int] = "auto"
    overlap: Union[str, bool] = "auto"
    net: Union[str, NetworkSpec] = "auto"
    pad_to: int = 1
    n_dense_hint: int = 64
    k_max: int = 4
    drift_threshold: float = 0.1
    donate: bool = True
    measure: Union[str, bool] = "auto"
    memory_budget: Optional[int] = None
    profile_topk: int = 3
    profile_iters: int = 3
    profile_warmup: int = 1
    check: Union[str, bool] = "auto"
    replicate: Union[int, str] = 1

    def __post_init__(self) -> None:
        if self.kernel not in _KERNELS:
            raise ValueError(
                f"kernel must be one of {_KERNELS}; got {self.kernel!r}")
        if self.edge is not None:
            if self.edge not in EDGE_FNS:
                raise ValueError(
                    f"edge must be None or one of "
                    f"{tuple(sorted(EDGE_FNS))}; got {self.edge!r}")
            if self.kernel == "spmm":
                raise ValueError(
                    "edge= applies to the sampled values of "
                    "kernel='sddmm'/'fused'; kernel='spmm' has none")
        if self.check not in ("auto", "full", True, False):
            raise ValueError(
                f"check must be 'auto', 'full', True or False; "
                f"got {self.check!r}")
        if isinstance(self.schedule, bool) or not (
                self.schedule in _SCHEDULE_POLICIES
                or (isinstance(self.schedule, int) and self.schedule >= 1)):
            raise ValueError(
                f"schedule must be 'auto', 'single' or an int K >= 1; "
                f"got {self.schedule!r}")
        if self.overlap not in ("auto", True, False):
            raise ValueError(
                f"overlap must be 'auto', True or False; "
                f"got {self.overlap!r}")
        if not (self.hier is None or self.hier == "auto"
                or (isinstance(self.hier, tuple) and len(self.hier) == 2)):
            raise ValueError(
                f"hier must be None, 'auto' or a (G, L) tuple; "
                f"got {self.hier!r}")
        if not self.backends:
            raise ValueError("at least one backend is required")
        if not (self.net == "auto" or isinstance(self.net, NetworkSpec)):
            raise ValueError(
                f"net must be 'auto' or a NetworkSpec; got {self.net!r}")
        if not (0.0 <= float(self.drift_threshold) <= 1.0):
            raise ValueError(
                f"drift_threshold is a Jaccard distance in [0, 1]; "
                f"got {self.drift_threshold!r}")
        if self.measure not in ("auto", True, False):
            raise ValueError(
                f"measure must be 'auto', True or False; "
                f"got {self.measure!r}")
        if self.memory_budget is not None and int(self.memory_budget) <= 0:
            raise ValueError(
                f"memory_budget is a per-device byte count > 0 (or None); "
                f"got {self.memory_budget!r}")
        if isinstance(self.replicate, bool) or not (
                self.replicate == "auto"
                or (isinstance(self.replicate, int) and self.replicate >= 1)):
            raise ValueError(
                f"replicate must be 'auto' or an int c >= 1; "
                f"got {self.replicate!r}")
        if self.replicate != 1 and self.kernel != "spmm":
            raise ValueError(
                f"replicate= applies to kernel='spmm' only; the sddmm/"
                f"fused executors have no replicated tier yet "
                f"(got kernel={self.kernel!r}, "
                f"replicate={self.replicate!r})")
        if int(self.profile_topk) < 1 or int(self.profile_iters) < 1 \
                or int(self.profile_warmup) < 0:
            raise ValueError(
                f"profiling needs topk >= 1, iters >= 1, warmup >= 0; got "
                f"topk={self.profile_topk!r} iters={self.profile_iters!r} "
                f"warmup={self.profile_warmup!r}")

    def backend_names(self) -> Tuple[str, ...]:
        return tuple(get_backend(spec).name for spec in self.backends)

    def resolve_net(self, topology: Topology) -> NetworkSpec:
        """The NetworkSpec the autotuner scores against on ``topology``."""
        if self.net == "auto":
            return topology.network()
        return self.net


# ---------------------------------------------------------------------------
# the handle
# ---------------------------------------------------------------------------


def _is_tracer(x: Any) -> bool:
    try:
        return isinstance(x, jax.core.Tracer)
    except AttributeError:  # pragma: no cover — future jax.core reshuffles
        return hasattr(x, "aval") and not isinstance(x, (np.ndarray,
                                                         jax.Array))


class DistSpmm:
    """A compiled distributed-SpMM handle: ``C = A @ B`` behind one call.

    Built by ``compile_spmm`` (or ``DistSpmm.load``); owns the offline
    plan, the autotuned schedule, the prepared backend layouts, and a
    memoized cache of AOT-compiled executables keyed by
    ``(n_cols, dtype, backend)``. Calls with concrete arrays hit the
    cache; calls under an outer trace (``jax.jit`` / ``grad`` around the
    handle) transparently use the traceable executor path instead.
    """

    def __init__(self, *, config: SpmmConfig, plan: SpmmPlan,
                 hier: Optional[HierPlan], schedule: CommSchedule,
                 ex: Union[FlatExecPlan, HierExecPlan, ReplicatedExecPlan],
                 mesh: Mesh,
                 axis_kwargs: Dict[str, str], decisions: Dict[str, Any],
                 snapshot: Optional[PatternSnapshot] = None,
                 topology: Optional[Topology] = None):
        self.config = config
        self.plan = plan
        self.hier = hier
        self.schedule = schedule
        self.ex = ex
        self.mesh = mesh
        self.topology = topology
        self.snapshot = snapshot
        self.last_drift: float = 0.0
        self.axis_kwargs = dict(axis_kwargs)
        self.decisions = dict(decisions)
        # autotuned execution mode: round-pipelined vs staged (decided in
        # compile_spmm, rides through save/load inside ``decisions``)
        self.overlap = bool(self.decisions.get("overlap", False))
        # default kernel family + edge nonlinearity (older pickled
        # configs predate the fields -> plain spmm)
        self.kernel = getattr(config, "kernel", "spmm")
        self.edge = getattr(config, "edge", None)
        self.default_backend = (config.default_backend
                                or self.decisions.get("backend")
                                or config.backend_names()[0])
        if self.default_backend not in self.ex.backends:
            raise ValueError(
                f"default_backend {self.default_backend!r} not among "
                f"prepared backends {self.ex.backends}")
        # key -> compiled executable; spmm keys are (n_cols, dtype_name,
        # backend) — unchanged since PR 3 so saved working sets stay
        # warmable — sibling kernels use tagged tuples:
        #   ("sddmm", F, dtype_x, dtype_y, backend, edge)
        #   ("fused", F, N, dtype_x, dtype_y, dtype_b, backend, edge)
        self._executables: Dict[Tuple[Any, ...], Any] = {}
        # same keys -> executable_memory() profile
        self._memory: Dict[Tuple[Any, ...], Dict[str, int]] = {}
        self.lowerings: List[Tuple[Any, ...]] = []
        self.cache_hits = 0
        self.values_refreshes = 0
        # guardrails (older pickled configs predate the field -> "auto")
        self._check = guards.check_mode(config)
        self.calls = 0             # concrete __call__ executions served
        self.numerical_faults = 0  # C sweeps that raised NumericalFault
        self.guard_host_bytes = 0  # bytes the output sweeps read back from devices
        # replicated (1.5D) rungs route by schedule kind: the plan slot
        # holds the s-shard base plan and the exec plan leads [c, s, ...]
        self.replicated = getattr(schedule, "kind", "") == "replicated"
        # B is row-sharded over every mesh axis; pinning it at lowering
        # time lets the AOT executables accept any caller layout (we
        # reshard on call instead of failing the dispatch-time check).
        # Replicated handles shard B over the lane axis only — the c-fold
        # copy over the replica axis IS the strategy's memory trade.
        if self.replicated:
            spec = PartitionSpec(self.axis_kwargs["axis"])
            ex_spec = PartitionSpec(*self.axis_kwargs.values())
        elif hier is not None:
            spec = PartitionSpec(tuple(self.axis_kwargs.values()))
            ex_spec = PartitionSpec(*self.axis_kwargs.values())
        else:
            spec = PartitionSpec(self.axis_kwargs["axis"])
            ex_spec = PartitionSpec(self.axis_kwargs["axis"])
        self._in_sharding = NamedSharding(self.mesh, spec)
        # exec-plan arrays ride into the executables as ARGUMENTS, not
        # baked constants: every leaf leads with the process axes ([P,...]
        # flat, [G,L,...] hier), so one sharding covers the whole pytree.
        # Same-pattern value refreshes then swap arrays under the compiled
        # code instead of re-lowering (see ``refresh_values``).
        self._ex_sharding = NamedSharding(self.mesh, ex_spec)
        self._ex_dev: Optional[Union[FlatExecPlan, HierExecPlan,
                                      ReplicatedExecPlan]] = None
        # B-buffer donation is only always-usable when C has B's exact
        # geometry (square operand) — skip otherwise rather than emit
        # unusable-donation warnings on every call. Sibling-kernel
        # handles skip it entirely: their executables take three
        # operands and the alias bookkeeping isn't worth the edge cases.
        # ... and replicated handles skip it too: B (lane-sharded,
        # replica-broadcast) and C (sharded over both axes) never share a
        # layout, so the alias is unusable by construction.
        self._donate = (bool(config.donate) and self.kernel == "spmm"
                        and not self.replicated
                        and plan.shape[0] == plan.shape[1])

    # ----- execution ---------------------------------------------------

    @property
    def strategy(self) -> str:
        """Chosen executor tier: 'flat', 'hier' or 'replicated'."""
        if self.replicated:
            return "replicated"
        return "hier" if self.hier is not None else "flat"

    @property
    def backends(self) -> Tuple[str, ...]:
        return self.ex.backends

    def _backend_name(self, backend: Optional[BackendSpec]) -> str:
        if backend is None:
            return self.default_backend
        return get_backend(backend).name

    def _raw_call(self, b: jax.Array, backend: str) -> jax.Array:
        """The traceable executor path (used under jit and for lowering)."""
        if self.replicated:
            return replicated_spmm(self.ex, b, self.mesh, backend=backend,
                                   **self.axis_kwargs)
        if self.hier is not None:
            return hier_spmm(self.ex, b, self.mesh, backend=backend,
                             overlap=self.overlap, **self.axis_kwargs)
        return flat_spmm(self.ex, b, self.mesh, backend=backend,
                         overlap=self.overlap, **self.axis_kwargs)

    def _raw_sddmm(self, x: jax.Array, y: jax.Array, backend: str,
                   edge: Optional[str]):
        """Traceable SDDMM path (same plan, dataflow reversed)."""
        if self.hier is not None:
            return hier_sddmm(self.ex, x, y, self.mesh, backend=backend,
                              edge=edge, **self.axis_kwargs)
        return flat_sddmm(self.ex, x, y, self.mesh, backend=backend,
                          edge=edge, **self.axis_kwargs)

    def _raw_fused(self, x: jax.Array, y: jax.Array, b: jax.Array,
                   backend: str, edge: Optional[str]) -> jax.Array:
        """Traceable FusedMM path: SDDMM -> SpMM in one comm phase."""
        if self.hier is not None:
            return hier_fused(self.ex, x, y, b, self.mesh, backend=backend,
                              edge=edge, **self.axis_kwargs)
        return flat_fused(self.ex, x, y, b, self.mesh, backend=backend,
                          edge=edge, **self.axis_kwargs)

    def _device_ex(self) -> Union[FlatExecPlan, HierExecPlan,
                                  ReplicatedExecPlan]:
        """The exec-plan pytree committed onto the mesh (lazy, cached)."""
        if self._ex_dev is None:
            self._ex_dev = jax.tree_util.tree_map(
                lambda x: jax.device_put(x, self._ex_sharding), self.ex)
        return self._ex_dev

    def _executable(self, n_cols: int, dtype, backend: str):
        key = (int(n_cols), jnp.dtype(dtype).name, backend)
        compiled = self._executables.get(key)
        if compiled is not None:
            self.cache_hits += 1
            return compiled
        if self.replicated:
            def call(ex, b):
                return replicated_spmm(ex, b, self.mesh, backend=backend,
                                       **self.axis_kwargs)
        elif self.hier is not None:
            def call(ex, b):
                return hier_spmm(ex, b, self.mesh, backend=backend,
                                 overlap=self.overlap, **self.axis_kwargs)
        else:
            def call(ex, b):
                return flat_spmm(ex, b, self.mesh, backend=backend,
                                 overlap=self.overlap, **self.axis_kwargs)
        fn = jax.jit(call, donate_argnums=(1,) if self._donate else ())
        sds = jax.ShapeDtypeStruct((self.plan.shape[1], int(n_cols)),
                                   jnp.dtype(dtype),
                                   sharding=self._in_sharding)
        compiled = fn.lower(self._device_ex(), sds).compile()
        self._compile_probe(compiled)
        return self._remember(key, compiled)

    def _compile_probe(self, compiled) -> None:
        """Compile the guard's probe of C beside C's executable, so that
        neither a first call nor a hot swap's (``warm_from``) compiles it."""
        if self._check:
            guards.compile_probe(compiled.out_info, mode=self._check)

    def _remember(self, key: Tuple[Any, ...], compiled) -> Any:
        """Cache a fresh executable + fire the lowering hooks."""
        self._executables[key] = compiled
        self._memory[key] = executable_memory(compiled)
        self.lowerings.append(key)
        for hook in list(_LOWERING_HOOKS):
            hook(self, key)
        return compiled

    def _sddmm_executable(self, n_feat: int, dtype_x, dtype_y, backend: str,
                          edge: Optional[str]):
        key = ("sddmm", int(n_feat), jnp.dtype(dtype_x).name,
               jnp.dtype(dtype_y).name, backend, edge)
        compiled = self._executables.get(key)
        if compiled is not None:
            self.cache_hits += 1
            return compiled
        if self.hier is not None:
            def call(ex, x, y):
                return hier_sddmm(ex, x, y, self.mesh, backend=backend,
                                  edge=edge, **self.axis_kwargs)
        else:
            def call(ex, x, y):
                return flat_sddmm(ex, x, y, self.mesh, backend=backend,
                                  edge=edge, **self.axis_kwargs)
        m, k = self.plan.shape
        sx = jax.ShapeDtypeStruct((m, int(n_feat)), jnp.dtype(dtype_x),
                                  sharding=self._in_sharding)
        sy = jax.ShapeDtypeStruct((k, int(n_feat)), jnp.dtype(dtype_y),
                                  sharding=self._in_sharding)
        compiled = jax.jit(call).lower(self._device_ex(), sx, sy).compile()
        return self._remember(key, compiled)

    def _fused_executable(self, n_feat: int, n_cols: int, dtype_x, dtype_y,
                          dtype_b, backend: str, edge: Optional[str]):
        key = ("fused", int(n_feat), int(n_cols), jnp.dtype(dtype_x).name,
               jnp.dtype(dtype_y).name, jnp.dtype(dtype_b).name, backend,
               edge)
        compiled = self._executables.get(key)
        if compiled is not None:
            self.cache_hits += 1
            return compiled
        if self.hier is not None:
            def call(ex, x, y, b):
                return hier_fused(ex, x, y, b, self.mesh, backend=backend,
                                  edge=edge, **self.axis_kwargs)
        else:
            def call(ex, x, y, b):
                return flat_fused(ex, x, y, b, self.mesh, backend=backend,
                                  edge=edge, **self.axis_kwargs)
        m, k = self.plan.shape
        sx = jax.ShapeDtypeStruct((m, int(n_feat)), jnp.dtype(dtype_x),
                                  sharding=self._in_sharding)
        sy = jax.ShapeDtypeStruct((k, int(n_feat)), jnp.dtype(dtype_y),
                                  sharding=self._in_sharding)
        sb = jax.ShapeDtypeStruct((k, int(n_cols)), jnp.dtype(dtype_b),
                                  sharding=self._in_sharding)
        compiled = jax.jit(call).lower(self._device_ex(), sx, sy,
                                       sb).compile()
        self._compile_probe(compiled)
        return self._remember(key, compiled)

    def _put(self, arr) -> jax.Array:
        """Commit one row-sharded dense operand onto the handle's mesh."""
        if self.topology is not None:
            return self.topology.put_global(arr, self._in_sharding)
        return jax.device_put(jnp.asarray(arr), self._in_sharding)

    def _resolve_call(self, kernel, edge) -> Tuple[str, Optional[str]]:
        """Per-call kernel/edge selection against the config defaults."""
        kern = self.kernel if kernel is None else kernel
        if kern not in _KERNELS:
            raise ValueError(
                f"kernel must be one of {_KERNELS}; got {kern!r}")
        if kern == "spmm":
            if edge is not _UNSET and edge is not None:
                raise TypeError(
                    "edge= applies to the sampled values of "
                    "kernel='sddmm'/'fused'; kernel='spmm' has none")
            return kern, None
        if self.replicated:
            raise ValueError(
                f"kernel={kern!r} has no replicated executor; this "
                f"handle was compiled with replicate="
                f"{self.decisions.get('replicate')} — recompile with "
                f"replicate=1 for sddmm/fused calls")
        edge_name = self.edge if edge is _UNSET else edge
        if edge_name is not None and edge_name not in EDGE_FNS:
            raise ValueError(
                f"edge must be None or one of {tuple(sorted(EDGE_FNS))}; "
                f"got {edge_name!r}")
        return kern, edge_name

    def __call__(self, *operands, backend: Optional[BackendSpec] = None,
                 kernel: Optional[str] = None, edge: Any = _UNSET):
        """One front door for the whole kernel family, cached per shape.

        Arity follows the (per-call overridable) kernel:

          ``h(b)``               kernel="spmm"  -> C = A @ b
          ``h(x, y)``            kernel="sddmm" -> values = A ⊙ (x yᵀ)
          ``h(x, y, b)``         kernel="fused" -> C = edge(A ⊙ (x yᵀ)) @ b

        Concrete arrays hit the AOT executable cache; calls under an
        outer trace (jit/grad) use the traceable executor path. Under
        ``config.check`` every dense operand is validated with an
        actionable error BEFORE device placement or lowering (tracers
        included — the checks are static), and the output gets a sampled
        ``isfinite`` sweep that raises ``NumericalFault`` naming the
        first bad element (or, for SDDMM's value pytree, the bad leaf).
        """
        name = self._backend_name(backend)
        kern, edge_name = self._resolve_call(kernel, edge)
        arity = {"spmm": 1, "sddmm": 2, "fused": 3}[kern]
        operand_names = {"spmm": "(B)", "sddmm": "(X, Y)",
                         "fused": "(X, Y, B)"}[kern]
        if len(operands) != arity:
            raise TypeError(
                f"kernel={kern!r} takes {arity} operand(s) "
                f"{operand_names}; got {len(operands)}")
        if kern == "sddmm":
            return self._call_sddmm(*operands, name=name, edge=edge_name)
        if kern == "fused":
            return self._call_fused(*operands, name=name, edge=edge_name)
        return self._call_spmm(operands[0], name)

    def _call_spmm(self, b, name: str) -> jax.Array:
        if _is_tracer(b):
            self._validate_b(b)
            return self._raw_call(b, name)
        # host spans (core.trace): dispatch, then with check on the wait
        # for the guard's device probe of C and the host sweep of its result
        with span("shiro.dispatch"):
            self._validate_b(b)
            b_in = b
            b = self._put(b)
            fn = self._executable(b.shape[1], b.dtype, name)
            if self._donate and isinstance(b_in, jax.Array):
                # the caller handed us a device array; placing it can hand
                # back THEIR buffer under a new Array (same device, an
                # equivalent sharding), and donating that would delete it
                # — donate a private copy instead
                b = b.copy()
            c = fn(self._device_ex(), b)
        self.calls += 1
        # chaos hook: nan_poison at site "output" models a broken
        # backend kernel — fires with or without check, exactly like the
        # real failure it stands in for
        c = faults.maybe_poison_array(c, site="output")
        if self._check:
            with span("shiro.wait"):
                # the probe depends on C, so its result is C's readiness too
                probes, read = guards.read_probes(
                    guards.probe_finite(c, mode=self._check))
            with span("shiro.guard") as guard:
                try:
                    guards.raise_nonfinite(
                        probes, mode=self._check, call_index=self.calls,
                        context=f"DistSpmm(P={self.plan.P}) backend={name!r}")
                except guards.NumericalFault:
                    self.numerical_faults += 1
                    raise
                self.guard_host_bytes += read
                guard.set_metadata(host_bytes=read)
        return c

    def _validate_b(self, b) -> None:
        if self._check:
            guards.validate_dense_operand(
                b, k_expected=self.plan.shape[1],
                context=f"DistSpmm(P={self.plan.P}) call")

    def _call_sddmm(self, x, y, *, name: str, edge: Optional[str]):
        if self._check:
            guards.validate_sddmm_operands(
                x, y, m_expected=self.plan.shape[0],
                k_expected=self.plan.shape[1],
                context=f"DistSpmm(P={self.plan.P}) sddmm call")
        if _is_tracer(x) or _is_tracer(y):
            return self._raw_sddmm(x, y, name, edge)
        x, y = self._put(x), self._put(y)
        fn = self._sddmm_executable(x.shape[1], x.dtype, y.dtype, name, edge)
        vals = fn(self._device_ex(), x, y)
        self.calls += 1
        vals = jax.tree_util.tree_map(
            lambda v: faults.maybe_poison_array(v, site="output"), vals)
        if self._check:
            try:
                self.guard_host_bytes += guards.sampled_finite_check_tree(
                    vals, mode=self._check, call_index=self.calls,
                    context=f"DistSpmm(P={self.plan.P}) sddmm "
                            f"backend={name!r}")
            except guards.NumericalFault:
                self.numerical_faults += 1
                raise
        return vals

    def _call_fused(self, x, y, b, *, name: str,
                    edge: Optional[str]) -> jax.Array:
        if self._check:
            ctx = f"DistSpmm(P={self.plan.P}) fused call"
            guards.validate_sddmm_operands(
                x, y, m_expected=self.plan.shape[0],
                k_expected=self.plan.shape[1], context=ctx)
            guards.validate_dense_operand(
                b, k_expected=self.plan.shape[1], context=ctx)
        if _is_tracer(x) or _is_tracer(y) or _is_tracer(b):
            return self._raw_fused(x, y, b, name, edge)
        x, y, b = self._put(x), self._put(y), self._put(b)
        fn = self._fused_executable(x.shape[1], b.shape[1], x.dtype,
                                    y.dtype, b.dtype, name, edge)
        c = fn(self._device_ex(), x, y, b)
        self.calls += 1
        c = faults.maybe_poison_array(c, site="output")
        if self._check:
            try:
                self.guard_host_bytes += guards.sampled_finite_check(
                    c, mode=self._check, call_index=self.calls,
                    context=f"DistSpmm(P={self.plan.P}) fused "
                            f"backend={name!r}")
            except guards.NumericalFault:
                self.numerical_faults += 1
                raise
        return c

    def warm_from(self, other: "DistSpmm") -> int:
        """Pre-lower every executable ``other`` has served.

        The hot-swap contract (``SpmmSession.replan``): the incoming
        handle compiles the outgoing handle's working set BEFORE the
        swap, so the first post-swap wave hits a warm cache instead of
        paying a lowering on the serving path. Each spmm/fused executable
        brings the guard's probe of its C (``_compile_probe``). Returns the
        number of executables warmed.
        """
        warmed = 0
        for key in list(other._executables):
            if key[0] == "sddmm":
                _, n_feat, dx, dy, backend, edge = key
                if backend not in self.ex.backends:
                    continue
                self._sddmm_executable(n_feat, dx, dy, backend, edge)
            elif key[0] == "fused":
                _, n_feat, n_cols, dx, dy, db, backend, edge = key
                if backend not in self.ex.backends:
                    continue
                self._fused_executable(n_feat, n_cols, dx, dy, db,
                                       backend, edge)
            else:
                n_cols, dtype_name, backend = key
                if backend not in self.ex.backends:
                    continue
                self._executable(n_cols, dtype_name, backend)
            warmed += 1
        return warmed

    def refresh_values(self, *, plan: SpmmPlan, hier: Optional[HierPlan],
                       schedule: CommSchedule, decisions: Dict[str, Any],
                       snapshot: Optional[PatternSnapshot]) -> bool:
        """Swap in same-pattern exec arrays, keeping compiled executables.

        The values-only half of a replan: the sparsity PATTERN (and with
        it the plan structure, schedule and layouts) is unchanged, only
        the nonzero values moved. The compiled executables take the exec
        arrays as runtime arguments, so they stay valid verbatim — this
        rebuilds the host/device exec arrays from the new plan in place
        and pays zero re-lowering. Returns False without touching the
        handle when the new plan's geometry doesn't match after all
        (caller should fall back to a full replan / hot swap).
        """
        overlap = bool(decisions.get("overlap", False))
        replicated = getattr(schedule, "kind", "") == "replicated"
        if (overlap != self.overlap
                or replicated != self.replicated
                or (hier is None) != (self.hier is None)):
            return False
        if replicated:
            new_ex = replicated_exec_arrays(schedule.rplan,
                                            backends=self.config.backends,
                                            schedule=schedule)
        elif hier is not None:
            new_ex = hier_exec_arrays(hier, backends=self.config.backends,
                                      schedule=schedule,
                                      overlap_layouts=overlap)
        else:
            new_ex = flat_exec_arrays(plan, backends=self.config.backends,
                                      schedule=schedule,
                                      overlap_layouts=overlap)
        old_leaves = jax.tree_util.tree_leaves(self.ex)
        new_leaves = jax.tree_util.tree_leaves(new_ex)
        if (new_ex.backends != self.ex.backends
                or len(old_leaves) != len(new_leaves)
                or any(o.shape != n.shape or o.dtype != n.dtype
                       for o, n in zip(old_leaves, new_leaves))):
            return False
        self.plan, self.hier, self.schedule = plan, hier, schedule
        self.decisions = dict(decisions)
        self.ex = new_ex
        self._ex_dev = None  # re-placed lazily; executables stay cached
        self.snapshot = snapshot
        self.last_drift = 0.0
        self.values_refreshes += 1
        return True

    def lowered_hlo(self, n_cols: Optional[int] = None, dtype=jnp.float32,
                    backend: Optional[BackendSpec] = None, *,
                    kernel: Optional[str] = None, n_feat: Optional[int] = None,
                    edge: Any = _UNSET) -> str:
        """Optimized HLO of the (cached) executable for one call shape.

        ``kernel=`` selects the family (default: the config's);
        ``n_feat`` is the F width of the dense X/Y operands for
        sddmm/fused, ``n_cols`` the B width for spmm/fused — both
        default to ``config.n_dense_hint``. Source metadata is stripped
        (``hlo_analysis.strip_metadata``), so identical programs give
        identical text whatever call site lowered them.
        """
        kern, edge_name = self._resolve_call(kernel, edge)
        n = int(n_cols if n_cols is not None else self.config.n_dense_hint)
        f = int(n_feat if n_feat is not None else self.config.n_dense_hint)
        name = self._backend_name(backend)
        if kern == "sddmm":
            compiled = self._sddmm_executable(f, dtype, dtype, name, edge_name)
        elif kern == "fused":
            compiled = self._fused_executable(f, n, dtype, dtype, dtype, name,
                                              edge_name)
        else:
            compiled = self._executable(n, dtype, name)
        return strip_metadata(compiled.as_text())

    # ----- introspection ----------------------------------------------

    def cache_info(self) -> Dict[str, Any]:
        return {"lowerings": len(self.lowerings),
                "hits": self.cache_hits,
                "keys": tuple(self.lowerings)}

    def drift(self, a_new) -> float:
        """Pattern drift of ``a_new`` vs the planned snapshot (Jaccard
        distance in [0, 1]); recorded so ``stats()`` and BENCH records
        carry the last observed value."""
        if self.snapshot is None:
            raise ValueError(
                "this handle carries no pattern snapshot (plan saved by "
                "an older version); recompile with compile_spmm to "
                "enable drift detection")
        self.last_drift = self.snapshot.drift(a_new)
        return self.last_drift

    def stats(self) -> Dict[str, Any]:
        """Autotune decisions + analytic/padded volumes + cache state."""
        plan = self.plan
        sched = self.schedule
        out: Dict[str, Any] = dict(self.decisions)
        out.update(
            kernel=self.kernel,
            edge=self.edge,
            strategy=self.strategy,
            plan_strategy=plan.strategy,
            P=plan.P,
            shape=plan.shape,
            backends=self.backends,
            default_backend=self.default_backend,
            schedule_kind=sched.kind,
            schedule_K=sched.K if sched.kind == "bucketed" else 1,
            overlap=self.overlap,
            volume_rows=plan.volume_rows(),
            volume_rows_padded=sched.volume_rows_padded(),
            cache=self.cache_info(),
            drift=self.last_drift,
            drift_threshold=self.config.drift_threshold,
            donated_buffers=("b",) if self._donate else (),
            values_refreshes=self.values_refreshes,
            check=self._check,
            calls=self.calls,
            guard_host_bytes=self.guard_host_bytes,
            numerical_faults=self.numerical_faults,
        )
        # the coo product's row fold, over every prepared piece of the
        # default backend (per-round segments too): the share of their
        # stored nonzeros it reduces, and its slots over those nonzeros
        stored, folded, slots = (sum(c) for c in zip(*(
            fold_counts(p)
            for p in self.ex.pieces[self.default_backend].values())))
        out["fold_share"] = folded / stored if stored else None
        out["fold_pad"] = slots / folded if folded else None
        out.setdefault("decision_source", "model")
        out.setdefault("measured_time", None)
        out.setdefault("replicate", 1)
        if self.replicated:
            # plan.P is the lane width s; the handle spans c·s devices
            out.update(P=sched.P, replicate=sched.c, replica_shards=sched.s,
                       schedule_K=sched.K)
        # prefer what the compiled executables actually pin over the
        # profiling-time record riding in ``decisions``
        mem = [m["total_allocation_size"] for m in self._memory.values()
               if m.get("total_allocation_size")]
        out["total_allocation_size"] = (
            max(mem) if mem else self.decisions.get("total_allocation_size"))
        if self.snapshot is not None:
            out["pattern_nnz"] = self.snapshot.nnz
            out["pattern_fingerprint"] = self.snapshot.fingerprint[:12]
        if self.topology is not None:
            out["topology"] = self.topology.describe()
        if self.hier is not None:
            out.update(G=self.hier.G, L=self.hier.L,
                       volume_rows_padded_single=single_round_hier_schedule(
                           self.hier).volume_rows_padded())
        else:
            out["volume_rows_padded_single"] = plan.volume_rows_padded()
        return out

    def __repr__(self) -> str:
        sched = self.schedule
        if self.replicated:
            tier = f"replicated(c={sched.c},s={sched.s})"
        elif self.hier is not None:
            tier = f"hier(G={self.hier.G},L={self.hier.L})"
        else:
            tier = "flat"
        P = sched.P if self.replicated else self.plan.P
        return (f"DistSpmm({self.plan.shape[0]}x{self.plan.shape[1]}, "
                f"P={P}, {tier}, schedule={sched.kind}"
                f"{f'/K={sched.K}' if sched.kind == 'bucketed' else ''}"
                f"{', overlapped' if self.overlap else ''}"
                f"{f', kernel={self.kernel}' if self.kernel != 'spmm' else ''}"
                f", backends={self.backends})")

    # ----- serialization ----------------------------------------------

    def save(self, path: str) -> None:
        """Persist the host-side plan (NumPy only — no device state).

        The file carries the offline planning results (SpmmPlan / HierPlan
        / chosen CommSchedule / decisions); device arrays and executables
        are rebuilt deterministically by ``load``, so loading is cheap and
        never re-runs MWVC.

        The container is a pickle: ``load`` only plans shipped over a
        trusted channel (your own artifact store / image), exactly like
        model checkpoints — unpickling attacker-controlled files executes
        arbitrary code.
        """
        with open(path, "wb") as f:
            pickle.dump(self.save_payload(), f)

    def save_payload(self) -> Dict[str, Any]:
        """The versioned host-side dict ``save`` pickles (also the
        per-rung unit ``SpmmSession.save`` bundles)."""
        return {
            "format": _SAVE_FORMAT,
            "version": _SAVE_VERSION,
            "config": self.config,
            "plan": self.plan,
            "hier": self.hier,
            "schedule": self.schedule,
            "decisions": self.decisions,
            "snapshot": self.snapshot,
        }

    @classmethod
    def load(cls, path: str,
             where: Union[Topology, Mesh, int, None] = None) -> "DistSpmm":
        """Rebuild a handle from ``save`` output on this process.

        ``where`` is anything ``Topology.resolve`` accepts — a Topology,
        a Mesh (any axis layout), an int P, or None (every local
        device). The only requirement is a device count matching the
        plan's P; mismatches raise here, with the counts, instead of
        surfacing as a shard_map shape error deep in the first call.

        TRUSTED INPUT ONLY: the file is a pickle (see ``save``) — load
        plans from your own fleet's artifact channel, never from
        untrusted sources.
        """
        if os.path.getsize(path) == 0:
            raise ValueError(
                f"{path!r} is empty (0 bytes) — the save was torn "
                f"mid-write or the copy never completed; re-fetch the "
                f"plan or re-run compile_spmm(...).save().")
        try:
            with open(path, "rb") as f:
                payload = pickle.load(f)
        except (EOFError, pickle.UnpicklingError) as e:
            raise ValueError(
                f"{path!r} is not a complete saved DistSpmm plan "
                f"({type(e).__name__}: {e}) — the file was truncated or "
                f"corrupted in transit; re-fetch it or re-run "
                f"compile_spmm(...).save().") from None
        if payload.get("format") != _SAVE_FORMAT:
            raise ValueError(f"{path!r} is not a saved DistSpmm handle")
        return materialize_payload(payload, where, source=path)


def check_payload_version(payload: Dict[str, Any], source: str) -> None:
    """Reject plan payloads this library version cannot rebuild."""
    version = payload.get("version")
    if version not in _KNOWN_VERSIONS:
        raise ValueError(
            f"{source!r} carries DistSpmm plan format version {version!r}; "
            f"this library understands versions {_KNOWN_VERSIONS}. The "
            f"plan was saved by a different library version — re-run "
            f"compile_spmm(...).save() (or SpmmSession.save) with the "
            f"version that will load it; plans are cheap to regenerate "
            f"from the operand matrix.")


def materialize_payload(payload: Dict[str, Any],
                        where: Union[Topology, Mesh, int, None],
                        source: str = "<payload>") -> "DistSpmm":
    """Version-check + topology-check + device prep for a saved plan."""
    check_payload_version(payload, source)
    plan: SpmmPlan = payload["plan"]
    schedule = payload["schedule"]
    # a replicated rung's plan slot holds the s-shard base plan; the
    # rung itself spans schedule.P = c·s devices
    want_p = (schedule.P
              if getattr(schedule, "kind", "") == "replicated" else plan.P)
    topo = Topology.resolve(want_p if where is None else where)
    if topo.P != want_p:
        raise ValueError(
            f"{source!r} was planned for P={want_p} processes but the "
            f"given topology has P={topo.P} devices ({topo.kind}); pass "
            f"any Topology/mesh with exactly {want_p} devices, or "
            f"re-plan for P={topo.P} (SpmmSession ladders pre-plan "
            f"multiple P rungs for exactly this).")
    return _materialize(payload["config"], plan, payload["hier"],
                        payload["schedule"], payload["decisions"], topo,
                        snapshot=payload.get("snapshot"))


# ---------------------------------------------------------------------------
# compilation pipeline
# ---------------------------------------------------------------------------


def _materialize(config: SpmmConfig, plan: SpmmPlan,
                 hier: Optional[HierPlan], schedule: CommSchedule,
                 decisions: Dict[str, Any], topo: Topology,
                 snapshot: Optional[PatternSnapshot] = None) -> DistSpmm:
    """Deterministic device-side prep: exec arrays + mesh + handle."""
    # only materialize the per-round consumable layouts when the
    # autotuned decision actually executes overlapped
    overlap = bool(decisions.get("overlap", False))
    if getattr(schedule, "kind", "") == "replicated":
        m, ra, ax = topo.replicated_mesh(schedule.c, schedule.s)
        ex = replicated_exec_arrays(schedule.rplan, backends=config.backends,
                                    schedule=schedule)
        axis_kwargs = {"replica_axis": ra, "axis": ax}
    elif hier is not None:
        m, ga, la = topo.hier_mesh(hier.G, hier.L)
        ex = hier_exec_arrays(hier, backends=config.backends,
                              schedule=schedule, overlap_layouts=overlap)
        axis_kwargs = {"group_axis": ga, "local_axis": la}
    else:
        m, ax = topo.flat_mesh()
        ex = flat_exec_arrays(plan, backends=config.backends,
                              schedule=schedule, overlap_layouts=overlap)
        axis_kwargs = {"axis": ax}
    return DistSpmm(config=config, plan=plan, hier=hier, schedule=schedule,
                    ex=ex, mesh=m, axis_kwargs=axis_kwargs,
                    decisions=decisions, snapshot=snapshot, topology=topo)


def _candidate_schedule(plan: SpmmPlan, hier: Optional[HierPlan],
                        kind: str, K: Optional[int]) -> CommSchedule:
    """Deterministically (re)build one candidate's schedule object.

    Shared between the model sweep and ``core.autotune`` — a cached
    measured decision replays through here, so cache hits reproduce the
    exact schedule the profiled run used.
    """
    if hier is not None:
        return (single_round_hier_schedule(hier) if kind == "single"
                else build_hier_comm_schedule(hier, K=int(K)))
    return (single_round_schedule(plan) if kind == "single"
            else build_comm_schedule(plan, K=int(K)))


def _schedule_fields(plan: SpmmPlan, hier: Optional[HierPlan],
                     schedule: CommSchedule, n_hint: int,
                     net: NetworkSpec) -> Dict[str, float]:
    """The three modeled-time decision fields for one candidate."""
    if hier is not None:
        return {
            "modeled_time_schedule": modeled_time_hier_schedule(
                schedule, n_hint, net),
            "modeled_time_staged": modeled_time_hier_staged(
                hier, schedule, n_hint, net),
            "modeled_time_overlap": modeled_time_hier_overlap(
                hier, schedule, n_hint, net),
        }
    return {
        "modeled_time_schedule": modeled_time_schedule(
            plan, schedule, n_hint, net),
        "modeled_time_staged": modeled_time_staged(
            plan, schedule, n_hint, net),
        "modeled_time_overlap": modeled_time_overlap(
            plan, schedule, n_hint, net),
    }


def _plan_and_tune(a: CSRMatrix, P: int, config: SpmmConfig,
                   topo: Topology) -> Tuple[SpmmPlan, Optional[HierPlan],
                                            CommSchedule, Dict[str, Any]]:
    """The offline pipeline: MWVC plan + every autotune decision.

    Pure host-side work — no devices are touched, so ladder rungs can be
    planned for P values the current fleet doesn't have, and replans run
    off the serving path. ``topo`` only informs the model (net="auto"
    derivation, intrinsic hier grouping), never device placement.
    """
    net, n_hint = config.resolve_net(topo), config.n_dense_hint
    kernel = getattr(config, "kernel", "spmm")

    plan = build_plan(a, P, config.strategy, pad_to=config.pad_to)
    decisions: Dict[str, Any] = {
        "kernel": kernel,
        "net": net.name,
        "net_source": "topology" if config.net == "auto" else "config",
        "n_dense_hint": n_hint,
        "modeled_time_flat": modeled_time(plan, n_hint, net),
    }

    # ----- flat vs hierarchical ---------------------------------------
    hier: Optional[HierPlan] = None
    hier_cand: Optional[HierPlan] = None
    if config.hier is not None:
        if config.hier == "auto":
            gl = (topo.auto_grouping(net) if topo.P == P
                  else _ladder_grouping(P, net))
        else:
            gl = (int(config.hier[0]), int(config.hier[1]))
        if gl is not None:
            G, L = gl
            if G * L != P:
                raise ValueError(f"hier=({G},{L}) incompatible with P={P}")
            hier_cand = build_hier_plan(plan, G, L, pad_to=config.pad_to)
            t_hier = modeled_time_hier(hier_cand, n_hint, net)
            decisions["modeled_time_hier"] = t_hier
            decisions["hier_candidate"] = (G, L)
            if config.hier != "auto" or \
                    t_hier < decisions["modeled_time_flat"]:
                hier = hier_cand

    # ----- communication schedule + execution mode --------------------
    # The "auto" schedule sweep co-optimizes K with the execution mode
    # (overlap hides padded bytes behind segment compute, shifting which
    # K wins); explicit schedules still get the mode decision below.
    # Sibling kernels score differently: "fused" moves [Y|B] jointly
    # (width F+N) plus the reversed X rounds, so its own α-β functions
    # pick K; "sddmm" moves the same rows as spmm at width F and always
    # executes staged, so the overlap-free sweep applies. n_dense_hint
    # stands in for both F and N.
    if hier is not None:
        if config.schedule == "single":
            schedule = single_round_hier_schedule(hier)
        elif isinstance(config.schedule, int):
            schedule = build_hier_comm_schedule(hier, K=config.schedule)
        elif kernel == "fused":
            schedule, _ = choose_hier_fused_schedule(hier, n_hint, n_hint,
                                                     net, k_max=config.k_max)
        elif kernel == "sddmm" or config.overlap is False:
            schedule, _ = choose_hier_schedule(hier, n_hint, net,
                                               k_max=config.k_max)
        else:
            schedule, _, _ = choose_hier_schedule(hier, n_hint, net,
                                                  k_max=config.k_max,
                                                  overlap=config.overlap)
    else:
        if config.schedule == "single":
            schedule = single_round_schedule(plan)
        elif isinstance(config.schedule, int):
            schedule = build_comm_schedule(plan, K=config.schedule)
        elif kernel == "fused":
            schedule, _ = choose_fused_schedule(plan, n_hint, n_hint, net,
                                                k_max=config.k_max)
        elif kernel == "sddmm" or config.overlap is False:
            schedule, _ = choose_schedule(plan, n_hint, net,
                                          k_max=config.k_max)
        else:
            schedule, _, _ = choose_schedule(plan, n_hint, net,
                                             k_max=config.k_max,
                                             overlap=config.overlap)

    fields = _schedule_fields(plan, hier, schedule, n_hint, net)
    decisions.update(fields)
    if kernel == "fused":
        decisions["modeled_time_fused"] = (
            modeled_time_hier_fused_schedule(schedule, n_hint, n_hint, net)
            if hier is not None
            else modeled_time_fused_schedule(plan, schedule, n_hint,
                                             n_hint, net))
    use_overlap = False
    if schedule.kind == "bucketed" and kernel == "spmm":
        if config.overlap is True:
            use_overlap = True
        elif config.overlap == "auto":
            use_overlap = (fields["modeled_time_overlap"]
                           < fields["modeled_time_staged"])
    decisions["overlap"] = use_overlap
    decisions["decision_source"] = "model"

    # ----- replication (1.5D): c lanes of s = P/c shards --------------
    # The only strategy that changes the mesh shape itself: B is
    # replicated across c lanes, each lane exchanges only its subset of
    # the s-shard shifts over the FAST s-device tier, and the partial C
    # pays one replica-axis reduce-scatter. Wins at high P where the
    # flat/hier exchange spans the slow tier but s <= group_size stays
    # on the fast one.
    decisions["replicate"] = 1
    replicate = getattr(config, "replicate", 1)
    if kernel == "spmm" and replicate != 1:
        # modeled_time_replicated includes the diagonal-block compute
        # that the staged/overlap fields exclude (their docstrings: it
        # is common to both execution MODES) — add the same term to the
        # unreplicated side so the cross-tier comparison is offset-free
        diag = (max(blk.nnz for blk in plan.a_diag) * 2.0 * n_hint / 1e12
                if plan.a_diag else 0.0)
        t_base = (fields["modeled_time_overlap"] if use_overlap
                  else fields["modeled_time_staged"]) + diag
        budget = (int(config.memory_budget)
                  if config.memory_budget is not None else None)
        cands = (2, 4, 8) if replicate == "auto" else (int(replicate),)
        best: Optional[Tuple[float, int, ReplicatedSchedule]] = None
        infeasible: Dict[int, str] = {}
        for c in cands:
            if P % c or P // c < 2:
                infeasible[c] = f"needs c | P={P} with s = P/c >= 2"
                continue
            s = P // c
            base = build_plan(a, s, config.strategy, pad_to=config.pad_to)
            sizes = {hi - lo for lo, hi in base.bounds}
            m_local = sizes.pop() if len(sizes) == 1 else None
            if m_local is None or m_local % c or base.shape[1] % s:
                infeasible[c] = (
                    f"needs uniform s={s}-way row/col blocks with "
                    f"c={c} | m_local for the tiled replica "
                    f"reduce-scatter (pad M and K first)")
                continue
            rp = replicate_plan(base, c)
            rsched = build_replicated_schedule(rp)
            # the budget prunes only the AUTO sweep (pick a c that
            # fits); a forced c rides through and lets the session's
            # rung filter skip it with the footprint on record
            if replicate == "auto" and budget is not None:
                need = replicated_device_bytes(rp, rsched, n_hint)
                if need > budget:
                    infeasible[c] = (f"replica footprint {need} B/device "
                                     f"exceeds memory_budget {budget}")
                    continue
            t_rep = modeled_time_replicated(rp, rsched, n_hint, net)
            decisions[f"modeled_time_replicated_c{c}"] = t_rep
            if best is None or t_rep < best[0]:
                best = (t_rep, c, rsched)
        if best is None and replicate != "auto":
            c = int(replicate)
            raise ValueError(
                f"replicate={c} is infeasible: "
                f"{infeasible.get(c, 'no candidate survived')}")
        if best is not None and (replicate != "auto" or best[0] < t_base):
            t_rep, c, rsched = best
            plan = rsched.rplan.base
            hier = None
            schedule = rsched
            use_overlap = False
            decisions["overlap"] = False
            decisions["replicate"] = c
            decisions["modeled_time_replicated"] = t_rep
            decisions["modeled_time_unreplicated"] = t_base

    # ----- measured overlay (timed profiling / on-disk cache) ---------
    # Only when measurement is enabled AND the plan targets THIS
    # substrate: a ladder rung with P != topo.P has no devices to time
    # on, and multi-controller fleets can't profile from one process.
    # The profiler drives spmm calls, so sibling kernels stay model-only.
    from . import autotune as _autotune

    # (replicated rungs stay model-only: the profiler drives the
    # flat/hier candidate set, and the replica decision is already a
    # cross-tier model comparison)
    if (kernel == "spmm" and _autotune.measurement_enabled(config)
            and decisions.get("replicate", 1) == 1
            and topo.P == P and not topo.is_multiprocess):
        plan, hier, schedule, decisions = _autotune.measured_decide(
            a, P, config, topo, plan=plan, hier=hier,
            hier_cand=hier_cand, schedule=schedule, decisions=decisions)

    return plan, hier, schedule, decisions


def _ladder_grouping(P: int, net: NetworkSpec) -> Optional[Tuple[int, int]]:
    """hier="auto" grouping for a ladder rung whose P differs from the
    topology's — the substrate's intrinsic tiers don't transfer, so only
    the structureless fallback sweep applies."""
    from ..distributed.topology import fallback_grouping

    return fallback_grouping(P, int(net.group_size))


def compile_spmm(a: CSRMatrix, where: Union[Topology, Mesh, int, None] = None,
                 config: Optional[SpmmConfig] = None,
                 **overrides) -> DistSpmm:
    """Plan, autotune and prepare a distributed SpMM handle for ``a``.

    ``where``: anything ``Topology.resolve`` accepts — a ``Topology``, a
    ``jax.sharding.Mesh`` (any axis layout — the handle re-axes its
    devices as needed), an int P (first P local devices) or None (every
    local device). ``config`` fields can also be passed as keyword
    overrides: ``compile_spmm(a, 8, backends=("coo", "bsr"),
    hier="auto")``.

    This is the thin one-rung form of ``SpmmSession``: the session it
    builds owns exactly one ladder rung at the topology's P and is
    discarded after handing out its handle. Keep the session instead
    (``SpmmSession.build``) when the pattern drifts or the fleet
    resizes.
    """
    from .session import SpmmSession

    return SpmmSession.build(a, where, config, **overrides).handle()


def compile_sddmm(a: CSRMatrix,
                  where: Union[Topology, Mesh, int, None] = None,
                  config: Optional[SpmmConfig] = None,
                  **overrides) -> DistSpmm:
    """``compile_spmm`` with ``kernel="sddmm"``: the handle's calls take
    the two dense operands and return A-patterned sampled values,
    ``h(x, y) = A ⊙ (x yᵀ)``, through the same autotuned plan."""
    overrides.setdefault("kernel", "sddmm")
    return compile_spmm(a, where, config, **overrides)


def compile_fused(a: CSRMatrix,
                  where: Union[Topology, Mesh, int, None] = None,
                  config: Optional[SpmmConfig] = None,
                  **overrides) -> DistSpmm:
    """``compile_spmm`` with ``kernel="fused"``: FusedMM handles —
    ``h(x, y, b) = edge(A ⊙ (x yᵀ)) @ b`` with the SDDMM and SpMM
    phases chained through ONE set of collectives (the B/Y gather rides
    the same rounds, width F+N)."""
    overrides.setdefault("kernel", "fused")
    return compile_spmm(a, where, config, **overrides)


# ---------------------------------------------------------------------------
# model-facing closure (migrated from models.gnn)
# ---------------------------------------------------------------------------


def make_spmm_fn(ex: Union[DistSpmm, FlatExecPlan, HierExecPlan],
                 mesh: Optional[Mesh] = None,
                 backend: Optional[BackendSpec] = None,
                 **axis_kwargs) -> Callable[[jax.Array], jax.Array]:
    """Close a SHIRO executor over its plan for model code (``H -> Â·H``).

    Preferred form: pass a ``DistSpmm`` handle (no mesh needed — the
    handle owns it); inside a jitted training step the closure traces the
    executor, eagerly it reuses the handle's executable cache. The raw
    ``FlatExecPlan`` / ``HierExecPlan`` forms remain for low-level code
    and need the ``mesh`` (plus optional ``axis=`` / ``group_axis=`` /
    ``local_axis=`` overrides).
    """
    if isinstance(ex, DistSpmm):
        if axis_kwargs:
            raise TypeError("axis overrides don't apply to a DistSpmm "
                            "handle; it owns its mesh axes")
        return lambda h: ex(h, backend=backend)
    if mesh is None:
        raise TypeError("mesh is required when passing a raw exec plan")
    if isinstance(ex, HierExecPlan):
        return lambda h: hier_spmm(ex, h, mesh, backend=backend,
                                   **axis_kwargs)
    return lambda h: flat_spmm(ex, h, mesh, backend=backend, **axis_kwargs)
