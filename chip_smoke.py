"""Run SHIRO's distributed SpMM end to end on a TPU and check its results.

    python chip_smoke.py              # one chip: phases (a)-(e)
    python chip_smoke.py --chips 4    # four local chips: the P=4 handles only

The operand has the size of ogbn-arxiv (Hu et al. 2020, "Open Graph
Benchmark"): 169,343 nodes, 1,166,243 directed edges, 128 features,
40 classes. The graph is made from ``--seed`` with power-law degrees
and GCN-normalized (``models.gnn.normalize_adjacency``); B is float32.

One chip:
  (a) ``compile_spmm(a, 1)`` with the default config, a few ``h(b)``
      calls, C checked against a float32 segment-sum on the same chip;
  (b) a few GCN training steps through ``make_spmm_fn(handle)``;
  (c) the Pallas kernels: the ``bsr`` backend and one ``kernel="fused"``
      call (the GAT edge) on a uniform graph with arxiv's rows and half
      its edges, whose ELL layout fits, and the executor's row gather /
      sorted scatter-add;
  (d) the platform, kernel path and ``tpu_custom_call`` checks;
  (e) device kind, count, peak device bytes and per-phase wall times.
``--chips 4``: flat single-round, flat auto-scheduled with overlap, and
hier (2, 2) handles over ``Topology.local(4)`` in this one process.

The last line of stdout is ``{"ok": true, "device": {...}}``. Any failed
check or phase, a platform other than TPU, ``REPRO_PALLAS_INTERPRET``
set, or a skipped autotune candidate exits non-zero before it. Wall
times printed here are smoke times, not benchmark metrics.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
import warnings
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec  # noqa: E402

from repro.core.api import compile_spmm, make_spmm_fn  # noqa: E402
from repro.core.local_backend import get_backend  # noqa: E402
from repro.core.dist_sddmm import EDGE_FNS  # noqa: E402
from repro.core.sparse import (  # noqa: E402
    CSRMatrix, coo_from_arrays, csr_from_coo, ell_bytes, power_law_graph,
)
from repro.distributed.topology import Topology  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models.gnn import GCN, gcn_loss, normalize_adjacency  # noqa: E402

ARXIV_NODES = 169_343
ARXIV_EDGES = 1_166_243
ARXIV_FEATURES = 128
ARXIV_CLASSES = 40
# Zipf rank exponent of both endpoints' degrees: a degree density that
# falls as k ** -(1 + 1 / alpha) ~ k ** -2.4, the range reported for
# citation graphs.
POWER_LAW_ALPHA = 0.7
BSR_BLOCK = (8, 8)  # BsrBackend's default block
# The (c) operand: uniform degrees, arxiv's rows, half its edges. A TPU
# pads each (8, 8) f32 block to (8, 128) lanes, and the fused call at
# arxiv's full edge count needs 16.07 GB of the chip's 15.75 GB (TPU
# compiler, v5e); at half it needs 11.1 GB.
PALLAS_EDGE_FRACTION = 2
# f32 sums of the same products in another order, over rows of up to a
# few thousand terms of magnitude <= 1: far inside 1e-4.
RTOL = ATOL = 1e-4


def say(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def timed(times: dict, name: str):
    """Record the wall seconds of the ``with`` body in ``times[name]``."""
    t0 = time.perf_counter()
    yield
    times[name] = time.perf_counter() - t0


# ---------------------------------------------------------------------------
# operands and the plain reference
# ---------------------------------------------------------------------------


def make_graph(n: int, nnz: int, alpha: float, seed: int) -> CSRMatrix:
    """GCN operand: a seeded ``nnz``-edge graph, normalized with self loops."""
    g = power_law_graph(n, nnz, alpha=alpha, seed=seed)
    if abs(g.nnz - nnz) > 0.01 * nnz:
        raise AssertionError(f"graph has {g.nnz} edges, not within 1% of {nnz}")
    return normalize_adjacency(g)


def operand_lines(a: CSRMatrix, n_cols: int) -> list:
    m, k = a.shape
    return [
        f"operand: {m} x {k}, nnz {a.nnz} ({a.nnz - m} edges + {m} self loops), "
        f"N = {n_cols} float32",
        f"bytes: B {k * n_cols * 4}  C {m * n_cols * 4}  A as COO (row, col, val) {a.nnz * 12}",
    ]


@jax.jit
def _segment_spmm(row, col, val, b):
    return jax.ops.segment_sum(val[:, None] * b[col], row, num_segments=b.shape[0])


def reference_spmm(a: CSRMatrix, b) -> jax.Array:
    """``A @ b`` as a float32 segment sum over A's nonzeros (square A)."""
    coo = a.to_coo()
    return _segment_spmm(jnp.asarray(coo.row), jnp.asarray(coo.col),
                         jnp.asarray(coo.val), jnp.asarray(b))


def reference_fused(a: CSRMatrix, q, k, v, edge: str) -> jax.Array:
    """``edge(A ⊙ (q kᵀ)) @ v`` over A's nonzeros, float32."""
    coo = a.to_coo()
    row, col = jnp.asarray(coo.row), jnp.asarray(coo.col)
    s = jnp.asarray(coo.val) * jnp.sum(q[row] * k[col], axis=1)
    e = EDGE_FNS[edge](s)
    return jax.ops.segment_sum(e[:, None] * v[col], row, num_segments=q.shape[0])


def check_close(name: str, got, want, rtol: float = RTOL, atol: float = ATOL) -> str:
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    if not np.isfinite(got).all():
        raise AssertionError(f"{name}: non-finite values in the result")
    np.testing.assert_allclose(got, want, rtol=rtol, atol=atol, err_msg=name)
    err = float(np.max(np.abs(got - want))) if got.size else 0.0
    return f"{name}: matches reference (max |diff| {err:.3e}, rtol {rtol}, atol {atol})"


def ell_line(name: str, a: CSRMatrix) -> str:
    return (f"bsr ELL layout of {name} ({a.nnz} nnz, {BSR_BLOCK} f32 blocks): "
            f"{ell_bytes(a, BSR_BLOCK, (1, 1))} bytes dense, "
            f"{ell_bytes(a, BSR_BLOCK)} bytes in (8, 128) device tiles")


def dense(n: int, width: int, seed: int) -> np.ndarray:
    return np.random.default_rng(seed).standard_normal((n, width)).astype(np.float32)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_spmm(a: CSRMatrix, b: np.ndarray, calls: int) -> dict:
    """(a) the default front door at P=1: compile, serve, check C."""
    times: dict = {}
    with timed(times, "compile"):
        h = compile_spmm(a, 1)
    b_dev = jnp.asarray(b)
    for i in range(calls):
        with timed(times, f"call{i}"):
            c = h(b_dev)
            c.block_until_ready()
    want = reference_spmm(a, b_dev)
    lines = [f"handle: {h!r}", check_close("spmm C (coo, P=1)", c, want)]
    return {"handle": h, "times": times, "lines": lines}


def phase_gcn(h, n_feat: int, n_classes: int, steps: int, seed: int) -> dict:
    """(b) GCN training steps through ``make_spmm_fn(handle)``."""
    n = h.plan.shape[0]
    model = GCN(n_nodes=n, feat_dim=n_feat, hidden=n_feat, n_classes=n_classes)
    key_p, key_x, key_y = jax.random.split(jax.random.PRNGKey(seed), 3)
    # committed to the handle's mesh up front, as the updated params come
    # back: the second step then reuses the first step's executable
    params, feats, labels = jax.device_put(
        (model.init(key_p),
         jax.random.normal(key_x, (n, n_feat), jnp.float32),
         jax.random.randint(key_y, (n,), 0, n_classes)),
        NamedSharding(h.mesh, PartitionSpec()))
    spmm_fn = make_spmm_fn(h)

    @jax.jit
    def step(p):
        loss, g = jax.value_and_grad(gcn_loss)(p, feats, labels, spmm_fn)
        return loss, jax.tree_util.tree_map(lambda w, dw: w - 0.1 * dw, p, g)

    times: dict = {}
    losses = []
    for i in range(steps):
        with timed(times, f"step{i}"):
            loss, params = step(params)
            loss = float(loss)
        if not np.isfinite(loss):
            raise AssertionError(f"GCN step {i}: loss {loss} is not finite")
        losses.append(loss)
    return {"times": times, "losses": losses,
            "lines": [f"gcn losses: {' '.join(f'{x:.6f}' for x in losses)}"]}


def phase_pallas(a: CSRMatrix, n_cols: int, n_feat: int, seed: int) -> dict:
    """(c) bsr backend, the fused GAT edge, and the executor row kernels.

    ``a`` must be a pattern whose ELL layout fits the device: its bytes
    are reckoned first and reported.
    """
    n = a.shape[0]
    times: dict = {}
    lines = [ell_line("this operand", a)]
    with timed(times, "compile"):
        h = compile_spmm(a, 1, backends=("coo", "bsr"))
    b = jnp.asarray(dense(n, n_cols, seed + 1))
    with timed(times, "bsr_call"):
        c = h(b, backend="bsr")
        c.block_until_ready()
    lines.append(check_close("spmm C (bsr, P=1)", c, reference_spmm(a, b)))

    q, k, v = (jnp.asarray(dense(n, w, seed + 2 + i))
               for i, w in enumerate((n_feat, n_feat, n_cols)))
    with timed(times, "fused_call"):
        f = h(q, k, v, kernel="fused", backend="bsr", edge="leaky_relu")
        f.block_until_ready()
    lines.append(check_close("fused leaky_relu(A*(QK^T)) @ V (bsr, P=1)", f,
                             reference_fused(a, q, k, v, "leaky_relu")))

    # the executor's send-buffer pack and partial-C aggregation: P=1
    # handles never exchange rows, so drive the two ops directly
    rng = np.random.default_rng(seed + 5)
    slots = n
    idx = rng.integers(-1, n, slots).astype(np.int32)
    tgt = rng.integers(-1, n, slots).astype(np.int32)
    parts = jnp.asarray(dense(slots, n_cols, seed + 6))
    perm, meta = ops.prepare_sorted_scatter(tgt)
    row_args = (jnp.asarray(tgt), jnp.asarray(perm), jnp.asarray(meta))
    with timed(times, "row_kernels"):
        packed = ops.pack_rows_op(b, jnp.asarray(idx))
        scattered = ops.scatter_add_rows_exec_op(c, parts, *row_args)
        jax.block_until_ready((packed, scattered))
    lines.append(check_close("row gather (pack_rows_op)", packed,
                             kref.gather_rows_ref(b, jnp.asarray(idx))))
    lines.append(check_close("row scatter-add (scatter_add_rows_exec_op)", scattered,
                             kref.scatter_add_rows_ref(c, parts, jnp.asarray(tgt))))

    hlo = {
        "bsr": h.lowered_hlo(n_cols, backend="bsr"),
        "fused": h.lowered_hlo(n_cols, backend="bsr", kernel="fused",
                               n_feat=n_feat, edge="leaky_relu"),
        "row_kernels": jax.jit(
            lambda b_, i_, c_, p_, t_, pm_, mt_: (
                ops.pack_rows_op(b_, i_),
                ops.scatter_add_rows_exec_op(c_, p_, t_, pm_, mt_))
        ).lower(b, jnp.asarray(idx), c, parts, *row_args).compile().as_text(),
    }
    interpret = {be.name: be.resolve_interpret()
                 for be in map(get_backend, h.config.backends)
                 if hasattr(be, "resolve_interpret")}
    return {"handle": h, "times": times, "lines": lines, "hlo": hlo,
            "interpret": interpret}


MULTICHIP_HANDLES = (
    ("flat single", dict(schedule="single", overlap=False)),
    ("flat auto+overlap", dict(schedule="auto", overlap=True)),
    ("hier (2,2)", dict(hier=(2, 2))),
)
DECISION_KEYS = ("strategy", "schedule_kind", "schedule_K", "overlap",
                 "default_backend", "volume_rows", "volume_rows_padded")


def phase_multichip(a: CSRMatrix, b: np.ndarray, P: int) -> dict:
    """P-chip handles over ``Topology.local(P)``, each checked against the
    segment-sum reference, with their decisions, HLO collective permutes
    and where C's shards landed."""
    topo = Topology.local(P)
    times: dict = {}
    lines = [f"topology: {topo.describe()}"]
    m = a.shape[0]
    pad = -m % P
    if pad:
        # the executors need equal row blocks (P | M): add empty rows and
        # columns, which leave the other rows of C unchanged
        coo = a.to_coo()
        a = csr_from_coo(coo_from_arrays((m + pad, m + pad), coo.row, coo.col, coo.val))
        b = np.pad(b, ((0, pad), (0, 0)))
        lines.append(f"padded {m} -> {m + pad} rows and columns so that P={P} divides them")
    want = reference_spmm(a, b)
    shard_devices = {}
    for name, overrides in MULTICHIP_HANDLES:
        with timed(times, f"{name} compile"):
            h = compile_spmm(a, topo, **overrides)
        with timed(times, f"{name} call"):
            c = h(b)
            c.block_until_ready()
        st = h.stats()
        hlo = h.lowered_hlo(b.shape[1])
        permutes = sum(" collective-permute(" in ln or " collective-permute-start(" in ln
                       for ln in hlo.splitlines())
        all_to_all = sum(" all-to-all(" in ln for ln in hlo.splitlines())
        shards = sorted(((s.index[0].start or 0), str(s.device)) for s in c.addressable_shards)
        shard_devices[name] = {d for _, d in shards}
        lines += [
            f"[{name}] {h!r}",
            f"[{name}] decisions: " + json.dumps({k: st.get(k) for k in DECISION_KEYS},
                                                 default=str),
            f"[{name}] HLO: {permutes} collective-permute, {all_to_all} all-to-all",
            f"[{name}] C shards (first row -> device): {shards}",
            check_close(f"[{name}] C", c, want),
        ]
        if len(shard_devices[name]) != P:
            raise AssertionError(f"[{name}] C's shards sit on {shard_devices[name]}, "
                                 f"not on {P} distinct devices")
    return {"times": times, "lines": lines, "shard_devices": shard_devices}


# ---------------------------------------------------------------------------
# entry point: the only place that insists on the chip
# ---------------------------------------------------------------------------


def _require_tpu(chips: int):
    if os.environ.get("REPRO_PALLAS_INTERPRET"):
        raise SystemExit("chip_smoke: REPRO_PALLAS_INTERPRET is set; it would "
                         "run the Pallas kernels in interpret mode on the chip")
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (jax.devices()[0].platform = "
                         f"{devs[0].platform!r}); nothing was run")
    if ops.kernel_backend() != "pallas":
        raise SystemExit(f"chip_smoke: kernel backend {ops.kernel_backend()!r}, "
                         f"not 'pallas'")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: --chips {chips} but {len(devs)} devices")
    return devs


def strict_autotune_warnings() -> None:
    """Turn the autotuner's skipped-candidate warning into an error: on the
    chip, a candidate that cannot build or profile is a kernel failure,
    not a reason to serve another backend."""
    warnings.filterwarnings("error", message="autotune candidate")


def _print_times(prefix: str, times: dict) -> None:
    say(f"smoke wall times, {prefix} (not metrics): " + ", ".join(
        f"{k} {v:.3f}s" for k, v in times.items()))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = _require_tpu(args.chips)
    strict_autotune_warnings()
    say(f"compile cache: {enable_compile_cache(ROOT / '.jax_cache')}")
    say(f"device: {devs[0].device_kind}, {len(devs)} devices, platform {devs[0].platform}")

    t0 = time.perf_counter()
    a = make_graph(ARXIV_NODES, ARXIV_EDGES, POWER_LAW_ALPHA, args.seed)
    say(f"power-law graph: {a.nnz - ARXIV_NODES} edges (target {ARXIV_EDGES}, "
        f"alpha {POWER_LAW_ALPHA}, seed {args.seed}), made in "
        f"{time.perf_counter() - t0:.3f}s")
    for line in operand_lines(a, ARXIV_FEATURES):
        say(line)
    b = dense(ARXIV_NODES, ARXIV_FEATURES, args.seed)

    if args.chips == 4:
        r = phase_multichip(a, b, 4)
        for line in r["lines"]:
            say(line)
        _print_times("--chips 4", r["times"])
    else:
        r = phase_spmm(a, b, calls=3)
        for line in r["lines"]:
            say(line)
        _print_times("(a) spmm", r["times"])

        r_gcn = phase_gcn(r["handle"], ARXIV_FEATURES, ARXIV_CLASSES, steps=3,
                          seed=args.seed)
        for line in r_gcn["lines"]:
            say(line)
        _print_times("(b) gcn", r_gcn["times"])

        say(ell_line("the power-law operand (not built)", a))
        say(ell_line("a uniform operand with all its edges (not built)",
                     make_graph(ARXIV_NODES, ARXIV_EDGES, 0.0, args.seed)))
        u = make_graph(ARXIV_NODES, ARXIV_EDGES // PALLAS_EDGE_FRACTION, 0.0, args.seed)
        r_pl = phase_pallas(u, ARXIV_FEATURES, 16, args.seed)
        for line in r_pl["lines"]:
            say(line)
        _print_times("(c) pallas", r_pl["times"])

        # (d) the device path was the one taken
        if any(r_pl["interpret"].values()):
            raise AssertionError(f"backends resolved interpret mode: {r_pl['interpret']}")
        for name, text in r_pl["hlo"].items():
            n_calls = text.count('custom_call_target="tpu_custom_call"')
            if not n_calls:
                raise AssertionError(f"{name}: no tpu_custom_call in its HLO")
            say(f"{name} HLO: {n_calls} tpu_custom_call")
        say(f"interpret resolved: {r_pl['interpret']}; kernel backend {ops.kernel_backend()}")

    stats = devs[0].memory_stats() or {}
    say(f"peak_bytes_in_use (device 0): {stats.get('peak_bytes_in_use')}")
    say(f"total smoke wall time (not a metric): {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {"platform": devs[0].platform,
                                             "kind": devs[0].device_kind,
                                             "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
