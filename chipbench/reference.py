"""The plain references that decide ``correct``, and their controls.

Nothing here imports the program. Each reference is straightforward
``jax.numpy`` in float32 over the operand's COO arrays:

* ``spmm``: C = A @ B as a segment sum of ``val * B[col]`` over ``row``.
* ``gcn_steps``: full-batch training of a GCN whose layers are
  ``h <- Â (h W + b)`` with ReLU between them, softmax cross-entropy
  averaged over every node, plain SGD; matrix products at ``highest``.

Each has a control: the same computation one precision step below the
float32 that the configuration states: bfloat16 inputs, products and sums.
``gcn_steps`` also plants the faults of a training run (``fault=``).

The compared numbers (``spmm_gap``, ``train_gaps``) are documented where
they are defined.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# the planted "answer altered" fault of a training run: the loss off by this
# share of itself where it is produced
ANSWER_FAULT = 1e-3


@functools.partial(jax.jit, static_argnames=("n", "dtype"))
def _segment_spmm(row, col, val, b, *, n, dtype):
    prod = val.astype(dtype)[:, None] * b.astype(dtype)[col]
    return jax.ops.segment_sum(prod, row, num_segments=n).astype(jnp.float32)


def spmm(graph, b, dtype=jnp.float32) -> np.ndarray:
    """A @ b as a segment sum in ``dtype`` (float32: the reference)."""
    out = _segment_spmm(jnp.asarray(graph.row), jnp.asarray(graph.col),
                        jnp.asarray(graph.val), jnp.asarray(np.asarray(b)), n=graph.n,
                        dtype=jnp.dtype(dtype))
    return np.asarray(out)


def spmm_control(graph, b) -> np.ndarray:
    """The reference one precision step below float32: bfloat16."""
    return spmm(graph, b, jnp.bfloat16)


def spmm_gap(got: np.ndarray, want: np.ndarray) -> float:
    """max |got - want| over max |want|: one number for a whole C."""
    if got.shape != want.shape:
        return float("inf")
    if not np.isfinite(got).all():
        return float("inf")
    scale = float(np.max(np.abs(want))) or 1.0
    return float(np.max(np.abs(got.astype(np.float64) - want))) / scale


# ---------------------------------------------------------------------------
# GCN training
# ---------------------------------------------------------------------------


def _gcn_loss(params, x, labels, row, col, val, *, n, dtype, fault):
    h, val = x.astype(dtype), val.astype(dtype)
    for i, lp in enumerate(params):
        z = jnp.dot(h, lp["w"].astype(dtype), precision=HIGHEST,
                    preferred_element_type=dtype) + lp["b"].astype(dtype)
        h = jax.ops.segment_sum(val[:, None] * z[col], row, num_segments=n)
        if i < len(params) - 1:
            h = jax.nn.relu(h)
    logz = jax.nn.logsumexp(h, axis=-1)
    gold = jnp.take_along_axis(h, labels[:, None], 1)[:, 0]
    per_node = logz - gold
    if fault == "half_batch":
        return jnp.mean(per_node[: n // 2])
    loss = jnp.mean(per_node)
    return loss * (1 + ANSWER_FAULT) if fault == "answer" else loss


@functools.partial(jax.jit, static_argnames=("n", "dtype", "fault", "lr"))
def _gcn_step(params, x, labels, row, col, val, *, n, dtype, fault, lr):
    loss, g = jax.value_and_grad(_gcn_loss)(params, x, labels, row, col, val, n=n,
                                            dtype=dtype, fault=fault)
    return loss, g, jax.tree_util.tree_map(lambda w, dw: w - lr * dw, params, g)


def gcn_steps(graph, params, x, labels, *, lr: float, steps: int,
              dtype=jnp.float32, fault: str = "") -> dict:
    """``steps`` SGD steps from ``params``: each step's loss, the first
    gradient, and the parameters after the last step (host arrays).

    ``dtype``: what the loss and its gradient are computed in (the weights
    stay float32); bfloat16 is the control. ``fault`` plants a fault of a
    training run: ``"half_batch"`` takes the mean over the first half of the
    nodes only, ``"answer"`` alters the loss where it is produced, by
    ``ANSWER_FAULT`` of itself.
    """
    arrays = tuple(jnp.asarray(a) for a in (graph.row, graph.col, graph.val))
    losses, first_grad = [], None
    for _ in range(steps):
        loss, g, params = _gcn_step(params, x, labels, *arrays, n=graph.n,
                                    dtype=jnp.dtype(dtype), fault=fault, lr=lr)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = jax.tree_util.tree_map(np.asarray, g)
    return {"losses": losses, "first_grad": first_grad,
            "params": jax.tree_util.tree_map(np.asarray, params)}


def _norms(tree, minus=None, scale: float = 1.0) -> np.ndarray:
    """Each leaf's norm, of ``(tree - minus) * scale`` where ``minus`` is given."""
    leaves = jax.tree_util.tree_leaves(tree)
    subs = jax.tree_util.tree_leaves(minus) if minus is not None else [0.0] * len(leaves)
    return np.array([float(np.linalg.norm((np.asarray(a, np.float64) - np.asarray(b, np.float64))
                                          * scale)) for a, b in zip(leaves, subs)])


def _worst_leaf_gap(got: np.ndarray, want: np.ndarray, keep: np.ndarray) -> float:
    """max over kept leaves of | |got| - |want| | / max(|want|, median |want|)."""
    med = float(np.median(want[keep]))
    den = np.maximum(want, med)
    den[den == 0] = 1.0
    return float(np.max(np.abs(got - want)[keep] / den[keep]))


def train_gaps(prog: dict, ref: dict, params0, *, lr: float) -> dict:
    """The compared numbers of a training cell.

    ``prog`` holds the program's ``losses`` of its first steps and its
    parameters after one step (``params1``) and after the last
    (``params_last``); ``ref`` is ``gcn_steps`` from the same ``params0``.

    * ``loss_gap``: the largest |loss - reference loss| / |reference loss|
      over the steps.
    * ``grad_gap``: the first gradient as the optimizer got it,
      (params0 - params1) / lr, against the reference's, by leaf norms.
    * ``change_gap``: params_last - params0 against the reference's change,
      by leaf norms.

    Both leaf gaps are the worst leaf's | |program| - |reference| |, over the
    larger of the reference's norm of that leaf and of the median leaf.
    Leaves whose reference gradient is under a thousandth of the median
    leaf's (nought to rounding) are left out of both.
    """
    g_ref = _norms(ref["first_grad"])
    keep = g_ref >= 1e-3 * float(np.median(g_ref))
    g_prog = _norms(params0, prog["params1"], 1.0 / lr)
    d_prog = _norms(prog["params_last"], params0)
    d_ref = _norms(ref["params"], params0)
    losses_p, losses_r = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    if losses_p.shape != losses_r.shape or not np.isfinite(losses_p).all():
        loss_gap = float("inf")
    else:
        loss_gap = float(np.max(np.abs(losses_p - losses_r) / np.abs(losses_r)))
    return {"loss_gap": loss_gap,
            "grad_gap": _worst_leaf_gap(g_prog, g_ref, keep),
            "change_gap": _worst_leaf_gap(d_prog, d_ref, keep)}
