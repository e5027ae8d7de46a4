"""Pallas kernel sweeps: shapes × dtypes vs the pure-jnp oracles (ref.py).

All kernels run in interpret mode (CPU container; TPU is the target).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import prefetch
from repro.kernels.bsr_spmm import bsr_spmm_acc_pallas, bsr_spmm_pallas
from repro.kernels.gather_rows import gather_rows_pallas
from repro.kernels.ops import (
    gather_rows_op, prepare_sorted_scatter, scatter_add_rows_op,
)
from repro.kernels.ref import (
    bsr_spmm_ref, gather_rows_ref, scatter_add_rows_ref,
)
from repro.kernels.scatter_add_rows import scatter_add_rows_sorted_pallas
from repro.kernels.sddmm import bsr_sddmm_pallas


BSR_SHAPES = [
    # (mb, t, bm, bk, kb, n, bn)
    (2, 3, 8, 8, 4, 16, 16),
    (3, 2, 16, 8, 5, 32, 16),
    (1, 1, 8, 8, 2, 8, 8),
    (4, 5, 32, 16, 8, 64, 64),
    (2, 4, 8, 32, 4, 128, 128),
]


@pytest.mark.parametrize("shape", BSR_SHAPES)
@pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16])
def test_bsr_spmm_sweep(shape, dtype):
    mb, t, bm, bk, kb, n, bn = shape
    rng = np.random.default_rng(hash(shape) % 2 ** 31)
    cols = rng.integers(-1, kb, size=(mb, t)).astype(np.int32)
    blocks = rng.standard_normal((mb, t, bm, bk)).astype(np.float32)
    blocks[cols < 0] = 0.0
    b = rng.standard_normal((kb * bk, n)).astype(np.float32)
    blocks_j = jnp.asarray(blocks, dtype)
    b_j = jnp.asarray(b, dtype)
    out = bsr_spmm_pallas(jnp.asarray(cols), blocks_j, b_j, bn=bn,
                          interpret=True)
    ref = bsr_spmm_ref(jnp.asarray(cols), blocks_j, b_j)
    tol = 1e-5 if dtype == np.float32 else 6e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("K,n,S", [(16, 8, 5), (64, 32, 20), (8, 128, 3),
                                   (128, 256, 64)])
def test_gather_rows_sweep(K, n, S):
    rng = np.random.default_rng(K * 1000 + S)
    b = rng.standard_normal((K, n)).astype(np.float32)
    idx = rng.integers(-1, K, size=S).astype(np.int32)
    out = gather_rows_pallas(jnp.asarray(b), jnp.asarray(idx), interpret=True)
    ref = gather_rows_ref(jnp.asarray(b), jnp.asarray(idx))
    np.testing.assert_allclose(out, ref)


@pytest.mark.parametrize("M,n,S", [(8, 16, 12), (16, 8, 30), (4, 8, 6),
                                   (32, 128, 100)])
def test_scatter_add_sweep(M, n, S):
    rng = np.random.default_rng(M * 77 + S)
    c = rng.standard_normal((M, n)).astype(np.float32)
    parts = rng.standard_normal((S, n)).astype(np.float32)
    tgt = rng.integers(-1, M, size=S).astype(np.int32)
    ref = scatter_add_rows_ref(jnp.asarray(c), jnp.asarray(parts),
                               jnp.asarray(tgt))
    perm, meta = prepare_sorted_scatter(tgt)
    out = scatter_add_rows_sorted_pallas(
        jnp.asarray(c), jnp.asarray(parts[perm]), jnp.asarray(meta),
        interpret=True)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_scatter_add_all_pads():
    c = np.ones((4, 8), np.float32)
    parts = np.full((3, 8), 7.0, np.float32)
    tgt = np.full(3, -1, np.int32)
    perm, meta = prepare_sorted_scatter(tgt)
    out = scatter_add_rows_sorted_pallas(
        jnp.asarray(c), jnp.asarray(parts[perm]), jnp.asarray(meta),
        interpret=True)
    np.testing.assert_allclose(out, c)


def test_ops_dispatch_ref_backend(monkeypatch):
    """On CPU without the interpret env, ops fall back to the oracle."""
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    rng = np.random.default_rng(0)
    b = jnp.asarray(rng.standard_normal((16, 8)).astype(np.float32))
    idx = jnp.asarray(rng.integers(-1, 16, 6).astype(np.int32))
    np.testing.assert_allclose(gather_rows_op(b, idx),
                               gather_rows_ref(b, idx))


def test_ops_dispatch_interpret_backend(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(0)
    c = jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32))
    parts = jnp.asarray(rng.standard_normal((5, 16)).astype(np.float32))
    tgt = np.array([0, 3, 3, -1, 7], np.int32)
    out = scatter_add_rows_op(c, parts, tgt)
    ref = scatter_add_rows_ref(c, parts, jnp.asarray(tgt))
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def _chunk_cases():
    """One call per kernel on operands whose index tables span several
    SMEM chunks once ``prefetch.TABLE_ENTRIES`` is patched down to 7."""
    rng = np.random.default_rng(3)
    mb, t, bm, bk, n = 5, 4, 8, 8, 16
    cols = rng.integers(-1, mb, size=(mb, t)).astype(np.int32)
    blocks = rng.standard_normal((mb, t, bm, bk)).astype(np.float32)
    blocks[cols < 0] = 0.0
    b = rng.standard_normal((mb * bk, n)).astype(np.float32)
    acc = rng.standard_normal((mb * bm, n)).astype(np.float32)
    x3 = rng.standard_normal((mb, bm, n)).astype(np.float32)
    idx = rng.integers(-1, mb * bk, 23).astype(np.int32)
    tgt = rng.integers(-1, mb * bm, 23).astype(np.int32)
    parts = rng.standard_normal((23, n)).astype(np.float32)
    perm, meta = prepare_sorted_scatter(tgt)
    cols, blocks, b, acc, x3, idx, parts, meta = map(
        jnp.asarray, (cols, blocks, b, acc, x3, idx, parts[perm], meta))
    return {
        "gather_rows": lambda: gather_rows_pallas(b, idx, interpret=True),
        "scatter_add_rows": lambda: scatter_add_rows_sorted_pallas(
            acc, parts, meta, interpret=True),
        "bsr_spmm": lambda: bsr_spmm_pallas(cols, blocks, b, bn=n,
                                            interpret=True),
        "bsr_spmm_acc": lambda: bsr_spmm_acc_pallas(cols, blocks, b, acc + 0,
                                                    bn=n, interpret=True),
        "bsr_sddmm": lambda: bsr_sddmm_pallas(cols, blocks, x3, x3,
                                              interpret=True),
    }


@pytest.mark.parametrize("kernel", sorted(_chunk_cases()))
def test_smem_chunking_is_bit_identical(kernel, monkeypatch):
    """Index tables split into SMEM-sized chunks (one pallas_call each)
    give the same bits as one call: segments and accumulation chains cut
    by a chunk boundary resume where they stopped."""
    call = _chunk_cases()[kernel]
    jax.clear_caches()
    whole = np.asarray(call())
    monkeypatch.setattr(prefetch, "TABLE_ENTRIES", 7)
    jax.clear_caches()  # the kernels read the budget while tracing
    try:
        chunked = np.asarray(call())
    finally:
        jax.clear_caches()
    np.testing.assert_array_equal(chunked, whole)
