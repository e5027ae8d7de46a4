"""Compiles of the main path for a TPU v5e that is described, not attached.

Interpret mode accepts kernels that the TPU compiler refuses: blocks not
aligned to the (8, 128) tiling, index tables larger than SMEM, programs
larger than HBM. These tests run the TPU compiler at the widths of
``chip_smoke.py`` (ogbn-arxiv: 169,343 rows, N = 128) for a ``v5e:2x2``
that libtpu describes, so no chip is needed. They compile; nothing runs.

The topology is described inside a fixture, never at import: only one
process may hold libtpu, and every test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.api import compile_spmm
from repro.core.local_backend import CooBackend
from repro.core.sparse import power_law_graph
from repro.distributed.topology import Topology
from repro.kernels import ops, prefetch
from repro.kernels.bsr_spmm import bsr_spmm_acc_pallas, bsr_spmm_pallas
from repro.kernels.gather_rows import gather_rows_pallas
from repro.kernels.ops import FOLD_UNROLL, fold_rows
from repro.kernels.scatter_add_rows import scatter_add_rows_sorted_pallas
from repro.kernels.sddmm import bsr_sddmm_pallas
from repro.robustness import guards

ROWS = 169_343  # ogbn-arxiv nodes
N = 128  # ogbn-arxiv feature width
BM = BK = 8  # BsrBackend's default block
MB = -(-ROWS // BM)
T = 48  # ELL slots per block row: a uniform arxiv-sized graph's order


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or it cannot describe a v5e here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(compiled) -> int:
    return compiled.as_text().count('custom_call_target="tpu_custom_call"')


def test_row_kernels_compile(one_chip):
    """The executor's pack and aggregation kernels, one slot per row, so
    their index tables span several SMEM chunks."""
    slots = ROWS
    b = _sds((ROWS, N), jnp.float32, one_chip)
    idx = _sds((slots,), jnp.int32, one_chip)
    parts = _sds((slots, N), jnp.float32, one_chip)
    meta = _sds((slots + 1,), jnp.int32, one_chip)
    n_chunks = len(prefetch.chunks(slots))
    assert n_chunks > 1
    gather = gather_rows_pallas.lower(b, idx).compile()
    scatter = scatter_add_rows_sorted_pallas.lower(b, parts, meta).compile()
    assert _custom_calls(gather) == n_chunks
    assert _custom_calls(scatter) == n_chunks


@pytest.mark.parametrize("kernel", ["bsr_spmm", "bsr_spmm_acc", "bsr_sddmm"])
def test_bsr_kernels_compile(kernel, one_chip):
    cols = _sds((MB, T), jnp.int32, one_chip)
    blocks = _sds((MB, T, BM, BK), jnp.float32, one_chip)
    b = _sds((MB * BK, N), jnp.float32, one_chip)
    if kernel == "bsr_spmm":
        compiled = bsr_spmm_pallas.lower(cols, blocks, b).compile()
    elif kernel == "bsr_spmm_acc":
        acc = _sds((MB * BM, N), jnp.float32, one_chip)
        compiled = bsr_spmm_acc_pallas.lower(cols, blocks, b, acc).compile()
    else:
        x3 = _sds((MB, BM, N), jnp.float32, one_chip)
        compiled = bsr_sddmm_pallas.lower(cols, blocks, x3, x3).compile()
    assert _custom_calls(compiled) == len(prefetch.chunks(MB, T))


def test_p4_flat_executor_compiles(topo, monkeypatch):
    """A P=4 flat handle over a mesh of the four described chips: the
    bucketed schedule's collective permutes, with the Pallas row kernels
    packing and aggregating the exchanged rows."""
    # code that asks jax.default_backend() sees this host's CPU: steer the
    # kernel dispatch and the coo row fold to the chip's path
    monkeypatch.setattr(ops, "kernel_backend", lambda: "pallas")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n = 8192
    a = power_law_graph(n, 8 * n, alpha=0.7, seed=0)
    described = Topology(kind="local", devices=tuple(topo.devices),
                         local_device_count=len(topo.devices))
    h = compile_spmm(a, described, schedule=2, overlap=False)
    # nothing can be placed on a described device: lower with shapes
    monkeypatch.setattr(h, "_device_ex", lambda: jax.tree_util.tree_map(
        lambda x: _sds(np.shape(x), x.dtype, h._ex_sharding), h.ex))
    hlo = h.lowered_hlo(N)
    assert "tpu_custom_call" in hlo
    assert " collective-permute" in hlo


@pytest.mark.parametrize("full", [False, True], ids=["auto", "full"])
def test_guard_probe_compiles(full, one_chip):
    """The served call's isfinite probe over one shard of C at arxiv's
    size; it reads C in place and moves nothing between chips."""
    c = _sds((ROWS, N), jnp.float32, one_chip)
    text = guards._device_probe().lower(c, full=full, vector_is_row=True).compile().as_text()
    assert not [op for op in ("all-gather", "all-reduce", "all-to-all",
                              "collective-permute") if op in text]


def test_row_fold_compiles(one_chip):
    """The coo product's row fold on the chip's path: unrolled chains for
    narrow buckets, loops for rows wider than FOLD_UNROLL, and no
    scatter or sort."""
    n = 32768
    a = power_law_graph(n, 8 * n, alpha=0.7, seed=0)
    assert np.diff(a.indptr).max() > FOLD_UNROLL
    fold = jax.tree_util.tree_map(
        lambda x: _sds(x.shape[1:], x.dtype, one_chip),
        CooBackend().prepare([a])["fold"])
    c = _sds((n, N), jnp.float32, one_chip)
    text = fold_rows.lower(c, fold, c, fused=True,
                           zero_acc=True).compile().as_text()
    assert " while(" in text
    assert not [op for op in (" scatter(", " sort(") if op in text]
