"""Local-compute backend parity (core.local_backend).

For every executor × sparsity pattern, the COO and BSR backends must
produce the same C = A @ B as the dense oracle — and, because backends
only swap the *local* compute, the collectives in the lowered HLO must be
bit-identical across backends (the communication schedule is fixed by the
planner, not the kernel substrate).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.api import compile_spmm
from repro.core.dist_spmm import (
    flat_exec_arrays, flat_spmm, hier_exec_arrays, hier_spmm,
)
from repro.core.hierarchy import build_hier_plan
from repro.core.local_backend import (
    BsrBackend, CooBackend, available_backends, backend_prepare_segments,
    coo_spmm_local, get_backend,
)
from repro.core.planner import build_plan
from repro.core.sparse import (
    coo_from_arrays, csr_from_coo, ell_from_csr, hub_sparse,
    power_law_graph, power_law_sparse, random_sparse,
)
from repro.kernels.ops import fold_chunk, fold_rows
from repro.models.gnn import normalize_adjacency
from repro.launch.hlo_analysis import collective_bytes
from repro.launch.mesh import make_spmm_mesh

# small (bm, bk) keeps interpret-mode Pallas grids tiny on 64×64 tests;
# real TPUs would use the 128×128 default
BSR_SMALL = BsrBackend(block=(8, 8), bn=16)


def _matrices():
    return [
        ("uniform", random_sparse(64, 64, 0.05, 1)),
        ("powerlaw", power_law_sparse(64, 64, 400, 1.2, 2)),
        ("hub", hub_sparse(64, 64, 2, 2, 0.3, 3)),
    ]


def test_registry():
    assert set(available_backends()) >= {"coo", "bsr"}
    assert get_backend("coo").name == "coo"
    assert isinstance(get_backend(BSR_SMALL), BsrBackend)
    with pytest.raises(ValueError, match="unknown backend"):
        get_backend("cusparse")


def test_ell_layout_roundtrip():
    a = power_law_sparse(30, 50, 200, 1.3, 0)
    cols, blocks = ell_from_csr(a, (8, 8))
    dense = np.zeros((32, 56), np.float32)
    for mb in range(cols.shape[0]):
        for t in range(cols.shape[1]):
            c = int(cols[mb, t])
            if c >= 0:
                dense[mb * 8:(mb + 1) * 8, c * 8:(c + 1) * 8] += blocks[mb, t]
    np.testing.assert_allclose(dense[:30, :50], a.to_dense(), rtol=1e-6)


def test_flat_backend_parity():
    """flat_spmm: coo == bsr == dense on ≥3 sparsity patterns."""
    rng = np.random.default_rng(0)
    P = 4
    mesh = make_spmm_mesh(P)
    for name, a in _matrices():
        b = rng.standard_normal((64, 16)).astype(np.float32)
        ref = a.to_dense() @ b
        ex = flat_exec_arrays(build_plan(a, P, "joint"),
                              backends=("coo", BSR_SMALL))
        out_coo = flat_spmm(ex, jnp.asarray(b), mesh, backend="coo")
        out_bsr = flat_spmm(ex, jnp.asarray(b), mesh, backend="bsr")
        np.testing.assert_allclose(np.asarray(out_coo), ref, rtol=1e-4,
                                   atol=1e-4, err_msg=f"{name}/coo")
        np.testing.assert_allclose(np.asarray(out_bsr), ref, rtol=1e-4,
                                   atol=1e-4, err_msg=f"{name}/bsr")
        np.testing.assert_allclose(np.asarray(out_bsr), np.asarray(out_coo),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_hier_backend_parity():
    """hier_spmm: coo == bsr == dense on ≥3 sparsity patterns."""
    rng = np.random.default_rng(1)
    G, L = 2, 2
    mesh = make_spmm_mesh(G * L, groups=G)
    for name, a in _matrices():
        b = rng.standard_normal((64, 8)).astype(np.float32)
        ref = a.to_dense() @ b
        hp = build_hier_plan(build_plan(a, G * L, "joint"), G, L)
        ex = hier_exec_arrays(hp, backends=("coo", BSR_SMALL))
        out_coo = hier_spmm(ex, jnp.asarray(b), mesh, backend="coo")
        out_bsr = hier_spmm(ex, jnp.asarray(b), mesh, backend="bsr")
        np.testing.assert_allclose(np.asarray(out_coo), ref, rtol=1e-4,
                                   atol=1e-4, err_msg=f"{name}/coo")
        np.testing.assert_allclose(np.asarray(out_bsr), ref, rtol=1e-4,
                                   atol=1e-4, err_msg=f"{name}/bsr")
        np.testing.assert_allclose(np.asarray(out_bsr), np.asarray(out_coo),
                                   rtol=1e-4, atol=1e-4, err_msg=name)


def test_default_bsr_backend_runs_pallas():
    """The registry 'bsr' default (128-wide tiles) works on tiny inputs."""
    rng = np.random.default_rng(2)
    a = random_sparse(64, 64, 0.05, 7)
    b = rng.standard_normal((64, 16)).astype(np.float32)
    mesh = make_spmm_mesh(4)
    ex = flat_exec_arrays(build_plan(a, 4, "joint"),
                          backends=("coo", "bsr"))
    out = flat_spmm(ex, jnp.asarray(b), mesh, backend="bsr")
    np.testing.assert_allclose(np.asarray(out), a.to_dense() @ b,
                               rtol=1e-4, atol=1e-4)


def test_bsr_ref_impl_matches_pallas():
    """impl='ref' (pure-jnp oracle fallback) == the Pallas kernel path."""
    rng = np.random.default_rng(3)
    a = power_law_sparse(64, 64, 300, 1.3, 4)
    b = rng.standard_normal((64, 16)).astype(np.float32)
    mesh = make_spmm_mesh(4)
    plan = build_plan(a, 4, "joint")
    ex = flat_exec_arrays(plan, backends=(BSR_SMALL,))
    out_pl = flat_spmm(ex, jnp.asarray(b), mesh)
    ref_be = BsrBackend(block=(8, 8), bn=16, impl="ref")
    out_rf = flat_spmm(ex, jnp.asarray(b), mesh, backend=ref_be)
    np.testing.assert_allclose(np.asarray(out_rf), np.asarray(out_pl),
                               rtol=1e-5, atol=1e-5)


def test_custom_unregistered_backend_addressable_by_name():
    """A backend passed by instance stays selectable via its own name,
    without a register_backend() call (the plan's instances win over the
    global registry)."""
    import dataclasses

    @dataclasses.dataclass(frozen=True)
    class Renamed(CooBackend):
        name = "renamed-coo"

    rng = np.random.default_rng(4)
    a = random_sparse(64, 64, 0.05, 8)
    b = rng.standard_normal((64, 16)).astype(np.float32)
    ex = flat_exec_arrays(build_plan(a, 4, "joint"), backends=(Renamed(),))
    assert ex.backends == ("renamed-coo",)
    mesh = make_spmm_mesh(4)
    out = flat_spmm(ex, jnp.asarray(b), mesh, backend="renamed-coo")
    np.testing.assert_allclose(np.asarray(out), a.to_dense() @ b,
                               rtol=1e-4, atol=1e-4)


def test_backend_not_prepared_raises():
    a = random_sparse(64, 64, 0.05, 5)
    ex = flat_exec_arrays(build_plan(a, 4, "joint"))  # coo only
    mesh = make_spmm_mesh(4)
    with pytest.raises(ValueError, match="no prepared pieces"):
        flat_spmm(ex, jnp.zeros((64, 16)), mesh, backend="bsr")


def test_collectives_identical_across_backends():
    """Acceptance: swapping backends must not change the communication
    schedule — same collective ops, same byte counts, in the lowered HLO."""
    a = power_law_sparse(64, 64, 400, 1.2, 6)
    b_sds = jax.ShapeDtypeStruct((64, 16), jnp.float32)

    # flat
    ex = flat_exec_arrays(build_plan(a, 4, "joint"),
                          backends=("coo", BSR_SMALL))
    mesh = make_spmm_mesh(4)
    colls = {}
    for be in ("coo", "bsr"):
        fn = jax.jit(lambda b, be=be: flat_spmm(ex, b, mesh, backend=be))
        colls[be] = collective_bytes(fn.lower(b_sds).compile().as_text())
    assert colls["coo"] == colls["bsr"]
    assert colls["coo"]["all-to-all"] > 0

    # hierarchical
    hp = build_hier_plan(build_plan(a, 4, "joint"), 2, 2)
    exh = hier_exec_arrays(hp, backends=("coo", BSR_SMALL))
    mesh2 = make_spmm_mesh(4, groups=2)
    collsh = {}
    for be in ("coo", "bsr"):
        fn = jax.jit(lambda b, be=be: hier_spmm(exh, b, mesh2, backend=be))
        collsh[be] = collective_bytes(fn.lower(b_sds).compile().as_text())
    assert collsh["coo"] == collsh["bsr"]


# ---------------------------------------------------------------------------
# the coo product's row fold (kernels.ops.coo_accumulate_rows_op)
# ---------------------------------------------------------------------------


def _arxiv_like(n=2000):
    """ogbn-arxiv's pattern in small: Zipf(0.7) endpoints over shuffled
    ids, self loops, GCN normalization (mean degree 8, widest row 335)."""
    return normalize_adjacency(power_law_graph(n, 7 * n, 0.7, 0))


def _grid(side=40):
    """A 5-point stencil on a side x side grid: uniform degrees 3 to 5."""
    i = np.arange(side * side).reshape(side, side)
    pairs = [(i, i), (i[1:], i[:-1]), (i[:-1], i[1:]),
             (i[:, 1:], i[:, :-1]), (i[:, :-1], i[:, 1:])]
    row = np.concatenate([r.ravel() for r, _ in pairs])
    col = np.concatenate([c.ravel() for _, c in pairs])
    val = np.random.default_rng(3).standard_normal(row.size).astype(np.float32)
    return csr_from_coo(coo_from_arrays((side * side,) * 2, row, col, val))


def _empty_rows():
    a = power_law_sparse(300, 300, 2000, 1.2, 5)
    coo = a.to_coo()
    return a.select_nonzeros(coo.row % 3 != 0)


_FOLD_PATTERNS = {
    "powerlaw": _arxiv_like,
    "mesh": _grid,
    "empty-rows": _empty_rows,
    "no-nonzeros": lambda: csr_from_coo(coo_from_arrays(
        (50, 60), np.zeros(0, int), np.zeros(0, int), np.zeros(0))),
}


def _stripped(piece):
    return jax.tree_util.tree_map(lambda x: x[0], piece)


def _operand(a, n, dtype="float32", seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal((a.shape[1], n)), dtype)


_compute = jax.jit(CooBackend().compute, static_argnums=2)
_scatter = jax.jit(coo_spmm_local, static_argnums=4)


def test_fold_fixtures_cover_the_cases():
    """The power-law fixture has a row wider than the widest unrolled
    chunk (``fold_chunk`` tops out at 256 slots); the others keep their
    rows empty or narrow."""
    deg = np.diff(_arxiv_like().indptr)
    assert deg.max() > max(fold_chunk(w) for w in (2 ** k for k in range(20)))
    assert (np.diff(_empty_rows().indptr) == 0).sum() >= 100
    assert set(np.diff(_grid().indptr)) == {3, 4, 5}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("n", [1, 40, 128])
@pytest.mark.parametrize("pattern", sorted(_FOLD_PATTERNS))
def test_fold_matches_scatter_bitwise(pattern, n, dtype):
    """compute() folds each row in COO order: the scatter-add's C, bit
    for bit, in B's dtype."""
    a = _FOLD_PATTERNS[pattern]()
    p = _stripped(CooBackend().prepare([a]))
    b = _operand(a, n, dtype)
    got = _compute(p, b, a.shape[0])
    want = _scatter(p["row"], p["col"], p["val"], b, a.shape[0])
    assert got.dtype == want.dtype == b.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("pattern", sorted(_FOLD_PATTERNS))
def test_fold_resumes_accumulator_bitwise(pattern):
    """From a nonzero accumulator, each row's chain starts at its
    accumulator row (rows with no nonzeros keep theirs): the scatter-add
    into that accumulator, bit for bit."""
    a = _FOLD_PATTERNS[pattern]()
    p = _stripped(CooBackend().prepare([a]))
    b = _operand(a, 40)
    acc = jnp.asarray(np.random.default_rng(5).standard_normal(
        (a.shape[0], 40)), jnp.float32)
    got = fold_rows(acc, p["fold"], b)
    want = jax.jit(lambda c: c.at[p["row"]].add(
        b[p["col"]] * p["val"][:, None]))(acc)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("pattern", sorted(_FOLD_PATTERNS))
def test_fold_resumes_accumulator_bitwise(pattern):
    """From a nonzero accumulator, each row's chain starts at its
    accumulator row (rows with no nonzeros keep theirs): the scatter-add
    into that accumulator, bit for bit."""
    a = _FOLD_PATTERNS[pattern]()
    p = _stripped(CooBackend().prepare([a]))
    b = _operand(a, 40)
    acc = jnp.asarray(np.random.default_rng(5).standard_normal(
        (a.shape[0], 40)), jnp.float32)
    got = fold_rows(acc, p["fold"], b, fused=False)
    want = jax.jit(lambda c: c.at[p["row"]].add(
        b[p["col"]] * p["val"][:, None]))(acc)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("pattern", ["powerlaw", "mesh", "empty-rows"])
def test_fold_segments_equal_staged_bitwise(pattern):
    """compute_segment over the segment cuts resumes the staged chain:
    the folded C equals one staged compute() bit for bit."""
    a = _FOLD_PATTERNS[pattern]()
    be = CooBackend()
    k = a.shape[1]
    cuts = (k // 5, k // 2, k // 2, k)  # the third segment is empty
    b = _operand(a, 16)
    acc = jnp.zeros((a.shape[0], 16), jnp.float32)
    step = jax.jit(be.compute_segment)
    for seg, hi in zip(backend_prepare_segments(be, [a], cuts), cuts):
        acc = step(_stripped(seg), b[:hi], acc)
    staged = _compute(_stripped(be.prepare([a])), b, a.shape[0])
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(staged))


def test_fold_grad_through_p1_handle_equals_scatter_grad():
    """The JVP is the COO expression, so reverse mode runs the scatter
    path's backward: the grad through a P=1 handle is bit for bit."""
    a = _arxiv_like(600)
    h = compile_spmm(a, 1)
    p = _stripped(h.ex.pieces["coo"]["diag"])
    b = _operand(a, 8)
    g = jax.grad(lambda x: jnp.sum(h(x) ** 2))(b)
    g_ref = jax.grad(lambda x: jnp.sum(_scatter(
        p["row"], p["col"], p["val"], x, a.shape[0]) ** 2))(b)
    np.testing.assert_array_equal(np.asarray(g), np.asarray(g_ref))


def test_fold_grads_through_flat_spmm_match_oracle():
    a = _arxiv_like(512)
    ex = flat_exec_arrays(build_plan(a, 8, "joint"))
    mesh = make_spmm_mesh(8)
    dense = jnp.asarray(a.to_dense())
    b = _operand(a, 8)
    g = jax.jit(jax.grad(lambda x: jnp.sum(flat_spmm(ex, x, mesh) ** 2)))(b)
    g_ref = jax.grad(lambda x: jnp.sum((dense @ x) ** 2))(b)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=5e-3, atol=5e-3)


def test_fold_jvp_in_b_and_values():
    """jax.jvp runs, and its tangent is the scatter path's, bit for bit."""
    a = _arxiv_like(600)
    p = _stripped(CooBackend().prepare([a]))
    m = a.shape[0]
    b, b_dot = _operand(a, 8), _operand(a, 8, seed=1)
    v_dot = jnp.asarray(np.random.default_rng(2).standard_normal(
        p["val"].shape), jnp.float32)

    def fold(x, v):
        return CooBackend().compute(CooBackend().with_values(p, v), x, m)

    def scat(x, v):
        return coo_spmm_local(p["row"], p["col"], v, x, m)

    out, tan = jax.jit(lambda *t: jax.jvp(fold, (b, p["val"]), t))(b_dot, v_dot)
    out_ref, tan_ref = jax.jvp(scat, (b, p["val"]), (b_dot, v_dot))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_ref))
    np.testing.assert_array_equal(np.asarray(tan), np.asarray(tan_ref))


def test_fold_reads_values_swapped_by_with_values():
    a = _arxiv_like(600)
    be = CooBackend()
    p = _stripped(be.prepare([a]))
    vals = jnp.asarray(np.random.default_rng(4).standard_normal(
        p["val"].shape), jnp.float32)
    b = _operand(a, 16)
    got = _compute(be.with_values(p, vals), b, a.shape[0])
    want = _scatter(p["row"], p["col"], vals, b, a.shape[0])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# P=4: buckets share one row count across processes, which pads more on
# 500-row pieces (ogbn-arxiv's 4-chip plan reads 1.078)
@pytest.mark.parametrize("P,max_pad", [(1, 1.15), (4, 1.25)])
def test_fold_layout_and_counters(P, max_pad):
    """Every stored nonzero sits in one slot of its row, in COO order;
    pieces pad little in a bounded number of buckets; stats() counts it."""
    a = _arxiv_like()
    h = compile_spmm(a, P)
    st = h.stats()
    assert st["fold_share"] == 1.0
    assert 1.0 <= st["fold_pad"] <= max_pad
    for piece in h.ex.pieces["coo"].values():
        fold = piece["fold"]
        assert len(fold.buckets) <= 40
        for proc in range(P):
            p = jax.tree_util.tree_map(lambda x: np.asarray(x[proc]), piece)
            f, pad, nnz = p["fold"], p["val"].shape[0], int(p["fold"].nnz)
            row, col = p["row"][:nnz], p["col"][:nnz]
            deg = np.bincount(row, minlength=f.perm.size)
            seen, r0, s0 = [np.zeros(0, np.int64)], 0, 0
            for w, n in f.buckets:
                # slot-major [w, n] -> one row a line [n, w]
                src, cols, vals = (x[s0:s0 + w * n].reshape(w, n).T
                                   for x in (f.src, f.col, f.val))
                rows = f.rows[r0:r0 + n]
                r0, s0 = r0 + n, s0 + w * n
                used = (src < pad).any(axis=1)
                rows, src, cols, vals = (rows[used], src[used], cols[used],
                                         vals[used])
                real = src < pad
                n_real = real.sum(axis=1)
                # each used row: its own nonzeros, all of them, in COO
                # order, then padding that repeats its last column
                assert (n_real == deg[rows]).all()
                assert (f.perm[rows] >= 0).all()
                assert (real[:, :-1] >= real[:, 1:]).all()
                assert (row[np.where(real, src, 0)][real]
                        == np.broadcast_to(rows[:, None], real.shape)[real]).all()
                assert (np.diff(src, axis=1)[real[:, 1:]] == 1).all()
                assert (cols[real] == col[src[real]]).all()
                assert (vals[real] == p["val"][src[real]]).all()
                assert (vals[~real] == 0).all()
                last = cols[np.arange(len(rows)), n_real - 1]
                assert (cols == last[:, None])[~real].all()
                seen.append(src[real])
            np.testing.assert_array_equal(np.sort(np.concatenate(seen)),
                                          np.arange(nnz))
            assert ((f.perm >= 0) == (deg > 0)).all()


@pytest.mark.parametrize("default,share", [("coo", 1.0), ("bsr", 0.0)])
def test_fold_share_is_the_default_backends_folded_share(default, share):
    """fold_share counts the stored nonzeros of the default backend's
    pieces that the row fold reduces: all on coo, none on bsr."""
    a = power_law_sparse(64, 64, 300, 1.2, 1)
    h = compile_spmm(a, 2, backends=("coo", BsrBackend(block=(8, 8), bn=16)),
                     default_backend=default)
    st = h.stats()
    assert st["fold_share"] == share
    assert st["fold_pad"] >= 1.0 if share else st["fold_pad"] is None
