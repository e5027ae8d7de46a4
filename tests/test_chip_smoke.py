"""chip_smoke.py rehearsed on CPU: its phases at a tiny size, its refusals.

The phases are platform-neutral functions that take their sizes; only
``main()`` insists on a TPU. Here they run on the host devices (Pallas in
interpret mode), so a wrong path, argument or mesh shows before any chip
time is spent. The persistent compile-cache helper the entry points call
is checked here too.
"""
import importlib.util
import warnings
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core.sparse import ell_bytes, ell_from_csr, power_law_graph
from repro.launch.compile_cache import CACHE_DIR_ENV, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_power_law_graph_has_exact_edge_count():
    g = power_law_graph(500, 4000, alpha=0.7, seed=3)
    coo = g.to_coo()
    assert g.nnz == 4000
    assert not np.any(coo.row == coo.col)
    assert np.all(coo.val == 1.0)
    again = power_law_graph(500, 4000, alpha=0.7, seed=3)
    assert np.array_equal(g.indptr, again.indptr)
    assert np.array_equal(g.indices, again.indices)
    with pytest.raises(ValueError, match="nnz"):
        power_law_graph(4, 13, seed=0)


@pytest.mark.parametrize("alpha", [0.0, 0.7])
def test_ell_bytes_matches_the_built_layout(alpha):
    g = power_law_graph(300, 2000, alpha=alpha, seed=1)
    cols, blocks = ell_from_csr(g, (8, 8))
    assert ell_bytes(g, (8, 8), (1, 1)) == cols.nbytes + blocks.nbytes
    # an (8, 8) f32 block takes an (8, 128) tile on the device
    assert ell_bytes(g, (8, 8)) == cols.nbytes + 16 * blocks.nbytes


def test_single_chip_phases(smoke):
    a = smoke.make_graph(256, 1200, smoke.POWER_LAW_ALPHA, seed=0)
    assert a.nnz == 1200 + 256
    b = smoke.dense(256, 16, seed=0)
    r = smoke.phase_spmm(a, b, calls=2)
    assert "matches reference" in r["lines"][-1]
    g = smoke.phase_gcn(r["handle"], n_feat=16, n_classes=4, steps=2, seed=0)
    assert len(g["losses"]) == 2 and np.all(np.isfinite(g["losses"]))
    u = smoke.make_graph(64, 200, 0.0, seed=0)
    p = smoke.phase_pallas(u, n_cols=16, n_feat=8, seed=0)
    assert sum("matches reference" in ln for ln in p["lines"]) == 4
    assert set(p["hlo"]) == {"bsr", "fused", "row_kernels"}
    # off a TPU the bsr backend resolves interpret mode; main() refuses that
    assert p["interpret"] == {"bsr": True}


def test_multichip_phase_pads_rows_and_spreads_shards(smoke):
    a = smoke.make_graph(101, 500, smoke.POWER_LAW_ALPHA, seed=0)
    b = smoke.dense(101, 8, seed=0)
    r = smoke.phase_multichip(a, b, 4)
    assert any("padded 101 -> 104" in ln for ln in r["lines"])
    assert sum("matches reference" in ln for ln in r["lines"]) == 3
    assert all(len(devs) == 4 for devs in r["shard_devices"].values())


def test_main_refuses_cpu(smoke, capsys, monkeypatch):
    monkeypatch.delenv("REPRO_PALLAS_INTERPRET", raising=False)
    with pytest.raises(SystemExit, match="no TPU"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_main_refuses_interpret_mode(smoke, capsys, monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    with pytest.raises(SystemExit, match="REPRO_PALLAS_INTERPRET"):
        smoke.main([])
    assert '"ok"' not in capsys.readouterr().out


def test_skipped_autotune_candidate_is_an_error(smoke):
    with warnings.catch_warnings():
        smoke.strict_autotune_warnings()
        with pytest.raises(UserWarning, match="autotune candidate"):
            warnings.warn("autotune candidate x failed to profile; skipping")


def test_compile_cache_dir(tmp_path, monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(CACHE_DIR_ENV, str(tmp_path / "from_env"))
        assert enable_compile_cache(tmp_path / "default") == str(
            tmp_path / "from_env")
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv(CACHE_DIR_ENV)
        assert enable_compile_cache(tmp_path / "default") == str(
            tmp_path / "default")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "default")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

