"""Work counts and the peaks table."""
import pytest

from chipbench import work


def test_spmm_work_of_a_4x4_operand():
    # A = [[1, 2, 0, 0], [0, 0, 3, 0], [0, 0, 0, 0], [4, 0, 0, 5]]: 5 nonzeros
    w = work.spmm_work(nnz=5, m=4, k=4, n=3)
    assert w["flops"] == 2 * 5 * 3
    assert w["bytes"] == 12 * 5 + 4 * 3 * (4 + 4)


def test_gcn_step_work_by_hand():
    # 4 nodes, 5 nonzeros, widths 2 -> 3 -> 2
    w = work.gcn_step_work(nnz=5, nodes=4, widths=[2, 3, 2])
    spmm3, spmm2 = work.spmm_work(5, 4, 4, 3), work.spmm_work(5, 4, 4, 2)
    flops = 2 * (spmm3["flops"] + spmm2["flops"])
    flops += 2 * (2 * 4 * 2 * 3)          # layer 0: h W and dW
    flops += 3 * (2 * 4 * 3 * 2)          # layer 1: h W, dW and dh
    bytes_ = 2 * (spmm3["bytes"] + spmm2["bytes"])
    bytes_ += 2 * 4 * 4 * (2 + 3) + 3 * 4 * 4 * (3 + 2)
    assert w == {"flops": flops, "bytes": bytes_}


def test_peaks_of_v5e_and_unknown_kind():
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert p["hbm_bytes"] == 16e9 and p["ici_bits_per_s"] == 1600e9
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("cpu")


def test_least_time_names_its_bound():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_time_s({"flops": 100.0, "bytes": 100.0}, peak, 2) == (5.0, "hbm_bytes")
    assert work.least_time_s({"flops": 4000.0, "bytes": 10.0}, peak, 4) == (10.0, "flops")
