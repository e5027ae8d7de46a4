"""The harness end to end on the CPU at toy sizes: every traffic kind, both
kinds of run, the result line, and pieces found by name."""
import hashlib
import json
import os
import subprocess
import sys
import time

import jax
import pytest

from chipbench import harness
from chipbench.tests import toy

CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return harness.Bench(toy.make_toy_bench(tmp_path_factory.mktemp("bench")))


def execute(bench, cell, trace, seconds=0.3, seed=2**31 + 11):
    return harness.execute(bench, cell, seed, seconds, trace, t_start=time.perf_counter(),
                           devs=jax.devices(), peaks=CPU_PEAKS)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", [t["name"] for t in toy.TOY_CELLS])
def test_cell_run(bench, cell, trace):
    r = execute(bench, cell, bool(trace))
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    want = {m["name"] for m in bench.metrics(cell, bool(trace))}
    assert set(r["metrics"]) == want
    assert all(m["value"] is not None for m in r["metrics"].values())
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == set(bench.data("workloads", cell)["limits"])
    dev = r["device"]
    assert dev["count"] == bench.cell(cell)["chips"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(dev)
    if trace:
        assert 0 < dev["busy_s"] <= dev["window_s"]
        assert 0 < len(r["breakdown"]["device_ops"]) <= 10
        assert 0 < len(r["breakdown"]["idle_gaps"]) <= 10
        for pct in ("spmm_roofline", "gcn_roofline", "idle_share.spmm", "idle_share.gcn"):
            if pct in r["metrics"]:
                assert 0 <= r["metrics"][pct]["value"] <= 100
    else:
        assert "breakdown" not in r
    json.dumps(r, allow_nan=False)


def _digests(root):
    return {p: hashlib.sha256(p.read_bytes()).hexdigest()
            for p in (root / "chipbench").rglob("*")
            if p.is_file() and "__pycache__" not in p.parts and ".operands" not in p.parts}


def test_new_pieces_found_by_name(tmp_path):
    """A configuration, a traffic mix, a cell and a metric are added as new
    files and BENCHMARK.json entries; no file of the benchmark changes."""
    root = toy.make_toy_bench(tmp_path)
    before = _digests(root)
    d = root / "chipbench"
    (d / "configs" / "toy-new.json").write_text(json.dumps(
        dict(toy.TOY_CONFIGS["toy-powerlaw"], num_nodes=300, num_edges=1500)))
    (d / "traffic" / "toy-closed-2b.json").write_text(json.dumps(
        {"loop": "serve", "operands": 2, "sample_calls": 2}))
    (d / "workloads" / "toy-new-cell.json").write_text(json.dumps({"limits": {"c_gap": 1e-5}}))
    (d / "metrics" / "toy_calls.py").write_text(
        "def read(rec):\n    return float(rec['attempted'])\n")
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "toy-new", "source": "toy",
                            "file": "chipbench/configs/toy-new.json", "reduced": [],
                            "why": "toy"})
    spec["workloads"].append({"name": "toy-new-cell", "config": "toy-new",
                              "traffic": "toy-closed-2b", "chips": 1, "why": "toy"})
    spec["end_to_end"][0]["workloads"].append("toy-new-cell")
    spec["per_layer"].append({"name": "toy_calls", "unit": "calls", "better": "higher",
                              "source": "host_clock", "layer": "device", "moves": "spmm_ms",
                              "workloads": ["toy-new-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    bench = harness.Bench(root)
    r = execute(bench, "toy-new-cell", True)
    assert r["correct"] and r["metrics"]["toy_calls"]["value"] == r["attempted"]
    assert "plan_s" in r["metrics"]
    after = _digests(root)
    assert all(after[p] == h for p, h in before.items())


def _run_py(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run([sys.executable, "chipbench/run.py", "--workload", "arxiv-spmm-p1",
                           "--seed", "1", "--seconds", "1", "--trace", "0"],
                          cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("env_extra", [{}, {"REPRO_PALLAS_INTERPRET": "1"},
                                       {"REPRO_MEASURE": "1"}])
def test_run_refuses_without_a_tpu(env_extra):
    p = _run_py(toy.ROOT, env_extra)
    assert p.returncode != 0 and p.stdout == ""
    assert "chipbench:" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files."""
    root = toy.make_toy_bench(tmp_path)
    p = _run_py(root, {})
    assert p.returncode != 0 and p.stdout == ""
