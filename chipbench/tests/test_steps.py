"""The executors' steps in a trace (``chipbench/steps.py``) and the readers
of the exchange layer, on a toy four-chip cell run through the harness on
CPU devices."""
import contextlib
import json
import time

import jax
import numpy as np
import pytest

from chipbench import harness, scopes, steps
from chipbench.tests import toy
from chipbench.tests.test_scopes import COPY, FUSION, SORT, _hlo, _pb, _plane
from repro.core.api import compile_spmm
from repro.core.sparse import power_law_graph
from repro.distributed.topology import Topology

CPU_PEAKS = {"flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
READERS = ("pack_ms.spmm", "aggregate_ms.spmm", "collective_ms", "exchange_rows")
CELL = "toy-steps-p4"


def _execute(bench, cell, trace=True):
    return harness.execute(bench, cell, 2**31 + 23, 0.3, trace, t_start=time.perf_counter(),
                           devs=jax.devices(), peaks=CPU_PEAKS)


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    """The toy bench with one more four-chip cell, added as a file and
    entries alone, that reports the exchange layer's readers; the toy
    one-chip cell reports them too."""
    root = toy.make_toy_bench(tmp_path_factory.mktemp("bench"))
    (root / "chipbench" / "workloads" / f"{CELL}.json").write_text(
        json.dumps({"limits": {"c_gap": 2e-4}}))
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"].append({"name": CELL, "config": "toy-powerlaw-4chips",
                              "traffic": "closed-1caller-4b", "chips": 4, "why": "toy"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if m["name"] == "spmm_ms":
            m["workloads"].append(CELL)
        elif m["name"] in READERS:
            m["workloads"] += [CELL, "toy-spmm-p1"]
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return harness.Bench(root)


@pytest.fixture(scope="module")
def traced(bench):
    """(result line, the readers' record of its trace) of one traced run of
    the four-chip cell."""
    r = _execute(bench, CELL)
    found = sorted((bench.dir / ".traces" / CELL).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    rec = {"chips": 4, "unit": "call", "attempted": r["attempted"], "failed": r["failed"],
           "trace": {"file": str(found[-1])}}
    return r, rec


def test_exchange_readers_on_a_four_chip_run(traced):
    r, _ = traced
    assert r["correct"] is True, r["checks"]
    assert set(r["metrics"]) == {"plan_s", *READERS}  # a traced run's: the layers
    for metric in READERS:
        assert r["metrics"][metric]["value"] > 0, metric


def test_steps_sum_within_the_executor_scope(traced):
    _, rec = traced
    by_step = steps.of(rec)["step_s"]
    by_scope = scopes.of(rec)["scope_s"]
    assert set(by_step) == set(by_scope) == set(range(4))
    for dev, d in by_step.items():
        assert {step for scope, step in d if scope == "spmm" and step} == set(steps.STEPS)
        in_steps = sum(s for (scope, step), s in d.items() if scope == "spmm" and step)
        assert 0 < in_steps <= by_scope[dev]["spmm"] * (1 + 1e-9)
        # each op of the scope counts once: in one of its steps or in none
        assert sum(s for (scope, _), s in d.items() if scope == "spmm") == pytest.approx(
            by_scope[dev]["spmm"], rel=1e-9)


def test_readers_are_silent_on_one_chip(bench):
    r = _execute(bench, "toy-spmm-p1")
    assert r["correct"] is True
    assert not set(READERS) & set(r["metrics"])


def test_program_without_step_names_reads_none(bench, tmp_path, monkeypatch):
    """A four-chip program that names no steps (a parent of the step
    scopes): the step readers return None and do not raise."""
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    g = power_law_graph(256, 2000, seed=3)
    h = compile_spmm(g, Topology.local(4))
    b = jax.numpy.asarray(np.random.default_rng(0).standard_normal((256, 16)), np.float32)
    h(b).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(3):
                h(b).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    rec = {"chips": 4, "unit": "call", "attempted": 3, "failed": 0,
           "trace": {"file": str(sorted(tmp_path.rglob("*.xplane.pb"))[-1])}}
    red = steps.of(rec)
    assert all(step is None for d in red["step_s"].values() for _, step in d)
    for metric in ("pack_ms.spmm", "aggregate_ms.spmm"):
        assert bench.reader(metric)(rec) is None


@pytest.mark.parametrize("op_name,want", [
    ("jit(call)/shiro.spmm/shard_map/pack/gather", "pack"),
    ("jit(call)/shiro.spmm/shard_map/exchange/ppermute", "exchange"),
    ("jit(step)/transpose(jvp(shiro.spmm))/shard_map/compute/mul", "compute"),
    ("jit(call)/shiro.spmm/shard_map/aggregate/pallas_call", "aggregate"),
    ("jit(call)/shiro.spmm/shard_map/reshape", None),
    ("jit(call)/pack/gather", None),
    ("", None),
])
def test_step_of(op_name, want):
    assert steps.step_of(op_name) == want


def test_tpu_layout(tmp_path):
    """Op names from the HLO stored in the trace and from the event's own
    stats, as a TPU's trace holds them: window 0–1000 ns, fusion.3
    210–400 under ``pack``, sort.1 410–480 under ``aggregate``, copy.2
    860–880 in no scope."""
    host = _plane("/host:CPU", [("python", [("window", 0, 1000, []), ("call", 100, 900, [])])],
                  ["window", "call"])
    device = _plane("/device:TPU:0", [
        ("XLA Modules", [("jit_call(7)", 205, 890, [])]),
        ("XLA Ops", [(FUSION, 210, 400, []),
                     (SORT, 410, 480,
                      [("tf_op", "jit(call)/shiro.spmm/shard_map/aggregate/sort")]),
                     (COPY, 860, 880, [])])],
        ["jit_call(7)", FUSION, SORT, COPY], ["tf_op"])
    hlo = _hlo("jit_call", [("fusion.3", "jit(call)/shiro.spmm/shard_map/pack/gather"),
                            ("sort.1", ""), ("copy.2", "jit(call)/copy")])
    meta = _plane("/host:metadata", [], ["jit_call(7)"], ["Hlo Proto"],
                  {"jit_call(7)": [("Hlo Proto", hlo)]})
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, host), (1, device), (1, meta)))
    assert steps.reduce(path)["step_s"] == {0: pytest.approx(
        {("spmm", "pack"): 190e-9, ("spmm", "aggregate"): 70e-9, ("other", None): 20e-9})}
    rec = {"chips": 4, "unit": "call", "attempted": 1, "failed": 0,
           "trace": {"file": str(path)}}
    assert steps.step_ms(rec, "spmm", "pack", "call") == pytest.approx(190e-9 * 1e3 / 4)
    assert steps.step_ms(rec, "spmm", "exchange", "call") is None
