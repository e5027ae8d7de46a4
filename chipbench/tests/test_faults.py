"""A run with the timed path broken underneath must read ``correct`` false,
once for each fault that a cell can have; and the control, the reference
one precision step below in the program's place, must fail a limit."""
import time

import jax
import jax.numpy as jnp
import pytest

import repro.core.dist_spmm as dist_spmm
import repro.models.gnn as gnn
from chipbench import calibrate, harness, reference
from chipbench.tests import toy
from chipbench.tests.test_harness import CPU_PEAKS
from repro.core.local_backend import CooBackend


@pytest.fixture(scope="module")
def bench(tmp_path_factory):
    return harness.Bench(toy.make_toy_bench(tmp_path_factory.mktemp("bench")))


def _wrap_compute(monkeypatch, alter):
    orig = CooBackend.compute
    monkeypatch.setattr(CooBackend, "compute",
                        lambda self, piece, b, m_out: alter(orig(self, piece, b, m_out)))


def answer_altered(monkeypatch):
    _wrap_compute(monkeypatch, lambda c: c.at[0, 0].add(1.0))


def half_the_rows_left_out(monkeypatch):
    _wrap_compute(monkeypatch, lambda c: c.at[c.shape[0] // 2:].set(0.0))


def exchange_left_out(monkeypatch):
    for name in ("ppermute", "all_to_all"):
        monkeypatch.setattr(dist_spmm, name, lambda x, *a, **k: jnp.zeros_like(x))


def state_unchanged(monkeypatch):
    orig = gnn.gcn_loss
    monkeypatch.setattr(gnn, "gcn_loss", lambda *a: jax.lax.stop_gradient(orig(*a)))


def loss_altered(monkeypatch):
    orig = gnn.gcn_loss
    monkeypatch.setattr(gnn, "gcn_loss", lambda *a: orig(*a) * (1 + reference.ANSWER_FAULT))


def half_the_batch_left_out(monkeypatch):
    def loss(params, feats, labels, spmm_fn):
        logits = gnn.gcn_forward(params, feats, spmm_fn)
        per_node = (jax.nn.logsumexp(logits, axis=-1)
                    - jnp.take_along_axis(logits, labels[:, None], 1)[:, 0])
        return jnp.mean(per_node[: per_node.shape[0] // 2])

    monkeypatch.setattr(gnn, "gcn_loss", loss)


FAULTS = [
    ("toy-spmm-p1", answer_altered), ("toy-spmm-p1", half_the_rows_left_out),
    ("toy-spmm-p4", exchange_left_out), ("toy-spmm-p4", answer_altered),
    ("toy-mesh-p1", answer_altered),
    ("toy-gcn-p1", state_unchanged), ("toy-gcn-p1", half_the_batch_left_out),
    ("toy-gcn-p1", loss_altered),
]


@pytest.mark.parametrize("cell,fault", FAULTS, ids=[f"{c}-{f.__name__}" for c, f in FAULTS])
def test_fault_reads_not_correct(bench, monkeypatch, cell, fault):
    fault(monkeypatch)
    r = harness.execute(bench, cell, 7, 0.2, False, t_start=time.perf_counter(),
                        devs=jax.devices(), peaks=CPU_PEAKS)
    assert r["correct"] is False, r["checks"]


@pytest.mark.parametrize("cell", [t["name"] for t in toy.TOY_CELLS])
def test_control_fails_a_limit(bench, cell):
    out = calibrate.calibrate(bench, cell, [3, 4], 0.1, say=lambda _s: None)
    limits = bench.data("workloads", cell)["limits"]
    assert all(v <= limits[k] for k, v in out["lower"].items()), out
    assert any(v > limits[k] for k, v in out["upper"]["control"].items()), out
    for fault, readings in out["upper"].items():
        assert any(v > limits[k] for k, v in readings.items()), (fault, out)
