"""The served call's host spans, the guard's byte counter and device probe,
the executors' named scope and the kernels' names (core.trace, core.api,
robustness.guards, core.dist_spmm, kernels/)."""
import ast
import contextlib
import re
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import api
from repro.core.api import SpmmConfig, compile_spmm
from repro.core.sparse import power_law_graph
from repro.core.trace import STEPS
from repro.distributed.topology import Topology
from repro.robustness import guards

KERNELS = Path(api.__file__).resolve().parents[1] / "kernels"
N = 16


@pytest.fixture(scope="module")
def graph():
    return power_law_graph(256, 2000, seed=3)


@pytest.fixture(scope="module")
def b(graph):
    return np.random.default_rng(0).standard_normal((graph.shape[1], N)).astype(np.float32)


class _Recorder:
    """Stands in for ``core.trace.span``: records each span's name and the
    numbers set on it."""

    def __init__(self):
        self.names, self.stats = [], []

    def __call__(self, name):
        rec = self

        class _Span:
            def __enter__(self):
                rec.names.append(name)
                return self

            def __exit__(self, *exc):
                return False

            def set_metadata(self, **kw):
                rec.stats.append((name, kw))

        return _Span()


@pytest.mark.parametrize("check,spans", [
    (False, ["shiro.dispatch"]),
    ("auto", ["shiro.dispatch", "shiro.wait", "shiro.guard"]),
])
def test_call_spans_and_guard_bytes(graph, b, monkeypatch, check, spans):
    h = compile_spmm(graph, 1, SpmmConfig(check=check))
    rec = _Recorder()
    monkeypatch.setattr(api, "span", rec)
    for i in range(3):
        h(b)
        assert rec.names == spans * (i + 1)
        want = (i + 1) * PROBE_BYTES if check else 0
        assert h.guard_host_bytes == want
        assert h.stats()["guard_host_bytes"] == want
    assert h.stats()["calls"] == 3
    assert rec.stats == ([("shiro.guard", {"host_bytes": PROBE_BYTES})] * 3 if check else [])


def test_traced_call_opens_no_span(graph, b, monkeypatch):
    h = compile_spmm(graph, 1)
    rec = _Recorder()
    monkeypatch.setattr(api, "span", rec)
    jax.jit(lambda x: h(x))(b).block_until_ready()
    assert rec.names == [] and h.guard_host_bytes == 0


def test_spans_leave_c_bit_identical(graph, b, tmp_path):
    on = compile_spmm(graph, 1)
    off = compile_spmm(graph, 1, SpmmConfig(check=False))
    c_plain = np.asarray(off(b))
    jax.profiler.start_trace(str(tmp_path))
    try:
        c_traced = np.asarray(on(b))
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(c_traced, c_plain)
    np.testing.assert_array_equal(np.asarray(on(b)), c_plain)


@pytest.mark.parametrize("config", [
    SpmmConfig(schedule="single"),
    SpmmConfig(schedule=2, overlap=False),
    SpmmConfig(schedule=2, overlap=True),
    SpmmConfig(hier=(2, 2), schedule=2, overlap=False),
    SpmmConfig(hier=(2, 2), schedule=2, overlap=True),
    SpmmConfig(hier=(2, 2), schedule="single"),
    SpmmConfig(replicate=2),
], ids=["single", "bucketed", "overlap", "hier", "hier-overlap", "hier-single", "replicated"])
def test_scopes_are_metadata_only(graph, monkeypatch, config):
    """The executors' named scopes, ``shiro.spmm`` and its steps, reach the
    HLO's op_name metadata and change nothing else of the compiled
    program."""
    where = Topology.local(4)
    scoped = compile_spmm(graph, where, config)
    text = scoped._executable(N, np.float32, scoped.default_backend).as_text()
    assert 'op_name="jit(call)/shiro.spmm/' in text
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        bare = compile_spmm(graph, where, config)
        bare_text = bare.lowered_hlo(N)
    assert "shiro.spmm" not in bare._executable(N, np.float32, bare.default_backend).as_text()
    assert scoped.lowered_hlo(N) == bare_text


# every shard_map body of the executors, by the handle options that select
# it at P=4, and the (strategy, schedule, overlap) the handle then reports
BODIES = {
    "single": ({"schedule": "single"}, ("flat", "single", False)),
    "bucketed": ({"schedule": 2, "overlap": False}, ("flat", "bucketed", False)),
    "overlap": ({"schedule": 2, "overlap": True}, ("flat", "bucketed", True)),
    "hier-single": ({"hier": (2, 2), "schedule": "single"}, ("hier", "single", False)),
    "hier": ({"hier": (2, 2), "schedule": 2, "overlap": False}, ("hier", "bucketed", False)),
    "hier-overlap": ({"hier": (2, 2), "schedule": 2, "overlap": True},
                     ("hier", "bucketed", True)),
    "replicated": ({"replicate": 2}, ("replicated", "replicated", False)),
}


@pytest.mark.parametrize("body", list(BODIES))
def test_every_executor_body_names_its_steps(graph, b, body):
    """At P=4 each body runs pack, exchange, compute and aggregate under
    scopes of those names below ``shiro.spmm``, and C still matches the
    float32 reference."""
    options, want = BODIES[body]
    h = compile_spmm(graph, Topology.local(4), **options)
    st = h.stats()
    assert (h.strategy, st["schedule_kind"], st["overlap"]) == want
    text = h._executable(N, np.float32, h.default_backend).as_text()
    names = set(re.findall(r'op_name="(jit\(call\)/shiro\.spmm/[^"]*)"', text))
    for step in STEPS:
        assert any(f"/{step}/" in name for name in names), step
    ref = graph.to_dense().astype(np.float64) @ b
    np.testing.assert_allclose(np.asarray(h(b)), ref, rtol=2e-4, atol=2e-4)


def test_every_pallas_call_is_named():
    names = []
    for path in sorted(KERNELS.glob("*.py")):
        for node in ast.walk(ast.parse(path.read_text())):
            if (isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "pallas_call"):
                kw = {k.arg: k.value for k in node.keywords}
                assert "name" in kw, f"{path.name}:{node.lineno} pallas_call has no name="
                names += [c.value for c in ast.walk(kw["name"])
                          if isinstance(c, ast.Constant) and isinstance(c.value, str)]
    assert sorted(names) == sorted(["gather_rows", "scatter_add_rows", "bsr_spmm",
                                    "bsr_spmm_acc", "bsr_sddmm", "rmsnorm"])


# bytes of one probe result: the int32 (row, col) pair and a float32 value
PROBE_BYTES = 2 * 4 + 4
COLLECTIVES = ("all-gather", "all-reduce", "all-to-all", "collective-permute",
               "reduce-scatter", "send(", "recv(")


def _where(P):
    return P if P == 1 else Topology.local(P)


@pytest.mark.parametrize("P", [1, 4])
def test_served_call_reads_back_only_the_probe(graph, P):
    """The guard reads a few scalars per shard, whatever C's size."""
    h = compile_spmm(graph, _where(P))
    read = []
    for n in (N, 8 * N):
        wide = np.random.default_rng(n).standard_normal((graph.shape[1], n)).astype(np.float32)
        before = h.guard_host_bytes
        c = h(wide)
        read.append(h.guard_host_bytes - before)
        assert len(c.addressable_shards) == P
    assert read[0] == read[1] == P * PROBE_BYTES < 1024


@pytest.mark.parametrize("mode", ["auto", "full"])
def test_probe_runs_on_each_shard_with_no_collective(graph, b, mode):
    """At P=4 each shard is reduced on its own device, by a program with no
    collective: no byte of C moves between chips."""
    h = compile_spmm(graph, Topology.local(4), SpmmConfig(check=mode))
    c = h(b)
    probes = guards.probe_finite(c, mode=mode)
    assert len(probes) == 4
    for shard, p in zip(c.addressable_shards, probes):
        assert p.found.devices() == p.value.devices() == {shard.device}
        text = guards._device_probe().lower(
            shard.data, full=mode == "full", vector_is_row=True).compile().as_text()
        assert not [op for op in COLLECTIVES if op in text], text
