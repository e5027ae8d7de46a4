"""The program's names in a trace (``chipbench/scopes.py``), on traces that
the test records on CPU devices, and the readers of the five metrics that
read them."""
import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from chipbench import harness, scopes, xplane
from repro.core.api import SpmmConfig, compile_spmm, make_spmm_fn
from repro.core.sparse import power_law_graph
from repro.distributed.topology import Topology

N = 16
SPANS = {"shiro.dispatch", "shiro.wait", "shiro.guard"}
READERS = {"guard_ms.spmm": "call", "dispatch_ms.spmm": "call", "wait_ms.spmm": "call",
           "guard_mb.spmm": "call", "executor_ms.gcn": "step"}


def _record(path, fn, unit="call", units=3):
    fn()
    jax.profiler.start_trace(str(path))
    try:
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(units):
                with jax.profiler.TraceAnnotation(unit):
                    fn()
    finally:
        jax.profiler.stop_trace()
    return path


@pytest.fixture(scope="module")
def graph():
    return power_law_graph(256, 2000, seed=3)


@pytest.fixture(scope="module")
def b(graph):
    return jax.numpy.asarray(
        np.random.default_rng(0).standard_normal((graph.shape[1], N)), np.float32)


@pytest.fixture(scope="module", params=["p1", "p4-bucketed"])
def served(request, graph, b, tmp_path_factory):
    """(trace path, handle, C) of three served calls."""
    if request.param == "p1":
        h = compile_spmm(graph, 1)
    else:
        h = compile_spmm(graph, Topology.local(4), SpmmConfig(schedule=2, overlap=False))
        assert h.stats()["schedule_kind"] == "bucketed"
    c = h(b)
    path = _record(tmp_path_factory.mktemp(request.param), lambda: h(b).block_until_ready())
    return path, h, c


def test_served_call_scopes_and_spans(served):
    path, h, c = served
    red = scopes.reduce(path)
    assert set(red["scope_s"]) == set(range(h.plan.P))
    for by_scope in red["scope_s"].values():
        assert by_scope.get("spmm", 0) > 0 and set(by_scope) <= {"spmm", "other"}
    assert set(red["program_spans_s"]) == SPANS
    assert all(v > 0 for v in red["program_spans_s"].values())
    assert red["span_stats"]["shiro.guard"]["host_bytes"] == 3 * c.nbytes


def test_existing_reduction_unchanged(served):
    """``xplane.reduce`` keeps its keys; ``scope_s`` splits its ``op_s``."""
    path = served[0]
    old = xplane.reduce(path, ("call",), outside="between_calls")
    assert set(old) == {"window_s", "devices", "file"}
    new = scopes.reduce(path)
    for dev, d in old["devices"].items():
        assert set(d) == {"busy_s", "op_s", "collective_s", "other_s", "gaps"}
        assert sum(new["scope_s"][dev].values()) == pytest.approx(
            sum(d["op_s"].values()), rel=1e-9)


def test_backward_lands_under_the_executor_scope(graph, b, tmp_path):
    """A ``jax.grad`` through ``make_spmm_fn``: the transposed ops carry
    ``transpose(jvp(shiro.spmm))`` and count as the executor's."""
    spmm = make_spmm_fn(compile_spmm(graph, 1))
    w = jax.numpy.ones((N, N), np.float32)
    step = jax.jit(jax.grad(lambda w, x: (spmm(x @ w) ** 2).sum()))
    text = step.lower(w, b).compile().as_text()
    assert "transpose(jvp(shiro.spmm))" in text
    red = scopes.reduce(_record(tmp_path, lambda: step(w, b).block_until_ready(), "step"))
    by_scope = red["scope_s"][0]
    assert by_scope.get("spmm", 0) > 0 and by_scope.get("other", 0) > 0
    # backward ops ran, and their scope is the executor's
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    backward = {name: scopes.scope_of(op) for table in scopes.hlo_op_names(path).values()
                for name, op in table.items() if "transpose(jvp(shiro.spmm))" in op}
    ran = {e.name for plane in ProfileData.from_file(str(path)).planes
           for line in plane.lines for e in line.events}
    assert set(backward) & ran
    assert set(backward.values()) == {"spmm"}


@pytest.mark.parametrize("op_name,want", [
    ("jit(call)/shiro.spmm/compute.diag/scatter-add", "spmm"),
    ("jit(call)/shiro.spmm/shard_map/exchange.b.r1/ppermute", "spmm"),
    ("jit(step)/transpose(jvp(shiro.spmm))/compute.diag/mul", "spmm"),
    ("jit(step)/jvp(shiro.spmm)/aggregate/custom_jvp_call/pjit(f)/gather", "spmm"),
    ("jit(call)/shiro.spmm/gather/all_gather", "spmm"),
    ("jit(call)/shiro.spmm/reshape", "spmm"),
    ("jit(step)/jvp(gcn)/dot_general", "other"),
    ("", "other"),
])
def test_scope_of(op_name, want):
    assert scopes.scope_of(op_name) == want


# --- readers ---------------------------------------------------------------

def _bench():
    return harness.Bench(harness.HERE.parent)


def _record_of(path, unit, units=3):
    return {"unit": unit, "chips": 1, "attempted": units, "failed": 0,
            "trace": {"file": str(sorted(path.rglob("*.xplane.pb"))[-1])}}


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_is_silent_without_its_trace(metric):
    read = _bench().reader(metric)
    unit = READERS[metric]
    other = {"call": "step", "step": "call"}[unit]
    assert read({"unit": unit, "chips": 1, "attempted": 3, "failed": 0, "trace": None}) is None
    assert read({"unit": other, "chips": 1, "attempted": 3, "failed": 0,
                 "trace": {"file": "missing.xplane.pb"}}) is None


def test_readers_on_a_served_trace(served):
    path, h, c = served
    rec = _record_of(path, "call")
    bench = _bench()
    assert bench.reader("guard_mb.spmm")(rec) == pytest.approx(c.nbytes / 1e6)
    for metric in ("guard_ms.spmm", "dispatch_ms.spmm", "wait_ms.spmm"):
        assert bench.reader(metric)(rec) > 0
    assert bench.reader("executor_ms.gcn")(rec) is None  # a served cell's unit is the call


def test_readers_find_nothing_in_a_program_without_names(tmp_path):
    """A trace with no program spans and no executor scope (a parent of
    this instrumentation) gives None, and does not raise."""
    f = jax.jit(lambda x: (x @ x.T).sum())
    x = jax.numpy.ones((64, 64))
    path = _record(tmp_path, lambda: f(x).block_until_ready())
    bench = _bench()
    for metric, unit in READERS.items():
        assert bench.reader(metric)(_record_of(path, unit)) is None


# --- a trace laid out as a TPU's, written by hand --------------------------

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _pb(*fields):
    """A protocol buffer message from (field number, int | str | bytes)."""
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += _varint(num << 3) + _varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += _varint(num << 3 | 2) + _varint(len(v)) + v
    return out


def _plane(name, lines, event_names, stat_names=(), event_stats=None):
    """An XPlane: ``lines`` is [(line name, [(event name, start_ns, end_ns,
    [(stat name, int | str)])])]; ``event_stats`` adds stats to the
    metadata of named events."""
    ids = {n: i + 1 for i, n in enumerate(event_names)}
    sids = {n: i + 1 for i, n in enumerate(stat_names)}

    def stat(k, v):
        return _pb((1, sids[k]), (4, v) if isinstance(v, int) else (6 if isinstance(v, bytes)
                                                                   else 5, v))

    fields = [(1, 1), (2, name)]
    for j, (line, events) in enumerate(lines):
        evs = [(4, _pb((1, ids[n]), (2, s * 1000), (3, (e - s) * 1000),
                       *[(4, stat(k, v)) for k, v in st]))
               for n, s, e, st in events]
        fields.append((3, _pb((1, j + 1), (2, line), (3, 0), *evs)))
    for n, i in ids.items():
        meta = [(1, i), (2, n)] + [(5, stat(k, v)) for k, v in (event_stats or {}).get(n, ())]
        fields.append((4, _pb((1, i), (2, _pb(*meta)))))
    for n, i in sids.items():
        fields.append((5, _pb((1, i), (2, _pb((1, i), (2, n))))))
    return _pb(*fields)


def _hlo(module, instructions):
    """An HloProto with one computation of (name, op_name) instructions."""
    instrs = [(2, _pb((1, n), (2, "fusion"), (7, _pb((2, op))), (35, i + 1)))
              for i, (n, op) in enumerate(instructions)]
    return _pb((1, _pb((1, module), (3, _pb((1, "main"), *instrs, (5, 1), (6, 1))))))


FUSION = "%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(f32[8,128]{1,0} %p), kind=kLoop"
SORT = "%sort.1 = (s32[64]{0}, s32[64]{0}) sort(s32[64]{0} %a, s32[64]{0} %b)"
COPY = "%copy.2 = f32[8,128]{1,0} copy(f32[8,128]{1,0} %c)"


@pytest.fixture(scope="module")
def tpu_trace(tmp_path_factory):
    """Window 0–1000 ns; a call 100–900 split into dispatch 100–200, wait
    200–500 and guard 500–850 (host_bytes 4096). Device ops of program 7:
    fusion.3 210–400 (its op_name from the HLO), sort.1 410–480 (its
    op_name on the event, tf_op), copy.2 860–880 (no scope)."""
    host = _plane("/host:CPU", [("python", [
        ("window", 0, 1000, []), ("call", 100, 900, []),
        ("shiro.dispatch", 100, 200, []), ("shiro.wait", 200, 500, []),
        ("shiro.guard", 500, 850, [("host_bytes", 4096)])])],
        ["window", "call", "shiro.dispatch", "shiro.wait", "shiro.guard"], ["host_bytes"])
    device = _plane("/device:TPU:0", [
        ("XLA Modules", [("jit_call(7)", 205, 890, [])]),
        ("XLA Ops", [(FUSION, 210, 400, []),
                     (SORT, 410, 480, [("tf_op", "jit(call)/shiro.spmm/sort")]),
                     (COPY, 860, 880, [])])],
        ["jit_call(7)", FUSION, SORT, COPY], ["tf_op"])
    hlo = _hlo("jit_call", [("fusion.3", "jit(call)/jvp(shiro.spmm)/mul"),
                            ("sort.1", ""), ("copy.2", "jit(call)/copy")])
    meta = _plane("/host:metadata", [], ["jit_call(7)"], ["Hlo Proto"],
                  {"jit_call(7)": [("Hlo Proto", hlo)]})
    path = tmp_path_factory.mktemp("tpu") / "t.xplane.pb"
    path.write_bytes(_pb((1, host), (1, device), (1, meta)))
    return path


def test_tpu_layout(tpu_trace):
    red = scopes.reduce(tpu_trace)
    names = scopes.hlo_op_names(tpu_trace)
    assert set(names) == {7, "jit_call"}
    assert names[7]["fusion.3"] == "jit(call)/jvp(shiro.spmm)/mul"
    assert red["scope_s"] == {0: pytest.approx({"spmm": 260e-9, "other": 20e-9})}
    assert red["program_spans_s"] == pytest.approx(
        {"shiro.dispatch": 100e-9, "shiro.wait": 300e-9, "shiro.guard": 350e-9})
    assert red["span_stats"]["shiro.guard"] == {"host_bytes": 4096}
    old = xplane.reduce(tpu_trace, ("call",), outside="between_calls")["devices"][0]
    assert sum(red["scope_s"][0].values()) == pytest.approx(sum(old["op_s"].values()))
