"""The trace reduction, on a trace that the test records on 4 CPU devices."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec

from chipbench import xplane
from repro.compat import ppermute, shard_map


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))

    def body(a):
        b = ppermute(a @ a.T, "x", [(i, (i + 1) % 4) for i in range(4)])
        return jax.lax.psum(b, "x")

    f = jax.jit(shard_map(body, mesh=mesh, in_specs=PartitionSpec("x"),
                          out_specs=PartitionSpec("x")))
    a = jnp.ones((4 * 256, 256))
    f(a).block_until_ready()
    d = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(d))
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("call"):
                f(a).block_until_ready()
    jax.profiler.stop_trace()
    return xplane.reduce(d, ("call",), outside="between_calls")


def test_busy_and_idle_fill_the_window(trace):
    assert set(trace["devices"]) == {0, 1, 2, 3}
    for d in trace["devices"].values():
        idle = sum(s for _, s in d["gaps"])
        assert d["busy_s"] > 0
        assert d["busy_s"] + idle == pytest.approx(trace["window_s"], rel=1e-6)
        assert {name for name, _ in d["gaps"]} <= {"call", "between_calls"}
        assert sum(d["op_s"].values()) == pytest.approx(d["collective_s"] + d["other_s"])
        assert len(d["op_s"]) > 1


def test_collectives_are_classified(trace):
    for d in trace["devices"].values():
        assert d["collective_s"] > 0 and d["other_s"] > 0


TPU_EVENTS = [  # names of op events as a TPU trace gives them
    ("%fusion.1 = f32[169343,128]{1,0:T(8,128)S(1)} fusion(s32[1335586]{0:T(1024)} %x, "
     "f32[]{:T(128)} %c), kind=kCustom, calls=%fused_computation.3", "", False),
    ("%all-to-all.2 = f32[4,4,128]{2,1,0:T(8,128)} all-to-all(f32[4,4,128]{2,1,0} %p), "
     "replica_groups={{0,1,2,3}}, dimensions={0}", "", True),
    ("%collective-permute-start.1 = (f32[8,128]{1,0}, f32[8,128]{1,0}) "
     "collective-permute-start(f32[8,128]{1,0} %a), source_target_pairs={{0,1}}", "", True),
    ("%slice-start = ((s32[1,133]{1,0:T(1,128)}), s32[1,33]{1,0:T(1,128)S(1)}, s32[]{:S(2)}) "
     "async-start(s32[1,133]{1,0:T(1,128)} %c), calls=%async_computation", "slice-done", False),
    ("%all-gather-start = (f32[8]{0}, f32[32]{0}) async-start(f32[8]{0} %a), "
     "calls=%async_computation.2", "all-gather-done", True),
]


@pytest.mark.parametrize("name,hlo_op,want", TPU_EVENTS + [
    ("all-to-all.3", "", True), ("collective-permute-done", "", True),
    ("all-reduce-start.2", "", True), ("reduce-scatter.7", "", True),
    ("fusion.12", "", False), ("copy-start.1", "", False),
    ("ppermute.18", "ppermute", True), ("dot.1", "dot", False),
])
def test_opcode_classification(name, hlo_op, want):
    assert xplane.is_collective(name, hlo_op) is want


def test_short_names_of_tpu_events():
    assert xplane.describe(TPU_EVENTS[0][0])[0] == "fusion.1 (fusion f32[169343,128])"
