"""Fault-injection harness: every fault kind asserts its documented
response end-to-end.

  worker_kill / stalls   -> Supervisor restart, then rung degradation
                            (fake spawns — the recovery logic needs no
                            jax fleet)
  wave_error             -> SpmmWaveServer retry/backoff; dropped stays 0
  autotune_corrupt       -> torn cache entry warns + re-profiles
  torn_checkpoint        -> manifest verification names the damaged file
  nan_poison             -> check= guardrails raise NumericalFault (and
                            check=False demonstrably lets NaN through)

Plus the FaultPlan determinism contract (site/rank/epoch matching,
after/times windows, env round-trip) and the guards' unit behavior.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from repro.core.api import DistSpmm, SpmmConfig, compile_spmm
from repro.core.session import SpmmSession
from repro.launch import multiprocess as mp
from repro.robustness import (
    KILL_EXIT_CODE, Fault, FaultPlan, InjectedFault, NumericalFault, inject,
)
from repro.robustness import faults as faults_mod
from repro.robustness import guards
from repro.serving.scheduler import SpmmRequest, SpmmWaveServer


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv(faults_mod.FAULTS_ENV, raising=False)
    monkeypatch.delenv(faults_mod.EPOCH_ENV, raising=False)
    faults_mod.uninstall()
    yield
    faults_mod.uninstall()


def _b(k=64, n=16, seed=0):
    return np.random.default_rng(seed).standard_normal(
        (k, n)).astype(np.float32)


# ---------------------------------------------------------------------------
# FaultPlan determinism
# ---------------------------------------------------------------------------


def test_fault_window_after_times():
    plan = FaultPlan([Fault(kind="wave_error", site="s", after=1, times=2)])
    fired = [plan.take("wave_error", "s") is not None for _ in range(5)]
    assert fired == [False, True, True, False, False]
    assert plan.fired("wave_error") == 2


def test_fault_site_rank_epoch_matching():
    plan = FaultPlan([Fault(kind="worker_kill", site="stage:serve", rank=1)])
    assert plan.take("worker_kill", "stage:init", 1) is None
    assert plan.take("worker_kill", "stage:serve", 0) is None
    assert plan.take("worker_kill", "stage:serve", 1) is not None
    # wildcard site matches anywhere; a mismatched epoch never fires
    wild = FaultPlan([Fault(kind="wave_error")], epoch=0)
    assert wild.take("wave_error", "anything") is not None
    later = FaultPlan([Fault(kind="wave_error", epoch=1)], epoch=0)
    assert later.take("wave_error", "anything") is None


def test_fault_validation():
    with pytest.raises(ValueError, match="unknown fault kind"):
        Fault(kind="meteor_strike")
    with pytest.raises(ValueError, match="times >= 1"):
        Fault(kind="wave_error", times=0)
    with pytest.raises(ValueError, match="corruption mode"):
        Fault(kind="autotune_corrupt", mode="subtle")


def test_fault_plan_env_roundtrip(tmp_path):
    plan = FaultPlan([Fault(kind="wave_error", site="wave", times=3),
                      Fault(kind="worker_kill", rank=1, epoch=2)])
    spec = plan.to_env()
    back = FaultPlan.from_env({faults_mod.FAULTS_ENV: spec})
    assert [f.to_dict() for f in back.faults] == \
        [f.to_dict() for f in plan.faults]
    # @file indirection and the epoch env
    p = tmp_path / "plan.json"
    p.write_text(spec)
    back2 = FaultPlan.from_env({faults_mod.FAULTS_ENV: f"@{p}",
                                faults_mod.EPOCH_ENV: "2"})
    assert back2.epoch == 2
    assert back2.take("worker_kill", "stage:init", 1) is not None
    assert FaultPlan.from_env({}) is None
    with pytest.raises(ValueError, match="not valid JSON"):
        FaultPlan.from_env({faults_mod.FAULTS_ENV: "{nope"})


def test_env_activation_and_inject_restore(monkeypatch):
    monkeypatch.setenv(faults_mod.FAULTS_ENV,
                       '[{"kind": "wave_error", "site": "wave"}]')
    faults_mod.uninstall()  # force a re-read of the env
    env_plan = faults_mod.active_plan()
    assert env_plan is not None and env_plan.faults[0].kind == "wave_error"
    with inject([Fault(kind="collective_delay", delay=0.0)]) as plan:
        assert faults_mod.active_plan() is plan
    assert faults_mod.active_plan() is env_plan  # restored


# ---------------------------------------------------------------------------
# guards (unit)
# ---------------------------------------------------------------------------


def test_validate_dense_operand_messages():
    with pytest.raises(ValueError, match=r"must be 2-D"):
        guards.validate_dense_operand(np.ones(8, np.float32),
                                      k_expected=8, context="t")
    with pytest.raises(ValueError, match=r"64 rows .*K=32"):
        guards.validate_dense_operand(np.ones((64, 4), np.float32),
                                      k_expected=32, context="t")
    with pytest.raises(TypeError, match="floating point"):
        guards.validate_dense_operand(np.ones((8, 4), np.int32),
                                      k_expected=8, context="t")
    guards.validate_dense_operand(np.ones((8, 4), np.float32),
                                  k_expected=8, context="t")  # clean pass


def test_validate_dense_operand_is_tracer_safe():
    """Shape/dtype checks are static — they must run under jit tracing
    (grad through a guarded handle) without concretizing the tracer."""
    import jax
    import jax.numpy as jnp

    def f(b):
        guards.validate_dense_operand(b, k_expected=8, context="t")
        return b.sum()

    jax.jit(f)(jnp.ones((8, 4), jnp.float32))  # must not raise


def test_sampled_finite_check_modes():
    c = np.ones((256, 4), np.float32)
    guards.sampled_finite_check(c, mode="auto", context="t")  # clean
    c[0, 2] = np.nan  # corner rows are always sampled
    with pytest.raises(NumericalFault, match=r"C\[0, 2\]"):
        guards.sampled_finite_check(c, mode="auto", context="t",
                                    call_index=7)
    c[0, 2] = 1.0
    c[131, 1] = np.inf  # a row the 32-row sample may skip...
    with pytest.raises(NumericalFault, match=r"C\[131, 1\]"):
        guards.sampled_finite_check(c, mode="full", context="t")


def _host_sweep(c, mode):
    """The sweep as a plain host loop over full copies of C's shards: the
    (global row, col, value) of the first non-finite element it reads."""
    for shard in c.addressable_shards:
        block = np.asarray(shard.data)
        n_rows = block.shape[0]
        if mode == "full" or n_rows <= guards._SAMPLE_ROWS:
            rows = np.arange(n_rows)
        else:
            rows = np.unique(np.linspace(0, n_rows - 1, guards._SAMPLE_ROWS, dtype=np.int64))
        bad = np.argwhere(~np.isfinite(block[rows]))
        if bad.size:
            r, col = bad[0]
            return int(shard.index[0].start or 0) + int(rows[r]), int(col), block[rows[r], col]
    return None


@pytest.mark.parametrize("mode", ["auto", "full"])
@pytest.mark.parametrize("P", [1, 4])
def test_device_sweep_finds_what_the_host_sweep_found(mode, P):
    """The device probe reads the rows the host sweep read and names the
    same element, with its global row, in every shard."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec

    mesh = jax.make_mesh((P,), ("x",), devices=jax.devices()[:P])
    sharding = NamedSharding(mesh, PartitionSpec("x"))
    rng = np.random.default_rng(P)
    rows_per_shard = 256 // P
    for trial in range(12):
        host = np.ones((256, 8), np.float32)
        for _ in range(trial % 3 + 1):  # one to three bad elements
            host[rng.integers(256), rng.integers(8)] = (np.nan, np.inf, -np.inf)[trial % 3]
        if P > 1 and trial == 0:  # a sampled row of a shard after the first
            host[:] = 1.0
            host[2 * rows_per_shard + 2, 5] = np.nan
        c = jax.device_put(host, sharding)
        want = _host_sweep(c, mode)
        if want is None:
            assert guards.sampled_finite_check(c, mode=mode, context="t") == P * 12
            continue
        row, col, val = want
        with pytest.raises(NumericalFault) as err:
            guards.sampled_finite_check(c, mode=mode, context="t", call_index=trial)
        assert str(err.value) == (
            f"t: non-finite C[{row}, {col}] = {val!r} on call #{trial} (check={mode} "
            f"isfinite sweep). The producer is upstream — a poisoned operand value or a "
            f"broken backend kernel; set check=False to serve unchecked.")


def test_validate_sparse_values_names_index(power_law_matrix):
    import dataclasses

    a = power_law_matrix()
    data = a.data.copy()
    data[3] = np.inf
    bad = dataclasses.replace(a, data=data)
    with pytest.raises(NumericalFault, match=r"data\[3\]"):
        guards.validate_sparse_values(bad, context="t")


def test_config_check_validation():
    with pytest.raises(ValueError, match="check must be"):
        SpmmConfig(check="paranoid")


# ---------------------------------------------------------------------------
# wave_error -> retry/backoff in SpmmWaveServer
# ---------------------------------------------------------------------------


def test_wave_error_retry_succeeds(power_law_matrix):
    a = power_law_matrix()
    handle = compile_spmm(a, 4, SpmmConfig(schedule="auto"))
    server = SpmmWaveServer(handle, max_batch=8, max_retries=2, backoff=0.0)
    reqs = [SpmmRequest(rid=i, b=_b()) for i in range(2)]
    for r in reqs:
        server.submit(r)
    with inject([Fault(kind="wave_error", site="wave")]) as plan:
        stats = server.run()
    assert plan.fired("wave_error") == 1
    assert stats.failed_waves == 1 and stats.retried_waves == 1
    assert stats.dropped_waves == 0 and stats.served == 2
    for r in reqs:
        np.testing.assert_array_equal(r.output, np.asarray(handle(r.b)))


def test_wave_error_exhausted_requeues_and_raises(power_law_matrix):
    a = power_law_matrix()
    handle = compile_spmm(a, 4, SpmmConfig(schedule="auto"))
    server = SpmmWaveServer(handle, max_batch=8, max_retries=1, backoff=0.0,
                            degrade=False)
    reqs = [SpmmRequest(rid=i, b=_b()) for i in range(3)]
    for r in reqs:
        server.submit(r)
    with inject([Fault(kind="wave_error", site="wave", times=10)]):
        with pytest.raises(InjectedFault):
            server.run()
    # nothing is lost: the whole wave went back to the queue, in order
    assert [r.rid for r in server.queue] == [0, 1, 2]
    assert server.stats.dropped_waves == 1
    assert server.stats.failed_waves == 2  # first try + one retry
    assert all(r.output is None for r in reqs)


def test_collective_delay_fires_on_wave(power_law_matrix):
    a = power_law_matrix()
    handle = compile_spmm(a, 4, SpmmConfig(schedule="auto"))
    handle(_b())  # pre-compile off the timed path
    server = SpmmWaveServer(handle, max_batch=8)
    server.submit(SpmmRequest(rid=0, b=_b()))
    t0 = time.perf_counter()
    with inject([Fault(kind="collective_delay", site="wave",
                       delay=0.2)]) as plan:
        server.run()
    assert time.perf_counter() - t0 >= 0.2
    assert plan.fired("collective_delay") == 1


# ---------------------------------------------------------------------------
# nan_poison -> check= guardrails
# ---------------------------------------------------------------------------


def test_nan_poison_operand_caught_at_plan_time(power_law_matrix):
    a = power_law_matrix()
    with inject([Fault(kind="nan_poison", site="operand")]):
        with pytest.raises(NumericalFault, match="non-finite"):
            SpmmSession.build(a, 4, SpmmConfig(schedule="auto"))


def test_nan_poison_operand_check_off_propagates(power_law_matrix):
    """check=False is the documented footgun: the poisoned operand plans
    fine and NaN lands in C — the contrast the guardrail exists for."""
    a = power_law_matrix()
    with inject([Fault(kind="nan_poison", site="operand")]):
        handle = compile_spmm(a, 4, SpmmConfig(schedule="auto",
                                               check=False))
    assert np.isnan(np.asarray(handle(_b()))).any()


def test_nan_poison_output_raises_numerical_fault(power_law_matrix):
    a = power_law_matrix()
    handle = compile_spmm(a, 4, SpmmConfig(schedule="auto"))
    b = _b()
    np.testing.assert_array_equal(np.asarray(handle(b)),
                                  np.asarray(handle(b)))  # healthy first
    with inject([Fault(kind="nan_poison", site="output")]):
        with pytest.raises(NumericalFault, match=r"C\[0, 0\]"):
            handle(b)
    stats = handle.stats()
    assert stats["numerical_faults"] == 1 and stats["check"] == "auto"
    # the same poison under check=False propagates silently instead
    unchecked = compile_spmm(a, 4, SpmmConfig(schedule="auto", check=False))
    with inject([Fault(kind="nan_poison", site="output")]):
        assert np.isnan(np.asarray(unchecked(b))[0, 0])


@pytest.mark.parametrize("kernel,P", [("spmm", 1), ("spmm", 4), ("sddmm", 4)])
def test_nan_poison_output_caught_by_device_sweep(power_law_matrix, kernel, P):
    """A poisoned served output raises through the device probe, and the
    guard reads back only the probe's scalars."""
    a = power_law_matrix()
    handle = compile_spmm(a, P, SpmmConfig(kernel=kernel))
    operands = (_b(),) if kernel == "spmm" else (_b(64, 8, 1), _b(64, 8, 2))
    handle(*operands)  # healthy first
    read = handle.guard_host_bytes
    assert 0 < read < 1024
    match = r"C\[0, 0\] = np.float32\(nan\) on call #2" if kernel == "spmm" else "output leaf"
    with inject([Fault(kind="nan_poison", site="output")]):
        with pytest.raises(NumericalFault, match=match):
            handle(*operands)
    assert handle.stats()["numerical_faults"] == 1
    assert handle.guard_host_bytes == read  # counted for sweeps that pass


def test_nan_poison_output_server_retries_to_success(power_law_matrix):
    a = power_law_matrix()
    handle = compile_spmm(a, 4, SpmmConfig(schedule="auto"))
    server = SpmmWaveServer(handle, max_batch=8, max_retries=2, backoff=0.0)
    req = SpmmRequest(rid=0, b=_b())
    server.submit(req)
    with inject([Fault(kind="nan_poison", site="output")]):
        stats = server.run()
    assert stats.retried_waves == 1 and stats.dropped_waves == 0
    assert np.isfinite(req.output).all()
    assert "NumericalFault" in server.events[0]["error"]


def test_no_faults_check_auto_is_bit_identical(power_law_matrix):
    """With no plan active and guards on, served bytes match check=False
    exactly — the guardrails observe, never perturb."""
    a = power_law_matrix()
    b = _b()
    cfg = SpmmConfig(schedule="auto")
    checked = compile_spmm(a, 4, cfg)(b)
    unchecked = compile_spmm(a, 4, SpmmConfig(schedule="auto",
                                              check=False))(b)
    np.testing.assert_array_equal(np.asarray(checked),
                                  np.asarray(unchecked))


# ---------------------------------------------------------------------------
# autotune_corrupt -> warn + re-profile (never crash)
# ---------------------------------------------------------------------------


def test_autotune_corrupt_entry_warns_and_reprofiles(
        power_law_matrix, tmp_path, monkeypatch):
    from repro.core import autotune

    monkeypatch.setenv(autotune.CACHE_ENV, str(tmp_path))
    a = power_law_matrix()
    cfg = SpmmConfig(schedule="auto", profile_topk=1, profile_iters=1,
                     profile_warmup=0)
    with inject([Fault(kind="autotune_corrupt", site="autotune_cache",
                       mode="empty")]) as plan:
        compile_spmm(a, 4, cfg)
    assert plan.fired("autotune_corrupt") == 1
    entries = [n for n in os.listdir(tmp_path) if n.endswith(".json")]
    assert len(entries) == 1
    assert os.path.getsize(tmp_path / entries[0]) == 0  # torn to zero bytes
    # a corrupt entry is a WARN + miss + re-profile, never a crash
    with pytest.warns(UserWarning, match="zero-byte entry"):
        compile_spmm(a, 4, cfg)
    assert os.path.getsize(tmp_path / entries[0]) > 0  # rewritten
    h3 = compile_spmm(a, 4, cfg)
    assert h3.stats()["decision_source"] == "cache"  # healthy hit again


def test_autotune_cache_zero_byte_entry_is_a_miss(tmp_path):
    from repro.core.autotune import AutotuneCache

    cache = AutotuneCache(str(tmp_path))
    (tmp_path / "k.json").write_text("")
    with pytest.warns(UserWarning, match="zero-byte entry"):
        assert cache.get("k") is None
    cache.put("k", {"tier": "flat"})  # atomic replace overwrites cleanly
    assert cache.get("k")["tier"] == "flat"
    assert not [n for n in os.listdir(tmp_path) if n.endswith(".tmp")]


# ---------------------------------------------------------------------------
# torn_checkpoint -> manifests name the damaged file
# ---------------------------------------------------------------------------


def test_torn_session_bundle_fails_naming_the_file(
        power_law_matrix, tmp_path):
    a = power_law_matrix()
    session = SpmmSession.build(a, 4, SpmmConfig(schedule="auto"),
                                p_ladder=(2, 4))
    path = str(tmp_path / "bundle")
    with inject([Fault(kind="torn_checkpoint", site="atomic_dir",
                       file="rung", mode="truncate")]) as plan:
        session.save(path)
    assert plan.fired("torn_checkpoint") == 1
    with pytest.raises(ValueError, match=r"rung_P\d+\.shiro.*truncated"):
        SpmmSession.load(path, 4)


def test_untorn_session_bundle_roundtrips(power_law_matrix, tmp_path):
    a = power_law_matrix()
    session = SpmmSession.build(a, 4, SpmmConfig(schedule="auto"))
    path = str(tmp_path / "bundle")
    session.save(path)
    meta = json.loads(
        (tmp_path / "bundle" / "session.json").read_text())
    assert set(meta["files"]) >= {"rung_P00004.shiro", "operand.pkl"}
    loaded = SpmmSession.load(path, 4)
    b = _b()
    np.testing.assert_array_equal(np.asarray(loaded.handle()(b)),
                                  np.asarray(session.handle()(b)))


def test_torn_model_checkpoint_fails_naming_arrays(tmp_path):
    from repro.checkpoint.manager import CheckpointManager

    mgr = CheckpointManager(str(tmp_path / "ckpt"))
    tree = {"w": np.arange(12, dtype=np.float32).reshape(3, 4)}
    with inject([Fault(kind="torn_checkpoint", site="atomic_dir",
                       file="arrays", mode="truncate")]):
        mgr.save(0, tree)
    with pytest.raises(ValueError, match=r"arrays\.npz"):
        mgr.restore(0, tree)
    # an untorn save still round-trips through the same manifest check
    mgr.save(1, tree)
    out = mgr.restore(1, tree)
    np.testing.assert_array_equal(np.asarray(out["w"]), tree["w"])


def test_truncated_distspmm_plan_file_is_actionable(
        power_law_matrix, tmp_path):
    a = power_law_matrix()
    handle = compile_spmm(a, 4, SpmmConfig(schedule="auto"))
    f = tmp_path / "plan.shiro"
    handle.save(str(f))
    data = f.read_bytes()
    f.write_bytes(data[: len(data) // 2])
    with pytest.raises(ValueError, match="truncated or corrupted"):
        DistSpmm.load(str(f), 4)
    f.write_bytes(b"")
    with pytest.raises(ValueError, match="empty"):
        DistSpmm.load(str(f), 4)


# ---------------------------------------------------------------------------
# worker_kill / stalls -> Supervisor (fake spawns, no jax fleet)
# ---------------------------------------------------------------------------


def _exit_proc(code=0, sleep=0.0):
    return subprocess.Popen(
        [sys.executable, "-c",
         f"import sys, time; time.sleep({sleep}); sys.exit({code})"])


def _policy(**over):
    kw = dict(heartbeat_timeout=30.0, max_restarts=2, backoff=0.0,
              backoff_max=0.0, poll=0.02, timeout=30.0)
    kw.update(over)
    return mp.SupervisorPolicy(**kw)


def test_supervisor_restarts_killed_fleet(capsys):
    def spawn(rank, nproc, epoch, coord, rundir):
        # rank 1 dies like a preempted host in the first epoch only —
        # the restarted fleet (epoch 1) runs clean
        code = KILL_EXIT_CODE if (epoch == 0 and rank == 1) else 0
        return _exit_proc(code)

    sup = mp.Supervisor(2, 4, policy=_policy(), spawn=spawn)
    assert sup.run() == 0
    assert sup.report["restarts"] == 1 and not sup.report["degraded"]
    assert sup.report["incidents"][0]["kind"] == "died"
    assert f"exit {KILL_EXIT_CODE}" in sup.report["incidents"][0]["detail"]
    assert "recovered" in capsys.readouterr().out


def test_supervisor_degrades_to_surviving_fleet(capsys):
    def spawn(rank, nproc, epoch, coord, rundir):
        # the full fleet keeps dying; a one-process fleet survives
        return _exit_proc(0 if nproc == 1 else 23)

    sup = mp.Supervisor(2, 4, policy=_policy(max_restarts=1), spawn=spawn)
    assert sup.run() == 0
    assert sup.report["degraded"] and sup.report["nproc"] == 1
    assert len(sup.report["incidents"]) == 2  # initial + 1 restart
    assert "DEGRADED" in capsys.readouterr().out


def test_supervisor_gives_up_after_exhausting_everything():
    sup = mp.Supervisor(2, 4, policy=_policy(max_restarts=0),
                        spawn=lambda *a: _exit_proc(3))
    assert sup.run() == 1
    assert sup.report["nproc"] == 1 and sup.report["degraded"]


def test_supervisor_detects_stalled_worker():
    # the worker neither exits nor makes progress; with no heartbeat
    # file the launch time is the reference, so the stall trips fast
    sup = mp.Supervisor(1, 4,
                        policy=_policy(heartbeat_timeout=0.3,
                                       max_restarts=0),
                        spawn=lambda *a: _exit_proc(0, sleep=60))
    t0 = time.perf_counter()
    assert sup.run() == 1
    assert time.perf_counter() - t0 < 20.0  # bounded: it never hangs
    assert sup.report["incidents"][0]["kind"] == "stalled"
    assert "no progress" in sup.report["incidents"][0]["detail"]


def test_supervisor_ladder_env_covers_every_fleet_size():
    sup = mp.Supervisor(3, 4, policy=_policy(), spawn=lambda *a: None)
    assert sup._ladder_env() == "4,8,12"


def test_heartbeat_roundtrip(tmp_path, monkeypatch):
    mp.write_heartbeat(str(tmp_path), 0, stage="serve", progress=7)
    hb = mp.read_heartbeat(str(tmp_path), 0)
    assert hb["stage"] == "serve" and hb["progress"] == 7
    assert hb["progress_time"] <= time.time()
    assert mp.read_heartbeat(str(tmp_path), 1) is None
    # no rundir env -> heartbeats are off (the unsupervised path)
    monkeypatch.delenv(mp.RUNDIR_ENV, raising=False)
    assert mp.Heartbeat.maybe_start(0) is None
