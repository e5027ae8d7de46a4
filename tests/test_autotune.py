"""Measured autotuning: cache hits, donation, memory-budgeted ladders.

The PR 6 contract: a second ``compile_spmm`` of an already-profiled
(pattern, topology, jax version) does ZERO timed profiling runs and
returns the same decisions bit-for-bit (``decision_source`` is the only
difference: ``measured`` vs ``cache``); any key ingredient changing —
jax version, topology, a corrupt cache file — re-profiles instead of
serving stale or crashing. Buffer donation is real (input/output alias
in the lowered HLO, strictly smaller per-device allocation) and NEVER
changes C. ``SpmmConfig.memory_budget`` drops over-budget ladder rungs
and says so in ``session.stats()``.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import autotune
from repro.core.api import (
    DistSpmm, SpmmConfig, compile_spmm, register_lowering_hook,
    unregister_lowering_hook,
)
from repro.core.session import SpmmSession
from repro.distributed.topology import Topology, TopologyError

P = 8
N = 16


@pytest.fixture
def counted_profiles():
    """Registered profile hook -> list of per-profiling info dicts."""
    events = []
    hook = autotune.register_profile_hook(events.append)
    yield events
    autotune.unregister_profile_hook(hook)


@pytest.fixture
def cache_env(tmp_path, monkeypatch):
    """A fresh autotune cache dir wired into the environment."""
    d = tmp_path / "atc"
    monkeypatch.setenv(autotune.CACHE_ENV, str(d))
    monkeypatch.delenv(autotune.MEASURE_ENV, raising=False)
    return d


def _cfg(**kw):
    """Small, fast measured config: one candidate, one timed run."""
    base = dict(backends=("coo",), schedule=2, overlap=False,
                n_dense_hint=N, profile_topk=1, profile_iters=1,
                profile_warmup=0)
    base.update(kw)
    return SpmmConfig(**base)


def _decisions_sans_source(h: DistSpmm) -> dict:
    return {k: v for k, v in h.decisions.items() if k != "decision_source"}


# ---------------------------------------------------------------------------
# cache behavior
# ---------------------------------------------------------------------------


def test_cache_hit_zero_profiling_bit_identical(power_law_matrix, cache_env,
                                                counted_profiles):
    a = power_law_matrix()
    h1 = compile_spmm(a, P, _cfg())
    assert h1.decisions["decision_source"] == "measured"
    assert h1.decisions["measured_time"] > 0
    n_first = len(counted_profiles)
    assert n_first > 0
    assert list(cache_env.glob("*.json")), "no cache file written"

    h2 = compile_spmm(a, P, _cfg())
    assert len(counted_profiles) == n_first  # ZERO new profiling runs
    assert h2.decisions["decision_source"] == "cache"
    assert _decisions_sans_source(h2) == _decisions_sans_source(h1)
    assert h2.schedule.kind == h1.schedule.kind
    assert h2.stats()["schedule_K"] == h1.stats()["schedule_K"]


def test_jax_version_change_misses_and_reprofiles(power_law_matrix,
                                                  cache_env,
                                                  counted_profiles,
                                                  monkeypatch):
    a = power_law_matrix()
    compile_spmm(a, P, _cfg())
    n_first = len(counted_profiles)
    monkeypatch.setattr(autotune, "jax_version", lambda: "9.9.9-other")
    h = compile_spmm(a, P, _cfg())
    assert len(counted_profiles) > n_first  # re-profiled under "new" jax
    assert h.decisions["decision_source"] == "measured"
    assert len(list(cache_env.glob("*.json"))) == 2  # both keys cached


def test_topology_change_misses_and_reprofiles(power_law_matrix, cache_env,
                                               counted_profiles):
    a = power_law_matrix()
    compile_spmm(a, P, _cfg())
    n_first = len(counted_profiles)
    h = compile_spmm(a, 4, _cfg())  # same pattern, different substrate
    assert len(counted_profiles) > n_first
    assert h.decisions["decision_source"] == "measured"


def test_corrupt_cache_file_warns_and_reprofiles(power_law_matrix,
                                                 cache_env,
                                                 counted_profiles):
    a = power_law_matrix()
    compile_spmm(a, P, _cfg())
    n_first = len(counted_profiles)
    (entry,) = cache_env.glob("*.json")
    entry.write_text("{ not json at all")
    with pytest.warns(UserWarning, match="unreadable"):
        h = compile_spmm(a, P, _cfg())
    assert h.decisions["decision_source"] == "measured"  # never crashed
    assert len(counted_profiles) > n_first
    # the re-profile overwrote the damage: next build hits again
    n_second = len(counted_profiles)
    h3 = compile_spmm(a, P, _cfg())
    assert len(counted_profiles) == n_second
    assert h3.decisions["decision_source"] == "cache"


def test_repro_measure_0_forces_model_only(power_law_matrix, cache_env,
                                           counted_profiles, monkeypatch):
    monkeypatch.setenv(autotune.MEASURE_ENV, "0")
    a = power_law_matrix()
    h = compile_spmm(a, P, _cfg(measure=True))
    assert counted_profiles == []
    assert h.decisions["decision_source"] == "model"
    assert h.stats()["measured_time"] is None


def test_no_cache_dir_keeps_default_builds_model_only(power_law_matrix,
                                                      monkeypatch,
                                                      counted_profiles):
    monkeypatch.delenv(autotune.CACHE_ENV, raising=False)
    monkeypatch.delenv(autotune.MEASURE_ENV, raising=False)
    h = compile_spmm(power_law_matrix(), P, _cfg())  # measure="auto"
    assert counted_profiles == []
    assert h.decisions["decision_source"] == "model"


def test_measure_true_profiles_without_cache_dir(power_law_matrix,
                                                 monkeypatch,
                                                 counted_profiles):
    monkeypatch.delenv(autotune.CACHE_ENV, raising=False)
    monkeypatch.delenv(autotune.MEASURE_ENV, raising=False)
    h = compile_spmm(power_law_matrix(), P, _cfg(measure=True))
    assert len(counted_profiles) > 0
    assert h.decisions["decision_source"] == "measured"


# ---------------------------------------------------------------------------
# buffer donation
# ---------------------------------------------------------------------------


def test_donation_aliases_hlo_and_shrinks_allocation(power_law_matrix):
    a = power_law_matrix()
    cfg = dict(backends=("coo",), schedule=4, overlap=False, n_dense_hint=N)
    hd = compile_spmm(a, P, SpmmConfig(donate=True, **cfg))
    hu = compile_spmm(a, P, SpmmConfig(donate=False, **cfg))
    assert hd.stats()["donated_buffers"] == ("b",)
    assert hu.stats()["donated_buffers"] == ()
    hlo_d = hd.lowered_hlo(N, backend="coo")
    hlo_u = hu.lowered_hlo(N, backend="coo")
    aliased = ("may-alias" in hlo_d) or ("input_output_alias" in hlo_d)
    assert aliased, "donated executable carries no input/output alias"
    assert "may-alias" not in hlo_u
    alloc_d = hd.stats()["total_allocation_size"]
    alloc_u = hu.stats()["total_allocation_size"]
    assert alloc_d is not None and alloc_u is not None
    assert alloc_d < alloc_u  # STRICTLY below — the alias is real


@pytest.mark.parametrize("overlap", [False, True])
def test_donation_never_changes_c(power_law_matrix, overlap):
    a = power_law_matrix()
    b = np.random.default_rng(3).standard_normal((a.shape[1], N))
    b = b.astype(np.float32)
    outs = []
    for donate in (True, False):
        h = compile_spmm(a, P, SpmmConfig(backends=("coo",), schedule=4,
                                          overlap=overlap, donate=donate))
        outs.append(np.asarray(h(b)))
    np.testing.assert_array_equal(outs[0], outs[1])


@pytest.mark.parametrize("placement", ["handle_sharding", "default_device"])
def test_donation_spares_caller_device_arrays(power_law_matrix, placement):
    """Donating must consume OUR copy, never the caller's array — also
    when placing it hands back the caller's buffer under a new Array (a
    default-device array fed to a one-device handle)."""
    import jax
    import jax.numpy as jnp

    a = power_law_matrix()
    p = P if placement == "handle_sharding" else 1
    h = compile_spmm(a, p, SpmmConfig(backends=("coo",), schedule=2))
    assert h._donate
    b = jnp.asarray(np.random.default_rng(0)
                    .standard_normal((a.shape[1], N)).astype(np.float32))
    if placement == "handle_sharding":
        b = jax.device_put(b, h._in_sharding)
    c1 = np.asarray(h(b))
    c2 = np.asarray(h(b))  # would raise on a deleted/donated caller buffer
    np.testing.assert_array_equal(c1, c2)


def test_memory_recorded_per_executable(power_law_matrix):
    h = compile_spmm(power_law_matrix(), P,
                     SpmmConfig(backends=("coo",), schedule=2))
    h.lowered_hlo(N)
    key = (N, "float32", "coo")
    mem = h._memory[key]
    assert mem["total_allocation_size"] > 0
    assert h.stats()["total_allocation_size"] == mem["total_allocation_size"]


# ---------------------------------------------------------------------------
# memory-budgeted ladders
# ---------------------------------------------------------------------------


def _rung_estimates(a, ladder):
    from repro.core.api import _plan_and_tune

    cfg = SpmmConfig(backends=("coo",))
    topo = Topology.local(P)
    out = {}
    for p in ladder:
        plan, hier, sched, dec = _plan_and_tune(a, p, cfg, topo)
        out[p] = autotune.rung_device_bytes(plan, sched, dec, cfg)
    return out


def test_memory_budget_skips_over_budget_rungs(power_law_matrix):
    a = power_law_matrix()
    est = _rung_estimates(a, (2, 4, 8))
    keep = min(est, key=est.get)
    budget = est[keep]  # exactly the cheapest rung: others must go
    assert any(v > budget for v in est.values())
    s = SpmmSession.build(a, P, SpmmConfig(backends=("coo",),
                                           memory_budget=int(budget)),
                          p_ladder=(2, 4, 8))
    assert s.ladder == (keep,)
    skipped = s.stats()["skipped_rungs"]
    assert set(skipped) == {p for p, v in est.items() if v > budget}
    assert all(v > budget for v in skipped.values())
    assert s.handle()(np.ones((a.shape[1], N), np.float32)) is not None


def test_memory_budget_all_skipped_raises(power_law_matrix):
    with pytest.raises(TopologyError, match="memory_budget"):
        SpmmSession.build(power_law_matrix(), P,
                          SpmmConfig(backends=("coo",), memory_budget=1),
                          p_ladder=(2, 4, 8))


def test_no_budget_keeps_every_rung(power_law_matrix):
    s = SpmmSession.build(power_law_matrix(), P,
                          SpmmConfig(backends=("coo",)), p_ladder=(2, 4, 8))
    assert s.ladder == (2, 4, 8)
    assert s.stats()["skipped_rungs"] == {}


# ---------------------------------------------------------------------------
# cross-wave executable carry-over (values-only drift)
# ---------------------------------------------------------------------------


def test_values_only_drift_keeps_executables(power_law_matrix):
    a = power_law_matrix()
    s = SpmmSession.build(a, P, SpmmConfig(backends=("coo",), schedule=4))
    h0 = s.handle()
    b = np.random.default_rng(5).standard_normal((a.shape[1], N))
    b = b.astype(np.float32)
    c_old = np.asarray(h0(b))
    assert h0.cache_info()["lowerings"] == 1

    events = []
    hook = register_lowering_hook(lambda h, key: events.append(key))
    try:
        a2 = dataclasses.replace(a, data=a.data * 2.0)
        d, swapped = s.maybe_replan(a2)
    finally:
        unregister_lowering_hook(hook)
    assert (d, swapped) == (0.0, False)
    assert s.handle() is h0             # same handle object keeps serving
    assert events == []                 # ZERO re-lowerings on the refresh
    assert s.stats()["values_refreshes"] == 1
    assert h0.values_refreshes == 1

    c_new = np.asarray(h0(b))           # reuses the memoized executable
    assert h0.cache_info()["lowerings"] == 1
    assert h0.cache_info()["hits"] >= 1
    np.testing.assert_allclose(c_new, 2.0 * c_old, rtol=1e-5, atol=1e-5)


def test_unchanged_values_do_not_refresh(power_law_matrix):
    a = power_law_matrix()
    s = SpmmSession.build(a, P, SpmmConfig(backends=("coo",)))
    d, swapped = s.maybe_replan(a)
    assert (d, swapped) == (0.0, False)
    assert s.stats()["values_refreshes"] == 0
    assert s.events[-1]["action"] == "drift_ok"
