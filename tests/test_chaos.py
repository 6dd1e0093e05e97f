"""Deterministic chaos harness (serving/chaos.py + serving/slo.py):
seeded plans replay exactly, every fault scenario recovers bit-identical
to the fault-free run, and the SLO layer's invariants hold.

The heavyweight acceptance check lives in test_recovery_matrix: all five
scenarios across the hadronio-family modes x event_loops in {1, 2, 4},
all recovering against ONE shared fault-free token reference (the
conformance contract makes served tokens invariant to mode, affinity and
loop count — which is exactly why one reference suffices)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import CommConfig, ModelConfig
from repro.core.backends import SyncContext, pipeline
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.serving import chaos, slo
from repro.serving.chaos import (SCENARIOS, STORM_UID_BASE, ChaosPlan,
                                 make_plan)
from repro.serving.dispatch import clear_serve_step_cache

HADRONIO_FAMILY = ("hadronio", "hadronio_rs", "hadronio_overlap",
                   "hadronio_overlap_rs")


# ---------------------------------------------------------------------------
# Seeded plans: same seed <=> same injection trace (no jax involved)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_plan_replay_identical(scenario):
    a = make_plan(scenario, 7, n_channels=4, n_loops=2)
    b = make_plan(scenario, 7, n_channels=4, n_loops=2)
    assert a.trace() == b.trace() and a.trace()
    assert a == b                       # frozen dataclasses compare whole


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_plan_seed_varies_trace(scenario):
    traces = {make_plan(scenario, s, n_channels=4, n_loops=2).trace()
              for s in range(8)}
    assert len(traces) > 1, "seed must actually drive the trace"


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_plan_shapes(scenario):
    plan = make_plan(scenario, 3, n_channels=4, n_loops=2, n_requests=4,
                     horizon=16)
    kinds = {e.kind for e in plan.events}
    steps = [e.step for e in plan.events]
    assert steps == sorted(steps)
    if scenario == "slow_channel":
        assert kinds == {"delay"} and steps[0] == 0
        assert len({e.target for e in plan.events}) == 1   # one channel
        assert all(0 < e.magnitude < 0.1 for e in plan.events)
    elif scenario == "stalled_loop":
        assert kinds == {"stall"} and steps[0] == 0
        assert all(0 <= e.target < 2 for e in plan.events)
    elif scenario == "dropped_flush":
        assert kinds <= {"drop", "dup"} and steps[0] == 0
    elif scenario == "admission_storm":
        assert kinds == {"burst"} and steps[0] == 1
        assert all(1 <= e.target <= 2 for e in plan.events)
    elif scenario == "reshard_mid_request":
        assert kinds == {"resize"} and len(plan.events) == 1
        assert plan.events[0].target in (1, 2, 4)
        assert plan.events[0].target != 2                  # != current
        assert 1 <= plan.events[0].step < 4
    else:   # mem_pressure
        assert kinds == {"pressure"} and steps[0] == 0
        assert all(0.5e-3 <= e.magnitude <= 2e-3 for e in plan.events)
        assert all(e.target == -1 for e in plan.events)
    assert all(e.step < 16 for e in plan.events)


# ---------------------------------------------------------------------------
# SLO layer units
# ---------------------------------------------------------------------------


def test_rtt_percentiles_monotone_and_degenerate():
    ps = slo.rtt_percentiles([3e-6, 1e-6, 2e-6, 50e-6])
    assert ps["p50"] <= ps["p99"] <= ps["p99.9"]
    one = slo.rtt_percentiles([7.0])
    assert one == {"p50": 7.0, "p99": 7.0, "p99.9": 7.0}
    with pytest.raises(ValueError, match="empty"):
        slo.rtt_percentiles([])


def test_token_recovery_ignores_storm_extras():
    ref = {0: (1, 2), 1: (3,)}
    ok, bad = slo.token_recovery(ref, {0: (1, 2), 1: (3,),
                                       STORM_UID_BASE: (9,)})
    assert ok and bad == ()
    ok, bad = slo.token_recovery(ref, {0: (1, 2)})          # 1 missing
    assert not ok and bad == (1,)
    ok, bad = slo.token_recovery(ref, {0: (1, 9), 1: (3,)})  # 0 differs
    assert not ok and bad == (0,)


def test_p999_inflation_and_assert_slo():
    rep = slo.make_report(scenario="s", seed=1, mode="hadronio",
                          event_loops=1, reference={0: (1,)},
                          served={0: (1,)}, fault_rtts=[2e-3],
                          baseline_rtts=[1e-3])
    assert rep.recovered and rep.p999_inflation == pytest.approx(2.0)
    slo.assert_slo(rep, max_p999_inflation=2.5)
    with pytest.raises(AssertionError, match="inflated"):
        slo.assert_slo(rep, max_p999_inflation=1.5)
    # token-only reference: no baseline, inflation unavailable, bound moot
    tokonly = slo.make_report(scenario="s", seed=1, mode="hadronio",
                              event_loops=1, reference={0: (1,)},
                              served={0: (1,)}, fault_rtts=[2e-3])
    assert tokonly.p999_inflation is None
    slo.assert_slo(tokonly, max_p999_inflation=0.1)     # does not bind
    # a zero baseline has nothing to inflate
    zero = slo.make_report(scenario="s", seed=1, mode="hadronio",
                           event_loops=1, reference={}, served={},
                           fault_rtts=[1e-3], baseline_rtts=[0.0])
    assert zero.p999_inflation == 1.0
    broken = slo.make_report(scenario="s", seed=2, mode="hadronio",
                             event_loops=1, reference={0: (1,)},
                             served={0: (2,)}, fault_rtts=[1e-3])
    with pytest.raises(AssertionError, match="diverged.*uids \\(0,\\)"):
        slo.assert_slo(broken)


# ---------------------------------------------------------------------------
# The flush-fault seam at the pipeline level: drops re-flush at the
# barrier, duplicates are idempotent — values NEVER change
# ---------------------------------------------------------------------------


def _emit(fault):
    mesh = make_mesh((1,), ("data",))
    rng = np.random.default_rng(0)
    items = [jnp.asarray(rng.normal(size=(64,)), jnp.float32)
             for _ in range(4)]

    def body(*xs):
        comm = CommConfig(mode="hadronio", channels=2, slice_bytes=128,
                          aggregate="channel", flush="ready",
                          hierarchical=False)
        ctx = SyncContext.resolve(comm, ("data",), None)
        st = pipeline.begin_emission(ctx, len(xs), "all_reduce")
        for i, x in enumerate(xs):
            pipeline.stage_slices(st, i, x)
        return tuple(pipeline.finish_emission(st))

    if fault is not None:
        pipeline.set_flush_fault(fault)
    try:
        assert pipeline.flush_fault_active() == (fault is not None)
        f = jax.jit(jax.shard_map(body, mesh=mesh,
                                  in_specs=(P(),) * 4,
                                  out_specs=(P(),) * 4, check_vma=False))
        return items, [np.asarray(o) for o in f(*items)]
    finally:
        pipeline.clear_flush_fault()
        assert not pipeline.flush_fault_active()


@pytest.mark.parametrize("name,fault", [
    ("none", None),
    ("drop_all", lambda c: "drop"),
    ("dup_all", lambda c: "dup"),
    ("drop_even", lambda c: "drop" if c % 2 == 0 else None),
])
def test_flush_fault_bit_identical(name, fault):
    """Any drop/dup pattern on the ready-flush schedule yields values
    bit-identical to the fault-free emission (one-device all_reduce is
    identity, so the inputs ARE the reference)."""
    items, out = _emit(fault)
    for x, o in zip(items, out):
        np.testing.assert_array_equal(np.asarray(x), o)


def test_flush_fault_consults_ready_channels():
    consulted = []

    def fault(c):
        consulted.append(c)
        return "drop"

    _emit(fault)
    assert consulted, "flush_ready never consulted the installed fault"
    assert set(consulted) <= {0, 1}


# ---------------------------------------------------------------------------
# End-to-end scenarios over a tiny dense model
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="chaos-tiny", family="dense", num_layers=1,
                      d_model=16, num_heads=2, num_kv_heads=2, d_ff=32,
                      vocab_size=64, head_dim=8, param_dtype="float32",
                      compute_dtype="float32")
    params = api.init(jax.random.PRNGKey(0), cfg)
    clear_serve_step_cache()
    return cfg, params


@pytest.fixture(scope="module")
def reference(tiny):
    """ONE fault-free run (hadronio, 1 loop) shared by the whole matrix:
    the conformance contract makes greedy tokens invariant to mode,
    affinity and loop count, so this token set is THE reference for
    every (mode, event_loops, scenario) cell. Token-only — tier-1 leans
    on the deterministic half of the SLO, not wall-clock."""
    cfg, params = tiny
    reqs = chaos.make_requests(4, vocab_size=cfg.vocab_size)
    base = chaos.run_baseline(cfg, params,
                              chaos.chaos_serve_config("hadronio", 1),
                              reqs)
    assert base.tokens and all(base.tokens.values())
    return chaos.Baseline(tokens=base.tokens), reqs


@pytest.mark.parametrize("scenario", SCENARIOS)
def test_replay_deterministic(tiny, reference, scenario):
    """The acceptance property, per scenario: same seed => same injection
    trace AND same runtime evidence (fired faults, drain trace, served
    tokens) — and the served tokens are bit-identical to the fault-free
    run."""
    cfg, params = tiny
    base, reqs = reference
    serve = chaos.chaos_serve_config("hadronio", 2)
    runs = [chaos.run_scenario(scenario, cfg, params, serve, reqs,
                               seed=11, baseline=base)
            for _ in range(2)]
    a, b = runs
    assert a.plan == b.plan and a.plan.trace() == b.plan.trace()
    assert a.fired == b.fired
    assert a.drains == b.drains
    assert a.tokens == b.tokens == base.tokens
    assert a.report.recovered and b.report.recovered
    assert a.report.n_injected == b.report.n_injected > 0
    slo.assert_slo(a.report)


def test_recovery_matrix(tiny, reference):
    """The acceptance matrix: every scenario recovers bit-identically
    across the hadronio-family modes x event_loops in {1, 2, 4}."""
    cfg, params = tiny
    base, reqs = reference
    for mode in HADRONIO_FAMILY:
        for el in (1, 2, 4):
            serve = chaos.chaos_serve_config(mode, el)
            for scenario in SCENARIOS:
                res = chaos.run_scenario(scenario, cfg, params, serve,
                                         reqs, seed=5, baseline=base)
                assert res.report.recovered, (scenario, mode, el)
                assert res.tokens == base.tokens, (scenario, mode, el)
                slo.assert_slo(res.report)


def test_stalled_loop_counts_stalls(tiny, reference):
    cfg, params = tiny
    base, reqs = reference
    res = chaos.run_scenario("stalled_loop", cfg, params,
                             chaos.chaos_serve_config("hadronio", 2),
                             reqs, seed=11, baseline=base)
    assert res.poll_stats.stalls > 0          # forced over-parks counted
    assert res.poll_stats.stalls == len(
        [f for f in res.fired if f[2] == "stall"])
    assert {f[2] for f in res.fired} == {"stall"}


def test_slow_channel_targets_owner_loop(tiny, reference):
    cfg, params = tiny
    base, reqs = reference
    serve = chaos.chaos_serve_config("hadronio", 2)
    res = chaos.run_scenario("slow_channel", cfg, params, serve, reqs,
                             seed=11, baseline=base)
    assert res.fired and {f[2] for f in res.fired} == {"delay"}
    # every fired delay was charged to the single owner loop
    assert len({f[1] for f in res.fired}) == 1
    assert res.poll_stats.stalls == 0         # delays are not stalls


def test_admission_storm_filters_injected_uids(tiny, reference):
    cfg, params = tiny
    base, reqs = reference
    res = chaos.run_scenario("admission_storm", cfg, params,
                             chaos.chaos_serve_config("hadronio", 2),
                             reqs, seed=11, baseline=base)
    assert {f[2] for f in res.fired} == {"burst"}
    assert res.report.n_injected > 0
    # storm uids never leak into the recovery comparison
    assert all(uid < STORM_UID_BASE for uid in res.tokens)
    assert res.tokens == base.tokens


def test_reshard_migrates_channels(tiny, reference):
    cfg, params = tiny
    base, reqs = reference
    res = chaos.run_scenario("reshard_mid_request", cfg, params,
                             chaos.chaos_serve_config("hadronio", 2),
                             reqs, seed=11, baseline=base)
    e = res.plan.events[0]
    assert e.kind == "resize" and e.target != 2
    assert res.moved_channels, "a loop-count change must migrate channels"
    assert res.fired == ((max(1, min(3, e.step)), e.target, "resize"),)
    assert res.tokens == base.tokens


def test_dropped_flush_traces_fresh_and_recovers(tiny, reference):
    cfg, params = tiny
    base, reqs = reference
    res = chaos.run_scenario("dropped_flush", cfg, params,
                             chaos.chaos_serve_config("hadronio", 2),
                             reqs, seed=11, baseline=base)
    assert {f[2] for f in res.fired} <= {"drop", "dup"} and res.fired
    # the armed window bypasses the serve-step cache, so this run traced
    # fresh programs — the collective-hook trace must be non-empty and
    # confined to the configured channel pool
    assert res.emissions
    assert {c for c, _ in res.emissions} <= set(range(4))
    assert res.tokens == base.tokens


def test_mem_pressure_consults_alloc_seam(tiny, reference):
    """The allocator seam: every planned pressure event that fires is a
    consult of the buffer-pool hook (recorded with its alloc index and
    channel), the run traces fresh programs (cache bypassed while the
    hook is armed), and recovery is bit-identical — allocation pressure
    slows a trace, never changes a value."""
    cfg, params = tiny
    base, reqs = reference
    res = chaos.run_scenario("mem_pressure", cfg, params,
                             chaos.chaos_serve_config("hadronio", 2),
                             reqs, seed=11, baseline=base)
    assert res.fired and {f[2] for f in res.fired} == {"pressure"}
    assert res.emissions                       # fresh traces happened
    assert res.tokens == base.tokens
    # the seam counts every coalesced-buffer build it consulted
    assert pipeline.EMISSION_STATS.allocs > 0
    assert not pipeline.alloc_hook_active()    # cleared after the run


def test_serve_step_cache_reuse_and_bypass(tiny):
    """Fault-free group builds share jitted serve steps (the cache that
    makes the matrix affordable); an armed flush fault bypasses both
    lookup and store so a faulted trace can never leak into fault-free
    callers."""
    from repro.serving import dispatch
    cfg, params = tiny
    serve = chaos.chaos_serve_config("hadronio", 2)
    clear_serve_step_cache()
    from repro.serving.engine import make_engine_group
    make_engine_group(cfg, params, serve)
    n = len(dispatch._STEP_CACHE)
    assert n > 0
    make_engine_group(cfg, params, serve)          # pure cache hits
    assert len(dispatch._STEP_CACHE) == n
    pipeline.set_flush_fault(lambda c: None)
    try:
        make_engine_group(cfg, params, serve)      # bypassed: no growth
    finally:
        pipeline.clear_flush_fault()
    assert len(dispatch._STEP_CACHE) == n
    # the allocation seam is a fault window too
    pipeline.set_alloc_hook(lambda c, nbytes: None)
    try:
        assert pipeline.fault_active()
        make_engine_group(cfg, params, serve)      # bypassed: no growth
    finally:
        pipeline.clear_alloc_hook()
    assert not pipeline.fault_active()
    assert len(dispatch._STEP_CACHE) == n
