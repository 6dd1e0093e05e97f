"""Observatory telemetry plane (repro/obs + the instrumentation seams).

Four contracts under test (docs/OBSERVABILITY.md):

* REGISTRY — typed metrics with the closed label taxonomy, snapshot
  sections split by determinism class, byte-stable JSON, RingLog
  bounds, the scoped emission-stats seam.
* SPANS — the staged emission + serving plane instrumentation yields a
  WELL-FORMED interval forest (every ``begin_emission`` closed, leader
  flushes nested in their lane's local flush), exports as loadable
  Chrome-trace JSON, and observation changes NOTHING (served tokens
  bit-identical with tracing on vs off).
* DETERMINISM — same seed + same ChaosPlan => byte-identical
  deterministic snapshot, across the hadronio-family modes x
  event_loops {1, 2, 4} and across every chaos scenario.
* GATE — bench_diff tolerance-band units and CLI exit codes.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.configs.base import CommConfig, ModelConfig
from repro.core.backends import SyncContext, pipeline
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.obs import baseline as bl
from repro.serving import chaos
from repro.serving.dispatch import clear_serve_step_cache

HADRONIO_FAMILY = ("hadronio", "hadronio_rs", "hadronio_overlap",
                   "hadronio_overlap_rs")


# ---------------------------------------------------------------------------
# Metrics registry units
# ---------------------------------------------------------------------------


def test_registry_sections_and_label_keys():
    reg = obs.MetricsRegistry()
    reg.counter("served", tenant="a", loop=0).inc()
    reg.counter("served", tenant="a", loop=0).inc(2)     # get-or-create
    reg.gauge("depth", loop=1).set(4)
    reg.gauge("spins", volatile=True, loop=1).set(99)
    reg.histogram("rtt", mode="hadronio").observe(1.5)
    snap = reg.snapshot()
    assert snap["counters"] == {"served{loop=0,tenant=a}": 3}
    assert snap["gauges"] == {"depth{loop=1}": 4}
    assert snap["volatile"] == {"spins{loop=1}": 99}
    h = snap["histograms"]["rtt{mode=hadronio}"]
    assert h["count"] == 1 and h["min"] == h["max"] == 1.5
    # the deterministic half excludes volatile gauges AND histograms
    det = reg.deterministic_snapshot()
    assert set(det) == {"counters", "gauges"}
    assert "spins{loop=1}" not in det["gauges"]


def test_registry_label_order_independent_and_unknown_rejected():
    reg = obs.MetricsRegistry()
    a = reg.counter("x", loop=1, mode="m")
    b = reg.counter("x", mode="m", loop=1)
    assert a is b
    with pytest.raises(ValueError, match="unknown metric label"):
        reg.counter("x", flavor="nope")


def test_registry_type_conflict_rejected():
    reg = obs.MetricsRegistry()
    reg.counter("x")
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("x")


def test_registry_to_json_byte_stable():
    def build(order):
        reg = obs.MetricsRegistry()
        for name, labels, v in order:
            reg.gauge(name, **labels).set(v)
        return reg.to_json(deterministic=True)

    rows = [("b", {"loop": 1}, 2), ("a", {}, 1), ("b", {"loop": 0}, 3)]
    assert build(rows) == build(list(reversed(rows)))


def test_ringlog_bounds_dropped_slice_eq():
    r = obs.RingLog(3)
    assert not r and len(r) == 0
    r.extend([1, 2, 3])
    assert r.dropped == 0 and r == [1, 2, 3]
    r.append(4)
    r.append(5)
    assert list(r) == [3, 4, 5] and r.dropped == 2
    assert r[0] == 3 and r[-1] == 5 and r[1:] == [4, 5]
    assert r == (3, 4, 5) and r != [3, 4]
    assert tuple(r) == (3, 4, 5)
    with pytest.raises(ValueError):
        obs.RingLog(0)


def test_stats_scope_shields_module_global():
    base = pipeline.EMISSION_STATS.drops
    with pipeline.stats_scope() as st:
        pipeline.current_stats().drops += 3
        with pipeline.stats_scope() as inner:     # nested scopes shadow
            pipeline.current_stats().dups += 1
            assert inner.dups == 1
        assert st.drops == 3 and st.dups == 0
    assert pipeline.EMISSION_STATS.drops == base  # global untouched
    assert pipeline.current_stats() is pipeline.EMISSION_STATS


# ---------------------------------------------------------------------------
# Trace recorder units + export round-trip
# ---------------------------------------------------------------------------


def test_recorder_nesting_and_round_trip():
    with obs.capture() as rec:
        with obs.span("emission", "e", items=2):
            with obs.span("flush", "ch0", channel=0):
                pass
            with obs.span("flush", "ch1", channel=1):
                pass
        obs.complete("heal", "restart", 0.0, 0.25, round=1)
    assert not obs.enabled()
    ok, problems = obs.well_formed(rec)
    assert ok, problems
    assert rec.kinds() == ["emission", "flush", "heal"]
    # export -> json round-trip: loadable, complete events, us stamps
    doc = json.loads(json.dumps(rec.to_chrome()))
    evs = doc["traceEvents"]
    assert len(evs) == 4
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in evs)
    em = [e for e in evs if e["cat"] == "emission"][0]
    fl = [e for e in evs if e["cat"] == "flush"]
    for f in fl:   # children contained in the parent (us timeline,
        #            0.01us slack for the 3-decimal export rounding)
        assert em["ts"] <= f["ts"] + 0.01
        assert f["ts"] + f["dur"] <= em["ts"] + em["dur"] + 0.01
    heal = [e for e in evs if e["cat"] == "heal"][0]
    assert abs(heal["dur"] - 0.25e6) < 1e3
    assert doc["otherData"]["open_spans"] == 0


def test_recorder_detects_malformed():
    with obs.capture() as rec:
        obs.begin("emission", "left-open")
    assert rec.open_spans() == [("emission", "left-open")]
    ok, problems = obs.well_formed(rec)
    assert not ok and "unclosed" in problems[0]

    with obs.capture() as rec2:
        outer = obs.begin("emission", "outer")
        obs.begin("flush", "inner")
        obs.end(outer)                 # non-LIFO: inner force-closed
    assert rec2.forced_closes == 1
    assert not obs.well_formed(rec2)[0]


def test_recorder_ring_eviction_counts():
    with obs.capture(capacity=4) as rec:
        for i in range(7):
            with obs.span("decode", f"s{i}"):
                pass
    assert len(rec.spans) == 4 and rec.dropped == 3
    assert rec.to_chrome()["otherData"]["dropped"] == 3


def test_disabled_gate_is_inert():
    assert not obs.enabled()
    assert obs.begin("emission") is None
    obs.end(None)                      # must not raise
    with obs.span("decode"):           # shared nullcontext
        pass
    obs.complete("heal", "x", 0.0, 1.0)
    assert obs.recorder() is None


# ---------------------------------------------------------------------------
# The instrumented serving plane
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = ModelConfig(name="obs-tiny", family="dense", num_layers=1,
                      d_model=16, num_heads=2, num_kv_heads=2, d_ff=32,
                      vocab_size=64, head_dim=8, param_dtype="float32",
                      compute_dtype="float32")
    params = api.init(jax.random.PRNGKey(0), cfg)
    clear_serve_step_cache()
    return cfg, params


@pytest.fixture(scope="module")
def reference(tiny):
    cfg, params = tiny
    # 6 requests > 1 loop x max_batch 2: the run queue is non-empty, so
    # the continuous-batching admission path (and its spans) is live
    reqs = chaos.make_requests(6, vocab_size=cfg.vocab_size)
    base = chaos.run_baseline(cfg, params,
                              chaos.chaos_serve_config("hadronio", 1),
                              reqs)
    assert base.tokens and all(base.tokens.values())
    return base, reqs


def test_traced_serve_well_formed_and_token_identical(tiny, reference):
    """One traced serve covers the whole span taxonomy: emission /
    stage / flush from the staged emission API (trace-time), build from
    the step builder, prefill / decode / admission from the engine,
    drain from the event loop — well-formed, and OBSERVATION ONLY
    (tokens bit-identical to the untraced run)."""
    cfg, params = tiny
    base, reqs = reference
    serve = chaos.chaos_serve_config("hadronio", 1)

    clear_serve_step_cache()
    off = chaos.run_baseline(cfg, params, serve, reqs)
    clear_serve_step_cache()           # fresh trace => emission spans
    with obs.capture() as rec:
        on = chaos.run_baseline(cfg, params, serve, reqs)
    assert on.tokens == off.tokens == base.tokens

    kinds = set(rec.kinds())
    assert {"emission", "stage", "flush", "build", "prefill", "decode",
            "admission", "drain"} <= kinds, kinds
    ok, problems = obs.well_formed(rec)
    assert ok, problems
    assert rec.forced_closes == 0 and rec.open_spans() == []
    # every flush nests inside an emission (the begin/finish bracket)
    for f in rec.spans_of("flush"):
        assert obs.containing(rec, f, "emission") is not None, f


@pytest.mark.parametrize("mode,el", [("hadronio", 1), ("hadronio", 2),
                                     ("hadronio_overlap", 2)])
def test_tracing_preserves_tokens_per_mode(tiny, reference, mode, el):
    cfg, params = tiny
    base, reqs = reference
    serve = chaos.chaos_serve_config(mode, el)
    clear_serve_step_cache()
    with obs.capture() as rec:
        res = chaos.run_baseline(cfg, params, serve, reqs)
    assert res.tokens == base.tokens, (mode, el)
    assert rec.spans_of("emission") and obs.well_formed(rec)[0]


def test_leader_flush_nests_inside_local_flush():
    """Two-level leader emission: the leader lane's cross-pod collective
    fires from INSIDE its triggering local lane's flush under
    flush="ready" — the span tree must show that containment."""
    mesh = make_mesh((1, 1), ("pod", "data"))
    comm = CommConfig(mode="hadronio", channels=4, aggregate="channel",
                      flush="ready", hierarchical=True, leader_channels=1,
                      slice_bytes=64)
    ctx = SyncContext.resolve(comm, ("data",), "pod")
    assert pipeline.leader_emission(ctx, 2)

    def body(x):
        return pipeline.emit_flat(x.reshape(-1), ctx, "all_reduce")

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=P(("pod", "data")),
                              out_specs=P(), check_vma=False))
    x = jnp.arange(96, dtype=jnp.float32).reshape(1, 96)
    with obs.capture() as rec:
        np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x[0]))
    leads = rec.spans_of("leader_flush")
    assert leads, "hierarchical emission must record leader flushes"
    for l in leads:
        host = obs.containing(rec, l, "flush")
        assert host is not None, (l.name, "no containing local flush")
    ok, problems = obs.well_formed(rec)
    assert ok, problems


def test_supervised_heal_spans_complete_taxonomy(tiny, reference):
    """The acceptance trace: a supervised dropped_flush run records >= 4
    span kinds including emission, flush, admission and heal — and the
    healing spans carry the supervisor's detect->heal window."""
    cfg, params = tiny
    base, reqs = reference
    serve = chaos.chaos_serve_config("hadronio", 1)
    with obs.capture() as rec:
        res = chaos.run_supervised("dropped_flush", cfg, params, serve,
                                   reqs, seed=11, baseline=base)
    assert res.report.recovered and res.tokens == base.tokens
    kinds = set(rec.kinds())
    assert {"emission", "flush", "admission", "heal"} <= kinds, kinds
    assert len(kinds) >= 4
    heals = rec.spans_of("heal")
    assert {s.name for s in heals} >= {"quarantine", "restart"}
    assert all(s.dur >= 0 for s in heals)
    ok, problems = obs.well_formed(rec)
    assert ok, problems


# ---------------------------------------------------------------------------
# Telemetry determinism: same seed + same ChaosPlan => byte-identical
# deterministic snapshot
# ---------------------------------------------------------------------------


def _scenario_snapshot(cfg, params, serve, reqs, base, scenario, seed):
    """One seeded chaos run -> the deterministic half of its telemetry,
    as bytes. Emission counters are read through a PRIVATE stats scope
    (the satellite seam: no cross-test module-global races); the
    serve-step cache is cleared so both runs of a pair trace
    identically."""
    clear_serve_step_cache()
    with pipeline.stats_scope() as st:
        res = chaos.run_scenario(scenario, cfg, params, serve, reqs,
                                 seed=seed, baseline=base)
    assert res.report.recovered, (scenario, serve.comm.mode)
    reg = obs.MetricsRegistry()
    obs.publish_emission_stats(reg, st, mode=serve.comm.mode,
                               scenario=scenario)
    obs.publish_chaos(reg, res, mode=serve.comm.mode, scenario=scenario)
    return reg.to_json(deterministic=True)


@pytest.mark.parametrize("mode", HADRONIO_FAMILY)
def test_snapshot_determinism_matrix(tiny, reference, mode):
    """The acceptance matrix: hadronio-family modes x event_loops
    {1, 2, 4}, dropped_flush (the scenario that exercises the emission
    counters) — two same-seed runs per cell, byte-identical snapshots."""
    cfg, params = tiny
    base, reqs = reference
    for el in (1, 2, 4):
        serve = chaos.chaos_serve_config(mode, el)
        a = _scenario_snapshot(cfg, params, serve, reqs,
                               base, "dropped_flush", seed=5)
        b = _scenario_snapshot(cfg, params, serve, reqs,
                               base, "dropped_flush", seed=5)
        assert a == b, (mode, el)
        snap = json.loads(a)
        assert any(v > 0 for v in snap["gauges"].values()), (mode, el)


@pytest.mark.parametrize("scenario", chaos.SCENARIOS)
def test_snapshot_determinism_every_scenario(tiny, reference, scenario):
    cfg, params = tiny
    base, reqs = reference
    serve = chaos.chaos_serve_config("hadronio", 1)
    a = _scenario_snapshot(cfg, params, serve, reqs, base, scenario, 9)
    b = _scenario_snapshot(cfg, params, serve, reqs, base, scenario, 9)
    assert a == b, scenario


# ---------------------------------------------------------------------------
# Adapters: live group / supervisor -> registry
# ---------------------------------------------------------------------------


def test_collect_publishes_group_and_supervisor(tiny, reference):
    cfg, params = tiny
    base, reqs = reference
    serve = chaos.chaos_serve_config("hadronio", 2)
    from repro.serving.supervisor import Supervisor
    sup = Supervisor(cfg, params, serve, seed=3)
    sup.submit(list(reqs))
    sup.run(threads=False)
    reg = obs.collect(supervisor=sup, mode="hadronio")
    snap = reg.snapshot()
    g = snap["gauges"]
    assert g["group.loops{mode=hadronio}"] == 2
    assert g["supervisor.rounds{mode=hadronio}"] >= 1
    assert g["loop.heartbeats{loop=0,mode=hadronio}"] >= 1
    assert "poll.waits{loop=0,mode=hadronio}" in g
    # wall-clock-coupled poll counters live in the volatile section only
    assert "poll.spins{loop=0,mode=hadronio}" in snap["volatile"]
    assert "poll.spins{loop=0,mode=hadronio}" not in g
    det = json.loads(reg.to_json(deterministic=True))
    assert "volatile" not in det


def test_group_poll_stats_survive_restart(tiny, reference):
    """The restart-fold satellite, observed end to end: group poll stats
    are monotone across a heal (lifetime merge, not a silent reset)."""
    cfg, params = tiny
    base, reqs = reference
    from repro.serving.engine import make_engine_group
    grp = make_engine_group(cfg, params,
                            chaos.chaos_serve_config("hadronio", 2))
    grp.submit(list(reqs))
    grp.run(threads=False)
    before = grp.poll_stats()
    assert before.waits > 0
    grp.loops[0].restart()             # the heal: fresh poller
    after = grp.poll_stats()
    assert after.waits == before.waits, "restart must not reset stats"
    assert grp.loops[0].poller.stats.waits == 0   # poller IS fresh


def test_dispatch_log_ring_is_bounded():
    from repro.serving.event_loop import EventLoop, EventLoopGroup
    loops = [EventLoop(0, channels=(0,), runner=lambda l, items: []),
             EventLoop(1, channels=(1,), runner=lambda l, items: [])]
    grp = EventLoopGroup(loops, tenants=(("a", 1, (0,)), ("b", 1, (1,))),
                         dispatch_log_capacity=4)

    class _Item:
        def __init__(self, tenant):
            self.tenant = tenant

    grp.submit([_Item("a"), _Item("b")] * 5)
    assert len(grp.dispatch_log) == 4
    assert grp.dispatch_log.dropped == 6
    reg = obs.MetricsRegistry()
    obs.publish_group(reg, grp)
    g = reg.snapshot()["gauges"]
    assert g["group.dispatch_log_dropped"] == 6
    assert g["group.dispatch_log_len"] == 4


def test_chaos_evidence_rings_bounded(tiny, reference):
    cfg, params = tiny
    plan = chaos.make_plan("dropped_flush", 3)
    inj = chaos._Injector(plan, cfg.vocab_size, evidence_capacity=2)
    for i in range(5):
        inj.fired.append((i, 0, "drop"))
    assert len(inj.fired) == 2 and inj.fired.dropped == 3


# ---------------------------------------------------------------------------
# bench_diff: tolerance-band units + CLI
# ---------------------------------------------------------------------------


def _row(metric="rtt_p50", value=10.0, unit="us", kind="measured",
         **over):
    r = {"benchmark": "b", "figure": "f", "mode": "m", "msg_bytes": 1024,
         "channels": 2, "metric": metric, "value": value, "unit": unit,
         "kind": kind, "seed": 0}
    r.update(over)
    return r


def test_tolerance_directions():
    lower = bl.Tolerance(rel=0.1, direction="lower_is_better")
    assert lower.judge(10.0, 10.9) == "ok"       # inside the band
    assert lower.judge(10.0, 11.2) == "regression"
    assert lower.judge(10.0, 8.0) == "improved"
    higher = bl.Tolerance(rel=0.1, direction="higher_is_better")
    assert higher.judge(10.0, 9.5) == "ok"
    assert higher.judge(10.0, 8.0) == "regression"
    assert higher.judge(10.0, 12.0) == "improved"
    exact = bl.Tolerance(abs=1e-9, direction="exact")
    assert exact.judge(3.0, 3.0) == "ok"
    assert exact.judge(3.0, 3.0000001) == "regression"
    assert bl.Tolerance(direction="ignore").judge(1.0, 1e9) == "ok"


def test_default_policy_by_unit_and_kind():
    assert bl.default_tolerance(_row()).direction == "lower_is_better"
    assert bl.default_tolerance(_row()).rel == 1.0
    d = bl.default_tolerance(_row(kind="derived"))
    assert d.rel == 0.05 and d.direction == "lower_is_better"
    assert bl.default_tolerance(
        _row(unit="ops", kind="derived")).direction == "exact"
    assert bl.default_tolerance(
        _row(unit="count", kind="derived")).direction == "ignore"
    assert bl.default_tolerance(
        _row(unit="GB/s")).direction == "ignore"   # measured non-time


def test_diff_statuses_and_seed_excluded_from_identity():
    base = [_row(), _row(metric="ops", unit="ops", kind="derived",
                         value=7.0), _row(metric="gone")]
    cand = [_row(value=25.0, seed=99),            # 2.5x: regression
            _row(metric="ops", unit="ops", kind="derived", value=7.0),
            _row(metric="new")]
    rep = bl.diff(base, cand)
    assert {d.status for d in rep.deltas} == \
        {"regression", "ok", "missing", "added"}
    assert not rep.ok
    [reg] = rep.regressions
    assert reg.key[5] == "rtt_p50" and reg.change == pytest.approx(1.5)


def test_diff_overrides_and_ignore():
    base, cand = [_row()], [_row(value=25.0)]
    rep = bl.diff(base, cand,
                  overrides=[("rtt_*", bl.Tolerance(rel=2.0))])
    assert rep.ok                      # widened band swallows the 2.5x
    rep2 = bl.diff(base, cand, ignore=["b:rtt_*"])
    assert rep2.ok and rep2.of("ignored")
    rep3 = bl.diff(base, cand, tol_measured=0.1)
    assert not rep3.ok


def test_derived_exact_units_trip_on_any_drift():
    base = [_row(metric="emitted_collective_ops", unit="ops",
                 kind="derived", value=8.0)]
    cand = [_row(metric="emitted_collective_ops", unit="ops",
                 kind="derived", value=9.0)]
    assert not bl.diff(base, cand).ok
    # count rows (volatile poll counters) never gate
    base2 = [_row(metric="poll_spins:el2", unit="count", kind="derived",
                  value=100.0)]
    cand2 = [_row(metric="poll_spins:el2", unit="count", kind="derived",
                  value=900000.0)]
    assert bl.diff(base2, cand2).ok


def test_bench_diff_cli_exit_codes(tmp_path):
    from benchmarks import bench_diff
    base_p = tmp_path / "base.json"
    good_p = tmp_path / "good.json"
    bad_p = tmp_path / "bad.json"
    rows = [_row(), _row(metric="ops", unit="ops", kind="derived",
                         value=4.0)]
    base_p.write_text(json.dumps(rows))
    good_p.write_text(json.dumps(rows))
    bad = [dict(rows[0], value=rows[0]["value"] * 10), rows[1]]
    bad_p.write_text(json.dumps(bad))
    assert bench_diff.main([str(base_p), str(good_p)]) == 0
    assert bench_diff.main([str(base_p), str(bad_p)]) == 1
    assert bench_diff.main([str(base_p), str(bad_p),
                            "--ignore", "rtt_*"]) == 0
    missing_p = tmp_path / "missing.json"
    missing_p.write_text(json.dumps(rows[:1]))
    assert bench_diff.main([str(base_p), str(missing_p)]) == 0
    assert bench_diff.main([str(base_p), str(missing_p),
                            "--strict-missing"]) == 1


def test_metrics_rows_flatten_deterministic_half():
    from benchmarks.common import metrics_rows
    reg = obs.MetricsRegistry()
    reg.counter("served", tenant="a").inc(5)
    reg.gauge("depth").set(2)
    reg.gauge("spins", volatile=True).set(123)
    rows = metrics_rows("serving_rtt", reg.snapshot())
    metrics = {r.metric: r.value for r in rows}
    assert metrics == {"obs:served{tenant=a}": 5.0, "obs:depth": 2.0}
    assert all(r.unit == "count" and r.kind == "derived" for r in rows)
