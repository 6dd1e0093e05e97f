"""Flush-when-ready scheduling units (core/flush_scheduler.py,
selector.ready_groups, channels.ChannelFill, the pipeline's staged
emission API). The end-to-end properties — bit-identical parity and the
jaxpr-level overlap evidence — live in tests/test_backend_conformance.py;
this file pins the combinatorial pieces directly."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import CommConfig
from repro.core.backends import SyncContext, pipeline
from repro.core.channels import ChannelFill, channel_groups
from repro.core.flush_scheduler import FLUSHES, make_flush_plan
from repro.core.selector import ready_groups
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# ready_groups: the contiguous bucket->channel grouping
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("n,c", [(1, 1), (3, 2), (6, 2), (7, 3), (8, 8),
                                 (5, 16), (12, 5)])
def test_ready_groups_partition(n, c):
    """Exact partition of the production order into contiguous runs,
    sizes balanced to within one, smaller runs FIRST (earliest
    readiness)."""
    groups = ready_groups(n, c)
    assert len(groups) == min(n, c)
    flat = [i for g in groups for i in g]
    assert flat == list(range(n))                 # partition, in order
    sizes = [len(g) for g in groups]
    assert max(sizes) - min(sizes) <= 1
    assert sizes == sorted(sizes)                 # smaller groups first
    for g in groups:
        assert g == tuple(range(g[0], g[0] + len(g)))   # contiguous


def test_ready_groups_reverse():
    """reverse=True partitions the reverse emission order instead."""
    groups = ready_groups(4, 2, reverse=True)
    assert [i for g in groups for i in g] == [3, 2, 1, 0]


# ---------------------------------------------------------------------------
# make_flush_plan
# ---------------------------------------------------------------------------


def test_step_plan_matches_round_robin():
    """flush="step" preserves the PR 3 layout exactly: round-robin
    groups, every item's channel is i % C."""
    plan = make_flush_plan(7, 3, "step")
    assert plan.groups == tuple(tuple(g) for g in channel_groups(7, 3))
    assert plan.assign == tuple(i % 3 for i in range(7))
    assert not plan.contiguous


def test_ready_plan_triggers_and_depth():
    """Triggers are each group's last (max) item; readiness depth — the
    number of buckets that must exist before the FIRST flush — is the
    first group's size under "ready" and the whole exchange under
    "step"."""
    plan = make_flush_plan(6, 2, "ready")
    assert plan.groups == ((0, 1, 2), (3, 4, 5))
    assert plan.triggers == (2, 5)
    assert plan.readiness_depth == 3
    assert plan.contiguous
    step = make_flush_plan(6, 2, "step")
    assert step.readiness_depth == 6
    assert plan.readiness_depth < step.readiness_depth


def test_plan_clamps_channels():
    """More channels than items degenerates to singleton groups (fully
    independent flushes) for both schedules."""
    for flush in FLUSHES:
        plan = make_flush_plan(3, 16, flush)
        assert plan.n_channels == 3
        assert plan.groups == ((0,), (1,), (2,))
        assert plan.readiness_depth == (1 if flush == "ready" else 3)


def test_plan_rejects_unknown_flush():
    with pytest.raises(AssertionError):
        make_flush_plan(4, 2, "eventually")


# ---------------------------------------------------------------------------
# ChannelFill: the readiness watermark
# ---------------------------------------------------------------------------


def test_channel_fill_watermark():
    fill = ChannelFill(frozenset({1, 3, 5}))
    assert fill.watermark == 0.0 and not fill.ready
    fill.stage(1)
    assert fill.watermark == pytest.approx(1 / 3) and not fill.ready
    fill.stage(3)
    fill.stage(5)
    assert fill.watermark == 1.0 and fill.ready
    fill.flushed = True
    assert not fill.ready                       # never flush twice


def test_channel_fill_rejects_bad_stage():
    fill = ChannelFill(frozenset({0, 1}))
    with pytest.raises(AssertionError):
        fill.stage(7)                           # not assigned here
    fill.stage(0)
    with pytest.raises(AssertionError):
        fill.stage(0)                           # double stage


# ---------------------------------------------------------------------------
# The staged emission API (pipeline.begin_emission / stage_slices /
# flush_ready / finish_emission)
# ---------------------------------------------------------------------------


def _ctx(**kw):
    kw.setdefault("mode", "hadronio")
    kw.setdefault("hierarchical", False)
    comm = CommConfig(**kw)
    return SyncContext.resolve(comm, ("data",), None)


def _items(n=5, elems=128):
    rng = np.random.default_rng(0)
    return [jnp.asarray(rng.normal(size=(elems,)), jnp.float32)
            for _ in range(n)]


@pytest.mark.parametrize("aggregate", ["slice", "channel"])
@pytest.mark.parametrize("flush", ["step", "ready"])
def test_incremental_staging_matches_oneshot(aggregate, flush):
    """Driving stage_slices item by item produces the same values as the
    emit_through_channels one-shot wrapper, for every schedule."""
    mesh = make_mesh((1,), ("data",))
    items = _items()

    def oneshot(*xs):
        ctx = _ctx(channels=2, aggregate=aggregate, flush=flush)
        return tuple(pipeline.emit_through_channels(list(xs), ctx,
                                                    "all_reduce"))

    def incremental(*xs):
        ctx = _ctx(channels=2, aggregate=aggregate, flush=flush)
        st = pipeline.begin_emission(ctx, len(xs), "all_reduce")
        for i, x in enumerate(xs):
            pipeline.stage_slices(st, i, x)
        return tuple(pipeline.finish_emission(st))

    outs = {}
    for name, fn in [("oneshot", oneshot), ("incremental", incremental)]:
        f = jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(P(),) * len(items),
            out_specs=(P(),) * len(items), check_vma=False))
        outs[name] = f(*items)
    for a, b, x in zip(outs["oneshot"], outs["incremental"], items):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_array_equal(np.asarray(a), np.asarray(x))


def test_step_schedule_defers_all_flushes():
    """Under flush="step" + aggregate="channel", stage_slices never
    emits (the barrier loop is finish_emission); under "ready" the flush
    fires the moment a channel's last item is staged."""
    mesh = make_mesh((1,), ("data",))
    items = _items(4)
    seen = {}

    def body(*xs):
        for flush in ("step", "ready"):
            ctx = _ctx(channels=2, aggregate="channel", flush=flush)
            st = pipeline.begin_emission(ctx, len(xs), "all_reduce")
            flushed = [pipeline.stage_slices(st, i, x)
                       for i, x in enumerate(xs)]
            seen[flush] = [list(f) for f in flushed]
            outs = pipeline.finish_emission(st)
        return tuple(outs)

    jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),) * 4,
                          out_specs=(P(),) * 4, check_vma=False))(*items)
    assert seen["step"] == [[], [], [], []]
    # ready groups of 4 items on 2 channels: (0,1) and (2,3)
    assert seen["ready"] == [[], [0, 1], [], [2, 3]]


def test_finish_asserts_complete():
    """finish_emission refuses a half-staged ready emission (a bucket
    never produced is a scheduling bug, not a silent drop)."""
    mesh = make_mesh((1,), ("data",))

    def body(x):
        ctx = _ctx(channels=2, aggregate="channel", flush="ready")
        st = pipeline.begin_emission(ctx, 3, "all_reduce")
        pipeline.stage_slices(st, 0, x)
        return pipeline.finish_emission(st)[0]

    with pytest.raises(AssertionError):
        jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                              out_specs=P(), check_vma=False))(jnp.ones((8,)))


def test_gather_flush_groups_keyed_to_schedule():
    """The ZeRO-1 update epilogue mirrors the flush schedule: grouped
    all-gathers only when the sync flushed per channel with contiguous
    (ready) groups; per-bucket everywhere else."""
    from repro.core.backends.hadronio_overlap import make_bucket_plan
    from repro.core.backends.hadronio_overlap_rs import gather_flush_groups
    tree = {"a": jnp.zeros((3000,)), "b": jnp.zeros((200,)),
            "c": jnp.zeros((100,)), "d": jnp.zeros((50,))}
    comm = CommConfig(mode="hadronio_overlap_rs", slice_bytes=1024,
                      channels=2, hierarchical=False)
    plan = make_bucket_plan(tree, comm)
    assert plan.n_buckets >= 3
    singles = tuple((b,) for b in range(plan.n_buckets))
    import dataclasses
    ready = dataclasses.replace(comm, aggregate="channel", flush="ready")
    assert gather_flush_groups(plan, ready) != singles
    assert sorted(i for g in gather_flush_groups(plan, ready)
                  for i in g) == list(range(plan.n_buckets))
    for agg, fl in [("slice", "ready"), ("channel", "step"),
                    ("slice", "step")]:
        c = dataclasses.replace(comm, aggregate=agg, flush=fl)
        assert gather_flush_groups(plan, c) == singles
