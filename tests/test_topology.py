"""The two-level serving fabric, tier-1 side: everything about the
topology machinery that is decidable WITHOUT a multi-device mesh —
config validation, the mesh factory, leader-lane carving, topology-aware
affinity, the leader flush plan, pod-aligned grouping, and the
replica-group evidence parser — plus a structural (1, 1) pod-mesh
lowering proving the leader emission path traces on one device.

The numeric flat-vs-hierarchical conformance needs real ring peers:
``tests/distributed/check_topology.py`` runs it at 8 devices under
``tests/test_system.py``, and the ``REPRO_CONFORMANCE_TOPO=pod`` CI leg
re-runs a 4-device slice in-process here (``tests/conftest.py`` forces
the host device count for that leg only).
"""
import os
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import CommConfig, ServeConfig
from repro.core.backends import pipeline
from repro.core.backends.base import SyncContext
from repro.core.flush_scheduler import make_leader_plan
from repro.core.selector import pod_aligned_groups, ready_groups
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import make_mesh, make_serve_mesh
from repro.serving.event_loop import channel_affinity

POD_LEG = (os.environ.get("REPRO_CONFORMANCE_TOPO") == "pod"
           and jax.device_count() >= 4)
pod_leg = pytest.mark.skipif(
    not POD_LEG,
    reason="pod conformance leg: set REPRO_CONFORMANCE_TOPO=pod "
           "(tests/conftest.py then forces 4 host devices)")


# -- config validation -------------------------------------------------


def test_serve_config_rejects_bad_pod_topology():
    with pytest.raises(ValueError, match="pods must be >= 1"):
        ServeConfig(pods=0)
    with pytest.raises(ValueError, match="pod_axis must be a non-empty"):
        ServeConfig(pod_axis="")
    with pytest.raises(ValueError, match="leader_loops"):
        ServeConfig(event_loops=2, leader_loops=3,
                    comm=CommConfig(channels=4))
    # carving every lane for cross-pod traffic leaves no local lane
    with pytest.raises(ValueError, match="no local lane"):
        ServeConfig(pods=2, comm=CommConfig(channels=2, leader_channels=2,
                                            hierarchical=True))
    # every loop must own at least one LOCAL channel
    with pytest.raises(ValueError, match="LOCAL channels"):
        ServeConfig(pods=2, event_loops=3,
                    comm=CommConfig(channels=4, leader_channels=2,
                                    hierarchical=True))
    # the same shape is fine when the emission stays flat
    ServeConfig(pods=2, event_loops=3,
                comm=CommConfig(channels=4, leader_channels=2,
                                hierarchical=False))


def test_comm_config_rejects_nonpositive_leader_channels():
    with pytest.raises(ValueError, match="leader_channels must be >= 1"):
        CommConfig(leader_channels=0)


def test_make_serve_mesh_shapes_and_validation():
    n = jax.device_count()
    flat = make_serve_mesh(1)
    assert tuple(flat.axis_names) == ("data",)
    assert flat.shape["data"] == n
    with pytest.raises(ValueError, match="pods must be >= 1"):
        make_serve_mesh(0)
    with pytest.raises(ValueError, match="divisors"):
        make_serve_mesh(n + 1)
    if n % 2 == 0:
        two = make_serve_mesh(2)
        assert tuple(two.axis_names) == ("pod", "data")
        assert two.shape["pod"] == 2 and two.shape["data"] == n // 2


# -- leader-lane carving (pipeline) ------------------------------------


def _ctx(channels, leader_channels, aggregate="channel", pod="pod"):
    return types.SimpleNamespace(
        pod_axis=pod,
        comm=CommConfig(channels=channels, leader_channels=leader_channels,
                        aggregate=aggregate))


def test_leader_emission_predicate():
    assert pipeline.leader_emission(_ctx(4, 1), 4)
    assert not pipeline.leader_emission(_ctx(4, 1, pod=None), 4)
    assert not pipeline.leader_emission(_ctx(4, 1, aggregate="slice"), 4)
    assert not pipeline.leader_emission(_ctx(4, 1), 1)  # nothing to carve


def test_leader_split_carves_the_pool_tail():
    assert pipeline._leader_split(_ctx(4, 1), (0, 1, 2, 3)) \
        == ((0, 1, 2), (3,))
    assert pipeline._leader_split(_ctx(4, 2), (0, 1, 2, 3)) \
        == ((0, 1), (2, 3))
    # leader_channels >= channels clamps to channels - 1
    assert pipeline._leader_split(_ctx(4, 9), (0, 1, 2, 3)) \
        == ((0,), (1, 2, 3))


def test_leader_split_never_leaves_a_side_empty():
    # an affinity slice owning no tail lane promotes its last local
    assert pipeline._leader_split(_ctx(4, 1), (0, 1)) == ((0,), (1,))
    # an affinity slice owning ONLY tail lanes keeps one as local
    assert pipeline._leader_split(_ctx(4, 2), (2, 3)) == ((2,), (3,))


# -- topology-aware affinity and the leader flush plan -----------------


def _assert_partition(groups, ids):
    flat = [c for g in groups for c in g]
    assert sorted(flat) == sorted(ids)
    assert len(flat) == len(set(flat))
    assert all(g for g in groups)


@pytest.mark.parametrize("n_loops,leader_loops", [(1, 1), (2, 1), (2, 2),
                                                  (4, 2)])
def test_channel_affinity_topology(n_loops, leader_loops):
    n_channels, leaders, n_pods = 6, 2, 2
    groups = channel_affinity(n_channels, n_loops, n_pods=n_pods,
                              leaders=leaders, leader_loops=leader_loops)
    _assert_partition(groups, range(n_channels))
    n_local = n_channels - leaders
    lead_ids = set(range(n_local, n_channels))
    owners = [i for i, g in enumerate(groups) if lead_ids & set(g)]
    assert owners == list(range(min(leader_loops, leaders)))
    # every loop owns at least one LOCAL lane; with loops >= pods its
    # locals never straddle a pod block, with fewer loops each owns
    # whole consecutive blocks (still pod-aligned, never a partial mix)
    blocks = ready_groups(n_local, n_pods)
    block_of = {c: b for b, g in enumerate(blocks) for c in g}
    for g in groups:
        locals_ = [c for c in g if c < n_local]
        assert locals_
        owned = {block_of[c] for c in locals_}
        if n_loops >= n_pods:
            assert len(owned) == 1
        else:
            assert all(c in locals_ for b in owned for c in blocks[b])


def test_channel_affinity_topology_errors():
    with pytest.raises(ValueError, match="LOCAL channel"):
        channel_affinity(4, 4, n_pods=2, leaders=1)
    with pytest.raises(ValueError, match="leader_loops"):
        channel_affinity(6, 2, n_pods=2, leaders=2, leader_loops=3)
    # leaders=0 keeps the original contiguous form
    assert channel_affinity(4, 2) == ((0, 1), (2, 3))


def test_make_leader_plan_contiguous_and_triggered():
    for n_local, n_leaders in [(4, 1), (4, 2), (5, 2), (3, 7)]:
        plan = make_leader_plan(n_local, n_leaders, "ready")
        _assert_partition(plan.groups, range(n_local))
        for l, g in enumerate(plan.groups):
            assert list(g) == list(range(min(g), max(g) + 1))
            assert plan.triggers[l] == max(g)
            assert all(plan.assign[c] == l for c in g)
        assert len(plan.groups) == min(n_leaders, n_local)


@pytest.mark.parametrize("n_slices,n_groups,n_blocks",
                         [(8, 4, 2), (8, 2, 4), (6, 3, 2), (5, 5, 2),
                          (7, 2, 3)])
def test_pod_aligned_groups_partition(n_slices, n_groups, n_blocks):
    groups = pod_aligned_groups(n_slices, n_groups, n_blocks)
    _assert_partition(groups, range(n_slices))
    blocks = ready_groups(n_slices, min(n_blocks, n_slices))
    block_of = {c: b for b, g in enumerate(blocks) for c in g}
    for g in groups:
        assert list(g) == list(range(min(g), max(g) + 1))  # contiguous
        if len(groups) >= len(blocks):
            assert len({block_of[c] for c in g}) == 1      # no straddle


# -- replica-group evidence parser -------------------------------------

_SYNTH = """\
module @decode {
  %0 = "stablehlo.all_reduce"(%a) {replica_groups = dense<[[0, 1], [2, 3]]> : tensor<2x2xi64>} : (tensor<8xf32>) -> tensor<8xf32>
  %1 = "stablehlo.all_gather"(%b) {replica_groups = dense<[[0, 2]]> : tensor<1x2xi64>} : (tensor<4xf32>) -> tensor<8xf32>
  %2 = "stablehlo.reduce_scatter"(%c) {replica_groups = dense<0> : tensor<1x1xi64>} : (tensor<8xf32>) -> tensor<8xf32>
  %3 = "stablehlo.all_reduce"(%d) {replica_groups = dense<[[0, 1, 2, 3]]> : tensor<1x4xi64>} : (tensor<8xf32>) -> tensor<8xf32>
  %4 = stablehlo.add %0, %1 : tensor<8xf32>
}
"""


def test_parse_replica_groups_forms():
    assert hlo.parse_replica_groups("stablehlo.add %0, %1") is None
    assert hlo.parse_replica_groups(
        "replica_groups = dense<0> : tensor<1x1xi64>") == [[0]]
    assert hlo.parse_replica_groups(
        "replica_groups = dense<[[0, 1], [2, 3]]> : tensor<2x2xi64>") \
        == [[0, 1], [2, 3]]


def test_cross_pod_collective_count_classification():
    cp = hlo.cross_pod_collective_count(_SYNTH, in_pod_size=2)
    # [[0,1],[2,3]] and the splat group stay in-pod at in_pod_size=2;
    # [[0,2]] and [[0,1,2,3]] straddle the pod boundary
    assert cp["in_pod"] == {"all-reduce": 1, "reduce-scatter": 1}
    assert cp["cross_pod"] == {"all-gather": 1, "all-reduce": 1}
    assert cp["in_pod_total"] == 2 and cp["cross_pod_total"] == 2
    # at in_pod_size=1 every multi-member group is cross-pod
    assert hlo.cross_pod_collective_count(
        _SYNTH, in_pod_size=1)["cross_pod_total"] == 3
    # at in_pod_size=4 everything collapses into one pod
    assert hlo.cross_pod_collective_count(
        _SYNTH, in_pod_size=4)["cross_pod_total"] == 0


# -- structural: the leader path traces on a (1, 1) pod mesh -----------


def test_leader_emission_traces_on_degenerate_pod_mesh():
    mesh = make_mesh((1, 1), ("pod", "data"))
    comm = CommConfig(mode="hadronio", channels=2, aggregate="channel",
                      flush="ready", hierarchical=True, leader_channels=1)
    ctx = SyncContext.resolve(comm, ("data",), "pod")
    assert ctx.pod_axis == "pod"
    assert pipeline.leader_emission(ctx, 2)

    def body(x):
        return pipeline.emit_flat(x.reshape(-1), ctx, "all_reduce")

    f = jax.jit(jax.shard_map(body, mesh=mesh,
                              in_specs=P(("pod", "data")),
                              out_specs=P(), check_vma=False))
    x = jnp.arange(1 * 37, dtype=jnp.float32).reshape(1, 37) * 0.5
    np.testing.assert_array_equal(np.asarray(f(x)), np.asarray(x[0]))


def test_serve_step_reports_pod_topology_facts():
    from repro.configs.registry import get_config
    from repro.serving import dispatch

    mesh = make_mesh((1, 1), ("pod", "data"))
    cfg = get_config("qwen2-0.5b-reduced")
    comm = CommConfig(mode="hadronio", channels=2, aggregate="channel",
                      hierarchical=True)
    step = dispatch.make_serve_step(cfg, comm, mesh)
    assert step.pod_axis == "pod" and step.n_pods == 1
    # flat emission on the same mesh keeps the pod axis out of the wire
    flat = dispatch.make_serve_step(
        cfg, CommConfig(mode="hadronio", channels=2, hierarchical=False),
        mesh)
    assert flat.pod_axis is None and flat.n_pods == 1
    with pytest.raises(ValueError, match="not a mesh axis"):
        dispatch.make_serve_step(cfg, comm, mesh, pod_axis="rack")
    with pytest.raises(ValueError, match="in-pod data axis"):
        dispatch.make_serve_step(cfg, comm, make_mesh((1,), ("pod",)))


# -- pod conformance leg (REPRO_CONFORMANCE_TOPO=pod, 4 devices) -------


@pod_leg
def test_psum_hierarchical_parity_pod():
    from functools import partial
    from repro.core.hierarchical import psum_hierarchical

    mesh = make_serve_mesh(2)
    axes = tuple(mesh.axis_names)
    for s in (16, 1003):                  # divisible and padded edges
        x = jnp.asarray(np.linspace(0.0, 1.0, 4 * s, dtype=np.float32)
                        .reshape(4, s))

        @jax.jit
        @partial(jax.shard_map, mesh=mesh, in_specs=P(axes),
                 out_specs=P(), check_vma=False)
        def hier(v):
            return psum_hierarchical(v.reshape(-1), "pod", "data")

        @jax.jit
        @partial(jax.shard_map, mesh=mesh, in_specs=P(axes),
                 out_specs=P(), check_vma=False)
        def flat(v):
            return jax.lax.psum(v.reshape(-1), axes)

        np.testing.assert_allclose(np.asarray(hier(x)),
                                   np.asarray(flat(x)), rtol=1e-5)


@pod_leg
@pytest.mark.parametrize("mode", ["hadronio", "hadronio_overlap",
                                  "hadronio_overlap_rs"])
def test_serve_dispatch_conformance_pod(mode):
    """Flat vs hierarchical emission on the (2, 2) fabric: prefill
    logits bitwise (gathers only move data), decode logits allclose with
    equal argmax (the two-level all-reduce re-associates)."""
    from repro.configs.registry import get_config
    from repro.models import api
    from repro.serving import dispatch

    mesh = make_serve_mesh(2)
    cfg = get_config("qwen2-0.5b-reduced")
    params = api.init(jax.random.PRNGKey(0), cfg)
    toks = np.zeros((4, 6), np.int32)
    lens = np.array([4, 5, 6, 3], np.int32)
    for r in range(4):
        toks[r, :lens[r]] = (np.arange(lens[r]) * (r + 3)) % cfg.vocab_size
    batch = {"tokens": jnp.asarray(toks), "last_pos": jnp.asarray(lens - 1)}

    def logits(hier):
        comm = CommConfig(mode=mode, slice_bytes=512, channels=4,
                          aggregate="channel", flush="ready",
                          hierarchical=hier, leader_channels=1)
        step = dispatch.make_serve_step(cfg, comm, mesh)
        lp, cache = step.prefill(params, batch)
        cache = api.grow_cache(cfg, cache, 24)
        dec = {"token": jnp.argmax(lp, -1).astype(jnp.int32),
               "pos": jnp.asarray(lens, jnp.int32)}
        ld, _ = step.decode(params, cache, dec)
        return np.asarray(lp), np.asarray(ld)

    hier_p, hier_d = logits(True)
    flat_p, flat_d = logits(False)
    np.testing.assert_array_equal(hier_p, flat_p)
    np.testing.assert_allclose(hier_d, flat_d, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(hier_d.argmax(-1), flat_d.argmax(-1))


@pod_leg
@pytest.mark.parametrize("el", [1, 2])
def test_served_tokens_conformance_pod(el):
    from repro.configs.registry import get_config
    from repro.models import api
    from repro.serving import Request, make_engine_group

    cfg = get_config("qwen2-0.5b-reduced")
    params = api.init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(11)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    size=int(rng.integers(4, 12))),
                    max_new=2) for i in range(3)]

    def tokens(hier):
        serve = ServeConfig(
            event_loops=el, poll="busy", max_batch=2, max_len=24, pods=2,
            comm=CommConfig(mode="hadronio_overlap", slice_bytes=512,
                            channels=4, aggregate="channel", flush="ready",
                            hierarchical=hier, leader_channels=1))
        grp = make_engine_group(cfg, params, serve)
        grp.submit(reqs)
        return [tuple(r.tokens.tolist())
                for r in sorted(grp.run(threads=False),
                                key=lambda r: r.uid)]

    assert tokens(True) == tokens(False)


@pod_leg
def test_cross_pod_collective_evidence_pod():
    from repro.configs.registry import get_config
    from repro.serving import dispatch

    mesh = make_serve_mesh(2)
    cfg = get_config("qwen2-0.5b-reduced")
    for leader_channels, hier, want in [(1, True, 1), (2, True, 2),
                                        (1, False, 4)]:
        comm = CommConfig(mode="hadronio_overlap", slice_bytes=512,
                          channels=4, aggregate="channel", flush="ready",
                          hierarchical=hier,
                          leader_channels=leader_channels)
        cp = hlo.cross_pod_collective_count(
            dispatch.lowered_decode_text(cfg, comm, batch=4, mesh=mesh), 2)
        assert cp["cross_pod_total"] == want, (leader_channels, hier, cp)
