"""Two-level serving fabric at 8 virtual devices (2 pods x 4): the
leader-channel emission, pod-aware dispatch wiring, and topology-aware
affinity — everything the 1-device tier-1 run degenerates to identity.
Invariants checked on the (2, 4) ("pod", "data") serve mesh:

* ``psum_hierarchical`` equals the flat psum numerically (allclose — the
  two summation orders legitimately differ in the last ulps) including
  the non-divisible-S padding edge, and gathers are BIT-identical;
* dispatch logits are BIT-identical across the hadronio-family modes and
  channel affinities WITHIN a fixed emission (the transparency claim,
  pod-aware); across flat vs hierarchical emission the prefill logits
  stay bitwise (gathers move data, they never re-associate) and decode
  logits agree to allclose with equal argmax;
* engine-group greedy TOKENS are identical for flat vs leader-channel
  hierarchical emission across event-loop counts {1, 2, 4};
* the lowered decode step's cross-pod collective count drops to
  ``comm.leader_channels`` under leader emission while flat emission
  keeps all ``comm.channels`` collectives cross-pod.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
import jax.numpy as jnp
from functools import partial
from jax.sharding import PartitionSpec as P

from jax import shard_map
from repro.configs.base import CommConfig, ServeConfig
from repro.configs.registry import get_config
from repro.core.hierarchical import (psum_hierarchical,
                                     psum_scatter_hierarchical)
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import make_serve_mesh
from repro.models import api
from repro.serving import Request, make_engine_group
from repro.serving import dispatch

mesh = make_serve_mesh(2)                   # (2, 4) ("pod", "data")
assert tuple(mesh.axis_names) == ("pod", "data")
cfg = get_config("qwen2-0.5b-reduced")
params = api.init(jax.random.PRNGKey(0), cfg)

# -- core/hierarchical.py in isolation ---------------------------------

for S in (64, 1003):                        # divisible and padded edges
    x = (np.arange(8 * S, dtype=np.float32).reshape(8, S) * 1e-3 + 0.1)
    xd = jax.device_put(jnp.asarray(x),
                        jax.NamedSharding(mesh, P(("pod", "data"))))

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P(("pod", "data")),
             out_specs=P(), check_vma=False)
    def hier(v):
        return psum_hierarchical(v.reshape(-1), "pod", "data")

    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P(("pod", "data")),
             out_specs=P(), check_vma=False)
    def flat(v):
        return jax.lax.psum(v.reshape(-1), ("pod", "data"))

    np.testing.assert_allclose(np.asarray(hier(xd)), np.asarray(flat(xd)),
                               rtol=1e-5)
    print(f"psum_hierarchical == flat psum (allclose) at S={S}")

try:
    @jax.jit
    @partial(shard_map, mesh=mesh, in_specs=P(("pod", "data")),
             out_specs=P(("pod", "data")), check_vma=False)
    def bad(v):
        return psum_scatter_hierarchical(v, "pod", "data")

    bad(jax.device_put(jnp.ones((8, 1003), jnp.float32),
                       jax.NamedSharding(mesh, P(("pod", "data")))))
    raise SystemExit("psum_scatter_hierarchical accepted non-divisible S")
except ValueError as e:
    assert "divisible by the in-pod ring size" in str(e)
    print("psum_scatter_hierarchical rejects non-divisible S with a clear "
          "error")

# -- dispatch conformance: flat vs hierarchical emission ---------------


def comm_for(mode, hier, channels=6, leader_channels=2):
    return CommConfig(mode=mode, slice_bytes=512, channels=channels,
                      aggregate="channel", flush="ready",
                      hierarchical=hier, leader_channels=leader_channels)


def step_logits(comm, affinity=None):
    step = dispatch.make_serve_step(cfg, comm, mesh,
                                    channel_indices=affinity)
    assert step.n_shards == 8
    assert step.n_pods == 2 and (step.pod_axis == "pod"
                                 if comm.hierarchical
                                 else step.pod_axis is None)
    toks = np.zeros((8, 8), np.int32)
    lens = np.array([5, 6, 7, 5, 4, 8, 6, 5], np.int32)
    for r in range(8):
        toks[r, :lens[r]] = (np.arange(lens[r]) * (r + 2)) % cfg.vocab_size
    batch = {"tokens": jnp.asarray(toks), "last_pos": jnp.asarray(lens - 1)}
    logits_p, cache = step.prefill(params, batch)
    cache = api.grow_cache(cfg, cache, 32)
    dec = {"token": jnp.argmax(logits_p, -1).astype(jnp.int32),
           "pos": jnp.asarray(lens, jnp.int32)}
    logits_d, _ = step.decode(params, cache, dec)
    return np.asarray(logits_p), np.asarray(logits_d)


hier_p, hier_d = step_logits(comm_for("hadronio", True))
for mode in ("hadronio_overlap", "hadronio_overlap_rs"):
    got_p, got_d = step_logits(comm_for(mode, True))
    np.testing.assert_array_equal(got_p, hier_p)
    np.testing.assert_array_equal(got_d, hier_d)
    print(f"hierarchical dispatch logits bit-identical: {mode}")
aff_p, aff_d = step_logits(comm_for("hadronio", True), affinity=(1, 2, 5))
np.testing.assert_array_equal(aff_p, hier_p)
np.testing.assert_array_equal(aff_d, hier_d)
print("hierarchical dispatch logits invariant to channel affinity")

flat_p, flat_d = step_logits(comm_for("hadronio", False))
ref_p, ref_d = step_logits(comm_for("gspmd", False))
np.testing.assert_array_equal(flat_p, ref_p)
np.testing.assert_array_equal(flat_d, ref_d)
# gathers are data movement: prefill logits stay bitwise across emissions
np.testing.assert_array_equal(hier_p, flat_p)
print("prefill logits BIT-identical across flat vs hierarchical emission")
# the all-reduce re-associates: decode logits agree to allclose, and the
# served (greedy) tokens are identical
np.testing.assert_allclose(hier_d, flat_d, rtol=1e-4, atol=1e-5)
np.testing.assert_array_equal(hier_d.argmax(-1), flat_d.argmax(-1))
print("decode logits allclose + argmax-equal across emissions")

# -- engine group: served tokens across emissions and loop counts ------

rng = np.random.default_rng(5)
reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                size=int(rng.integers(4, 14))),
                max_new=2) for i in range(4)]


def group_tokens(hier, el):
    serve = ServeConfig(event_loops=el, poll="busy", max_batch=2,
                        max_len=32, pods=2,
                        leader_loops=min(el, 2) if hier else 1,
                        comm=comm_for("hadronio_overlap", hier))
    grp = make_engine_group(cfg, params, serve, mesh=mesh)
    if hier:
        leads = {c for l in grp.loops for c in l.channels if c >= 4}
        owners = [l.index for l in grp.loops
                  if any(c >= 4 for c in l.channels)]
        assert leads == {4, 5}, leads
        assert owners == list(range(len(owners))), owners
    grp.submit(reqs)
    res = sorted(grp.run(threads=False), key=lambda r: r.uid)
    return [tuple(r.tokens.tolist()) for r in res]


base = group_tokens(False, 1)
for el in (1, 2, 4):
    got = group_tokens(True, el)
    assert got == base, (el, got, base)
    print(f"served tokens identical, flat vs hierarchical, "
          f"event_loops={el}")

# -- cross-pod collective evidence -------------------------------------

for leader_channels in (1, 2):
    comm = comm_for("hadronio_overlap", True,
                    leader_channels=leader_channels)
    cp = hlo.cross_pod_collective_count(
        dispatch.lowered_decode_text(cfg, comm, batch=8, mesh=mesh), 4)
    assert cp["cross_pod_total"] == leader_channels, (leader_channels, cp)
    assert cp["in_pod_total"] > 0, cp
flat_cp = hlo.cross_pod_collective_count(
    dispatch.lowered_decode_text(cfg, comm_for("hadronio_overlap", False),
                                 batch=8, mesh=mesh), 4)
assert flat_cp["cross_pod_total"] == 6, flat_cp    # every channel
print("cross-pod collectives: n_leader_channels (hierarchical) vs "
      "n_channels (flat)")

print("ALL OK")
