import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.configs.base import CommConfig
from repro.core import tac, aggregation as agg
from repro.core.backends import get_backend
from repro.launch.mesh import make_mesh

mesh = make_mesh((4, 2), ("data", "model"))

def tree(rng):
    ks = jax.random.split(rng, 4)
    return {"a": jax.random.normal(ks[0], (33, 7)),
            "b": {"c": jax.random.normal(ks[1], (129,)),
                  "d": jax.random.normal(ks[2], (2, 3, 5))},
            "e": jax.random.normal(ks[3], (1024,))}

grads = tree(jax.random.PRNGKey(0))
# expected: mean over data shards? No - psum = sum over data axis of per-shard grads.
# We feed identical grads per shard (replicated), so psum = n_data * grads.

MODES = ("sockets", "vma", "hadronio", "hadronio_overlap", "hadronio_rs",
         "hadronio_overlap_rs")

results = {}
for mode in MODES:
    comm = CommConfig(mode=mode, slice_bytes=1024, ring_capacity_bytes=64 * 1024,
                      hierarchical=False)

    @jax.jit
    def run(g):
        def inner(g):
            r = tac.sync_grads(g, comm, data_axis="data")
            # zero1 modes reconstruct via the backend's gather epilogue
            return get_backend(mode).gathered_grads(r, g)
        return shard_map(inner, mesh=mesh, in_specs=(P(),), out_specs=P(),
                         check_vma=False)(g)

    out = run(grads)
    ref = jax.tree.map(lambda g: g * 4.0, grads)
    errs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), out, ref)
    maxerr = max(jax.tree.leaves(errs))
    results[mode] = maxerr
    assert maxerr < 1e-4, (mode, maxerr)
    print(f"{mode:20s} max err vs 4*g: {maxerr:.2e}")

# hierarchical with pod axis
mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
for mode in ("hadronio", "hadronio_rs", "hadronio_overlap",
             "hadronio_overlap_rs"):
    for hier in (False, True):
        comm = CommConfig(mode=mode, slice_bytes=1024, ring_capacity_bytes=64 * 1024,
                          hierarchical=hier)

        @jax.jit
        def run(g):
            def inner(g):
                r = tac.sync_grads(g, comm, data_axis="data", pod_axis="pod")
                return get_backend(mode).gathered_grads(r, g)
            return shard_map(inner, mesh=mesh3, in_specs=(P(),), out_specs=P(),
                             check_vma=False)(g)
        out = run(grads)
        ref = jax.tree.map(lambda g: g * 4.0, grads)
        errs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), out, ref)
        maxerr = max(jax.tree.leaves(errs))
        assert maxerr < 1e-4, (mode, hier, maxerr)
        print(f"{mode:20s} hier={hier} (2,2,2): max err: {maxerr:.2e}")

# compression: every codec-capable mode, both EF keyings, both pack impls
# on the real 4-peer ring (chunk indexing / scale math is invisible on 1
# device, so this is the coverage that catches shard-order bugs)
for mode in ("hadronio", "hadronio_overlap", "hadronio_rs",
             "hadronio_overlap_rs"):
    for compress, pack in (("bf16", "jnp"), ("bf16", "pallas"),
                           ("int8_ef", "jnp")):
        comm = CommConfig(mode=mode, slice_bytes=1024, ring_capacity_bytes=64*1024,
                          compress=compress, pack=pack, hierarchical=False)
        @jax.jit
        def run(g):
            def inner(g):
                r = tac.sync_grads(g, comm, data_axis="data")
                return get_backend(mode).gathered_grads(r, g)
            return shard_map(inner, mesh=mesh, in_specs=(P(),), out_specs=P(),
                             check_vma=False)(g)
        out = run(grads)
        ref = jax.tree.map(lambda g: g * 4.0, grads)
        if compress == "bf16":
            # bf16 rounding is relative to the element
            errs = jax.tree.map(lambda a, b: float(jnp.max(
                jnp.abs(a - b) / (jnp.abs(b) + 1e-3))), out, ref)
            maxerr = max(jax.tree.leaves(errs))
            assert maxerr < 0.02, (mode, compress, pack, maxerr)
            kind = "rel"
        else:
            # int8 max-abs quantization error is absolute: bounded by
            # n_peers * slice_amax / 254 (~0.05 here)
            errs = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))),
                                out, ref)
            maxerr = max(jax.tree.leaves(errs))
            assert maxerr < 0.1, (mode, compress, pack, maxerr)
            kind = "abs"
        print(f"{mode:20s} compress={compress:8s} pack={pack:6s} max {kind} err: {maxerr:.2e}")
print("done")
