"""Property-style tests of error-feedback compression.

The EF contract: truncation error is never dropped, only deferred — over
K steps the accumulated wire output plus the final residual equals the
accumulated input exactly (the telescoping sum), so the compressed
update is unbiased over time. Verified here for

* both codecs (``bf16`` and ``int8_ef``),
* both EF keyings — global ring plan (``hadronio``) and per-bucket
  (``hadronio_overlap`` / ``hadronio_overlap_rs``),
* both pack-stage implementations (jnp and the fused pallas kernel),
* a tree whose biggest leaf exceeds a bucket (the oversized-singleton
  edge case of the greedy bucketing).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import CommConfig, RunConfig, ShapeConfig
from repro.configs.registry import get_config
from repro.core import aggregation as agg
from repro.core import tac
from repro.core.backends import get_backend
from repro.core.backends import hadronio_overlap as ho
from repro.core.backends import hadronio_overlap_rs as hors
from repro.launch.mesh import make_mesh

K_STEPS = 4
SLICE_BYTES = 4096
BUCKET_MODES = ("hadronio_overlap", "hadronio_overlap_rs")


def _tree(step: int):
    """Per-step random gradients; the 3000-elem leaf carries 12 KB of
    payload > slice_bytes, so bucketing gives it its own bucket."""
    ks = jax.random.split(jax.random.PRNGKey(100 + step), 3)
    return {"a": jax.random.normal(ks[0], (17, 9)),
            "b": jax.random.normal(ks[1], (200,)),
            "big": jax.random.normal(ks[2], (3000,))}


def _comm(mode, compress, pack="jnp"):
    return CommConfig(mode=mode, compress=compress, pack=pack,
                      slice_bytes=SLICE_BYTES, hierarchical=False)


def _bucket_plan(like, comm):
    return ho.make_bucket_plan(like, comm) \
        if comm.mode == "hadronio_overlap" \
        else hors.rs_bucket_plan(like, comm, 1)


def _zero_ef(like, comm):
    """The zero residual in the backend's own EF keying."""
    if comm.mode in BUCKET_MODES:
        plan = _bucket_plan(like, comm)
        return tuple(jnp.zeros((p,), jnp.float32) for p in plan.padded)
    plan = agg.make_plan(like, comm)
    return jnp.zeros((plan.n_slices, plan.slice_elems), jnp.float32)


def _decode_ef(ef, like, comm):
    """Carve a residual (ring- or bucket-keyed) back into tree space."""
    f32 = jax.tree.map(lambda l: jnp.zeros(l.shape, jnp.float32), like)
    if comm.mode in BUCKET_MODES:
        plan = _bucket_plan(like, comm)
        leaves = jax.tree.leaves(f32)
        out = [None] * len(leaves)
        for b in range(plan.n_buckets):
            ho.unpack_bucket(ef[b], plan, b, leaves, out)
        return jax.tree.unflatten(jax.tree.structure(like), out)
    plan = agg.make_plan(like, comm)
    return agg.unpack(agg.from_slices(ef, plan), plan, f32)


CASES = [(m, c, p)
         for m in ("hadronio",) + BUCKET_MODES
         for c, p in (("bf16", "jnp"), ("bf16", "pallas"),
                      ("int8_ef", "jnp"))]


@pytest.mark.parametrize("mode,compress,pack", CASES)
def test_ef_unbiased_over_k_steps(mode, compress, pack):
    """sum_k(wire_k) + final_residual == sum_k(input_k): the accumulated
    wire+EF drift goes to zero, for global-ring AND per-bucket keying."""
    comm = _comm(mode, compress, pack)
    backend = get_backend(mode)
    mesh = make_mesh((1,), ("data",))
    like = _tree(0)

    def body(g, ef):
        r = tac.sync_grads(g, comm, data_axis=("data",), ef=ef)
        return backend.gathered_grads(r, g), r.ef

    ef = _zero_ef(like, comm)
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                              out_specs=(P(), P()), check_vma=False))

    total_in = jax.tree.map(jnp.zeros_like, like)
    total_out = jax.tree.map(jnp.zeros_like, like)
    for k in range(K_STEPS):
        x = _tree(k)
        out, ef = f(x, ef)
        total_in = jax.tree.map(jnp.add, total_in, x)
        total_out = jax.tree.map(jnp.add, total_out, out)

    resid = _decode_ef(ef, like, comm)
    drift = jax.tree.map(lambda o, r, i: jnp.max(jnp.abs(o + r - i)),
                         total_out, resid, total_in)
    assert max(float(d) for d in jax.tree.leaves(drift)) < 1e-4

    # the lossy wire really was lossy (EF had something to carry)
    resid_max = max(float(jnp.max(jnp.abs(r)))
                    for r in jax.tree.leaves(resid))
    assert resid_max > 1e-6


@pytest.mark.parametrize("mode", BUCKET_MODES)
def test_oversized_leaf_gets_own_bucket_and_ef(mode):
    """One leaf bigger than a bucket: the greedy bucketing gives it a
    singleton bucket whose EF leaf covers the whole (padded) payload."""
    comm = _comm(mode, "bf16")
    like = _tree(0)
    plan = _bucket_plan(like, comm)
    sizes = dict(zip(range(len(plan.sizes)), plan.sizes))
    big = max(sizes, key=sizes.get)
    assert plan.sizes[big] * 4 > comm.slice_bytes
    assert (big,) in plan.buckets       # its own bucket
    b = plan.buckets.index((big,))
    assert plan.padded[b] >= plan.sizes[big]
    ef = _zero_ef(like, comm)
    assert len(ef) == plan.n_buckets
    assert ef[b].shape == (plan.padded[b],)


@pytest.mark.parametrize("mode", BUCKET_MODES)
def test_state_ef_keyed_by_bucket_id(mode):
    """state_specs' EF pytree is keyed by bucket id — one (ring, padded)
    leaf per bucket, independent of any global ring plan."""
    cfg = get_config("qwen2-0.5b-reduced")
    run = RunConfig(model=cfg, shape=ShapeConfig("t", "train", 16, 4),
                    comm=_comm(mode, "bf16"))
    from repro.models import api
    eff = 4
    plan = ho.make_bucket_plan(api.abstract(cfg), run.comm) \
        if mode == "hadronio_overlap" \
        else hors.rs_bucket_plan(api.abstract(cfg), run.comm, eff)
    specs = get_backend(mode).state_specs(run, eff)
    assert isinstance(specs.ef, tuple)
    assert len(specs.ef) == plan.n_buckets
    for b, e in enumerate(specs.ef):
        assert tuple(e.shape) == (eff, plan.padded[b])
        assert e.dtype == jnp.float32
