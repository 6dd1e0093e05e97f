"""Cross-backend conformance suite.

The JIB-benchmark lesson (Nothaas et al., arXiv:1910.02245): transport
variants are only trustworthy when ONE harness exercises every
implementation identically. Every registered comm backend runs through
the same fixture matrix here:

* **sync parity** — on a 1-peer ring psum == identity, so the
  reconstructed synced gradients must equal the inputs within the wire
  codec's dtype tolerance, for every supported ``(compress, pack)``
  combination; unsupported combinations must be REJECTED by
  ``validate()`` with a clear error (never silently ignored).
* **state round-trip** — ``state_specs`` / ``init`` / ``apply_update``
  agree: a jitted train step returns a state matching the abstract specs
  leaf-for-leaf (structure, shape, dtype), for compress off AND on.
* **gspmd parity** — every manual backend's two-step loss equals the
  gspmd reference within tolerance (the paper's transparency claim).
* **bucket independence** — in BOTH overlap modes, each bucket's
  collective depends only on its own leaves (+ its own per-bucket error
  feedback): a jaxpr-level dependency check, for every codec.

The matrix is generated from ``available_modes()`` and indexed into
``SUPPORTED_COMPRESS`` at collection time — registering a backend
without declaring its conformance expectations fails collection.

* **aggregate parity** — ``comm.aggregate="channel"`` (one coalesced
  wire flush per connection) must be BIT-identical to the per-slice
  schedule for every hadronio-family mode and codec, including the
  ZeRO-1 flat-shard ordering, and the per-exchange collective count must
  drop from n_slices to n_channels (checked on the emitted StableHLO via
  ``launch/hlo_analysis``).

* **flush parity** — ``comm.flush="ready"`` (the flush-when-ready
  channel schedule from ``core/flush_scheduler``) must be BIT-identical
  to the ``"step"`` schedule for every hadronio-family mode × codec ×
  pack at BOTH aggregate granularities, and the jaxpr-level evidence
  tests prove the overlap recovery: under ``aggregate="channel"`` with
  channels < n_buckets the first channel's collective is emitted before
  the last bucket's pack (and depends only on its own contiguous run of
  first-produced buckets), which ``"step"`` structurally cannot do.

Set ``REPRO_CONFORMANCE_PACK=jnp|pallas`` to pin the pack-stage
implementation (CI runs the jnp fallback explicitly),
``REPRO_CONFORMANCE_AGG=slice|channel`` to pin the wire-flush
granularity, and ``REPRO_CONFORMANCE_FLUSH=step|ready`` to pin the
channel schedule the whole matrix runs under — CI runs one conformance
leg per pin (a workflow matrix with fail-fast off).
"""
import functools
import os

import numpy as np
import jax
import jax.extend.core
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import CommConfig, RunConfig, ShapeConfig
from repro.configs.registry import get_config
from repro.core import tac
from repro.core.backends import (SyncContext, available_modes, get_backend)
from repro.core.backends import hadronio_overlap as ho
from repro.core.backends import hadronio_overlap_rs as hors
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh

COMPRESS = ("none", "bf16", "int8_ef")
_PACK_ENV = os.environ.get("REPRO_CONFORMANCE_PACK")
PACKS = (_PACK_ENV,) if _PACK_ENV else ("jnp", "pallas")
assert all(p in ("jnp", "pallas") for p in PACKS), _PACK_ENV
# wire-flush granularity the whole matrix runs under (the aggregate-parity
# tests below always exercise BOTH, so the default leg stays "slice");
# empty values (unset legs of the CI matrix) fall back to the default
AGG = os.environ.get("REPRO_CONFORMANCE_AGG") or "slice"
assert AGG in ("slice", "channel"), AGG
# channel schedule the whole matrix runs under (the flush-parity tests
# below always exercise BOTH, so the default leg stays "step")
FLUSH = os.environ.get("REPRO_CONFORMANCE_FLUSH") or "step"
assert FLUSH in ("step", "ready"), FLUSH

# Which codecs each registered mode must honor; everything not listed
# must be rejected by validate(). EVERY registered mode needs an entry —
# the matrix below indexes this dict with each name in available_modes()
# at collection time, so a backend registered without conformance
# coverage fails before a single test runs.
SUPPORTED_COMPRESS = {
    "gspmd": ("none",),
    "sockets": ("none",),
    "vma": ("none", "bf16"),
    "hadronio": ("none", "bf16", "int8_ef"),
    "hadronio_rs": ("none", "bf16", "int8_ef"),
    "hadronio_overlap": ("none", "bf16", "int8_ef"),
    "hadronio_overlap_rs": ("none", "bf16", "int8_ef"),
}

SYNC_CASES = [(m, c, p)
              for m in available_modes()
              for c in SUPPORTED_COMPRESS[m]      # KeyError => no coverage
              for p in PACKS]
REJECT_CASES = [(m, c)
                for m in available_modes()
                for c in COMPRESS if c not in SUPPORTED_COMPRESS[m]]
STEP_CASES = [(m, c) for m in available_modes()
              for c in SUPPORTED_COMPRESS[m]]
BUCKET_MODES = ("hadronio_overlap", "hadronio_overlap_rs")

# int8 quantizes per slice/bucket against the group amax; tolerance is
# absolute against the tree's amax (~4 for unit normals)
TOL = {"none": dict(rtol=1e-6, atol=1e-6),
       "bf16": dict(rtol=1e-2, atol=1e-3),
       "int8_ef": dict(rtol=0.0, atol=0.05)}


def test_matrix_covers_registry_exactly():
    """No registered mode without coverage, no stale matrix entries."""
    assert set(SUPPORTED_COMPRESS) == set(available_modes())


def _grad_tree():
    """Mixed-shape synthetic gradients: a scalar-ish 1-D leaf, odd dims,
    and one 3000-element leaf that is BIGGER than a 4 KiB bucket (12 KB
    payload -> its own bucket)."""
    ks = jax.random.split(jax.random.PRNGKey(7), 4)
    return {"a": jax.random.normal(ks[0], (33, 7)),
            "b": {"c": jax.random.normal(ks[1], (129,)),
                  "d": jax.random.normal(ks[2], (2, 3, 5))},
            "e": jax.random.normal(ks[3], (3000,))}


def _comm(mode, compress="none", pack="jnp", **kw):
    kw.setdefault("slice_bytes", 4096)
    kw.setdefault("hierarchical", False)
    kw.setdefault("aggregate", AGG)
    kw.setdefault("flush", FLUSH)
    return CommConfig(mode=mode, compress=compress, pack=pack, **kw)


@pytest.mark.parametrize("mode,compress,pack", SYNC_CASES)
def test_sync_parity(mode, compress, pack):
    """Identity on a 1-peer ring, reconstructed through the backend's own
    gathered_grads (exercises the zero1 gather epilogues too)."""
    backend = get_backend(mode)
    if not backend.manual:
        pytest.skip("no manual sync; covered by the step round-trip")
    comm = _comm(mode, compress, pack)
    backend.validate(comm)
    grads = _grad_tree()
    mesh = make_mesh((1,), ("data",))

    def body(g):
        r = tac.sync_grads(g, comm, data_axis=("data",))
        return backend.gathered_grads(r, g)

    out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                out_specs=P(), check_vma=False))(grads)
    assert jax.tree.structure(out) == jax.tree.structure(grads)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(grads)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   **TOL[compress])


@pytest.mark.parametrize("mode,compress", REJECT_CASES)
def test_unsupported_codec_rejected(mode, compress):
    """A codec the strategy cannot honor must raise at validate() —
    silently ignoring compression is a conformance failure."""
    comm = _comm(mode, compress)
    with pytest.raises(ValueError, match="compress"):
        get_backend(mode).validate(comm)


# ---------------------------------------------------------------------------
# Step-level round-trip + gspmd parity (one cached 2-step run per case)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def _two_step(mode, compress):
    """(final_state, abstract_specs, [loss1, loss2]) for a jitted 2-step
    run of the given mode on a 1-device mesh."""
    cfg = get_config("qwen2-0.5b-reduced")
    run = RunConfig(model=cfg, shape=ShapeConfig("t", "train", 16, 4),
                    comm=_comm(mode, compress, slice_bytes=16 * 1024))
    mesh = make_mesh((1,), ("data",))
    with jax.set_mesh(mesh):
        step_fn, state_sh, _ = steps_mod.make_train_step(run, mesh)
        if get_backend(mode).manual:
            sds = steps_mod.abstract_tac_state(run, 1)
            state = steps_mod.init_tac_state(jax.random.PRNGKey(0), run, 1)
        else:
            sds = steps_mod.abstract_train_state(run)
            state = steps_mod.init_train_state(jax.random.PRNGKey(0), run)
        batch = {"tokens": jnp.ones((4, 16), jnp.int32),
                 "labels": jnp.ones((4, 16), jnp.int32)}
        jf = jax.jit(step_fn)
        losses = []
        for _ in range(2):
            state, m = jf(state, batch)
            losses.append(float(m["loss"]))
    return state, sds, losses


@pytest.mark.parametrize("mode,compress", STEP_CASES)
def test_state_roundtrip(mode, compress):
    """The state a step RETURNS matches the state_specs layout the
    backend DECLARED — structure, shape, and dtype, leaf for leaf (error
    feedback included when the codec carries one)."""
    state, sds, losses = _two_step(mode, compress)
    assert jax.tree.structure(state) == jax.tree.structure(sds)
    paths_out = jax.tree_util.tree_flatten_with_path(state)[0]
    paths_sds = jax.tree_util.tree_flatten_with_path(sds)[0]
    for (pa, a), (pb, b) in zip(paths_out, paths_sds):
        assert pa == pb
        assert tuple(a.shape) == tuple(b.shape), (pa, a.shape, b.shape)
        assert a.dtype == b.dtype, (pa, a.dtype, b.dtype)
    assert all(np.isfinite(l) for l in losses), losses
    if get_backend(mode).needs_ef(CommConfig(mode=mode, compress=compress,
                                             hierarchical=False)):
        assert state.ef is not None
    else:
        assert state.ef is None


@pytest.mark.parametrize("mode", [m for m in available_modes()
                                  if get_backend(m).manual])
def test_gspmd_parity(mode):
    """Two-step loss trajectory equals the gspmd reference (transparency:
    the synchronization strategy must not change the math)."""
    _, _, ref = _two_step("gspmd", "none")
    _, _, got = _two_step(mode, "none")
    np.testing.assert_allclose(got, ref, rtol=0, atol=2e-3)


# ---------------------------------------------------------------------------
# Bucket independence (jaxpr-level): each bucket's collective depends
# only on its own leaves (+ its own per-bucket EF residual)
# ---------------------------------------------------------------------------


def _collective_deps(mode, compress, pack):
    """Trace the backend's sync inside the shard_map and return
    (plan, [(primitive_name, dep_label_set)]) for every collective eqn.
    Labels: ('leaf', i) for gradient leaf i, ('ef', b) for bucket b's
    residual."""
    comm = _comm(mode, compress, pack, channels=64, slice_bytes=1024,
                 ring_capacity_bytes=1 << 20)
    grads = _grad_tree()
    leaves, treedef = jax.tree.flatten(grads)
    backend = get_backend(mode)
    plan = ho.make_bucket_plan(grads, comm) if mode == "hadronio_overlap" \
        else hors.rs_bucket_plan(grads, comm, 1)
    n_ef = plan.n_buckets if compress != "none" else 0
    mesh = make_mesh((1,), ("data",))

    def body(*args):
        g = jax.tree.unflatten(treedef, list(args[:len(leaves)]))
        efs = tuple(args[len(leaves):]) or None
        ctx = SyncContext.resolve(comm, ("data",), None, efs)
        r = backend.sync(g, ctx)
        outs = jax.tree.leaves(r.grads) if r.grads is not None \
            else [r.flat_shard]
        return tuple(outs)

    args = leaves + [jnp.zeros((p,), jnp.float32) for p in plan.padded[:n_ef]]
    n_out = len(leaves) if mode == "hadronio_overlap" else 1
    f = jax.shard_map(body, mesh=mesh, in_specs=(P(),) * len(args),
                      out_specs=(P(),) * n_out, check_vma=False)
    jaxpr = jax.make_jaxpr(f)(*args)

    inner = None
    for eqn in jaxpr.jaxpr.eqns:
        if eqn.primitive.name == "shard_map":
            inner = eqn.params["jaxpr"]
            break
    assert inner is not None, "no shard_map eqn found"

    Literal = jax.extend.core.Literal
    deps = {}
    for i, v in enumerate(inner.invars):
        deps[v] = frozenset([("leaf", i) if i < len(leaves)
                             else ("ef", i - len(leaves))])
    for v in inner.constvars:
        deps[v] = frozenset()

    def var_deps(a):
        return frozenset() if isinstance(a, Literal) \
            else deps.get(a, frozenset())

    collectives = []
    for eqn in inner.eqns:
        d = frozenset().union(*[var_deps(a) for a in eqn.invars]) \
            if eqn.invars else frozenset()
        name = eqn.primitive.name
        if any(k in name for k in ("psum", "all_gather", "all_to_all",
                                   "ppermute", "reduce_scatter")):
            collectives.append((name, d))
        for ov in eqn.outvars:
            deps[ov] = d
    return plan, collectives


# ---------------------------------------------------------------------------
# Channel-level gathering-write aggregation (comm.aggregate="channel"):
# bit-identical numerics, fewer wire flushes
# ---------------------------------------------------------------------------

HADRONIO_FAMILY = tuple(m for m in available_modes()
                        if m.startswith("hadronio"))
AGG_CASES = [(m, c, p)
             for m in HADRONIO_FAMILY
             for c in SUPPORTED_COMPRESS[m]
             for p in PACKS]


def _sync_outputs(mode, comm, grads):
    """(leaves-or-flat-shard tuple, ef leaves tuple) of one jitted sync,
    plus the emitted StableHLO collective stats."""
    from repro.launch import hlo_analysis as hlo
    backend = get_backend(mode)

    def body(g):
        r = tac.sync_grads(g, comm, data_axis=("data",))
        outs = tuple(jax.tree.leaves(r.grads)) if r.grads is not None \
            else (r.flat_shard,)
        efs = tuple(jax.tree.leaves(r.ef)) if r.ef is not None else ()
        return outs + efs

    mesh = make_mesh((1,), ("data",))
    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                              out_specs=P(), check_vma=False))
    stats = hlo.stablehlo_collective_stats(f.lower(grads).as_text())
    return f(grads), stats


@pytest.mark.parametrize("mode,compress,pack", AGG_CASES)
def test_aggregate_channel_parity(mode, compress, pack):
    """aggregate="channel" (one coalesced wire flush per connection) is
    BIT-identical to the per-slice schedule — synced grads for the tree
    modes, the flat-shard ordering for the ZeRO-1 modes, and the
    error-feedback residuals — with fewer channels than slices/buckets so
    coalescing genuinely merges buffers."""
    grads = _grad_tree()
    outs = {}
    for aggregate in ("slice", "channel"):
        comm = _comm(mode, compress, pack, channels=2, slice_bytes=1024,
                     ring_capacity_bytes=1 << 20, aggregate=aggregate)
        outs[aggregate], _ = _sync_outputs(mode, comm, grads)
    assert len(outs["slice"]) == len(outs["channel"])
    for a, b in zip(outs["slice"], outs["channel"]):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("mode", HADRONIO_FAMILY)
def test_aggregate_collective_count_drops_to_channel_count(mode):
    """The gathering-write payoff, read off the emitted StableHLO
    (launch/hlo_analysis): per exchange, the per-slice schedule emits one
    collective per slice/bucket; aggregate="channel" emits exactly
    min(channels, n_items) — one coalesced flush per connection."""
    grads = _grad_tree()
    n_channels = 2       # < n_buckets (3) and < n_slices (7) at 1 KiB
    counts = {}
    for aggregate in ("slice", "channel"):
        comm = _comm(mode, "none", "jnp", channels=n_channels,
                     slice_bytes=1024, ring_capacity_bytes=1 << 20,
                     aggregate=aggregate)
        _, stats = _sync_outputs(mode, comm, grads)
        counts[aggregate] = stats.total_ops
    if mode in BUCKET_MODES:
        plan = ho.make_bucket_plan(grads, _comm(mode, slice_bytes=1024)) \
            if mode == "hadronio_overlap" \
            else hors.rs_bucket_plan(grads, _comm(mode, slice_bytes=1024), 1)
        n_items = plan.n_buckets
    else:
        from repro.core import aggregation as agg
        n_items = agg.make_plan(_grad_tree(),
                                _comm(mode, slice_bytes=1024)).n_slices
    assert n_items > n_channels, (n_items, n_channels)
    assert counts["slice"] == n_items, counts
    assert counts["channel"] == n_channels, counts


def test_channel_flush_preserves_scatter_layout(np_rng):
    """The reduce-scatter flush interleave: peer p's contiguous 1/group
    chunk of the coalesced buffer equals the concatenation of p's
    per-slice chunks — the property that keeps the ZeRO-1 flat-shard
    ordering identical across aggregate granularities."""
    from repro.core.backends import pipeline
    group = 4
    sizes = [512, 1024, 512]
    flats = [jnp.asarray(np_rng.normal(size=(s,)), jnp.float32)
             for s in sizes]
    buf = np.asarray(pipeline.interleave_for_scatter(flats, group))
    assert buf.shape == (sum(sizes),)
    c = buf.shape[0] // group
    for p in range(group):
        expect = np.concatenate(
            [np.asarray(f)[p * (len(f) // group):(p + 1) * (len(f) // group)]
             for f in flats])
        np.testing.assert_array_equal(buf[p * c:(p + 1) * c], expect)
    # single-buffer flush needs no interleave (identity)
    np.testing.assert_array_equal(
        np.asarray(pipeline.interleave_for_scatter(flats[:1], group)),
        np.asarray(flats[0]))


# ---------------------------------------------------------------------------
# Flush-when-ready channel schedule (comm.flush="ready",
# core/flush_scheduler): bit-identical numerics, overlap recovered under
# aggregate="channel" with fewer channels than buckets
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode,compress,pack", AGG_CASES)
def test_flush_ready_parity(mode, compress, pack):
    """flush="ready" (contiguous production-order groups, each flushed
    the moment its last bucket is staged) is BIT-identical to the
    flush="step" barrier loop at BOTH aggregate granularities, for every
    hadronio-family mode, codec and pack impl — synced grads, ZeRO-1
    flat-shard ordering and EF residuals. The schedule moves the same
    bytes; only the emission structure may differ."""
    grads = _grad_tree()
    for aggregate in ("slice", "channel"):
        outs = {}
        for flush in ("step", "ready"):
            comm = _comm(mode, compress, pack, channels=2,
                         slice_bytes=1024, ring_capacity_bytes=1 << 20,
                         aggregate=aggregate, flush=flush)
            outs[flush], _ = _sync_outputs(mode, comm, grads)
        assert len(outs["step"]) == len(outs["ready"])
        for a, b in zip(outs["step"], outs["ready"]):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _sync_trace(mode, flush):
    """Inner-jaxpr eqn list of one backend.sync under
    aggregate="channel" with channels < n_buckets, plus the bucket plan
    and the per-eqn transitive gradient-leaf dependency sets."""
    comm = _comm(mode, "none", PACKS[0], channels=2, slice_bytes=1024,
                 ring_capacity_bytes=1 << 20, aggregate="channel",
                 flush=flush)
    grads = _grad_tree()
    leaves, treedef = jax.tree.flatten(grads)
    backend = get_backend(mode)
    plan = ho.make_bucket_plan(grads, comm) if mode == "hadronio_overlap" \
        else hors.rs_bucket_plan(grads, comm, 1)
    mesh = make_mesh((1,), ("data",))

    def body(*args):
        g = jax.tree.unflatten(treedef, list(args))
        ctx = SyncContext.resolve(comm, ("data",), None)
        r = backend.sync(g, ctx)
        outs = jax.tree.leaves(r.grads) if r.grads is not None \
            else [r.flat_shard]
        return tuple(outs)

    n_out = len(leaves) if not backend.zero1 else 1
    f = jax.shard_map(body, mesh=mesh, in_specs=(P(),) * len(leaves),
                      out_specs=(P(),) * n_out, check_vma=False)
    jaxpr = jax.make_jaxpr(f)(*leaves)
    inner = next(e for e in jaxpr.jaxpr.eqns
                 if e.primitive.name == "shard_map").params["jaxpr"]

    Literal = jax.extend.core.Literal
    deps = {v: frozenset([i]) for i, v in enumerate(inner.invars)}
    for v in inner.constvars:
        deps[v] = frozenset()
    eqn_deps = []
    for eqn in inner.eqns:
        d = frozenset().union(
            *[deps.get(a, frozenset()) for a in eqn.invars
              if not isinstance(a, Literal)]) if eqn.invars else frozenset()
        eqn_deps.append((eqn.primitive.name, d))
        for ov in eqn.outvars:
            deps[ov] = d
    return plan, eqn_deps


def _is_collective(name: str) -> bool:
    return any(k in name for k in ("psum", "all_gather", "all_to_all",
                                   "ppermute", "reduce_scatter"))


@pytest.mark.parametrize("mode", BUCKET_MODES)
def test_flush_ready_recovers_channel_overlap(mode):
    """The tentpole acceptance, on the real sync dataflow: under
    aggregate="channel" with channels < n_buckets, flush="ready" makes
    the FIRST-emitted channel collective (a) appear in the jaxpr BEFORE
    any op that reads the last bucket's leaves — the flush goes out
    mid-exchange, before the later buckets are even packed — and (b)
    depend ONLY on the first contiguous run of production-order buckets,
    so the latency-hiding scheduler may start it while the remaining
    backward compute runs. flush="step" structurally forfeits both: every
    flush follows every pack, and round-robin puts a late bucket on the
    channel that carries bucket 0."""
    for flush in ("step", "ready"):
        plan, eqn_deps = _sync_trace(mode, flush)
        assert plan.n_buckets >= 3
        last_leaves = set(plan.buckets[-1])
        colls = [(i, d) for i, (n, d) in enumerate(eqn_deps)
                 if _is_collective(n)]
        assert colls, "sync emitted no collectives"
        first_coll_idx, first_coll_deps = colls[0]
        reads_last = [i for i, (n, d) in enumerate(eqn_deps)
                      if set(d) & last_leaves and not _is_collective(n)]
        if flush == "ready":
            # (a) emitted before the FIRST op that touches the last
            # bucket's leaves (its pack hasn't even been traced yet)
            assert first_coll_idx < min(reads_last), \
                (first_coll_idx, min(reads_last))
            # (b) depends exactly on the first-produced bucket(s), never
            # on the last bucket
            assert set(first_coll_deps) == set(plan.buckets[0])
            assert not set(first_coll_deps) & last_leaves
        else:
            # the barrier loop: the first flush comes after the last
            # bucket's pack started
            assert first_coll_idx > min(reads_last), \
                (first_coll_idx, min(reads_last))
            # round-robin: bucket 0's channel also waits on the last
            # bucket (n_buckets=3, channels=2 -> channel 0 = {0, 2})
            with_b0 = [d for _, d in colls
                       if set(plan.buckets[0]) <= set(d)]
            assert with_b0 and any(set(d) & last_leaves for d in with_b0)


def test_flush_ready_first_flush_precedes_final_bucket_grad():
    """The mid-backward emission property, stated positionally: drive
    the staged emission API (pipeline.begin_emission / stage_slices /
    finish_emission) with bucket "gradients" produced by a sequential
    chain (g_b = tanh(g_{b-1}) — the backward-pass analogue: bucket b's
    grads exist only after bucket b-1's), staging each one the moment it
    is produced. Under flush="ready" the traced program emits the first
    channel's collective BEFORE the eqn computing the LAST bucket's
    gradient; under flush="step" every collective comes after it."""
    from repro.core.backends import pipeline
    n_buckets, n_channels, elems = 6, 2, 512
    mesh = make_mesh((1,), ("data",))

    def positions(flush):
        comm = _comm("hadronio_overlap", channels=n_channels,
                     aggregate="channel", flush=flush)

        def body(x):
            ctx = SyncContext.resolve(comm, ("data",), None)
            st = pipeline.begin_emission(ctx, n_buckets, "all_reduce",
                                         unpack=True)
            g = x
            for b in range(n_buckets):
                g = jnp.tanh(g)            # bucket b's gradient
                pipeline.stage_slices(st, b, g[None])
            outs = pipeline.finish_emission(st)
            return jnp.stack([o.reshape(-1) for o in outs])

        f = jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                          out_specs=P(), check_vma=False)
        jaxpr = jax.make_jaxpr(f)(jnp.ones((elems,), jnp.float32))
        inner = next(e for e in jaxpr.jaxpr.eqns
                     if e.primitive.name == "shard_map").params["jaxpr"]
        names = [e.primitive.name for e in inner.eqns]
        first_coll = min(i for i, n in enumerate(names) if "psum" in n)
        last_grad = max(i for i, n in enumerate(names) if n == "tanh")
        return first_coll, last_grad

    first_ready, last_grad_ready = positions("ready")
    assert first_ready < last_grad_ready, \
        (first_ready, last_grad_ready)
    first_step, last_grad_step = positions("step")
    assert first_step > last_grad_step, (first_step, last_grad_step)


# ---------------------------------------------------------------------------
# Serving conformance (the event-loop serving subsystem): identical
# logits per comm mode × channel affinity × event-loop count, plus jaxpr
# evidence that serving collectives flow through the staged emission API.
# Parametrized straight from available_modes(), so a newly registered
# backend is serving-conformance-tested without edits here.
# ---------------------------------------------------------------------------


def _serve_model():
    return _serve_model_cached()


@functools.lru_cache(maxsize=None)
def _serve_model_cached():
    cfg = get_config("qwen2-0.5b-reduced")
    from repro.models import api
    params = api.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _serve_comm(mode, **kw):
    kw.setdefault("channels", 4)
    kw.setdefault("slice_bytes", 512)     # logit payload -> several slices
    return _comm(mode, "none", PACKS[0], **kw)


@functools.lru_cache(maxsize=None)
def _serve_logits(mode, affinity):
    """(prefill logits, one-step decode logits) of the dispatch-built
    serve step for (mode, channel affinity), on fixed inputs."""
    from repro.models import api
    from repro.serving import dispatch as serve_dispatch
    cfg, params = _serve_model()
    step = serve_dispatch.make_serve_step(cfg, _serve_comm(mode),
                                          channel_indices=affinity)
    toks = np.zeros((2, 8), np.int32)
    toks[0, :6] = (np.arange(6) * 3) % cfg.vocab_size
    toks[1, :8] = (np.arange(8) * 5) % cfg.vocab_size
    batch = {"tokens": jnp.asarray(toks),
             "last_pos": jnp.asarray([5, 7])}
    logits_p, cache = step.prefill(params, batch)
    cache = api.grow_cache(cfg, cache, 32)
    dec = {"token": jnp.argmax(logits_p, -1).astype(jnp.int32),
           "pos": jnp.asarray([6, 8], jnp.int32)}
    logits_d, _ = step.decode(params, cache, dec)
    return np.asarray(logits_p), np.asarray(logits_d)


@pytest.mark.parametrize("mode", available_modes())
def test_serving_logits_identical_across_modes(mode):
    """The serving transparency claim: every registered strategy's wire
    path (raw whole-payload collectives for gspmd/sockets/vma, the staged
    slice pipeline for the hadronio family) yields BIT-identical prefill
    and decode logits — summing per element and gathering peer-major
    commute with slicing."""
    ref_p, ref_d = _serve_logits("gspmd", None)
    got_p, got_d = _serve_logits(mode, None)
    np.testing.assert_array_equal(got_p, ref_p)
    np.testing.assert_array_equal(got_d, ref_d)


@pytest.mark.parametrize("affinity", [(0, 1), (2, 3), (1,)])
def test_serving_logits_invariant_to_channel_affinity(affinity):
    """Channel affinity (which disjoint run of the pool an event loop
    emits on) changes the emission structure, never the logits — the
    dispatch-level statement of event-loop-count invariance."""
    ref_p, ref_d = _serve_logits("hadronio", None)
    got_p, got_d = _serve_logits("hadronio", affinity)
    np.testing.assert_array_equal(got_p, ref_p)
    np.testing.assert_array_equal(got_d, ref_d)


def test_serving_tokens_identical_across_event_loops():
    """The subsystem-level acceptance row: greedy tokens are identical
    for event_loops ∈ {1, 2, 4} (with continuous admission in play:
    more requests than slots per loop at el=1)."""
    from repro.configs.base import ServeConfig
    from repro.serving import Request, make_engine_group
    cfg, params = _serve_model()
    rng = np.random.default_rng(11)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    size=int(rng.integers(4, 16))),
                    max_new=3) for i in range(6)]
    outs = {}
    for el in (1, 2, 4):
        serve = ServeConfig(event_loops=el, poll="busy", max_batch=2,
                            max_len=48, comm=_serve_comm("hadronio"))
        grp = make_engine_group(cfg, params, serve)
        grp.submit(reqs)
        res = sorted(grp.run(threads=False), key=lambda r: r.uid)
        outs[el] = [tuple(r.tokens.tolist()) for r in res]
    assert outs[1] == outs[2] == outs[4]


@pytest.mark.parametrize("mode", HADRONIO_FAMILY)
def test_serving_collectives_flow_through_staged_emission(mode):
    """Jaxpr-level evidence: the serve decode's logit reduction is the
    staged emission API's schedule — one collective per ring slice under
    aggregate="slice", exactly min(channels, n_slices) coalesced flushes
    under "channel" — while sockets emits ONE unsliced op and gspmd
    none (1-device local reference)."""
    from repro.launch import hlo_analysis as hlo
    from repro.serving import dispatch as serve_dispatch
    cfg, _ = _serve_model()
    n_channels = 2
    counts = {}
    for aggregate in ("slice", "channel"):
        comm = _serve_comm(mode, channels=n_channels, aggregate=aggregate)
        text = serve_dispatch.lowered_decode_text(cfg, comm, batch=2,
                                                  max_len=32)
        counts[aggregate] = hlo.stablehlo_collective_stats(text).total_ops
    n_slices = serve_dispatch.logit_payload_slices(
        cfg, 2, _serve_comm(mode, channels=n_channels))
    assert n_slices > n_channels, (n_slices, n_channels)
    assert counts["slice"] == n_slices, counts
    assert counts["channel"] == n_channels, counts
    # baselines: per-buffer (1 op) and XLA-owned (0 ops on 1 device)
    sockets = serve_dispatch.lowered_decode_text(
        cfg, _serve_comm("sockets"), batch=2, max_len=32)
    assert hlo.stablehlo_collective_stats(sockets).total_ops == 1
    local = serve_dispatch.lowered_decode_text(
        cfg, _serve_comm("gspmd"), batch=2, max_len=32)
    assert hlo.stablehlo_collective_stats(local).total_ops == 0


@pytest.mark.parametrize("mode", available_modes())
def test_serving_rejects_wire_compression(mode):
    """Serving payloads are activations — a lossy codec has no EF state
    to stay unbiased against, so the dispatch layer must reject it for
    EVERY mode (never silently ignore it)."""
    from repro.serving import dispatch as serve_dispatch
    with pytest.raises(ValueError, match="compress"):
        serve_dispatch.validate_serve_comm(
            CommConfig(mode=mode, compress="bf16", hierarchical=False))


@pytest.mark.parametrize("mode", BUCKET_MODES)
@pytest.mark.parametrize("compress", COMPRESS)
@pytest.mark.parametrize("pack", PACKS)
def test_bucket_collectives_depend_only_on_own_leaves(mode, compress, pack):
    """The overlap property, stated on the dataflow graph itself: with
    enough channels, every collective's transitive input set is exactly
    one bucket's leaves (plus that bucket's own EF residual) — so the
    latency-hiding scheduler may start it as soon as those leaves exist,
    in BOTH the all-reduce and the reduce-scatter (ZeRO-1) modes, with
    and without wire compression, for both pack implementations."""
    plan, collectives = _collective_deps(mode, compress, pack)
    assert plan.n_buckets >= 3          # the fixture really is multi-bucket
    assert any(len(b) == 1 for b in plan.buckets)   # oversized-leaf bucket
    assert collectives, "sync emitted no collectives"
    buckets_hit = set()
    for name, d in collectives:
        leaf_deps = {i for kind, i in d if kind == "leaf"}
        ef_deps = {b for kind, b in d if kind == "ef"}
        owners = [b for b in range(plan.n_buckets)
                  if leaf_deps == set(plan.buckets[b])]
        assert len(owners) == 1, \
            (f"{name}: leaf deps {sorted(leaf_deps)} are not exactly one "
             f"bucket of {plan.buckets}")
        assert ef_deps <= {owners[0]}, \
            (f"{name}: bucket {owners[0]} collective reads EF of "
             f"buckets {sorted(ef_deps)}")
        buckets_hit.add(owners[0])
    assert buckets_hit == set(range(plan.n_buckets))


# ---------------------------------------------------------------------------
# Model-family serving conformance (docs/FAMILIES.md §The support matrix).
# FAMILY_ARCH is indexed with EVERY family in the arch registry at
# collection time (KeyError => a family shipped without a serving
# conformance row) — the SUPPORTED_COMPRESS pattern applied to model
# families. Each matrix row below is the named test a FAMILIES.md row
# points at.
# ---------------------------------------------------------------------------

from repro.configs.registry import ARCH_IDS  # noqa: E402

FAMILY_ARCH = {
    "dense": "qwen2-0.5b-reduced",
    "moe": "mixtral-8x7b-reduced",
    "ssm": "rwkv6-7b-reduced",
    "hybrid": "recurrentgemma-9b-reduced",
    "encdec": "whisper-tiny-reduced",
    "vlm": "llava-next-mistral-7b-reduced",
}
REGISTERED_FAMILIES = sorted({get_config(a).family for a in ARCH_IDS})
FAMILY_CASES = [(f, FAMILY_ARCH[f])            # KeyError => no coverage
                for f in REGISTERED_FAMILIES]


def test_family_matrix_covers_registry_exactly():
    """No registered family without a serving row, no stale rows."""
    assert set(FAMILY_ARCH) == set(REGISTERED_FAMILIES)


def test_every_family_declares_a_cache_layout():
    """The gathering write is family-agnostic BECAUSE every family
    declares its decode-state batch layout (the cache-layout contract,
    docs/FAMILIES.md); an undeclared family must fail at build time
    with an error naming the missing declaration."""
    from repro.serving import cache_layout
    for fam in REGISTERED_FAMILIES:
        assert cache_layout.layout_for(fam) is not None
    with pytest.raises(ValueError, match="declares no cache layout"):
        cache_layout.layout_for("made-up-family")


@functools.lru_cache(maxsize=None)
def _family_model(family):
    from repro.models import api
    cfg = get_config(FAMILY_ARCH[family])
    params = api.init(jax.random.PRNGKey(0), cfg)
    return cfg, params


def _family_batch(cfg, b=2, s=8):
    rng = np.random.default_rng(0)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(b, s)), jnp.int32)}
    if cfg.family not in ("ssm", "hybrid"):
        batch["last_pos"] = jnp.asarray([s - 3, s - 1], jnp.int32)
    if cfg.family == "vlm":
        batch["patches"] = jnp.zeros((b, cfg.num_patches, cfg.d_model),
                                     jnp.dtype(cfg.compute_dtype))
    if cfg.family == "encdec":
        batch["frames"] = jnp.zeros((b, cfg.num_frames, cfg.d_model),
                                    jnp.dtype(cfg.compute_dtype))
    return batch


@functools.lru_cache(maxsize=None)
def _family_outputs(family, mode):
    """(prefill logits, grown-cache leaves, one-step decode logits) of
    the dispatch-built serve step for (family, mode) on fixed inputs."""
    from repro.models import api
    from repro.serving import dispatch as serve_dispatch
    cfg, params = _family_model(family)
    step = serve_dispatch.make_serve_step(cfg, _serve_comm(mode))
    batch = _family_batch(cfg)
    lg, cache = step.prefill(params, batch)
    cache = api.grow_cache(cfg, cache, 16)
    tok = jnp.argmax(lg, -1).astype(jnp.int32)
    pos = (jnp.asarray([6, 8], jnp.int32) if "last_pos" in batch
           else jnp.asarray(8, jnp.int32))
    dl, _ = step.decode(params, cache, {"token": tok, "pos": pos})
    return (np.asarray(lg),
            tuple(np.asarray(l) for l in jax.tree.leaves(cache)),
            np.asarray(dl))


@pytest.mark.parametrize("family", [f for f, _ in FAMILY_CASES])
@pytest.mark.parametrize("mode", HADRONIO_FAMILY)
def test_family_serving_bitwise_vs_solo(family, mode):
    """docs/FAMILIES.md matrix row: EVERY registered family's sharded
    prefill (per-family cache layout through the one gathering write),
    decode-state and one-step decode logits are BIT-identical between
    the pure-local gspmd reference and the mode's wire path — the
    transparency claim, per family, per hadronio-family mode."""
    ref = _family_outputs(family, "gspmd")
    got = _family_outputs(family, mode)
    np.testing.assert_array_equal(got[0], ref[0])
    assert len(got[1]) == len(ref[1])
    for a, b in zip(got[1], ref[1]):
        np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(got[2], ref[2])


def test_moe_expert_exchange_flows_through_staged_alltoall():
    """docs/FAMILIES.md MoE row evidence: expert-parallel
    dispatch/combine is the staged emission API's all_to_all kind — the
    serve step's channels NOTE all_to_all at trace time (the chaos
    hook), and the traced decode jaxpr carries the all_to_all
    primitive. At >1 device the lowered module keeps stablehlo
    all-to-all ops (a size-1 exchange folds away locally, which is the
    point: same program, the wire appears with the ring)."""
    from repro.core import channels
    from repro.models import api
    from repro.serving import dispatch as serve_dispatch
    cfg, params = _family_model("moe")
    comm = _serve_comm("hadronio", slice_bytes=768)   # un-memoized step
    kinds = []
    channels.set_collective_hook(lambda idx, kind: kinds.append(kind))
    try:
        step = serve_dispatch.make_serve_step(cfg, comm)
        batch = _family_batch(cfg)
        lg, cache = step.prefill(params, batch)
    finally:
        channels.clear_collective_hook()
    assert "all_to_all" in kinds, kinds
    cache = api.grow_cache(cfg, cache, 16)
    dec = {"token": jnp.argmax(lg, -1).astype(jnp.int32),
           "pos": jnp.asarray([6, 8], jnp.int32)}
    txt = str(jax.make_jaxpr(step.decode)(params, cache, dec))
    assert "all_to_all" in txt
    if jax.device_count() > 1:
        from repro.launch import hlo_analysis as hlo
        low = serve_dispatch.lowered_decode_text(cfg, comm)
        st = hlo.stablehlo_collective_stats(low)
        assert st.counts.get("all-to-all", 0) > 0, st.counts
