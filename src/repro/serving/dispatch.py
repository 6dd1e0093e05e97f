"""Serve-step dispatch: inference collectives through the CommBackend wire.

The training path's transparency boundary (callers never branch on mode
names; the registered backend owns the wire) applied to serving. A
:class:`ServeStep` is a pair of jitted functions with the engine's exact
call signatures — ``prefill(params, batch)`` / ``decode(params, cache,
dec)`` — that run inside a fully-manual ``shard_map`` over the mesh and
emit their collectives via ``CommBackend.serve_emit``:

* **prefill** — batch-sharded for EVERY registered family: each ring
  peer prefills its contiguous run of the request batch locally, then
  every decode-state leaf plus the last-token logits are coalesced into
  ONE flat wire payload and all-gathered — the serving gathering write
  (paper §III-C applied to inference: many small cache buffers become
  one large request), carved back per leaf with the batch dimension
  re-merged peer-major. WHERE each leaf carries its batch axis is the
  family's declared cache layout (``serving/cache_layout.py``) — the
  one family-specific fact, kept declarative so this layer stays
  generic (docs/FAMILIES.md).
* **decode** — tensor-parallel LM head: every peer runs the (replicated)
  trunk, computes partial logits from its contiguous ``d_model`` shard,
  and the partial-logit sum is all-reduced — the serving logit
  reduction. The reduction flows through the SAME staged emission API
  the gradient path uses (``pipeline.begin_emission`` / ``stage_slices``
  / ``flush_ready`` via ``pipeline.emit_flat``), so ``comm.mode`` /
  ``channels`` / ``slice_bytes`` / ``aggregate`` / ``flush`` all shape
  serving traffic, and an event loop's channel affinity
  (``ctx.channel_indices``) bounds which connections it may emit on.
* **MoE expert parallelism** — when the ring divides the expert count,
  the expert-compute stage runs expert-parallel: the dispatched
  ``(B, E, C, D)`` buffer rides an ``all_to_all`` exchange through the
  same staged emission API (each peer receives every batch row's slice
  of the expert axis, runs its local expert slice, and the reverse
  exchange brings the outputs home). Pure data movement + identical
  per-expert einsums, so tokens stay bit-identical to the local expert
  stage; a non-dividing expert count falls back to local compute.

All registered modes return bit-identical logits (per-element sums and
peer-major gathers commute with slicing — conformance-tested in
``tests/test_backend_conformance.py``); only the emitted program
structure differs. Serving payloads are activations: wire compression is
an error-feedback (training-state) feature and is rejected here.
"""
from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.configs.base import CommConfig, ModelConfig
from repro.core.backends import get_backend
from repro.core.backends.base import SyncContext
from repro.launch.mesh import make_mesh
from repro.models import api
from repro.obs import trace as obs_trace
from repro.models import moe as moe_mod
from repro.models.layers import no_shard
from repro.serving import cache_layout

PyTree = Any


class ServeStep(NamedTuple):
    """Jitted serve entry points (engine-compatible signatures) plus the
    resolved topology facts the engine needs for batch padding."""
    prefill: Callable             # (params, batch) -> (logits, cache)
    decode: Callable              # (params, cache, dec) -> (logits, cache)
    n_shards: int                 # ring size: batch rows padded to a multiple
    mesh: Any
    comm: CommConfig
    channel_indices: Optional[tuple]
    pod_axis: Optional[str] = None   # resolved pod axis (None = flat ring:
    #                               no pod dim in the mesh, or hierarchical
    #                               collectives disabled in the config)
    n_pods: int = 1


def validate_serve_comm(comm: CommConfig):
    """Serving-path config validation; returns the backend."""
    backend = get_backend(comm.mode)
    if comm.compress != "none":
        raise ValueError(
            f"serving cannot honor compress={comm.compress!r}: the wire "
            "carries activations (logit partial sums, KV gathers), not "
            "gradients — there is no error-feedback state to make a lossy "
            "codec unbiased; use compress='none'")
    return backend


_STEP_CACHE: dict = {}


def clear_serve_step_cache() -> None:
    """Drop every memoized ServeStep (tests that need fresh traces)."""
    _STEP_CACHE.clear()


def make_serve_step(cfg: ModelConfig, comm: CommConfig, mesh=None, *,
                    channel_indices: Optional[tuple] = None,
                    pod_axis: Optional[str] = None) -> ServeStep:
    if not obs_trace.enabled():
        return _make_serve_step(cfg, comm, mesh,
                                channel_indices=channel_indices,
                                pod_axis=pod_axis)
    with obs_trace.span("build", f"serve_step:{cfg.name}",
                        mode=comm.mode, channels=comm.channels):
        return _make_serve_step(cfg, comm, mesh,
                                channel_indices=channel_indices,
                                pod_axis=pod_axis)


def _make_serve_step(cfg: ModelConfig, comm: CommConfig, mesh=None, *,
                     channel_indices: Optional[tuple] = None,
                     pod_axis: Optional[str] = None) -> ServeStep:
    """Build the TAC serve step for one (model, comm, mesh, affinity)
    combination. ``channel_indices`` is the emitting event loop's owned
    run of the global channel pool (None = the full pool).

    Steps are MEMOIZED per (cfg, comm, mesh, affinity, pod_axis): the
    jitted functions close over nothing but the static topology (params
    and cache are call arguments), so every engine/group built for the
    same combination shares one compiled program instead of re-tracing
    it — the chaos matrix and repeated conformance builds pay one
    compile per affinity. The cache is bypassed (no lookup, no store)
    while any trace-affecting fault is armed — a flush fault
    (``pipeline.set_flush_fault``) or an allocator hook
    (``pipeline.set_alloc_hook``) — so a faulted emission trace can
    never leak into fault-free callers.

    ``pod_axis`` names the mesh's pod dimension for the two-level fabric
    (``launch/mesh.make_serve_mesh``); None auto-detects an axis named
    ``"pod"``. A detected pod axis flows into ``SyncContext.resolve``,
    so the decode all-reduce and the prefill gathering write decompose
    into in-pod stages plus the leader lanes' cross-pod collectives —
    gated, like the training path, on ``comm.hierarchical`` (a False
    config keeps the flat ring over the very same mesh)."""
    from repro.core.backends import pipeline
    backend = validate_serve_comm(comm)
    if mesh is None:
        mesh = make_mesh((jax.device_count(),), ("data",))
    cacheable = not pipeline.fault_active()
    key = (cfg, comm, mesh,
           tuple(channel_indices) if channel_indices is not None else None,
           pod_axis)
    if cacheable and key in _STEP_CACHE:
        return _STEP_CACHE[key]
    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    # every family is batch-shardable — its declared cache layout tells
    # the gathering write where each decode-state leaf carries batch; a
    # family with NO layout fails here, at build time, with an error
    # naming what to declare (serving/cache_layout.py)
    cache_layout.layout_for(cfg.family)
    chans = tuple(channel_indices) if channel_indices is not None else None
    pod = pod_axis if pod_axis is not None else \
        ("pod" if "pod" in axes else None)
    if pod is not None and pod not in axes:
        raise ValueError(f"pod_axis={pod!r} is not a mesh axis of {axes}")
    data = tuple(a for a in axes if a != pod) if pod is not None else axes
    if pod is not None and not data:
        raise ValueError(
            f"mesh {axes} has only the pod axis; the two-level fabric "
            "needs an in-pod data axis (make_serve_mesh builds one)")
    ctx = SyncContext.resolve(comm, data, pod, channel_indices=chans)
    # the pure-local reference path: nothing to wire (same gate for the
    # TP head, the gathering write and the expert exchange)
    pure_local = n_shards == 1 and not chans and comm.mode == "gspmd"

    # -- MoE expert-parallel dispatch/combine (the expert exchange) -----

    ep = cfg.moe.num_experts // n_shards if cfg.family == "moe" else 0
    use_ep = (cfg.family == "moe" and not pure_local
              and cfg.moe.num_experts % n_shards == 0)

    def ep_experts(mp, buf, _cfg, _shard_fn):
        """Expert-parallel expert stage: all_to_all the dispatched
        buffer peer-major (each peer gets EVERY batch row's slice of the
        expert axis), run the local ``ep``-expert slice, reverse the
        exchange. Data movement + identical per-expert einsums — tokens
        are bit-identical to the local expert stage."""
        b, e, cap, d = buf.shape
        dt = buf.dtype
        p_idx = jax.lax.axis_index(ctx.flat_axes)
        snd = buf.astype(jnp.float32).reshape(b, n_shards, ep, cap, d)
        snd = jnp.moveaxis(snd, 1, 0)                # (n, b, ep, c, d)
        got = backend.serve_emit(snd.reshape(-1), ctx, "all_to_all")
        got = got.reshape(n_shards * b, ep, cap, d)  # all rows, my slice
        wslice = {w: jax.lax.dynamic_slice_in_dim(
                      mp[w].astype(jnp.float32), p_idx * ep, ep, axis=0)
                  for w in ("wi", "wg", "wo")}
        out = moe_mod.apply_experts(wslice, got, cfg)
        back = backend.serve_emit(out.reshape(-1), ctx, "all_to_all")
        back = back.reshape(n_shards, b, ep, cap, d)
        back = jnp.moveaxis(back, 0, 1).reshape(b, e, cap, d)
        return back.astype(dt)

    expert_fn = ep_experts if use_ep else None

    # -- tensor-parallel LM head (the serving logit reduction) ----------

    def tp_head(embed: dict, x: jax.Array, shard_fn=no_shard) -> jax.Array:
        w = embed.get("out")
        if w is None:
            w = embed["tok"].T                       # tied: (d, V)
        d = x.shape[-1]
        ds = -(-d // n_shards)                       # ceil: zero-pad shards
        pad = ds * n_shards - d
        xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, pad)]) if pad else x
        wp = jnp.pad(w, ((0, pad), (0, 0))) if pad else w
        p = jax.lax.axis_index(ctx.flat_axes)
        xs = jax.lax.dynamic_slice_in_dim(xp, p * ds, ds, axis=x.ndim - 1)
        ws = jax.lax.dynamic_slice_in_dim(wp, p * ds, ds, axis=0)
        partial = jnp.einsum("...d,dv->...v", xs, ws.astype(x.dtype))
        red = backend.serve_emit(
            partial.astype(jnp.float32).reshape(-1), ctx, "all_reduce")
        return red.reshape(partial.shape).astype(x.dtype)

    # -- batch-sharded prefill + coalesced KV gathering write -----------

    def prefill_body(params: PyTree, batch: dict):
        b = batch["tokens"].shape[0]
        assert b % n_shards == 0, \
            f"serve batch {b} not padded to the ring size {n_shards}"
        bs = b // n_shards
        p = jax.lax.axis_index(ctx.flat_axes)
        local = jax.tree.map(
            lambda t: jax.lax.dynamic_slice_in_dim(t, p * bs, bs, axis=0),
            batch)
        logits, cache = api.prefill(params, local, cfg, no_shard,
                                    expert_fn=expert_fn)
        if pure_local:
            return logits, cache       # pure local reference, nothing to wire

        # ONE gathering write for the whole prefill result: every cache
        # leaf + the last-token logits coalesced into a single flat f32
        # payload, gathered peer-major, carved back per leaf with the
        # batch axis re-merged (slot k of the full batch = peer k//bs,
        # local row k%bs — matching the engine's row padding).
        leaves, treedef = jax.tree.flatten((cache, logits))
        flats = [l.astype(jnp.float32).reshape(-1) for l in leaves]
        sizes = [f.shape[0] for f in flats]
        wire = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        g = backend.serve_emit(wire, ctx, "all_gather").reshape(n_shards, -1)

        outs, off = [], 0
        # flatten order: cache leaves then logits. Each cache leaf's
        # batch axis is the family's DECLARED layout (cache_layout.py);
        # the logits row always merges at axis 0 (this layer's own
        # output contract, not a family fact).
        bas = cache_layout.batch_axes(cfg.family, cache) + [0]
        assert len(bas) == len(leaves), (len(bas), len(leaves))
        for leaf, n, ba in zip(leaves, sizes, bas):
            seg = g[:, off:off + n].reshape((n_shards,) + leaf.shape)
            off += n
            m = jnp.moveaxis(seg, 0, ba)
            shape = leaf.shape
            merged = m.reshape(shape[:ba] + (n_shards * shape[ba],)
                               + shape[ba + 1:])
            outs.append(merged.astype(leaf.dtype))
        full_cache, full_logits = jax.tree.unflatten(treedef, outs)
        return full_logits, full_cache

    # -- replicated decode + TP logit reduction -------------------------

    def decode_body(params: PyTree, cache: PyTree, dec: dict):
        head = None if pure_local else tp_head
        return api.decode_step(params, cache, dec, cfg, no_shard,
                               logits_fn=head, expert_fn=expert_fn)

    prefill = jax.jit(jax.shard_map(
        prefill_body, mesh=mesh, in_specs=(P(), P()),
        out_specs=(P(), P()), check_vma=False))
    decode = jax.jit(jax.shard_map(
        decode_body, mesh=mesh, in_specs=(P(), P(), P()),
        out_specs=(P(), P()), check_vma=False))
    step = ServeStep(prefill=prefill, decode=decode, n_shards=n_shards,
                     mesh=mesh, comm=comm, channel_indices=chans,
                     pod_axis=ctx.pod_axis,
                     n_pods=mesh.shape[pod] if pod is not None else 1)
    if cacheable:
        _STEP_CACHE[key] = step
    return step


def lowered_decode_text(cfg: ModelConfig, comm: CommConfig, *,
                        batch: int = 2, max_len: int = 32, mesh=None,
                        channel_indices: Optional[tuple] = None,
                        pod_axis: Optional[str] = None) -> str:
    """Emitted StableHLO of one serve decode step (shape-only lowering) —
    the evidence surface for 'serving collectives flow through the staged
    emission API' (conformance tests + benchmark evidence rows count its
    collectives with ``launch/hlo_analysis``; the topology rows classify
    them as in-pod vs cross-pod with ``cross_pod_collective_count``)."""
    step = make_serve_step(cfg, comm, mesh, channel_indices=channel_indices,
                           pod_axis=pod_axis)
    params = api.abstract(cfg)
    cache = api.cache_specs(cfg, batch, max_len)
    dec = {"token": jax.ShapeDtypeStruct((batch,), jnp.int32),
           "pos": jax.ShapeDtypeStruct((batch,), jnp.int32)}
    return step.decode.lower(params, cache, dec).as_text()


def logit_payload_slices(cfg: ModelConfig, batch: int,
                         comm: CommConfig) -> int:
    """How many ring-buffer slices one decode logit reduction carves into
    (the expected per-step collective count under ``aggregate="slice"``)."""
    from repro.core.ring_buffer import plan_slices
    return plan_slices(batch * cfg.vocab_size * 4, comm).n_slices
