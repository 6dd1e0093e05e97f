"""The composable slice pipeline shared by the hadronio-family backends.

One gradient exchange is a fixed sequence of stages, written once here
instead of per-branch in every mode:

    pack -> ring-buffer plan -> pack stage (cast/EF) -> per-channel
    collective -> unpack stage -> unpack

``pack``/``plan`` live in :mod:`repro.core.aggregation` (the gathering
write); this module owns the wire stages:

* :func:`channels_for` — build the connection pool for a resolved axis
  topology (pod-aware when the context says so).
* :func:`pack_wire` — the pack stage: the fused add-error-feedback /
  cast-to-wire-dtype copy pass (the paper's §III-C gathering-write hot
  spot). ``comm.pack`` selects the implementation: ``"pallas"`` runs the
  fused one-HBM-pass kernel (kernels/ring_pack.py, interpret mode
  off-TPU), ``"jnp"`` the reference elementwise path; both produce
  bit-identical wire bytes. int8 needs a per-slice amax reduction the
  kernel does not fuse, so it always takes the jnp path.
* :func:`begin_emission` / :func:`stage_slices` / :func:`flush_ready` /
  :func:`finish_emission` — the worker-per-connection schedule as a
  STAGED emission: wire buffers are staged one at a time in production
  order and flushed per the bucket->channel schedule from
  :mod:`repro.core.flush_scheduler` (``comm.flush``: round-robin with
  one end-of-exchange flush loop under ``"step"``; contiguous
  production-order groups flushed the moment they fill under
  ``"ready"`` — hadroNIO's flush-on-writable, §III-B). The flush
  granularity is ``comm.aggregate``. Under ``"slice"`` each channel
  issues its collectives IN ORDER (an ``optimization_barrier`` chains
  consecutive ops on the same channel — the selector's ordering lever
  from :mod:`repro.core.selector`), while different channels stay
  data-independent. Under ``"channel"`` every channel coalesces its
  slices into ONE contiguous wire buffer and flushes a single collective
  — hadroNIO's ring-buffer gathering write (§III-C, §V-B), where many
  small application writes become one large UCX request per connection.
  :func:`emit_through_channels` is the one-shot wrapper over the four.
* :func:`unpack_wire` — the unpack stage (the scattering-read
  counterpart of the pack stage): one fused cast-from-wire-dtype +
  re-slice HBM pass over the stacked collective results, replacing the
  old per-slice ``.astype(f32)`` epilogue. Implementation selection is
  the same ``comm.pack`` switch (kernels/ring_pack.unpack_slices_kernel
  vs jnp), with identical outputs.
* :func:`reduce_slices` / :func:`scatter_slices` — pack stage + per-slice
  all-reduce / reduce-scatter + unpack stage composed over the channel
  schedule.

Under a pod-aware context with ``comm.aggregate="channel"`` the staged
emission runs the TWO-LEVEL **leader-channel** schedule (the UCX
multi-rail analogue: cross-pod links are the scarce resource and get
dedicated connections): the pool is carved into LOCAL lanes and
``comm.leader_channels`` LEADER lanes (:func:`channels_for`). A local
lane's coalesced flush becomes the IN-POD stage only (reduce-scatter /
gather over the data axis) and parks its 1/n_data intermediate; each
leader lane coalesces the intermediates of its assigned local lanes
(``flush_scheduler.make_leader_plan``) into ONE cross-pod collective,
carves them back, and the in-pod return stage completes per lane. Under
``comm.flush="ready"`` the leader flush fires the moment its last local
lane stages (each pod's local flush triggers the leader flush —
hadroNIO's flush-on-writable applied across the hierarchy), not at a
global barrier. Cross-pod collective count drops from n_channels to
n_leader_channels; numerics are bit-identical to the per-channel
hierarchical path (identical per-element summation trees — concatenation
before an elementwise psum changes nothing; gathers are data movement).
The ``all_to_all`` kind (the MoE expert exchange, serving path) is the
one exception: it carries source-target traffic over the full flattened
ring and bypasses the leader split entirely (see
:func:`begin_emission`).

Backends compose these; none of them re-implements a stage.
"""
from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

from repro.configs.base import CommConfig
from repro.core import compress as comp
from repro.core.channels import ChannelFill, CommChannel, make_channels
from repro.core.flush_scheduler import (FlushPlan, make_flush_plan,
                                        make_leader_plan)
from repro.core.hierarchical import in_group_size
from repro.core.selector import barrier

from repro.core.backends.base import SyncContext

_KINDS = ("all_reduce", "reduce_scatter", "all_gather", "all_to_all")

# ---------------------------------------------------------------------------
# Chaos seam: an injectable flush fault (serving/chaos.py). The callable is
# consulted by flush_ready() once per READY channel with the channel's pool
# position and returns "drop" (defer the flush — the finish_emission step
# barrier recovers it), "dup" (flush twice; re-emitting the identical
# collective is idempotent, XLA dedups/DCEs the shadow), or None. Faults act
# at TRACE time, so a seeded plan yields a deterministic injection trace, and
# the staged-emission completeness contract guarantees recovery: every drop
# is re-flushed at the barrier, every dup overwrites outs with equal values.
# ---------------------------------------------------------------------------

_FLUSH_FAULT = None


def set_flush_fault(fault) -> None:
    """Install ``fault(channel) -> "drop" | "dup" | None`` on the staged
    emission's flush path. Callers MUST pair with
    :func:`clear_flush_fault` (try/finally); the serve-step cache
    (``serving/dispatch.py``) is bypassed while a fault is armed so a
    faulted trace never poisons fault-free callers."""
    global _FLUSH_FAULT
    _FLUSH_FAULT = fault


def clear_flush_fault() -> None:
    global _FLUSH_FAULT
    _FLUSH_FAULT = None


def flush_fault_active() -> bool:
    return _FLUSH_FAULT is not None


# ---------------------------------------------------------------------------
# Allocator seam: a buffer-pool hook on the staged emission path. The staged
# emission materialises ONE coalesced wire buffer per channel flush (and one
# wire buffer per item under aggregate="slice") — the ring-buffer allocation
# of the paper's §III-C connection-granularity design. The hook is consulted
# with (global channel index, wire bytes) right before that buffer is built;
# it may sleep (host memory pressure / gc thrash — the chaos class ROADMAP
# asked for) or raise (pool exhaustion), and like the flush fault it acts at
# TRACE time, so seeded plans replay deterministically and the serve-step
# cache is bypassed while armed (dispatch checks fault_active()).
# ---------------------------------------------------------------------------

_ALLOC_HOOK = None


@dataclass
class EmissionStats:
    """Trace-time emission counters (cumulative module state — consumers
    snapshot and diff): ``drops``/``dups`` = flush-fault verdicts applied,
    ``allocs`` = wire-buffer allocations consulted. Deterministic for a
    given program trace, which is what makes them usable as supervisor
    health signals (``serving/supervisor.py`` diffs drops around each
    drain to detect dropped flushes without any wall clock)."""
    drops: int = 0
    dups: int = 0
    allocs: int = 0


EMISSION_STATS = EmissionStats()

# Scoped emission stats: mutation sites write to the ACTIVE scope — the
# module global unless a stats_scope() is armed on this context. Scopes
# are contextvars, so parallel tests and the supervisor's worker threads
# stop racing on global resets; code that never arms a scope (and the
# default scope itself) sees the historical module-global behavior
# unchanged.
_STATS_SCOPE: contextvars.ContextVar = contextvars.ContextVar(
    "emission_stats", default=None)


def current_stats() -> EmissionStats:
    """The EmissionStats all mutation sites write to: the innermost
    armed :func:`stats_scope`, else the module-global ``EMISSION_STATS``."""
    st = _STATS_SCOPE.get()
    return EMISSION_STATS if st is None else st


@contextlib.contextmanager
def stats_scope(stats: EmissionStats = None):
    """Arm a private EmissionStats for the duration of the block (and
    any jit TRACING it triggers — the counters are trace-time). Yields
    the scoped stats; nested scopes shadow, the module global is the
    default scope when none is armed."""
    st = EmissionStats() if stats is None else stats
    tok = _STATS_SCOPE.set(st)
    try:
        yield st
    finally:
        _STATS_SCOPE.reset(tok)


def set_alloc_hook(hook) -> None:
    """Install ``hook(channel_index, nbytes)`` on every staged wire-buffer
    allocation. Pair with :func:`clear_alloc_hook` (try/finally)."""
    global _ALLOC_HOOK
    _ALLOC_HOOK = hook


def clear_alloc_hook() -> None:
    global _ALLOC_HOOK
    _ALLOC_HOOK = None


def alloc_hook_active() -> bool:
    return _ALLOC_HOOK is not None


def fault_active() -> bool:
    """Any trace-affecting fault armed (flush fault OR alloc hook) — the
    serve-step cache gate (``serving/dispatch.py``)."""
    return _FLUSH_FAULT is not None or _ALLOC_HOOK is not None


def _consult_alloc(channel_index: int, flats: list) -> None:
    current_stats().allocs += 1
    if _ALLOC_HOOK is not None:
        nbytes = sum(int(f.size) * f.dtype.itemsize for f in flats)
        _ALLOC_HOOK(channel_index, nbytes)


def leader_emission(ctx: SyncContext, pool_size: int) -> bool:
    """True when the two-level leader-channel schedule applies: pod-aware
    context, channel-granularity flushes, and a pool big enough to carve
    (a 1-channel pool keeps the per-channel hierarchical path)."""
    return (ctx.pod_axis is not None and ctx.comm.aggregate == "channel"
            and pool_size >= 2)


def _leader_split(ctx: SyncContext, idx: tuple) -> tuple:
    """Carve the emitting pool into (local, leader) channel ids. The
    GLOBAL leader lanes are the last ``comm.leader_channels`` ids of the
    ``comm.channels`` pool (the topology-aware affinity pins exactly
    those to the designated leader loops); an emitting pool that owns
    none — a non-leader event loop — promotes its last owned lane, so
    every loop can complete its cross-pod stage independently (numerics
    are invariant to which lane carries it). A pool is never left
    without a local lane."""
    n_lead = min(ctx.comm.leader_channels, ctx.comm.channels - 1)
    tail = range(ctx.comm.channels - n_lead, ctx.comm.channels)
    leads = tuple(i for i in idx if i in tail)
    locs = tuple(i for i in idx if i not in tail)
    if not leads:
        locs, leads = idx[:-1], (idx[-1],)
    if not locs:
        locs, leads = (leads[0],), leads[1:]
    return locs, leads


def channels_for(ctx: SyncContext, n_slices: int) -> list[CommChannel]:
    """The connection pool: at most ``comm.channels`` workers, pod-aware
    when the context resolved a pod axis. A context carrying
    ``channel_indices`` (the event-loop channel-affinity API) gets
    exactly that disjoint run of the global pool instead — the emitting
    event loop OWNS those channels (serving/event_loop.py). Under the
    two-level schedule (:func:`leader_emission`) the pool's leader lanes
    come back flagged ``leader=True``, locals first."""
    if ctx.channel_indices:
        idx = tuple(ctx.channel_indices)[:max(1, n_slices)]
    else:
        idx = tuple(range(max(1, min(ctx.comm.channels, n_slices))))
    leaders = frozenset()
    if leader_emission(ctx, len(idx)):
        locs, leads = _leader_split(ctx, idx)
        idx = locs + leads
        leaders = frozenset(leads)
    return make_channels(len(idx), ctx.flat_axes, pod_axis=ctx.pod_axis,
                         data_axis=ctx.data_axis, indices=idx,
                         leaders=leaders)


def pack_wire(slices: jax.Array, ef, comm: CommConfig):
    """The pack stage over a ``(n, S)`` slice view: one fused pass doing
    add-EF, cast-to-wire-dtype, and residual capture.

    Returns ``(wire, new_ef, int8_scale)``. ``new_ef`` is None when the
    codec carries no residual; a non-None ``int8_scale`` signals that the
    caller must use :func:`comp.int8_allreduce`-style summation."""
    if comm.compress == "int8_ef":
        # amax reduction + quant: jnp path regardless of comm.pack
        q, scale, new_ef = comp.int8_quantize(slices, ef)
        return q, new_ef, scale
    with_ef = comm.compress == "bf16"
    wire_dtype = "bfloat16" if with_ef else jnp.dtype(slices.dtype).name
    if comm.pack == "pallas":
        from repro.kernels import ops
        n, s = slices.shape
        wire, new_ef = ops.pack_slices(slices.reshape(-1), ef, n_slices=n,
                                       slice_elems=s, wire_dtype=wire_dtype,
                                       with_ef=with_ef)
        return wire, new_ef, None
    if with_ef:
        wire, new_ef = comp.bf16_compress(slices, ef)
        return wire, new_ef, None
    return slices, None, None


def unpack_wire(wire: jax.Array, comm: CommConfig,
                out_dtype=jnp.float32) -> jax.Array:
    """The unpack stage — the paper's scattering read (§III-C): one fused
    cast-from-wire-dtype + re-slice HBM pass over the stacked ``(n, S)``
    collective results, instead of one ``.astype`` round trip per slice.
    ``comm.pack`` selects the implementation exactly like the pack stage
    (pallas kernel vs jnp reference; bit-identical outputs). A wire
    already in ``out_dtype`` needs no pass at all."""
    if wire.dtype == jnp.dtype(out_dtype):
        return wire
    if comm.pack == "pallas":
        from repro.kernels import ops
        return ops.unpack_slices(
            wire, out_dtype=jnp.dtype(out_dtype).name).reshape(wire.shape)
    return wire.astype(out_dtype)


def interleave_for_scatter(flats: list, group: int) -> jax.Array:
    """Peer-major coalescing of 1-D wire buffers for ONE reduce-scatter
    flush: peer ``p``'s contiguous ``1/group`` chunk of the result is the
    concatenation of ``p``'s chunk of every buffer, in buffer order — so
    a coalesced reduce-scatter hands every peer exactly the same
    per-slice shards (and therefore the same ZeRO-1 flat-shard ordering)
    as one collective per slice."""
    if len(flats) == 1:
        return flats[0]
    return jnp.concatenate([f.reshape(group, -1) for f in flats],
                           axis=1).reshape(-1)


def _scattered_shape(shape: tuple, group: int) -> tuple:
    return shape[:-1] + (shape[-1] // group,)


@dataclass
class EmitState:
    """In-flight state of one staged emission (built by
    :func:`begin_emission`, driven by :func:`stage_slices` /
    :func:`flush_ready`, closed by :func:`finish_emission`)."""
    ctx: SyncContext
    kind: str
    group: int
    unpack: bool                  # run the unpack stage per flush
    plan: FlushPlan
    chans: list                   # CommChannel pool
    fills: list                   # per-channel ChannelFill watermark
    staged: dict                  # item id -> wire array
    outs: list                    # per-item results
    last: dict                    # channel idx -> previous collective
    #                               output (aggregate="slice" chaining)
    # -- two-level leader emission (empty leads = flat schedule) --------
    leads: list = field(default_factory=list)   # leader CommChannels
    lplan: FlushPlan = None       # local lane -> leader lane schedule
    lfills: list = field(default_factory=list)  # per-leader ChannelFill
    pending: dict = field(default_factory=dict)  # local lane id -> parked
    #                               in-pod intermediate (awaiting leader)
    lpad: dict = field(default_factory=dict)     # local lane id -> zero
    #                               pad added for in-pod divisibility


def _unpack_flush(buf: jax.Array, comm: CommConfig) -> jax.Array:
    """Unpack stage over ONE flushed buffer (any shape): the fused
    cast-from-wire-dtype pass keyed to the flush, not the bucket."""
    if buf.dtype == jnp.float32:
        return buf
    return unpack_wire(buf.reshape(1, -1), comm).reshape(buf.shape)


def _carve_reduce(st: EmitState, c: int, red: jax.Array) -> None:
    """Carve one lane's fully reduced buffer back per item (all_reduce) —
    the scattering read."""
    red = _unpack_flush(red, st.ctx.comm) if st.unpack else red
    off = 0
    for i in st.plan.groups[c]:
        n = st.staged[i].size
        st.outs[i] = jax.lax.slice_in_dim(red, off, off + n).reshape(
            st.staged[i].shape)
        off += n


def _carve_gather(st: EmitState, c: int, g: jax.Array) -> None:
    """Carve one lane's gathered buffer back per item: the tiled result
    is peer-major over the whole coalesced buffer, so item i's gathered
    bytes are the same column range of every peer block."""
    g = (_unpack_flush(g, st.ctx.comm) if st.unpack
         else g).reshape(st.group, -1)
    off = 0
    for i in st.plan.groups[c]:
        n = st.staged[i].size
        st.outs[i] = jax.lax.slice(g, (0, off),
                                   (st.group, off + n)).reshape(-1)
        off += n


def _carve_alltoall(st: EmitState, c: int, ex: jax.Array) -> None:
    """Carve one lane's exchanged buffer back per item (all_to_all): the
    coalesced wire is peer-major (:func:`interleave_for_scatter`), so the
    exchanged result's row ``p`` holds peer ``p``'s chunk of every item
    in buffer order — item i's exchange is the same column range of
    every row, exactly the gather carve with a per-item width of
    ``size // group``."""
    ex = (_unpack_flush(ex, st.ctx.comm) if st.unpack
          else ex).reshape(st.group, -1)
    off = 0
    for i in st.plan.groups[c]:
        n = st.staged[i].size // st.group
        st.outs[i] = jax.lax.slice(ex, (0, off),
                                   (st.group, off + n)).reshape(-1)
        off += n


def _carve_scatter(st: EmitState, c: int, sh: jax.Array) -> None:
    """Carve one lane's scattered shard back per item (reduce_scatter:
    each item contributes 1/group of its elements)."""
    sh = _unpack_flush(sh, st.ctx.comm) if st.unpack else sh
    off = 0
    for i in st.plan.groups[c]:
        n = st.staged[i].size // st.group
        st.outs[i] = jax.lax.slice_in_dim(sh, off, off + n).reshape(
            _scattered_shape(st.staged[i].shape, st.group))
        off += n


def _stage_local(st: EmitState, c: int, flats: list) -> None:
    """The IN-POD stage of one local lane's coalesced flush (leader
    emission): issue only the data-axis collective and park the 1/n_data
    intermediate for the lane's leader. The all-reduce pad rule matches
    ``psum_hierarchical`` exactly (zero tail scatters onto the last
    shard), so the summation trees stay bit-identical."""
    ch = st.chans[c]
    if st.kind == "all_reduce":
        buf = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        pad = (-buf.shape[0]) % in_group_size(ch.data_axis)
        if pad:
            buf = jnp.pad(buf, (0, pad))
        st.lpad[c] = pad
        st.pending[c] = ch.in_pod_reduce_scatter(buf)
    elif st.kind == "all_gather":
        buf = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        st.pending[c] = ch.in_pod_all_gather(buf)
    else:
        buf = interleave_for_scatter(flats, st.group)
        st.pending[c] = ch.in_pod_reduce_scatter(buf)


def _flush_leader(st: EmitState, l: int) -> None:
    with jax.named_scope(f"comm.leader.l{l}"):
        _flush_leader_lanes(st, l)


def _flush_leader_lanes(st: EmitState, l: int) -> None:
    """The CROSS-POD stage: ONE coalesced leader-lane collective carrying
    every parked in-pod intermediate of the local lanes assigned to
    leader ``l``, carved back per lane, then the in-pod return stage
    (all-reduce only) completes each lane's items. This is where the
    cross-pod collective count drops from n_channels to
    n_leader_channels."""
    lanes = st.lplan.groups[l]
    parts = [st.pending.pop(c) for c in lanes]
    lens = [p.shape[0] for p in parts]
    buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    lead = st.leads[l]
    if st.kind == "all_gather":
        g = lead.cross_pod_all_gather(buf)
        n_pods = g.shape[0] // buf.shape[0]
        g = g.reshape(n_pods, -1)
        off = 0
        for c, n in zip(lanes, lens):
            lane = jax.lax.slice(g, (0, off), (n_pods, off + n))
            off += n
            # (pods, data*len) -> (pods*data, len): pod-major peer order,
            # matching the flat tiled gather over (pod,)+data axes
            _carve_gather(st, c, lane.reshape(st.group, -1))
    else:
        red = lead.cross_pod_all_reduce(buf)
        off = 0
        for c, n in zip(lanes, lens):
            shard = jax.lax.slice_in_dim(red, off, off + n)
            off += n
            if st.kind == "all_reduce":
                full = st.chans[c].in_pod_all_gather(shard)
                if st.lpad.get(c):
                    full = jax.lax.slice_in_dim(
                        full, 0, full.shape[0] - st.lpad[c])
                _carve_reduce(st, c, full)
            else:
                _carve_scatter(st, c, shard)
    st.lfills[l].flushed = True


def _flush_channel(st: EmitState, c: int) -> None:
    with jax.named_scope(f"comm.flush.ch{st.chans[c].index}"):
        _flush_channel_items(st, c)


def _flush_channel_items(st: EmitState, c: int) -> None:
    """One coalesced wire flush: concatenate the channel's staged items
    into a single contiguous buffer, issue ONE collective, optionally run
    the unpack stage on the flushed buffer, carve the results back out
    (the scattering read). Under leader emission the flush is only the
    in-pod stage; the items complete when the lane's leader flushes
    (:func:`_flush_leader`)."""
    idx = st.plan.groups[c]
    flats = [st.staged[i].reshape(-1) for i in idx]
    _consult_alloc(st.chans[c].index, flats)   # coalesced wire buffer
    if st.leads:
        _stage_local(st, c, flats)
        st.fills[c].flushed = True
        l = st.lplan.assign[c]
        st.lfills[l].stage(c)
        if st.ctx.comm.flush == "ready" and st.lfills[l].ready:
            _flush_leader(st, l)
        return
    if st.kind == "all_reduce":
        buf = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        _carve_reduce(st, c, st.chans[c].all_reduce(buf))
    elif st.kind == "all_gather":
        # the serving gathering write: ONE coalesced gather per channel
        buf = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
        _carve_gather(st, c, st.chans[c].all_gather(buf))
    elif st.kind == "all_to_all":
        # the expert exchange: peer-major coalescing keeps every item's
        # per-peer chunks contiguous per row, ONE exchange per channel
        buf = interleave_for_scatter(flats, st.group)
        _carve_alltoall(st, c, st.chans[c].all_to_all(
            buf.reshape(st.group, -1)))
    else:
        buf = interleave_for_scatter(flats, st.group)
        _carve_scatter(st, c, st.chans[c].reduce_scatter(buf))
    st.fills[c].flushed = True


def begin_emission(ctx: SyncContext, n_items: int, kind: str, *,
                   group: int = 1, unpack: bool = False) -> EmitState:
    """Open one staged emission of ``n_items`` wire buffers through the
    connection pool. The bucket->channel schedule is ``comm.flush``
    (``core/flush_scheduler``): round-robin + end-of-exchange flush loop
    under ``"step"``, contiguous production-order groups flushed the
    moment they fill under ``"ready"``. ``unpack=True`` additionally runs
    the unpack stage per flush (channel-local instead of bucket-local —
    the scattering read keyed to the flush that produced the bytes).

    Under leader emission (:func:`leader_emission`) the pool splits into
    local lanes (they get the bucket->channel plan) and leader lanes
    (they get the second-level local-lane->leader plan,
    ``make_leader_plan``); ``st.chans`` holds only the local lanes so
    plan group ids stay aligned."""
    assert kind in _KINDS, kind
    pool = channels_for(ctx, n_items)
    if kind == "all_to_all":
        # the expert exchange BYPASSES leader emission: all-to-all
        # carries source-target pairs over the full flattened ring (the
        # ring IS the expert axis), not replica groups, so there is no
        # in-pod/cross-pod decomposition to carve leader lanes for —
        # leader-flagged lanes flush flat like locals
        local, leads = list(pool), []
    else:
        local = [c for c in pool if not c.leader]
        leads = [c for c in pool if c.leader]
    plan = make_flush_plan(n_items, len(local), ctx.comm.flush)
    fills = [ChannelFill(frozenset(g)) for g in plan.groups]
    st = EmitState(ctx=ctx, kind=kind, group=group, unpack=unpack,
                   plan=plan, chans=local, fills=fills, staged={},
                   outs=[None] * n_items, last={})
    if leads:
        st.leads = leads
        st.lplan = make_leader_plan(plan.n_channels, len(leads),
                                    ctx.comm.flush)
        st.lfills = [ChannelFill(frozenset(g)) for g in st.lplan.groups]
    return st


def stage_slices(st: EmitState, i: int, wire: jax.Array) -> list:
    """Stage item ``i``'s wire bytes (items MUST be staged in production
    order, 0..n-1) and emit whatever that makes ready:

    * ``aggregate="slice"`` — the item's own collective goes out
      immediately, barrier-chained on the channel's previous op (one
      in-flight collective per channel; the selector's ordering lever).
    * ``aggregate="channel"``, ``flush="ready"`` — if ``i`` completes its
      channel's assigned set, the channel's coalesced flush is emitted
      NOW (mid-backward when driven from a bucketed backend).
    * ``aggregate="channel"``, ``flush="step"`` — staging only; every
      flush waits for :func:`finish_emission` (the step barrier).

    Returns the item ids flushed by this call."""
    st.staged[i] = wire
    c = st.plan.assign[i]
    st.fills[c].stage(i)
    if st.ctx.comm.aggregate == "slice":
        ch = st.chans[c]
        x = wire
        _consult_alloc(ch.index, [x.reshape(-1)])  # per-item wire buffer
        if ch.index in st.last:
            x, _ = barrier(x, st.last[ch.index])
        with jax.named_scope(f"comm.flush.ch{ch.index}"):
            if st.kind == "all_reduce":
                y = ch.all_reduce(x)
            elif st.kind == "all_gather":
                y = ch.all_gather(x.reshape(-1))
            elif st.kind == "all_to_all":
                y = ch.all_to_all(x.reshape(st.group, -1)).reshape(-1)
            else:
                y = ch.reduce_scatter(x)
        st.last[ch.index] = y
        st.outs[i] = _unpack_flush(y, st.ctx.comm) if st.unpack else y
        if st.fills[c].ready:
            st.fills[c].flushed = True
        return [i]
    if st.ctx.comm.flush == "ready":
        return flush_ready(st)
    return []


def flush_ready(st: EmitState) -> list:
    """Flush every channel whose fill watermark reached its assigned set
    (the selector reporting writable channels). Returns the item ids
    flushed."""
    flushed: list = []
    for c, fill in enumerate(st.fills):
        if fill.ready:
            if _FLUSH_FAULT is not None:
                act = _FLUSH_FAULT(c)
                if act == "drop":
                    # deferred, not lost: the fill stays ready, so a later
                    # flush_ready retries it and finish_emission's step
                    # barrier flushes it unconditionally — the recovery
                    # invariant the chaos harness asserts
                    current_stats().drops += 1
                    continue
                if act == "dup" and not st.leads:
                    current_stats().dups += 1
                    _flush_channel(st, c)   # shadow flush: idempotent —
                    #                         outs re-carved from an equal
                    #                         collective result below
            _flush_channel(st, c)
            flushed.extend(st.plan.groups[c])
    return flushed


def finish_emission(st: EmitState) -> list:
    """Close the emission: under ``flush="step"`` this is the
    end-of-exchange flush loop (every channel flushed at one barrier, in
    channel order — PR 3's schedule); under ``"ready"`` everything
    already went out and this only asserts completeness. Returns the
    per-item results."""
    if st.ctx.comm.aggregate == "channel":
        for c, fill in enumerate(st.fills):
            if not fill.flushed:
                assert fill.ready or st.ctx.comm.flush == "step", \
                    (c, fill.watermark)
                _flush_channel(st, c)
        # leader emission, flush="step": the second-level flush loop —
        # every leader's coalesced cross-pod collective at the barrier
        for l, fill in enumerate(st.lfills):
            if not fill.flushed:
                assert fill.ready or st.ctx.comm.flush == "step", \
                    (l, fill.watermark)
                _flush_leader(st, l)
    assert all(o is not None for o in st.outs), "emission incomplete"
    return st.outs


def emit_through_channels(items: list, ctx: SyncContext, kind: str,
                          *, group: int = 1, unpack: bool = False) -> list:
    """Issue the collective ``kind`` ("all_reduce" | "reduce_scatter")
    for every item through the connection pool, at the flush granularity
    ``ctx.comm.aggregate``:

    * ``"slice"`` — one collective per item. Items on the SAME channel
      are chained (each op's input is barrier-pinned on the channel's
      previous output, so the compiler must run them in order — one
      in-flight collective per channel); different channels stay
      data-independent and may overlap freely.
    * ``"channel"`` — one coalesced wire flush per channel: all items
      assigned to a channel become ONE contiguous buffer and ONE
      collective (n_channels collectives per exchange instead of
      n_slices). Reduce-scatter flushes are peer-major interleaved
      (:func:`interleave_for_scatter`) so each item's shard is unchanged.

    ``comm.flush`` picks the schedule (``core/flush_scheduler``):
    ``"step"`` is the round-robin assignment with one end-of-exchange
    flush loop; ``"ready"`` groups items contiguously in production
    order and flushes each channel the moment its last item is staged.
    This one-shot wrapper stages everything before finishing, so the
    dataflow (not the Python order) is what ``"ready"`` improves here;
    bucketed backends drive :func:`stage_slices` incrementally instead.

    Returns per-item results: reduced arrays in the item's own shape
    (all_reduce), or the item's scatter shard with the trailing dim
    divided by ``group`` (reduce_scatter). All four granularity/schedule
    combinations return bit-identical values."""
    st = begin_emission(ctx, len(items), kind, group=group, unpack=unpack)
    for i, x in enumerate(items):
        stage_slices(st, i, x)
    return finish_emission(st)


def emit_flat(flat: jax.Array, ctx: SyncContext, kind: str, *,
              group: int = 1) -> jax.Array:
    """The serving wire path: carve ONE flat f32 payload (a partial logit
    sum, a coalesced KV-cache write) into ring-buffer slices and emit
    them through the staged channel schedule — the same gathering write
    the gradient path uses, applied to inference traffic. ``kind`` is
    ``"all_reduce"`` (returns the summed payload, ``flat``'s own shape)
    or ``"all_gather"`` (``group`` = ring size; returns the peer-major
    concatenation, shape ``(group * len,)``) or ``"all_to_all"``
    (``group`` = ring size; ``flat`` is the peer-major ``(group, len //
    group)`` exchange payload flattened, and the result is the received
    payload in the same layout — the MoE expert dispatch/combine).
    Zero-padding added by the slice plan is trimmed from the result (per
    peer block for gathers and exchanges), so callers see exactly their
    payload."""
    assert flat.ndim == 1, flat.shape
    assert kind in ("all_reduce", "all_gather", "all_to_all"), \
        ("serving payloads are replicated, gathered or exchanged, "
         f"never scattered: {kind}")
    from repro.core.ring_buffer import plan_slices
    n_elems = flat.shape[0]
    itemsize = jnp.dtype(flat.dtype).itemsize
    if kind == "all_to_all":
        # the exchange payload is a (group, row) peer-major block; the
        # ring-buffer plan carves the per-peer ROW, so every staged
        # slice (a column block, flattened group-major) is itself a
        # complete peer-major exchange payload and the carved results
        # re-concatenate per row — slicing commutes with the exchange
        # exactly like it does with gathers
        assert n_elems % group == 0, (n_elems, group)
        row = n_elems // group
        sp = plan_slices(row * itemsize, ctx.comm)
        elems = max(1, sp.slice_bytes // itemsize)
        n = sp.n_slices
        pad = n * elems - row
        assert pad >= 0, (sp, row)
        view = flat.reshape(group, row)
        if pad:
            view = jnp.pad(view, ((0, 0), (0, pad)))
        st = begin_emission(ctx, n, kind, group=group)
        for i in range(n):
            stage_slices(st, i, jax.lax.slice(
                view, (0, i * elems),
                (group, (i + 1) * elems)).reshape(-1))
        outs = finish_emission(st)
        ex = outs[0].reshape(group, -1) if len(outs) == 1 else \
            jnp.concatenate([o.reshape(group, -1) for o in outs], axis=1)
        return ex[:, :row].reshape(-1)
    sp = plan_slices(n_elems * itemsize, ctx.comm)
    elems = max(1, sp.slice_bytes // itemsize)
    # the plan's slice count IS the emitted-collective prediction
    # (dispatch.logit_payload_slices, evidence rows) — never recompute it
    n = sp.n_slices
    pad = n * elems - n_elems
    assert pad >= 0, (sp, n_elems)
    if pad:
        flat = jnp.pad(flat, (0, pad))
    slices = flat.reshape(n, elems)
    st = begin_emission(ctx, n, kind, group=group)
    for i in range(n):
        stage_slices(st, i, slices[i])
    outs = finish_emission(st)
    if kind == "all_gather":
        g = outs[0].reshape(group, -1) if len(outs) == 1 else \
            jnp.concatenate([o.reshape(group, -1) for o in outs], axis=1)
        return g[:, :n_elems].reshape(-1)
    out = outs[0].reshape(-1) if len(outs) == 1 else \
        jnp.concatenate([o.reshape(-1) for o in outs])
    return out[:n_elems]


def raw_emit(flat: jax.Array, ctx: SyncContext, kind: str) -> jax.Array:
    """The unsliced serving emission (gspmd / sockets / vma overrides of
    ``CommBackend.serve_emit``): one collective for the whole payload —
    per-buffer sends with no ring-buffer aggregation. Bit-identical
    values to :func:`emit_flat` (summing per element and concatenating
    peer-major commute with slicing); only the emission structure
    differs."""
    if kind == "all_reduce":
        return jax.lax.psum(flat, ctx.flat_axes)
    if kind == "all_to_all":
        group = jax.lax.psum(1, ctx.flat_axes)
        return jax.lax.all_to_all(
            flat.reshape(group, -1), ctx.flat_axes, split_axis=0,
            concat_axis=0, tiled=True).reshape(-1)
    assert kind == "all_gather", kind
    return jax.lax.all_gather(flat, ctx.flat_axes, axis=0, tiled=True)


def scatter_group(ctx: SyncContext):
    """(gather_axes, group_size) for the ZeRO-1 reduce-scatter: in-pod
    when pod-aware (shards replicate across pods), the whole flattened
    ring otherwise. ``group_size`` is a static int (psum-of-1 idiom)."""
    gather_axes = ctx.data_axes_tuple if ctx.pod_axis is not None \
        else ctx.flat_axes
    return gather_axes, jax.lax.psum(1, gather_axes)


def reduce_slices(slices: jax.Array, ctx: SyncContext):
    """Per-slice all-reduce with the pack/unpack stages, scheduled over
    the channel pool at the configured flush granularity. slices: (n, S)
    f32. Returns (reduced (n, S) f32, new_ef)."""
    wire, new_ef, scale = pack_wire(slices, ctx.ef, ctx.comm)
    if scale is not None:
        # int8: all-gather + local dequant-sum (one fused exchange)
        return comp.int8_allreduce(wire, scale, ctx.flat_axes), new_ef

    outs = emit_through_channels(
        [wire[i] for i in range(wire.shape[0])], ctx, "all_reduce")
    return unpack_wire(jnp.stack(outs), ctx.comm), new_ef


def scatter_slices(slices: jax.Array, ctx: SyncContext):
    """Per-slice reduce-scatter (the ZeRO-1 exchange) over the channel
    schedule, with the pack/unpack stages. slices: (n, S) f32
    (wire-compressible). Returns (flat_shard, new_ef, gather_axes) where
    flat_shard is the peer's (n * S/group,) ZeRO-1 slice and
    ``gather_axes`` are the axes the shard must be all-gathered over."""
    gather_axes, group = scatter_group(ctx)
    wire, new_ef, scale = pack_wire(slices, ctx.ef, ctx.comm)
    if scale is not None:
        # int8: full dequant-sum everywhere, then keep this peer's chunk
        # of every slice (pods replicate shards, matching gather_axes)
        red = comp.int8_allreduce(wire, scale, ctx.flat_axes)
        n, s = red.shape
        assert s % group == 0, (s, group)
        my = jax.lax.axis_index(gather_axes)
        shard = jax.lax.dynamic_slice_in_dim(red, my * (s // group),
                                             s // group, axis=1)
        return shard.reshape(-1), new_ef, gather_axes

    shards = emit_through_channels(
        [wire[i] for i in range(wire.shape[0])], ctx, "reduce_scatter",
        group=group)
    # (n_slices, S/group) -> flat local shard, ZeRO-1 layout
    flat_shard = unpack_wire(jnp.stack(shards), ctx.comm).reshape(-1)
    return flat_shard, new_ef, gather_axes
