"""Figures 3/5/7 — round-trip latency vs connection count.

Paper setup: ping-pong between two nodes, one thread per connection,
message sizes 16 B / 1 KiB / 64 KiB, connections 1..16.

TPU reading: one "connection" = one independent ppermute channel on the
ring (a message to the neighbour and back = one RTT). ``channels``
independent ping-pongs are issued in a single XLA program; the measured
time per round trip shows how channel count degrades latency per channel
(the paper's Fig. 3 scaling axis). Derived numbers report the per-op
collective schedule from the compiled HLO.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks.common import (Row, block, derived_collective_time,
                               percentile_rows, slice_view, timeit,
                               timeit_samples)
from repro.configs.base import CommConfig
from repro.core.backends import pipeline
from repro.core.backends.base import SyncContext
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import make_mesh

MSG_SIZES = [16, 1024, 64 * 1024]
CHANNELS = [1, 2, 4, 8, 16]
SLICE_SIZES = [16 * 1024, 64 * 1024, 256 * 1024, 1024 * 1024]


def _pingpong_fn(mesh, n_channels: int, msg_elems: int, n_dev: int):
    perm_fwd = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    perm_bwd = [(i, (i - 1) % n_dev) for i in range(n_dev)]

    def body(*xs):
        outs = []
        for x in xs:        # independent channels — no data deps
            y = jax.lax.ppermute(x, "data", perm_fwd)
            z = jax.lax.ppermute(y, "data", perm_bwd)
            outs.append(z)
        return tuple(outs)

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=tuple([P("data", None)] * n_channels),
                      out_specs=tuple([P("data", None)] * n_channels),
                      check_vma=False)
    return jax.jit(f)


FLUSHES = ("step", "ready")
AGGREGATES = ("slice", "channel")


def recommend_channels(rtt_by_channels: dict[int, float], msg_size: int,
                       mode: str = "hadronio") -> tuple[int, list[Row]]:
    """Pick the channel count maximizing aggregate round-trip throughput
    from measured (channels -> RTT seconds) points — the paper's Fig. 3
    trade-off: more connections overlap more, but degrade per-channel
    latency. Returns (best, rows) with one ``recommended_channels`` CSV
    row plus the derived per-point throughputs. ``mode`` labels the rows
    (sweeps over the overlap modes stay distinguishable in the CSV)."""
    rows, best, best_tput = [], None, -1.0
    for ch, t in sorted(rtt_by_channels.items()):
        tput = ch * msg_size / max(t, 1e-12)
        rows.append(Row("latency", "autotune", mode, msg_size, ch,
                        "sweep_throughput", tput / 1e6, "MB/s", "derived"))
        if tput > best_tput:
            best_tput, best = tput, ch
    rows.append(Row("latency", "autotune", mode, msg_size, best,
                    "recommended_channels", best, "channels", "derived"))
    return best, rows


def autotune_channels(mesh=None, *, msg_size: int = 64 * 1024,
                      channels=CHANNELS, iters: int = 10,
                      mode: str = "hadronio", joint: bool = False):
    """Channel-count autotune (ROADMAP item): sweep ``comm.channels``
    over the ping-pong microbenchmark ON THIS MESH and pick a per-mesh
    default. Returns ``(best_channels, rows)``; feed ``best_channels``
    into ``CommConfig(channels=...)``. ``run()`` derives the same
    recommendation from its own sweep without re-measuring. ``mode`` is
    the row label only (the ping-pong primitive is mode-agnostic).

    ``joint=True`` recommends over the JOINT ``flush`` × ``aggregate`` ×
    ``channels`` space instead, driving the LIVE wire pipeline
    (:func:`autotune_flush_schedule`): the aggregation-vs-latency
    trade-off the benchmark paper shows must be tunable is three-axis
    once the flush schedule exists, so the channel count is only
    meaningful per (flush, aggregate) point. Returns
    ``((flush, aggregate, channels), rows)``."""
    if joint:
        return autotune_flush_schedule(mesh, payload_bytes=8 * msg_size,
                                       channels=channels, iters=iters,
                                       mode=mode)
    if mesh is None:
        n = len(jax.devices())
        mesh = make_mesh((n,), ("data",))
    n_dev = mesh.shape["data"]
    elems = max(1, msg_size // 4)
    rows, rtts = [], {}
    for ch in channels:
        xs = tuple(jnp.zeros((n_dev, elems), jnp.float32) + i
                   for i in range(ch))
        fn = _pingpong_fn(mesh, ch, elems, n_dev)
        t = timeit(lambda: block(fn(*xs)), warmup=1, iters=iters)
        rtts[ch] = t
        rows.append(Row("latency", "autotune", mode, msg_size, ch,
                        "sweep_rtt", t * 1e6, "us", "measured"))
    best, rec_rows = recommend_channels(rtts, msg_size, mode)
    return best, rows + rec_rows


# ---------------------------------------------------------------------------
# Slice-size autotune (the ROADMAP's open bucket-granularity sweep)
# ---------------------------------------------------------------------------


def _slice_exchange_fn(mesh, comm: CommConfig, payload_elems: int):
    """One jitted gradient exchange of ``payload_elems`` f32 through the
    LIVE wire pipeline (pack stage -> channel schedule at the configured
    aggregate granularity -> unpack stage)."""

    def body(x):
        ctx = SyncContext.resolve(comm, ("data",), None)
        sl, _ = slice_view(x, comm)
        red, _ = pipeline.reduce_slices(sl, ctx)
        return red.reshape(-1)[:payload_elems]

    f = jax.shard_map(body, mesh=mesh, in_specs=(P(),), out_specs=P(),
                      check_vma=False)
    return jax.jit(f)


def recommend_slice_bytes(goodput_by_size: dict[int, float],
                          mode: str = "hadronio",
                          channels: int = 4) -> tuple[int, list[Row]]:
    """Pick the slice granularity maximizing goodput from already-measured
    (slice_bytes -> bytes/s) points — no re-measurement. Returns (best,
    rows) with the per-mesh ``recommended_slice_bytes`` default row, the
    granularity analogue of ``recommend_channels``."""
    best = max(sorted(goodput_by_size), key=goodput_by_size.get)
    row = Row("latency", "autotune", mode, best, channels,
              "recommended_slice_bytes", best, "bytes", "derived")
    return best, [row]


def autotune_slice_bytes(mesh=None, *, payload_bytes: int = 4 * 1024 * 1024,
                         slice_sizes=SLICE_SIZES, channels: int = 4,
                         aggregate: str = "slice", mode: str = "hadronio",
                         iters: int = 10):
    """Slice/bucket-granularity autotune (ROADMAP follow-up: the channel
    sweep existed, the ``comm.slice_bytes`` sweep did not): exchange a
    fixed payload through the live wire pipeline once per candidate
    granularity ON THIS MESH, and pick the slice size maximizing goodput
    — the paper's §V-B trade-off (small slices pay per-send overhead,
    huge slices forfeit overlap). Returns ``(best_slice_bytes, rows)``;
    feed the result into ``CommConfig(slice_bytes=...)``. The
    ``recommended_slice_bytes`` row is derived from the sweep without
    re-measuring; ``aggregate`` selects the flush granularity under test
    and ``mode`` labels the rows."""
    if mesh is None:
        n = len(jax.devices())
        mesh = make_mesh((n,), ("data",))
    payload_elems = max(1, payload_bytes // 4)
    rows, goodput = [], {}
    for sb in slice_sizes:
        comm = CommConfig(mode=mode, slice_bytes=sb, channels=channels,
                          aggregate=aggregate, hierarchical=False,
                          ring_capacity_bytes=max(64 * sb,
                                                  2 * payload_bytes))
        fn = _slice_exchange_fn(mesh, comm, payload_elems)
        x = jnp.ones((payload_elems,), jnp.float32)
        t = timeit(lambda: block(fn(x)), warmup=1, iters=iters)
        goodput[sb] = payload_bytes / max(t, 1e-12)
        rows.append(Row("latency", "autotune", mode, sb, channels,
                        "sweep_slice_goodput", goodput[sb] / 1e6, "MB/s",
                        "measured"))
    best, rec_rows = recommend_slice_bytes(goodput, mode, channels)
    return best, rows + rec_rows


# ---------------------------------------------------------------------------
# Joint flush-schedule autotune (flush x aggregate x channels — the
# three-axis coalescing trade-off once the flush-when-ready schedule
# exists)
# ---------------------------------------------------------------------------


def recommend_flush_schedule(goodput_by_combo: dict,
                             payload_bytes: int,
                             mode: str = "hadronio") -> tuple:
    """Pick the (flush, aggregate, channels) combo maximizing goodput
    from already-measured points. The recommended-default row encodes
    the combo in its metric name (CSV stays one-value-per-row):
    ``recommended_flush_schedule:<flush>:<aggregate>`` with the channel
    count as the value."""
    best = max(sorted(goodput_by_combo), key=goodput_by_combo.get)
    flush, aggregate, ch = best
    row = Row("latency", "autotune", mode, payload_bytes, ch,
              f"recommended_flush_schedule:{flush}:{aggregate}", ch,
              "channels", "derived")
    return best, [row]


def autotune_flush_schedule(mesh=None, *,
                            payload_bytes: int = 512 * 1024,
                            slice_bytes: int = 32 * 1024,
                            channels=(1, 2, 4), flushes=FLUSHES,
                            aggregates=AGGREGATES, iters: int = 10,
                            mode: str = "hadronio"):
    """The joint sweep the flush axis makes necessary: exchange a fixed
    payload through the LIVE wire pipeline once per (flush, aggregate,
    channels) combo ON THIS MESH — the paper's aggregation-vs-latency
    trade-off (§V-B) plus the readiness schedule from
    ``core/flush_scheduler`` — and recommend the best combo. Returns
    ``((flush, aggregate, channels), rows)``; each measured row's metric
    is ``sweep_flush_goodput:<flush>:<aggregate>``."""
    if mesh is None:
        n = len(jax.devices())
        mesh = make_mesh((n,), ("data",))
    payload_elems = max(1, payload_bytes // 4)
    rows, goodput = [], {}
    for flush in flushes:
        for aggregate in aggregates:
            for ch in channels:
                comm = CommConfig(
                    mode=mode, slice_bytes=slice_bytes, channels=ch,
                    aggregate=aggregate, flush=flush, hierarchical=False,
                    ring_capacity_bytes=max(64 * slice_bytes,
                                            2 * payload_bytes))
                fn = _slice_exchange_fn(mesh, comm, payload_elems)
                x = jnp.ones((payload_elems,), jnp.float32)
                t = timeit(lambda: block(fn(x)), warmup=1, iters=iters)
                goodput[(flush, aggregate, ch)] = \
                    payload_bytes / max(t, 1e-12)
                rows.append(Row(
                    "latency", "autotune", mode, payload_bytes, ch,
                    f"sweep_flush_goodput:{flush}:{aggregate}",
                    goodput[(flush, aggregate, ch)] / 1e6, "MB/s",
                    "measured"))
    best, rec_rows = recommend_flush_schedule(goodput, payload_bytes, mode)
    return best, rows + rec_rows


def run(mesh=None, *, msg_sizes=MSG_SIZES, channels=CHANNELS,
        iters: int = 10, quick: bool = False):
    if mesh is None:
        n = len(jax.devices())
        mesh = make_mesh((n,), ("data",))
    n_dev = mesh.shape["data"]
    rows = []
    rtts_at_max = {}
    for msg in msg_sizes:
        elems = max(1, msg // 4)
        for ch in channels:
            xs = tuple(jnp.zeros((n_dev, elems), jnp.float32) + i
                       for i in range(ch))
            fn = _pingpong_fn(mesh, ch, elems, n_dev)
            lowered = fn.lower(*([jax.ShapeDtypeStruct((n_dev, elems),
                                                       jnp.float32)] * ch))
            stats = hlo.stablehlo_collective_stats(lowered.as_text())
            samples = timeit_samples(lambda: block(fn(*xs)), iters=iters)
            t = float(np.median(samples))
            if msg == max(msg_sizes):
                rtts_at_max[ch] = t
            rtt_us = t * 1e6
            rows.append(Row("latency", "fig3/5/7", "hadronio", msg, ch,
                            "rtt", rtt_us, "us", "measured"))
            # the hhu-benchmark percentile view of the same sample stream
            rows.extend(percentile_rows("latency", "fig3/5/7", "hadronio",
                                        msg, ch, samples))
            rows.append(Row("latency", "fig3/5/7", "hadronio", msg, ch,
                            "emitted_collective_ops", stats.total_ops,
                            "ops", "derived"))
            rows.append(Row("latency", "fig3/5/7", "hadronio", msg, ch,
                            "rtt_v5e_model",
                            derived_collective_time(stats) * 1e6 / ch,
                            "us", "derived"))
    # per-mesh recommended comm.channels default (ROADMAP autotune item)
    # derived from the sweep just measured — no re-measurement
    _, rec_rows = recommend_channels(rtts_at_max, max(msg_sizes))
    rows.extend(rec_rows)
    # per-mesh recommended comm.slice_bytes default (the granularity sweep)
    sb_kw = dict(payload_bytes=256 * 1024,
                 slice_sizes=(16 * 1024, 64 * 1024)) if quick else {}
    _, sb_rows = autotune_slice_bytes(mesh, iters=max(1, iters // 2),
                                      **sb_kw)
    rows.extend(sb_rows)
    # joint flush x aggregate x channels sweep + recommended combo (the
    # flush-when-ready schedule makes coalescing a three-axis trade-off)
    fl_kw = dict(payload_bytes=128 * 1024, channels=(1, 2)) if quick \
        else {}
    _, fl_rows = autotune_flush_schedule(mesh, iters=max(1, iters // 2),
                                         **fl_kw)
    rows.extend(fl_rows)
    return rows
