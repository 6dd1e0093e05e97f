"""Figures 4/6/8 — sustained throughput vs connection count.

Paper setup: each connection streams messages; netty aggregates
(flush-interval) so many small sends become few large writes.

TPU reading: per channel, a stream of ``flush_interval`` messages is
either sent one collective per message (mode=sockets — the pre-fix
hadroNIO loop of §III-C) or aggregated into ring-buffer slices with one
collective per slice (mode=hadronio — the gathering write). mode=vma
fuses the whole stream into a single monolithic collective.
mode=hadronio_agg sweeps the NEW ``comm.aggregate="channel"`` axis: the
stream's slices are coalesced into ONE wire flush per connection (the
paper's full gathering write — §V-B's one large buffer handed to UCX per
connection), routed through the live pipeline (pack stage -> coalesced
flush -> unpack stage). The measured axis is bytes moved per wall-clock
second across channels; derived numbers give the HLO op count — the
paper's "number of send calls".
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks.common import (Row, block, derived_collective_time,
                               slice_view, timeit)
from repro.configs.base import CommConfig
from repro.core.backends import pipeline
from repro.core.backends.base import SyncContext
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import make_mesh

MSG_SIZES = [16, 1024, 64 * 1024]
CHANNELS = [1, 2, 4, 8, 16]
FLUSH_INTERVAL = {16: 64, 1024: 16, 64 * 1024: 4}      # paper §V-B


def _stream_fn(mesh, mode: str, n_channels: int, n_msgs: int,
               msg_elems: int, slice_bytes: int):
    """One step: per channel, reduce n_msgs messages across the ring."""

    def body(*xs):
        outs = []
        for x in xs:                       # x: (n_msgs, msg_elems)
            if mode == "sockets":
                parts = [jax.lax.psum(x[i], "data")
                         for i in range(x.shape[0])]
                outs.append(jnp.stack(parts))
            elif mode == "vma":
                outs.append(jax.lax.psum(x.reshape(-1),
                                         "data").reshape(x.shape))
            elif mode == "hadronio":
                # pack into slices, one collective per slice
                total = x.size * 4
                sl, sp = slice_view(x.reshape(-1), CommConfig(
                    mode="hadronio", slice_bytes=slice_bytes,
                    ring_capacity_bytes=max(slice_bytes * 64, total)))
                red = [jax.lax.psum(sl[i], "data")
                       for i in range(sp.n_slices)]
                out = jnp.stack(red).reshape(-1)
                outs.append(out[: x.size].reshape(x.shape))
            else:  # hadronio_agg: ONE coalesced wire flush per stream,
                #    through the live pipeline (aggregate="channel")
                total = x.size * 4
                comm = CommConfig(
                    mode="hadronio", slice_bytes=slice_bytes,
                    channels=1, aggregate="channel", hierarchical=False,
                    ring_capacity_bytes=max(slice_bytes * 64, total))
                sl, _ = slice_view(x.reshape(-1), comm)
                ctx = SyncContext.resolve(comm, ("data",), None)
                red, _ = pipeline.reduce_slices(sl, ctx)
                outs.append(red.reshape(-1)[: x.size].reshape(x.shape))
        return tuple(outs)

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=tuple([P()] * n_channels),
                      out_specs=tuple([P()] * n_channels),
                      check_vma=False)
    return jax.jit(f)


def run(mesh=None, *, msg_sizes=MSG_SIZES, channels=CHANNELS,
        modes=("sockets", "vma", "hadronio", "hadronio_agg"),
        slice_bytes: int = 64 * 1024, iters: int = 5):
    if mesh is None:
        n = len(jax.devices())
        mesh = make_mesh((n,), ("data",))
    rows = []
    for msg in msg_sizes:
        elems = max(1, msg // 4)
        n_msgs = FLUSH_INTERVAL[msg]
        for ch in channels:
            xs = tuple(jnp.ones((n_msgs, elems), jnp.float32) * (i + 1)
                       for i in range(ch))
            sds = [jax.ShapeDtypeStruct((n_msgs, elems), jnp.float32)] * ch
            for mode in modes:
                fn = _stream_fn(mesh, mode, ch, n_msgs, elems, slice_bytes)
                lowered = fn.lower(*sds)
                emitted = hlo.stablehlo_collective_stats(lowered.as_text())
                t = timeit(lambda: block(fn(*xs)), iters=iters)
                payload = ch * n_msgs * msg
                rows.append(Row("throughput", "fig4/6/8", mode, msg, ch,
                                "goodput", payload / t / 1e6, "MB/s",
                                "measured"))
                rows.append(Row("throughput", "fig4/6/8", mode, msg, ch,
                                "emitted_collective_ops",
                                emitted.total_ops, "ops", "derived"))
                rows.append(Row("throughput", "fig4/6/8", mode, msg, ch,
                                "goodput_v5e_model",
                                payload / derived_collective_time(emitted)
                                / 1e6, "MB/s", "derived"))
    return rows
