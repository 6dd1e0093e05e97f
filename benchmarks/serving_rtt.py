"""Figs. 5-8 analog — uni-/bi-directional RTT percentiles through the
EventLoopGroup (the paper's multi-threaded netty microbenchmark).

Paper setup: an EventLoopGroup of worker threads, each owning a set of
connections; uni-directional streams one side's messages, bi-directional
keeps both directions in flight; results are reported as latency
percentiles over the message stream (the hhu JIB-benchmark methodology,
arXiv:1910.02245 — p50/p99/p99.9, never means).

TPU reading: one "connection" = one independent ppermute ping-pong on
the ring, OWNED by one event loop (disjoint channel affinity —
``serving/event_loop.py``); a loop drains its run queue by dispatching
its connections' round trips in a single jitted program and polling
completion per the configured strategy (busy / park / adaptive). The
sweep axes are event-loop count x connections-per-loop x message size,
uni (fwd-then-bwd chained) and bi (both directions concurrently in
flight per connection). Samples from every loop merge into ONE ragged
distribution per point (benchmarks/common.percentiles).

Also emits serving-dispatch evidence rows: the decode-step program of
``serving/dispatch.py`` lowered per comm mode, with emitted collective
counts and the first-collective position (None-safe on programs with no
collectives — the 1-device local reference).

  PYTHONPATH=src python -m benchmarks.serving_rtt --smoke \
      --json BENCH_serving.json
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from benchmarks.common import (Row, block, percentile_rows, timeit_samples)
from repro.configs.base import CommConfig
from repro.core.backends import pipeline
from repro.launch import hlo_analysis as hlo
from repro.launch.mesh import make_mesh
from repro.serving.event_loop import EventLoop, EventLoopGroup

MSG_SIZES = [16, 1024, 64 * 1024]
LOOPS = [1, 2, 4]
CONNS_PER_LOOP = [1, 2]
DIRECTIONS = ("uni", "bi")

EVIDENCE_MODES = ("sockets", "hadronio")


def _rtt_fn(mesh, n_conns: int, n_dev: int, direction: str):
    """One event loop's jitted program: every owned connection completes
    one round trip. ``uni`` chains fwd-then-bwd per connection; ``bi``
    keeps a second, reverse-starting payload in flight per connection
    (both directions on the wire at once)."""
    perm_fwd = [(i, (i + 1) % n_dev) for i in range(n_dev)]
    perm_bwd = [(i, (i - 1) % n_dev) for i in range(n_dev)]

    def trip(x, first, second):
        y = jax.lax.ppermute(x, "data", first)
        return jax.lax.ppermute(y, "data", second)

    def body(*xs):
        outs = []
        for x in xs:                     # independent connections
            outs.append(trip(x, perm_fwd, perm_bwd))
            if direction == "bi":
                outs.append(trip(x, perm_bwd, perm_fwd))
        return tuple(outs)

    f = jax.shard_map(body, mesh=mesh,
                      in_specs=tuple([P("data", None)] * n_conns),
                      out_specs=tuple([P("data", None)] * n_conns
                                         * (2 if direction == "bi" else 1)),
                      check_vma=False)
    return jax.jit(f)


def _loop_runner(fns: dict, mesh, elems: int, n_dev: int, direction: str,
                 iters: int):
    """Runner bound to each event loop: dispatch the loop's connections
    through the SHARED jitted program for that connection count (one
    compile per (n_conns, shape) across all loops — a per-loop jit would
    recompile the identical program once per loop), poll completions per
    the loop's strategy, return the RTT sample stream."""
    def runner(loop: EventLoop, items: list) -> list:
        n = len(items)
        if n == 0:
            return []
        if n not in fns:
            fns[n] = _rtt_fn(mesh, n, n_dev, direction)
        fn = fns[n]
        xs = tuple(jnp.zeros((n_dev, elems), jnp.float32) + u
                   for u in items)

        def once():
            out = fn(*xs)
            loop.poller.wait(out)        # busy / park / adaptive
            block(out)

        return [timeit_samples(once, warmup=1, iters=iters)]
    return runner


def _dispatch_evidence_rows(channels: int = 2) -> list:
    """Serving-dispatch evidence: emitted collective counts + first
    collective position of one lowered decode step per comm mode —
    proof the serve path flows through the staged emission API (and the
    None-safe position contract for collective-free programs)."""
    from repro.configs.registry import get_config
    from repro.serving import dispatch

    cfg = get_config("qwen2-0.5b-reduced")
    rows = []
    for mode in EVIDENCE_MODES:
        comm = CommConfig(mode=mode, slice_bytes=512, channels=channels,
                          aggregate="channel", flush="ready",
                          hierarchical=False)
        text = dispatch.lowered_decode_text(cfg, comm, batch=2, max_len=32)
        st = hlo.stablehlo_collective_stats(text)
        rows.append(Row("serving_rtt", "dispatch-evidence", mode, 0,
                        channels, "emitted_collective_ops", st.total_ops,
                        "ops", "derived"))
        pos = hlo.first_collective_position(text)
        if pos is not None:
            first, total = pos
            rows.append(Row("serving_rtt", "dispatch-evidence", mode, 0,
                            channels, "first_collective_pos",
                            first / max(total, 1), "frac", "derived"))
    return rows


TOPO_MSG_SIZES = [1024, 64 * 1024]
TOPO_MODE = "hadronio_overlap"


def _topo_emit_fn(mesh, ctx, elems: int):
    """One jitted serving logit-reduction through the staged emission
    wire: every ring peer contributes a partial payload, the sum comes
    back replicated (the decode TP-head exchange, isolated from model
    compute so the rows measure emission structure only)."""
    axes = tuple(mesh.axis_names)

    def body(x):
        return pipeline.emit_flat(x.reshape(-1), ctx, "all_reduce")

    f = jax.shard_map(body, mesh=mesh, in_specs=P(axes),
                      out_specs=P(), check_vma=False)
    return jax.jit(f)


def _topo_ctx(comm: CommConfig, mesh):
    """Resolve the emission context for a serve mesh: pod-aware when the
    mesh carries a pod axis (gated on ``comm.hierarchical``, exactly
    like ``serving/dispatch.make_serve_step``)."""
    from repro.core.backends.base import SyncContext
    axes = tuple(mesh.axis_names)
    if "pod" in axes:
        data = tuple(a for a in axes if a != "pod")
        return SyncContext.resolve(comm, data, "pod")
    return SyncContext.resolve(comm, axes, None)


def run_topo(*, msg_sizes=TOPO_MSG_SIZES, pod_counts=None,
             channels: int = 4, leader_channels: int = 1,
             iters: int = 20, smoke: bool = False) -> list:
    """The mesh-growth sweep (the tentpole's headline table): RTT
    percentiles of the serving logit reduction x pod count x emission
    {flat, hierarchical leader-channel}, plus the cross-pod-collective
    evidence rows — under leader emission the cross-pod count stays at
    ``leader_channels`` as pods grow while flat emission keeps every
    one of its ``channels`` collectives on the cross-pod link."""
    from repro.configs.registry import get_config
    from repro.launch.mesh import make_serve_mesh
    from repro.serving import dispatch

    n = len(jax.devices())
    if pod_counts is None:
        pod_counts = [p for p in (1, 2, 4) if p <= n and n % p == 0]
    if smoke:
        iters = min(iters, 5)
        pod_counts = pod_counts[:2]
    rows = []
    cfg = get_config("qwen2-0.5b-reduced")
    for pods in pod_counts:
        mesh = make_serve_mesh(pods)
        emissions = ("flat",) if pods == 1 else ("flat", "hierarchical")
        for emission in emissions:
            comm = CommConfig(
                mode=TOPO_MODE, channels=channels,
                aggregate="channel", flush="ready",
                hierarchical=emission == "hierarchical",
                leader_channels=leader_channels,
                slice_bytes=max(64, min(msg_sizes) // channels))
            ctx = _topo_ctx(comm, mesh)
            for msg in msg_sizes:
                elems = max(1, msg // 4)
                fn = _topo_emit_fn(mesh, ctx, elems)
                x = jnp.ones((n, elems), jnp.float32)

                def once():
                    block(fn(x))

                samples = timeit_samples(once, warmup=2, iters=iters)
                rows.extend(percentile_rows(
                    "serving_rtt", "topo-sweep", emission, msg, channels,
                    [samples], suffix=f"pods{pods}"))
            if pods > 1:
                # jaxpr evidence: in-pod vs cross-pod collective counts
                # of one lowered decode step over this very mesh
                text = dispatch.lowered_decode_text(cfg, comm, batch=n,
                                                    mesh=mesh)
                cp = hlo.cross_pod_collective_count(text, n // pods)
                rows.append(Row(
                    "serving_rtt", "topo-evidence", emission, 0, channels,
                    f"cross_pod_collectives:pods{pods}",
                    cp["cross_pod_total"], "ops", "derived"))
                rows.append(Row(
                    "serving_rtt", "topo-evidence", emission, 0, channels,
                    f"in_pod_collectives:pods{pods}",
                    cp["in_pod_total"], "ops", "derived"))
    return rows


def run(mesh=None, *, msg_sizes=MSG_SIZES, loops=LOOPS,
        conns_per_loop=CONNS_PER_LOOP, directions=DIRECTIONS,
        iters: int = 20, poll: str = "busy", smoke: bool = False,
        threads: bool = True, evidence: bool = True):
    if smoke:
        loops = [1, 2]
        conns_per_loop = [2]
        iters = min(iters, 5)
    if mesh is None:
        n = len(jax.devices())
        mesh = make_mesh((n,), ("data",))
    n_dev = mesh.shape["data"]
    rows = []
    for direction in directions:
        # ONE jitted wrapper per connection count for the whole direction
        # sweep (jit re-specializes per message shape on its own) —
        # shared across loops, loop counts and message sizes
        fns = {n: _rtt_fn(mesh, n, n_dev, direction)
               for n in set(conns_per_loop)}
        for msg in msg_sizes:
            elems = max(1, msg // 4)
            for el in loops:
                for cpl in conns_per_loop:
                    total = el * cpl
                    runner = _loop_runner(fns, mesh, elems, n_dev,
                                          direction, iters)
                    evloops = [EventLoop(i, channels=(i,), poll=poll,
                                         runner=runner)
                               for i in range(el)]
                    grp = EventLoopGroup(evloops)
                    grp.submit(list(range(total)))   # round-robin conns
                    samples = grp.run(threads=threads)   # ragged per loop
                    rows.extend(percentile_rows(
                        "serving_rtt", "fig5-8", direction, msg, total,
                        samples, suffix=f"el{el}"))
                    st = grp.poll_stats()
                    rows.append(Row("serving_rtt", "fig5-8", direction,
                                    msg, total, f"poll_parks:el{el}",
                                    st.parks, "count", "derived"))
                    rows.append(Row("serving_rtt", "fig5-8", direction,
                                    msg, total, f"poll_spins:el{el}",
                                    st.spins, "count", "derived"))
    if evidence:
        rows.extend(_dispatch_evidence_rows())
    return rows


def main() -> int:
    from benchmarks import common
    common.ensure_devices()
    import argparse

    from benchmarks.common import write_json, write_rows

    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--smoke", action="store_true",
                   help="CI sweep: 3 msg sizes x {1,2} loops x 2 conns")
    p.add_argument("--poll", default="busy",
                   choices=("busy", "park", "adaptive"))
    p.add_argument("--iters", type=int, default=20)
    p.add_argument("--csv", default="")
    p.add_argument("--json", default="")
    p.add_argument("--seed", type=int, default=0,
                   help="recorded in every row's seed column (JIB "
                        "methodology: rows carry their reproduction "
                        "conditions)")
    p.add_argument("--topo", action="store_true",
                   help="run the pod-topology sweep instead (RTT "
                        "percentiles x pod count x emission "
                        "{flat, hierarchical} + cross-pod collective "
                        "evidence rows)")
    p.add_argument("--trace-out", default="",
                   help="write a Chrome-trace JSON of the bench's spans "
                        "(drains, staged emissions from the dispatch-"
                        "evidence lowering) here")
    p.add_argument("--metrics-out", default="",
                   help="write the obs registry snapshot (poll/emission "
                        "counters of the bench run) here")
    args = p.parse_args()
    common.set_run_seed(args.seed)
    if args.trace_out:
        from repro import obs
        obs.enable()
        # the dispatch-evidence lowering must trace FRESH programs or a
        # warm serve-step cache yields an emission-span-free trace
        from repro.serving import dispatch
        dispatch.clear_serve_step_cache()
    if args.topo:
        rows = run_topo(iters=args.iters, smoke=args.smoke)
    else:
        rows = run(iters=args.iters, poll=args.poll, smoke=args.smoke)
    if args.metrics_out:
        from repro import obs
        reg = obs.collect(mode="bench")
        with open(args.metrics_out, "w") as f:
            f.write(reg.to_json())
        # the deterministic half also rides the row artifact (unit
        # "count": inspectable in BENCH_*.json, ignored by bench_diff)
        rows.extend(common.metrics_rows("serving_rtt", reg.snapshot(),
                                        mode="bench"))
        print(f"[serving_rtt] metrics snapshot -> {args.metrics_out}")
    text = write_rows(rows, args.csv or None)
    if args.json:
        write_json(rows, args.json)
    if args.trace_out:
        from repro import obs
        rec = obs.disable()
        doc = rec.write(args.trace_out)
        print(f"[serving_rtt] span trace -> {args.trace_out} "
              f"({len(doc['traceEvents'])} spans, kinds={rec.kinds()})")
    print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
