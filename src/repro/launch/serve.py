"""Serving launcher: load (or init) params, run the event-loop serving
subsystem (EventLoopGroup of decode engines over the CommBackend wire).

CLI::

  python -m repro.launch.serve --arch qwen2-0.5b-reduced --requests 8 \
      --max-new 16 --ckpt /tmp/run1        # params from a train checkpoint

  # paper §IV topology: 2 event loops, busy polling, hadronio wire
  python -m repro.launch.serve --arch qwen2-0.5b-reduced --requests 16 \
      --event-loops 2 --poll busy --comm-mode hadronio --channels 4

  # two-level fabric: 2 pods, hierarchical leader-channel emission —
  # intra-pod traffic stays on local channels, the 1/n-reduced shard
  # rides the leader lane pinned to loop 0
  python -m repro.launch.serve --arch qwen2-0.5b-reduced --requests 16 \
      --event-loops 2 --comm-mode hadronio_overlap --channels 4 \
      --aggregate channel --flush ready --pods 2 --emission hierarchical

  # self-healing supervisor: bounded admission, retry/backoff healing,
  # autoscaling between --event-loops (floor) and --max-loops
  python -m repro.launch.serve --arch qwen2-0.5b-reduced --requests 32 \
      --event-loops 1 --supervised --max-loops 4 --scale-up-depth 4 \
      --admission-capacity 16 --dispatch-quantum 8

  # multi-tenant: two model FAMILIES side by side in one group — each
  # --tenant NAME=ARCH[:WEIGHT[:LOOPS]] owns a contiguous loop range,
  # requests route by tenant with weighted-fair admission (2:1 here)
  python -m repro.launch.serve --requests 12 --comm-mode hadronio \
      --tenant chat=qwen2-0.5b-reduced:2 \
      --tenant rnn=rwkv6-7b-reduced:1
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro import obs
from repro.configs.registry import get_config
from repro.configs.base import CommConfig, ServeConfig, TenantConfig
from repro.checkpoint import CheckpointStore
from repro.core.backends import available_modes
from repro.launch.compile_cache import enable_compile_cache
from repro.models import api
from repro.serving import (Request, RetryBudget, Supervisor,
                           SupervisorConfig, make_engine_group)


def parse_tenant_specs(specs) -> tuple:
    """``NAME=ARCH[:WEIGHT[:LOOPS]]`` -> TenantConfig tuple (shared by
    this launcher and examples/serve_batched.py)."""
    out = []
    for spec in specs or ():
        name, _, rest = spec.partition("=")
        if not name or not rest:
            raise ValueError(
                f"--tenant {spec!r}: expected NAME=ARCH[:WEIGHT[:LOOPS]]")
        parts = rest.split(":")
        out.append(TenantConfig(
            name, arch=parts[0],
            weight=int(parts[1]) if len(parts) > 1 else 1,
            event_loops=int(parts[2]) if len(parts) > 2 else 1))
    return tuple(out)


def load_params(args, cfg):
    if args.ckpt:
        store = CheckpointStore(args.ckpt)
        step = store.latest_step()
        if step is not None:
            from repro.launch import steps as steps_mod
            from repro.configs.base import RunConfig, ShapeConfig
            run = RunConfig(model=cfg, shape=ShapeConfig(
                "serve", "decode", args.max_len, args.batch))
            like = steps_mod.abstract_train_state(run)
            state = store.restore(step, like)
            print(f"[serve] restored params from step {step}")
            return state.params
    return api.init(jax.random.PRNGKey(args.seed), cfg)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="",
                   help="registry id (required unless --tenant is given)")
    p.add_argument("--tenant", action="append", default=[],
                   metavar="NAME=ARCH[:WEIGHT[:LOOPS]]",
                   help="repeatable: serve several models in ONE group — "
                        "each tenant owns LOOPS event loops (contiguous "
                        "range, disjoint channels) and a WEIGHT share of "
                        "weighted-fair admission; requests route by "
                        "Request.tenant (docs/FAMILIES.md)")
    p.add_argument("--requests", type=int, default=8)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--max-len", type=int, default=256)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--ckpt", default="")
    p.add_argument("--seed", type=int, default=0)
    # the event-loop serving subsystem (ServeConfig)
    p.add_argument("--event-loops", type=int, default=1,
                   help="EventLoopGroup size; each loop owns a disjoint "
                        "run of the channel pool")
    p.add_argument("--poll", default="busy",
                   choices=ServeConfig.POLLS,
                   help="completion polling: busy spins, park blocks, "
                        "adaptive spins then parks (hadroNIO §IV-B)")
    p.add_argument("--comm-mode", default="gspmd",
                   choices=available_modes(),
                   help="CommBackend the serving collectives (KV gathers, "
                        "TP logit reductions) flow through")
    p.add_argument("--channels", type=int, default=4,
                   help="global CommChannel pool partitioned across loops")
    p.add_argument("--aggregate", default="slice",
                   choices=CommConfig.AGGREGATES)
    p.add_argument("--flush", default="step", choices=CommConfig.FLUSHES)
    # the two-level serving fabric (pod topology)
    p.add_argument("--pods", type=int, default=1,
                   help="pod count of the two-level fabric; must divide "
                        "the device count (1 = flat ring)")
    p.add_argument("--pod-axis", default="pod",
                   help="mesh axis name of the pod dimension")
    p.add_argument("--leader-loops", type=int, default=1,
                   help="event loops pinned to the cross-pod leader lanes")
    p.add_argument("--leader-channels", type=int, default=1,
                   help="channels carved from the pool tail as dedicated "
                        "cross-pod leader lanes")
    p.add_argument("--emission", default="flat",
                   choices=("flat", "hierarchical"),
                   help="flat: one-level ring collectives over all "
                        "devices; hierarchical: pod-aware two-level "
                        "leader-channel emission (bit-identical tokens, "
                        "different wire structure)")
    # the self-healing supervisor (serving/supervisor.py)
    p.add_argument("--supervised", action="store_true",
                   help="run under the Supervisor: failure detection, "
                        "retry/backoff healing, elastic autoscaling and "
                        "admission backpressure")
    p.add_argument("--admission-capacity", type=int, default=64,
                   help="bounded admission queue; over capacity the "
                        "lowest-priority request is shed with an "
                        "explicit rejected outcome")
    p.add_argument("--dispatch-quantum", type=int, default=0,
                   help="requests dispatched per supervision round "
                        "(0 = drain the whole queue)")
    p.add_argument("--retry-limit", type=int, default=3,
                   help="drain retry attempts before a structured "
                        "retry_exhausted outcome")
    p.add_argument("--max-loops", type=int, default=0,
                   help="autoscale ceiling (0 = channel pool size); "
                        "--event-loops is the starting size")
    p.add_argument("--scale-up-depth", type=float, default=8.0,
                   help="queued requests per loop that votes to grow "
                        "the fleet")
    p.add_argument("--scale-down-depth", type=float, default=-1.0,
                   help="backlog per loop that votes to shrink "
                        "(negative disables shrinking)")
    # the Observatory telemetry plane (repro/obs, docs/OBSERVABILITY.md)
    p.add_argument("--trace-out", default="",
                   help="write a Chrome-trace/Perfetto JSON of the run's "
                        "spans here (enables tracing; tokens stay "
                        "bit-identical to an untraced run)")
    p.add_argument("--metrics-out", default="",
                   help="write the unified metrics snapshot (obs "
                        "registry JSON: poll/emission/loop/tenant/"
                        "supervisor counters) here")
    args = p.parse_args()
    enable_compile_cache()

    if args.trace_out:
        obs.enable()

    tenants = parse_tenant_specs(args.tenant)
    if not tenants and not args.arch:
        p.error("--arch is required (or pass one or more --tenant specs)")
    if tenants and args.supervised:
        p.error("--supervised requires a single-tenant group: tenant loop "
                "ranges pin the fleet size, which autoscaling would "
                "resize (drop --tenant or --supervised)")
    if tenants:
        cfg = {t.name: get_config(t.arch) for t in tenants}
        params = {t.name: api.init(jax.random.PRNGKey(args.seed + i),
                                   cfg[t.name])
                  for i, t in enumerate(tenants)}
        if args.event_loops == 1:      # default: one loop per tenant
            args.event_loops = sum(t.event_loops for t in tenants)
    else:
        cfg = get_config(args.arch)
        params = load_params(args, cfg)
    # no silent clamping: ServeConfig raises its own clear errors when
    # event_loops > channels (each loop must own a disjoint run), the
    # pod topology cannot be honored (leader lanes must leave every loop
    # a local lane), or the tenant loop counts do not sum to the fleet
    # size; make_serve_mesh rejects pods not dividing devices
    serve = ServeConfig(
        event_loops=args.event_loops, poll=args.poll,
        max_batch=args.batch, max_len=args.max_len,
        pods=args.pods, pod_axis=args.pod_axis,
        leader_loops=args.leader_loops, tenants=tenants,
        comm=CommConfig(mode=args.comm_mode, channels=args.channels,
                        aggregate=args.aggregate, flush=args.flush,
                        hierarchical=args.emission == "hierarchical",
                        leader_channels=args.leader_channels))
    sup = None
    if args.supervised:
        sup = Supervisor(cfg, params, serve, seed=args.seed,
                         config=SupervisorConfig(
                             admission_capacity=args.admission_capacity,
                             dispatch_quantum=args.dispatch_quantum,
                             max_loops=args.max_loops,
                             scale_up_depth=args.scale_up_depth,
                             scale_down_depth=args.scale_down_depth,
                             retry=RetryBudget(limit=args.retry_limit)))
        group = sup.group
    else:
        group = make_engine_group(cfg, params, serve, seed=args.seed)
    if args.pods > 1:
        eng = group.loops[0].engine
        print(f"[serve] two-level fabric: pods={args.pods} "
              f"(axis {args.pod_axis!r}), emission={args.emission}, "
              f"leader lanes={args.leader_channels} -> "
              f"loops 0..{args.leader_loops - 1}, "
              f"mesh={dict(eng.step.mesh.shape)}")

    rng = np.random.default_rng(args.seed)
    if tenants:
        names = [t.name for t in tenants]
        reqs = []
        for i in range(args.requests):
            name = names[i % len(names)]
            reqs.append(Request(
                uid=i,
                prompt=rng.integers(0, cfg[name].vocab_size,
                                    size=rng.integers(4, 32)),
                max_new=args.max_new, temperature=args.temperature,
                tenant=name))
    else:
        reqs = [Request(uid=i,
                        prompt=rng.integers(0, cfg.vocab_size,
                                            size=rng.integers(4, 32)),
                        max_new=args.max_new,
                        temperature=args.temperature)
                for i in range(args.requests)]
    t0 = time.time()
    if sup is not None:
        sup.submit(reqs)
        results = sup.run(threads=args.event_loops > 1)
        group = sup.group          # may have been rebuilt by a resize
    else:
        group.submit(reqs)
        results = sorted(group.run(threads=args.event_loops > 1),
                         key=lambda r: r.uid)
    dt = time.time() - t0
    tok = sum(len(r.tokens) for r in results)
    st = sup.poll_stats() if sup is not None else group.poll_stats()
    print(f"[serve] {len(results)} requests, {tok} tokens in {dt:.2f}s "
          f"({tok / dt:.1f} tok/s) | {serve.event_loops} event loop(s), "
          f"poll={serve.poll} (spins={st.spins} parks={st.parks}), "
          f"comm={args.comm_mode}")
    if sup is not None:
        shed = sum(1 for o in sup.outcomes.values()
                   if o.status == "rejected")
        print(f"[serve] supervisor: {sup.rounds} rounds, "
              f"{len(sup.trace)} healing actions, {shed} shed, "
              f"fleet={sup.group.n_loops} loops, mttr="
              f"{sup.mttr_s() if sup.trace else None}")
        for a in sup.healing_trace():
            print(f"  heal round={a[0]} {a[1]} target={a[2]} {a[3]}")
    if tenants:
        print(f"[serve] tenants: fairness={group.fairness_counters} "
              f"dispatch={group.dispatch_log[:12]}")
    for loop in group.loops:
        print(f"  loop {loop.index}: channels={loop.channels} "
              f"results={len(loop.results)}")
    for r in results[:4]:
        print(f"  uid={r.uid} prompt_len={r.prompt_len} -> "
              f"{r.tokens[:12].tolist()}")
    if args.metrics_out:
        reg = obs.collect(group=group, supervisor=sup,
                          mode=args.comm_mode)
        with open(args.metrics_out, "w") as f:
            f.write(reg.to_json())
        snap = reg.snapshot()
        print(f"[serve] metrics snapshot -> {args.metrics_out} "
              f"({len(snap['counters']) + len(snap['gauges'])} "
              f"deterministic metrics)")
    if args.trace_out:
        rec = obs.disable()
        doc = rec.write(args.trace_out)
        print(f"[serve] span trace -> {args.trace_out} "
              f"({len(doc['traceEvents'])} spans, kinds={rec.kinds()})")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
