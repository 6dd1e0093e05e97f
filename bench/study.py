"""Readings that a cell's correctness limits are set from.

    python3 bench/study.py --workload <cell> --seeds 12 --control 3 --faults 3

In one process on the chip, at the cell's own sizes: the program's numbers
on ``--seeds`` seeds, the control's (the plain reference computed in fp8 in
the program's place) on the first ``--control`` of them, and each planted
fault's (``bench/faults.py``) on the first ``--faults``. A training cell
needs no window; a serving cell serves one round per seed, which holds the
mix's longest request, and compares as many requests as a run does. Prints
one JSON line per reading and a summary: per number, the program's largest
and the control's and each fault's smallest. The benchmark's runs never
run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import faults, harness, model  # noqa: E402


def _free() -> None:
    """Drop dead arrays and unload every compiled program: each loaded
    program keeps its scratch memory reserved on the chip."""
    gc.collect()
    jax.clear_caches()


def train_readings(ctx, seeds, n_control, n_faults, emit):
    from bench.drivers import train
    rows = ctx.mix["rows_per_chip"] * len(ctx.devs)

    def program(seed, plant=None):
        sess = train.Session(ctx.mc, ctx.cfg, ctx.mix, ctx.devs)
        if plant:
            plant(sess)
        state, got = train.first_steps(sess, seed, sess.initial_state(seed))
        del state, sess
        _free()
        return got

    for i, seed in enumerate(seeds):
        prog = program(seed)
        ref = train.reference_readings(ctx.cfg, ctx.mix, seed, rows)
        _free()
        emit("program", seed, train.compare(prog, ref), prog["losses"])
        if i < n_control:
            ctl = train.reference_readings(ctx.cfg, ctx.mix, seed, rows,
                                           prec="fp8")
            _free()
            emit("control", seed, train.compare(ctl, ref), ctl["losses"])
        if i < n_faults:
            for name, plant in faults.TRAIN.items():
                got = program(seed, plant)
                emit("fault:" + name, seed, train.compare(got, ref),
                     got["losses"])


def _serve_round(ctx, seed, plant=None) -> dict:
    from bench import traffic
    from bench.drivers import serve_closed
    sess = serve_closed.Session(ctx.mc, ctx.cfg, ctx.mix, ctx.devs, seed)
    if plant:
        plant(sess)
    results, sent, _ = sess.round(traffic.round_requests(
        ctx.mix, ctx.cfg["vocab_size"], seed, 0))
    done = {u: (q.prompt, results[u].tokens) for u, q in sent.items()
            if u in results}
    del sess
    _free()
    return done


def serve_readings(ctx, seeds, n_control, n_faults, emit):
    from bench.drivers import serve_closed

    def gap(seed, done, pick="served"):
        g = serve_closed.reference_gap(ctx.cfg, ctx.mix, seed, done, pick)
        _free()
        return {"logit_gap": g}

    for i, seed in enumerate(seeds):
        done = _serve_round(ctx, seed)
        emit("program", seed, gap(seed, done), len(done))
        if i < n_control:
            emit("control", seed, gap(seed, done, "fp8"), len(done))
        if i < n_faults:
            for name, plant in faults.SERVE.items():
                bad = _serve_round(ctx, seed, plant)
                emit("fault:" + name, seed, gap(seed, bad), len(bad))


def summary(rows: list) -> dict:
    out: dict = {}
    for r in rows:
        for k, v in r["readings"].items():
            d = out.setdefault(k, {})
            if r["kind"] == "program":
                d["program_max"] = max(d.get("program_max", v), v)
            else:
                key = r["kind"] + "_min"
                d[key] = min(d.get(key, v), v)
    return out


def main(argv=None) -> int:
    from bench.run import Context
    from bench import peaks
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", type=int, default=12)
    p.add_argument("--first-seed", type=int, default=1_000_003)
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--faults", type=int, default=3)
    p.add_argument("--out", default="")
    args = p.parse_args(argv)
    bench = harness.benchmark()
    cell = harness.cell_of(bench, args.workload)
    cfg = harness.config_of(bench, cell["config"])
    mix = harness.traffic_of(cell["traffic"])
    devs = harness.chips(cell["chips"])
    harness.enable_compile_cache()
    ctx = Context(cell=cell["name"], cfg=cfg, mc=model.program_config(cfg),
                  mix=mix, seed=0, seconds=0, trace=False, devs=devs,
                  peaks=peaks.peaks_for(devs[0].device_kind),
                  t_start=time.perf_counter())
    seeds = [args.first_seed + 7919 * i for i in range(args.seeds)]
    rows = []
    out = open(args.out, "w") if args.out else None

    def emit(kind, seed, readings, extra):
        row = {"kind": kind, "seed": seed, "readings": readings,
               "extra": extra, "t": time.perf_counter() - ctx.t_start}
        rows.append(row)
        line = json.dumps(row)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    fn = train_readings if mix["driver"] == "train" else serve_readings
    fn(ctx, seeds, args.control, args.faults, emit)
    print(json.dumps({"summary": summary(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
