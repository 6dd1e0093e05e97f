"""One engine wave of long prompts against the plain reference.

    python3 bench/wave_check.py --config starcoder2-3b --phase serve \
        --prompts 8192,5000 --new 65 --max-len 8704 --seeds 11,12 --out DIR
    python3 bench/wave_check.py --config starcoder2-3b --phase reference \
        --prompts 8192,5000 --new 65 --max-len 8704 --seeds 11,12 --out DIR

On the chip, at the configuration's published widths with weights drawn
from each seed. ``serve`` builds the engine group as the serving cells do
(one event loop with a slot per prompt), serves all prompts in one wave
(padded to the longest), then each prompt alone in a wave of its own, and
saves the prompts and the greedy tokens under ``--out``. ``reference``,
in a process of its own so that its memory peak is its own, runs the
serving check (``serve_closed.request_gap``, every sequence padded to
``--max-len``) over each served row: ``logit_gap`` against the reference,
and the fp8 control's gap on the same rows. Prints one JSON line per
reading. The benchmark's runs never run this.
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
import numpy as np  # noqa: E402

from bench import harness, model, traffic  # noqa: E402


def mix_for(args) -> dict:
    """A serving mix dict as ``serve_closed`` reads one."""
    n = len(args.prompts)
    return {"serve": {"event_loops": 1, "max_batch": n,
                      "max_len": args.max_len, "poll": "busy",
                      "comm": {"mode": "hadronio", "channels": 4}},
            "output": {"max": args.new}}


def prompts_for(cfg: dict, seed: int, lengths: list) -> list:
    rng = traffic.rng_for(seed, 5)
    return [rng.integers(0, cfg["vocab_size"], size=n, dtype=np.int32)
            for n in lengths]


def emit(out: Path, row: dict) -> None:
    line = json.dumps(row)
    print(line, flush=True)
    with open(out / "readings.jsonl", "a") as f:
        f.write(line + "\n")


def serve(args, cfg, out: Path) -> None:
    from bench.drivers.serve_closed import Session
    devs = harness.chips(1)
    mc = model.program_config(cfg)
    mix = mix_for(args)
    for seed in args.seeds:
        t0 = time.perf_counter()
        sess = Session(mc, cfg, mix, devs, seed)
        prompts = prompts_for(cfg, seed, args.prompts)
        rows = {}
        results, sent, dt = sess.round([(p, args.new) for p in prompts])
        for i, uid in enumerate(sorted(sent)):
            rows[f"wave.{args.prompts[i]}"] = results[uid].tokens
        emit(out, {"phase": "serve", "seed": seed, "wave_s": dt})
        for p in prompts:
            results, sent, dt = sess.round([(p, args.new)])
            rows[f"alone.{len(p)}"] = results[min(sent)].tokens
            emit(out, {"phase": "serve", "seed": seed, "alone": len(p),
                       "wave_s": dt})
        np.savez(out / f"seed{seed}.npz",
                 **{f"prompt.{len(p)}": p for p in prompts},
                 **{k: np.asarray(v, np.int32) for k, v in rows.items()})
        emit(out, {"phase": "serve", "seed": seed,
                   "seconds": time.perf_counter() - t0,
                   "device": harness.device_info(devs)})
        del sess
        gc.collect()
        jax.clear_caches()


def reference(args, cfg, out: Path) -> None:
    from bench.drivers import serve_closed
    devs = harness.chips(1)
    mix = mix_for(args)
    for seed in args.seeds:
        saved = np.load(out / f"seed{seed}.npz")
        params = model.init_weights(cfg, seed)
        for pick in ("served", "fp8"):
            fn = serve_closed._gaps_fn(cfg, pick)
            for row in sorted(k for k in saved.files
                              if not k.startswith("prompt.")):
                prompt = saved["prompt." + row.split(".")[1]]
                t0 = time.perf_counter()
                gap = serve_closed.request_gap(fn, params, mix, prompt,
                                               saved[row])
                emit(out, {"phase": "reference", "seed": seed, "row": row,
                           "against": pick, "logit_gap": gap,
                           "seconds": time.perf_counter() - t0})
        del params
        gc.collect()
    emit(out, {"phase": "reference", "device": harness.device_info(devs)})


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--config", required=True)
    p.add_argument("--phase", choices=("serve", "reference"), required=True)
    p.add_argument("--prompts", required=True,
                   type=lambda s: [int(x) for x in s.split(",")])
    p.add_argument("--new", type=int, required=True)
    p.add_argument("--max-len", type=int, required=True)
    p.add_argument("--seeds", required=True,
                   type=lambda s: [int(x) for x in s.split(",")])
    p.add_argument("--out", required=True)
    args = p.parse_args(argv)
    cfg = harness.load_json(harness.BENCH / "configs" / f"{args.config}.json")
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    harness.enable_compile_cache()
    (serve if args.phase == "serve" else reference)(args, cfg, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
