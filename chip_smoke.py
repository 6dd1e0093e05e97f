"""Bring-up smoke: serve and train full-width qwen2-0.5b on TPU.

    python chip_smoke.py              # one chip: serve, checks, train
    python chip_smoke.py --chips 4    # four chips: hadronio vs gspmd only

One chip (the default):

* serve — 8 greedy requests (prompts of 16-128 tokens, 16 new tokens,
  ``max_len`` 512) through ``make_engine_group`` / ``EventLoopGroup.run``
  with 2 event loops on ``CommConfig(mode="hadronio", channels=4)``,
  exactly as ``repro.launch.serve`` drives them. Checks: (a) the same
  requests served with ``mode="gspmd"`` give identical tokens; (b) the
  served prefill's last-token logits match a float32 forward at highest
  matmul precision within ``PREFILL_REL_TOL``, and each request's first
  token is the argmax of its served logits; (c) every prefill logit, and
  those of one decode step, is finite.
* pack stage — the ``ring_pack`` kernel (``pack="pallas"``) and
  ``pack="jnp"`` at the training plan's slice size, each bit for bit
  against a host (numpy) reference.
* train — 3 steps of ``Trainer.run_loop`` under ``hadronio_overlap`` at
  sequence length 512; every loss must be finite.

Four chips: the train step on a 4-chip ``("data",)`` mesh under
``hadronio_overlap`` against ``gspmd`` from the same seed (losses within
``LOSS_ATOL``), and serving over the 4-shard mesh, ``hadronio`` tokens
against ``gspmd`` tokens.

Weights are random, made from ``--seed``. Everything runs in this one
process, which holds the chips. The script exits non-zero when JAX finds
no TPU and on any failed check or exception; the last line of stdout is
``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

from repro.configs.base import (CommConfig, RunConfig,  # noqa: E402
                                ServeConfig, ShapeConfig)
from repro.configs.registry import get_config  # noqa: E402
from repro.core.backends import pipeline  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.launch.train import Trainer  # noqa: E402
from repro.models import api  # noqa: E402
from repro.serving import Request, make_engine_group  # noqa: E402

ARCH = "qwen2-0.5b"
N_REQUESTS = 8
PROMPT_LEN = (16, 128)
MAX_NEW = 16
MAX_LEN = 512
EVENT_LOOPS = 2
CHANNELS = 4
SEQ_LEN = 512
TRAIN_STEPS = 3
# the largest of {8, 4, 2} whose hadronio_overlap step compiles inside one
# v5e chip's 16 GB (AOT compile for a described v5e); four chips keep the
# same rows per chip
GLOBAL_BATCH_PER_CHIP = 8
# bf16 weights and activations through 24 layers against an f32 forward:
# per-request relative L2 error of the last-token logits
PREFILL_REL_TOL = 5e-2
# hadronio_overlap reduces f32 gradients in its own slice order, gspmd
# reduces bf16 gradients in XLA's; AdamW's first steps normalise every
# element, so rounding-level gradient differences move the loss by far
# less than this
LOSS_ATOL = 2e-2


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


class Checks:
    """Every check's verdict, logged as it is made. A failed check does
    not stop the later phases, so one run reports them all; the script
    then exits non-zero without the result line."""

    def __init__(self):
        self.failed: list = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        log(f"check {name}: {'PASS' if ok else 'FAIL'} ({detail})")
        if not ok:
            self.failed.append(name)


def make_requests(cfg, seed: int) -> list:
    rng = np.random.default_rng(seed)
    lo, hi = PROMPT_LEN
    return [Request(uid=i, max_new=MAX_NEW,
                    prompt=rng.integers(0, cfg.vocab_size,
                                        size=int(rng.integers(lo, hi + 1))))
            for i in range(N_REQUESTS)]


def serve(cfg, params, mode: str, reqs: list, seed: int):
    """Serve ``reqs`` through a fresh engine group; returns the group and
    the results in uid order."""
    serve_cfg = ServeConfig(event_loops=EVENT_LOOPS, max_len=MAX_LEN,
                            comm=CommConfig(mode=mode, channels=CHANNELS))
    group = make_engine_group(cfg, params, serve_cfg, seed=seed)
    group.submit(reqs)
    results = sorted(group.run(threads=True), key=lambda r: r.uid)
    if [r.uid for r in results] != [r.uid for r in reqs]:
        raise RuntimeError(f"{mode}: served {len(results)} of {len(reqs)}")
    return group, results


def same_tokens(check: Checks, what: str, a: list, b: list) -> None:
    bad = [x.uid for x, y in zip(a, b)
           if not np.array_equal(x.tokens, y.tokens)]
    check(what, not bad, f"{len(a) - len(bad)}/{len(a)} requests "
          f"identical; differing uids {bad}")


def loop_batches(group, reqs: list):
    """Each loop's first wave as its engine prefilled it: requests are
    assigned round-robin, prompts right-padded to the wave's longest."""
    for loop in group.loops:
        mine = reqs[loop.index::group.n_loops]
        lens = np.array([len(r.prompt) for r in mine], np.int32)
        toks = np.zeros((len(mine), lens.max()), np.int32)
        for i, r in enumerate(mine):
            toks[i, :lens[i]] = r.prompt
        yield loop.engine, mine, {"tokens": jnp.asarray(toks),
                                  "last_pos": jnp.asarray(lens - 1)}


def check_prefill(check: Checks, cfg, params, group, reqs: list,
                  results: list) -> None:
    """(b) served prefill logits against an f32 forward, (c) finiteness
    of the served prefill and of one decode step."""
    cfg32 = dataclasses.replace(cfg, param_dtype="float32",
                                compute_dtype="float32")
    params32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref_prefill = jax.jit(lambda p, b: api.prefill(p, b, cfg32)[0])
        errs, finite, firsts, agree = [], True, True, 0
        for eng, mine, batch in loop_batches(group, reqs):
            served, cache = eng.step.prefill(params, batch)
            ref = ref_prefill(params32, batch)
            s = np.asarray(served.astype(jnp.float32))
            r = np.asarray(ref)
            finite &= bool(np.isfinite(s).all() and np.isfinite(r).all())
            errs += list(np.linalg.norm(s - r, axis=-1)
                         / np.linalg.norm(r, axis=-1))
            agree += int((s.argmax(-1) == r.argmax(-1)).sum())
            for row, req in enumerate(mine):
                firsts &= int(results[req.uid].tokens[0]) == int(
                    s[row].argmax())
            cache = api.grow_cache(cfg, cache, MAX_LEN)
            dec = {"token": jnp.asarray(s.argmax(-1), jnp.int32),
                   "pos": batch["last_pos"] + 1}
            logits, _ = eng.step.decode(params, cache, dec)
            finite &= bool(np.isfinite(
                np.asarray(logits.astype(jnp.float32))).all())
    worst = float(max(errs))
    check("(b) served prefill logits vs f32 reference",
          worst <= PREFILL_REL_TOL and firsts,
          f"worst per-request relative L2 error {worst!r}, tolerance "
          f"{PREFILL_REL_TOL}; argmax agrees with f32 for "
          f"{agree}/{len(reqs)}; first served token == argmax of served "
          f"logits: {firsts}")
    check("(c) all logits finite", finite,
          "served prefill, f32 reference and one decode step")


def check_pack_stage(check: Checks, seed: int) -> None:
    """The wire pack/unpack stages with ``pack="pallas"`` (the ring_pack
    kernel, compiled by Mosaic) and ``pack="jnp"`` at the training plan's
    slice size, each against a host reference: bit-identical wire,
    residual and unpacked f32."""
    n, elems = 8, CommConfig().slice_bytes // 4
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    slices = jax.random.normal(k1, (n, elems))
    ef = jax.random.normal(k2, (n, elems)) * 0.01
    x = np.asarray(slices) + np.asarray(ef)
    ref_wire = x.astype(ml_dtypes.bfloat16)
    ref_back = ref_wire.astype(np.float32)
    ref = (ref_wire, x - ref_back, ref_back)
    for impl in ("pallas", "jnp"):
        comm = CommConfig(mode="hadronio", compress="bf16", pack=impl)
        wire, new_ef, _ = jax.jit(
            lambda s, e: pipeline.pack_wire(s, e, comm))(slices, ef)
        back = jax.jit(lambda w: pipeline.unpack_wire(w, comm))(wire)
        bad = [int(np.count_nonzero(np.asarray(a) != b))
               for a, b in zip((wire, new_ef, back), ref)]
        check(f"pack={impl} pack/unpack vs host reference", not any(bad),
              f"{n} x {elems} f32 slices, bf16 wire with error feedback; "
              f"elements differing in wire, residual, unpacked f32: {bad}")


def train(check: Checks, cfg, mode: str, mesh, global_batch: int,
          seed: int) -> list:
    """``TRAIN_STEPS`` steps of ``Trainer.run_loop``; returns the losses.
    Step times are host-clock gaps between the per-step log lines (each
    follows a blocking read of that step's loss); step 0 includes its
    compile."""
    run = RunConfig(model=cfg,
                    shape=ShapeConfig("chip-smoke", "train", SEQ_LEN,
                                      global_batch),
                    comm=CommConfig(mode=mode), total_steps=TRAIN_STEPS,
                    warmup_steps=1, seed=seed)
    stamps = [time.perf_counter()]

    def step_log(msg: str) -> None:
        stamps.append(time.perf_counter())
        log(f"train {mode}: {msg}")

    out = Trainer(run, mesh, log_every=1, log_fn=step_log).run_loop()
    losses = out["losses"]
    gaps = [float(g) for g in np.diff(stamps)]
    log(f"train {mode}: global batch {global_batch} x {SEQ_LEN} tokens; "
        f"build + step 0 (compile included, set-up) {gaps[0]!r} s; "
        f"steady step times {gaps[1:]} s")
    check(f"train {mode} losses finite",
          len(losses) == TRAIN_STEPS and all(map(math.isfinite, losses)),
          f"losses {losses}")
    return losses


def peak_bytes(device) -> str:
    stats = device.memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


def one_chip(check: Checks, cfg, seed: int) -> None:
    params = api.init(jax.random.PRNGKey(seed), cfg)
    reqs = make_requests(cfg, seed)
    t0 = time.perf_counter()
    group, results = serve(cfg, params, "hadronio", reqs, seed)
    t_setup = time.perf_counter() - t0
    t0 = time.perf_counter()
    group.submit(reqs)
    again = sorted(group.run(threads=True), key=lambda r: r.uid)
    dt = time.perf_counter() - t0
    n_tok = sum(len(r.tokens) for r in again)
    log(f"serve hadronio: first run (compile included, set-up) {t_setup!r} "
        f"s; second run {n_tok} tokens in {dt!r} s = {n_tok / dt!r} tok/s")
    same_tokens(check, "serve rerun determinism", results, again)
    _, ref = serve(cfg, params, "gspmd", reqs, seed)
    same_tokens(check, "(a) hadronio vs gspmd tokens", results, ref)
    check_prefill(check, cfg, params, group, reqs, results)
    log(f"serve: peak_bytes_in_use {peak_bytes(jax.devices()[0])}")
    del group, params

    check_pack_stage(check, seed)
    mesh = make_mesh((1,), ("data",))
    train(check, cfg, "hadronio_overlap", mesh, GLOBAL_BATCH_PER_CHIP, seed)
    log(f"train: peak_bytes_in_use {peak_bytes(jax.devices()[0])}")


def four_chips(check: Checks, cfg, seed: int) -> None:
    mesh = make_mesh((4,), ("data",))
    gb = 4 * GLOBAL_BATCH_PER_CHIP
    tac = train(check, cfg, "hadronio_overlap", mesh, gb, seed)
    ref = train(check, cfg, "gspmd", mesh, gb, seed)
    diff = float(np.max(np.abs(np.subtract(tac, ref))))
    check("4-chip train hadronio_overlap vs gspmd losses",
          diff <= LOSS_ATOL,
          f"max |loss difference| {diff!r}, tolerance {LOSS_ATOL}")

    params = api.init(jax.random.PRNGKey(seed), cfg)
    reqs = make_requests(cfg, seed)
    _, results = serve(cfg, params, "hadronio", reqs, seed)
    _, gspmd = serve(cfg, params, "gspmd", reqs, seed)
    same_tokens(check, "4-shard serve hadronio vs gspmd tokens", results,
                gspmd)
    log("peak_bytes_in_use per chip "
        f"{[peak_bytes(d) for d in jax.devices()]}")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="1: serve + train on one chip; 4: the cross-chip "
                        "hadronio vs gspmd comparisons only")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX sees "
              f"{devices[0].platform!r} devices); this smoke runs only on "
              "a TPU", file=sys.stderr)
        return 1
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} TPU devices", file=sys.stderr)
        return 1

    log(f"compile cache: {enable_compile_cache()}")
    cfg = get_config(ARCH)
    log(f"{ARCH}: {cfg.num_layers} layers, d_model {cfg.d_model}, vocab "
        f"{cfg.vocab_size}, {cfg.param_dtype}; {len(devices)} x "
        f"{devices[0].device_kind}")
    t0 = time.perf_counter()
    check = Checks()
    (one_chip if args.chips == 1 else four_chips)(check, cfg, args.seed)
    log(f"phases ran in {time.perf_counter() - t0!r} s")
    if check.failed:
        print(f"chip_smoke: failed checks: {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
