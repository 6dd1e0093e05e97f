"""Fault-tolerant training driver.

Structure (DESIGN.md §4, fault tolerance):

* ``Trainer`` — owns mesh, step function, checkpoint store, data source.
  One ``run()`` call trains from the latest checkpoint (or step 0) to
  ``total_steps``; data is addressed by step index (stateless pipeline),
  so resume needs nothing beyond the restored step counter.
* ``train_with_restarts`` — the supervision loop: catches step-time
  failures (including injected faults and watchdog timeouts), restores
  from the last good checkpoint and continues, up to ``max_restarts``.
  On a real cluster this loop runs per-host under the cluster manager;
  the logic is identical.
* Watchdog — a monitor thread that aborts a step stuck longer than
  ``watchdog_secs`` (straggler/hang mitigation: the sync train step means
  a dead peer manifests as a hang; the watchdog turns it into a restart).
* Elastic restarts — ``Trainer`` takes the mesh as a constructor arg;
  restoring a checkpoint saved on a different mesh works because
  checkpoints are mesh-agnostic (see checkpoint/store.py). See
  launch/elastic.py for the device-count-change path.

Fault injection for tests/demos: set ``REPRO_FAULT_AT_STEP=<k>`` to make
step k raise once (the file flag keeps it once-per-process-tree).

CLI::

  python -m repro.launch.train --arch qwen2-0.5b-reduced --steps 50 \
      --global-batch 8 --seq-len 128 --mode hadronio --ckpt /tmp/run1
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import threading
import time
from typing import Callable, Optional

import jax
import numpy as np

from repro.core.backends import available_modes, get_backend

from repro.configs.base import CommConfig, RunConfig, ShapeConfig
from repro.configs.registry import get_config
from repro.checkpoint import CheckpointStore
from repro.data import DataConfig, batch_at, make_source
from repro.launch import steps as steps_mod
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import api


class WatchdogTimeout(RuntimeError):
    pass


class Watchdog:
    """Aborts the process out of a stuck step: arm() before blocking work,
    disarm() after. CPU-friendly (a single timer thread)."""

    def __init__(self, timeout_secs: float, on_timeout: Callable[[], None]):
        self.timeout = timeout_secs
        self.on_timeout = on_timeout
        self._timer: Optional[threading.Timer] = None

    def arm(self):
        self.disarm()
        self._timer = threading.Timer(self.timeout, self.on_timeout)
        self._timer.daemon = True
        self._timer.start()

    def disarm(self):
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None


def _maybe_inject_fault(step: int):
    at = os.environ.get("REPRO_FAULT_AT_STEP")
    if at is None:
        return
    flag = os.environ.get("REPRO_FAULT_FLAG", "/tmp/repro_fault_fired")
    if int(at) == step and not os.path.exists(flag):
        with open(flag, "w") as f:
            f.write(str(step))
        raise RuntimeError(f"injected fault at step {step}")


class Trainer:
    def __init__(self, run: RunConfig, mesh, *, log_every: int = 10,
                 watchdog_secs: float = 0.0,
                 log_fn: Callable[[str], None] = print):
        self.run = run
        self.mesh = mesh
        self.log_every = log_every
        self.log_fn = log_fn
        self.n_shards = int(np.prod(list(mesh.shape.values())))
        self.store = (CheckpointStore(run.checkpoint_dir,
                                      keep=run.keep_checkpoints)
                      if run.checkpoint_dir else None)
        self.source = make_source(run)
        self.dc = DataConfig(seq_len=run.shape.seq_len,
                             global_batch=run.shape.global_batch)
        self.watchdog = None
        if watchdog_secs > 0:
            def _abort():
                # deliberately crash the step: the restart loop recovers
                self.log_fn(f"[watchdog] step exceeded {watchdog_secs}s")
                os._exit(42)
            self.watchdog = Watchdog(watchdog_secs, _abort)

        with jax.set_mesh(mesh):
            step_fn, self.state_sh, batch_sh_fn = \
                steps_mod.make_train_step(run, mesh)
            self._batch_sh_fn = batch_sh_fn
            self._jitted = jax.jit(
                step_fn,
                donate_argnums=(0,))

    # -- state ----------------------------------------------------------

    def init_state(self, seed: Optional[int] = None):
        rng = jax.random.PRNGKey(self.run.seed if seed is None else seed)
        pod = self.mesh.shape.get("pod", 1)
        if get_backend(self.run.comm.mode).manual:
            state = steps_mod.init_tac_state(rng, self.run, self.n_shards,
                                             pod)
        else:
            state = steps_mod.init_train_state(rng, self.run)
        return jax.device_put(state, self.state_sh)

    def abstract_state(self):
        if not get_backend(self.run.comm.mode).manual:
            return steps_mod.abstract_train_state(self.run)
        return steps_mod.abstract_tac_state(self.run, self.n_shards,
                                            self.mesh.shape.get("pod", 1))

    def restore_or_init(self):
        if self.store is not None:
            latest = self.store.latest_step()
            if latest is not None:
                from repro.launch.elastic import make_on_mismatch
                self.log_fn(f"[trainer] restoring step {latest}")
                state = self.store.restore(
                    latest, self.abstract_state(), self.state_sh,
                    on_mismatch=make_on_mismatch(self.run))
                return state, latest
        return self.init_state(), 0

    # -- loop ------------------------------------------------------------

    def run_loop(self) -> dict:
        run = self.run
        state, start = self.restore_or_init()
        metrics = {}
        losses = []
        with jax.set_mesh(self.mesh):
            # double-buffered host data: build batch k+1 while step k runs
            next_batch = batch_at(self.source, self.dc, start)
            for step in range(start, run.total_steps):
                _maybe_inject_fault(step)
                batch = jax.device_put(
                    next_batch, self._batch_sh_fn(self.mesh, next_batch))
                if self.watchdog:
                    self.watchdog.arm()
                state, metrics = self._jitted(state, batch)
                if step + 1 < run.total_steps:
                    next_batch = batch_at(self.source, self.dc, step + 1)
                loss = float(metrics["loss"])   # also blocks for watchdog
                if self.watchdog:
                    self.watchdog.disarm()
                losses.append(loss)
                if step % self.log_every == 0 or step == run.total_steps - 1:
                    self.log_fn(
                        f"[trainer] step {step} loss {loss:.4f} "
                        f"gnorm {float(metrics['grad_norm']):.3f} "
                        f"lr {float(metrics['lr']):.2e}")
                if self.store is not None and (
                        (step + 1) % run.checkpoint_every == 0
                        or step == run.total_steps - 1):
                    save = (self.store.save_async if run.async_checkpoint
                            else self.store.save)
                    save(step + 1, state,
                         extra={"loss": loss, "arch": run.model.name})
            if self.store is not None:
                self.store.wait()
        return {"final_loss": losses[-1] if losses else None,
                "losses": losses, "state": state}


def train_with_restarts(make_trainer: Callable[[], Trainer],
                        max_restarts: Optional[int] = None,
                        log_fn: Callable[[str], None] = print) -> dict:
    """Supervision loop: restart from the last checkpoint on failure."""
    trainer = make_trainer()
    limit = (trainer.run.max_restarts if max_restarts is None
             else max_restarts)
    attempts = 0
    while True:
        try:
            return trainer.run_loop()
        except Exception as e:         # noqa: BLE001 — supervision boundary
            attempts += 1
            if attempts > limit:
                raise
            log_fn(f"[supervisor] step failed ({type(e).__name__}: {e}); "
                   f"restart {attempts}/{limit}")
            trainer = make_trainer()   # fresh mesh/state; restores ckpt


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def build_run(args) -> RunConfig:
    cfg = get_config(args.arch)
    shape = ShapeConfig(name="cli", kind="train",
                        seq_len=args.seq_len, global_batch=args.global_batch)
    comm = CommConfig(mode=args.mode, slice_bytes=args.slice_bytes,
                      hierarchical=not args.flat_collectives,
                      compress=args.compress, pack=args.pack,
                      aggregate=args.aggregate, flush=args.flush)
    return RunConfig(model=cfg, shape=shape, comm=comm,
                     lr=args.lr, total_steps=args.steps,
                     warmup_steps=max(args.steps // 10, 1),
                     microbatches=args.microbatches,
                     checkpoint_dir=args.ckpt,
                     checkpoint_every=args.ckpt_every,
                     data_path=args.data, seed=args.seed)


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", required=True,
                   help="arch id; append -reduced for the smoke variant")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--mode", default="hadronio",
                   choices=list(available_modes()))
    p.add_argument("--compress", default="none",
                   choices=list(CommConfig.COMPRESS_CODECS))
    p.add_argument("--pack", default="jnp",
                   choices=list(CommConfig.PACK_IMPLS),
                   help="pack/cast/EF copy-path impl (pallas = fused "
                        "ring_pack kernel)")
    p.add_argument("--aggregate", default="slice",
                   choices=list(CommConfig.AGGREGATES),
                   help="wire-flush granularity: 'slice' = one collective "
                        "per ring slice/bucket; 'channel' = coalesce each "
                        "channel's slices into one flush (paper §III-C "
                        "gathering write; bit-identical numerics)")
    p.add_argument("--flush", default="step",
                   choices=list(CommConfig.FLUSHES),
                   help="channel schedule: 'step' = round-robin groups "
                        "flushed at one end-of-exchange loop; 'ready' = "
                        "flush-when-ready (contiguous production-order "
                        "groups, each emitted the moment its last bucket "
                        "is staged — recovers overlap under "
                        "--aggregate channel; bit-identical numerics)")
    p.add_argument("--slice-bytes", type=int, default=4 * 1024 * 1024)
    p.add_argument("--flat-collectives", action="store_true")
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--lr", type=float, default=3e-4)
    p.add_argument("--ckpt", default="")
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--data", default="", help="binary shard dir (else synthetic)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--mesh", default="",
                   help="'4x2' style; default: all devices on one data axis")
    p.add_argument("--watchdog-secs", type=float, default=0.0)
    p.add_argument("--max-restarts", type=int, default=None)
    args = p.parse_args()
    enable_compile_cache()

    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model")[: len(dims)] if len(dims) <= 2 else \
            ("pod", "data", "model")
    else:
        dims = (len(jax.devices()),)
        axes = ("data",)
    run = build_run(args)
    mesh = make_mesh(dims, axes)

    out = train_with_restarts(
        lambda: Trainer(run, mesh, watchdog_secs=args.watchdog_secs),
        max_restarts=args.max_restarts)
    print(f"final loss: {out['final_loss']:.4f}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
