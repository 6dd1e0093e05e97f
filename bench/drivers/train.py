"""Training cells: ``launch/train.py``'s ``Trainer`` jitted step on a
``("data",)`` mesh of the cell's chips, fed seeded token rows.

Set-up builds one trainer, hands it the benchmark's weights and drives it
through its first ``checked_steps`` steps with the window's own call and
feed. The window then continues the same state. After the window the
plain reference repeats the checked steps from the same weights on the same
rows, and three numbers are compared:

* ``loss_gap`` — the largest |program loss - reference loss| over the
  checked steps, in nats;
* ``grad_norm_gap`` — the first step's clipped gradient as the optimizer
  got it (its first moment over 1 - beta1), leaf by leaf: the worst
  |norm(program) - norm(reference)| over max(norm(reference leaf),
  norm(median leaf));
* ``update_norm_gap`` — the parameters' change after the checked steps,
  measured the same way, over the leaves whose reference gradient is at
  least a thousandth of the median leaf's (a leaf with no gradient, such
  as a key bias under softmax, moves by round-off alone).
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType

from bench import flops, harness, model, reference, traffic

MIN_LEAF_GRAD = 1e-3          # share of the median leaf's gradient norm


class Session:
    """One trainer on the cell's mesh, with its feed."""

    def __init__(self, mc, cfg: dict, mix: dict, devs: list):
        from repro.configs.base import CommConfig, RunConfig, ShapeConfig
        from repro.launch.train import Trainer
        opt = mix["optimizer"]
        self.rows = mix["rows_per_chip"] * len(devs)
        self.cfg, self.mix = cfg, mix
        run = RunConfig(
            model=mc, shape=ShapeConfig("bench", "train", mix["seq_len"],
                                        self.rows),
            comm=CommConfig(**mix["comm"]), lr=opt["lr"],
            weight_decay=opt["weight_decay"], beta1=opt["beta1"],
            beta2=opt["beta2"], eps=opt["eps"], grad_clip=opt["grad_clip"],
            warmup_steps=opt["warmup_steps"],
            total_steps=opt["total_steps"])
        self.mesh = jax.make_mesh((len(devs),), ("data",), devices=devs,
                                  axis_types=(AxisType.Auto,))
        self.trainer = Trainer(run, self.mesh, log_every=1 << 30)

    def initial_state(self, seed: int):
        """The trainer's state around the benchmark's weights."""
        from repro.launch.steps import TrainState
        from repro.optim.adamw import AdamState
        abstract = self.trainer.abstract_state()
        params = model.init_weights(self.cfg, seed)
        want = jax.tree.map(lambda s: (s.shape, s.dtype), abstract.params)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), params)
        if want != got:
            raise ValueError("the benchmark's weight tree differs from the "
                             f"program's: {got} vs {want}")
        zeros = lambda t: None if t is None else jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), t)
        state = TrainState(
            params=params,
            opt=AdamState(zeros(abstract.opt.mu), zeros(abstract.opt.nu),
                          jnp.zeros((), jnp.int32)),
            step=jnp.zeros((), jnp.int32), ef=zeros(abstract.ef))
        return jax.device_put(state, self.trainer.state_sh)

    def batch(self, seed: int, step: int):
        b = traffic.train_batch(self.mix, self.cfg["vocab_size"], seed,
                                step, self.rows)
        return jax.device_put(b, self.trainer._batch_sh_fn(self.mesh, b))

    def step(self, state, batch):
        with jax.set_mesh(self.mesh):
            return self.trainer._jitted(state, batch)


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(lambda a: jnp.linalg.norm(a.astype(jnp.float32)),
                        tree)


@jax.jit
def _delta_norms(new, old):
    return jax.tree.map(
        lambda a, b: jnp.linalg.norm(a.astype(jnp.float32)
                                     - b.astype(jnp.float32)), new, old)


def _named(tree) -> dict:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {jax.tree_util.keystr(p): float(v) for p, v in flat}


def first_steps(sess: Session, seed: int, state) -> tuple:
    """The checked steps through the window's call and feed: (state after
    them, program readings). Step 1 compiles."""
    n = sess.mix["checked_steps"]
    b1 = sess.mix["optimizer"]["beta1"]
    losses, grad = [], None
    for k in range(1, n + 1):
        state, m = sess.step(state, sess.batch(seed, k))
        losses.append(float(m["loss"]))
        if k == 1:
            grad = {p: v / (1.0 - b1)
                    for p, v in _named(_leaf_norms(state.opt.mu)).items()}
    delta = _named(_delta_norms(state.params,
                                model.init_weights(sess.cfg, seed)))
    return state, {"losses": losses, "grad": grad, "delta": delta}


def reference_readings(cfg: dict, mix: dict, seed: int, rows: int,
                       prec: str = "f32") -> dict:
    """The plain reference through the checked steps, from the same
    weights on the same rows: losses, the first step's clipped gradient
    norms, the change's norms, and the first unclipped gradient norms."""
    opt = mix["optimizer"]
    params = model.init_weights(cfg, seed)
    decay = jax.tree_util.tree_map_with_path(
        lambda p, a: a.ndim - jax.tree_util.keystr(p).startswith(
            "['layers']") >= 2, params)
    mu = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    nu = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
    losses, grad, raw = [], None, None
    for k in range(1, mix["checked_steps"] + 1):
        b = traffic.train_batch(mix, cfg["vocab_size"], seed, k, rows)
        l, g = reference.loss_and_grad(params, b, cfg, prec)
        losses.append(float(l))
        if k == 1:
            raw = _named(_leaf_norms(g))
        params, mu, nu, scale = reference.adamw_step(
            params, g, mu, nu, k, opt, decay)
        del g
        if k == 1:
            grad = {p: v * scale for p, v in raw.items()}
    delta = _named(_delta_norms(params, model.init_weights(cfg, seed)))
    return {"losses": losses, "grad": grad, "delta": delta, "raw": raw}


def _worst_leaf(prog: dict, ref: dict, leaves) -> float:
    median = float(np.median([ref[k] for k in leaves]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], median, 1e-30)
               for k in leaves)


def compare(prog: dict, ref: dict) -> dict:
    raw_median = float(np.median(list(ref["raw"].values())))
    moving = [k for k, v in ref["raw"].items()
              if v >= MIN_LEAF_GRAD * raw_median]
    return {
        "loss_gap": max(abs(a - b) for a, b in zip(prog["losses"],
                                                   ref["losses"])),
        "grad_norm_gap": _worst_leaf(prog["grad"], ref["grad"],
                                     list(ref["grad"])),
        "update_norm_gap": _worst_leaf(prog["delta"], ref["delta"], moving),
    }


def run(ctx) -> dict:
    sess = Session(ctx.mc, ctx.cfg, ctx.mix, ctx.devs)
    if ctx.fault:
        ctx.fault(sess)
    state = sess.initial_state(ctx.seed)
    state, prog = first_steps(sess, ctx.seed, state)
    step = ctx.mix["checked_steps"] + 1
    nxt = sess.batch(ctx.seed, step)
    setup_s = time.perf_counter() - ctx.t_start

    tracer = harness.Tracer(ctx.trace)
    times, losses = [], []
    with tracer.window():
        t0 = last = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.train_step"):
                state, m = sess.step(state, nxt)
                step += 1
                nxt = sess.batch(ctx.seed, step)
                losses.append(float(m["loss"]))
            now = time.perf_counter()
            times.append(now - last)
            last = now
            if now - t0 >= ctx.window_seconds():
                break
    window_s = last - t0
    device = harness.device_info(ctx.devs)
    del state, nxt, m, sess
    gc.collect()
    jax.clear_caches()            # unload the step before the reference

    ref = reference_readings(ctx.cfg, ctx.mix, ctx.seed,
                             ctx.mix["rows_per_chip"] * len(ctx.devs))
    readings = compare(prog, ref)
    tokens = len(times) * ctx.mix["rows_per_chip"] * len(ctx.devs) \
        * ctx.mix["seq_len"]
    record = {
        "kind": "train", "window_s": window_s, "steps": len(times),
        "tokens_per_s": tokens / window_s, "chips": len(ctx.devs),
        "flops_per_token": flops.train_flops_per_token(
            ctx.cfg, ctx.mix["seq_len"]),
        "peaks": ctx.peaks,
        "trace": tracer.reduce() if ctx.trace else None,
    }
    failed = sum(not np.isfinite(x) for x in losses)
    return {"record": record, "readings": readings, "device": device,
            "attempted": len(times), "failed": int(failed),
            "end_to_end": {
                "train_tokens_per_s": record["tokens_per_s"],
                "train_step_p95_ms": harness.percentile(times, 95) * 1e3,
                "setup_s": setup_s}}
