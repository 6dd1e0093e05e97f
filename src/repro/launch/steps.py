"""Step builders: the executable units the launcher / dry-run lower.

Two train-step families (DESIGN.md §4):

* ``gspmd`` — the production 2D-sharded step. Parameters follow the
  logical-axis rules (FSDP over ``data``, TP over ``model``), activations
  carry SP constraints, XLA owns every collective. This is the substrate
  every architecture (including the 110B/132B cells) runs on, and the
  baseline the roofline table is derived from.

* TAC modes (every registered backend with ``manual=True``) — the paper's
  regime: data-parallel peers exchanging gradient traffic, with the
  synchronization strategy swapped behind a fixed API (the transparency
  claim). The step runs inside a fully-manual ``shard_map`` over every
  mesh axis (one flattened DP ring — each device is one netty
  "connection"); model compute is purely local, gradient sync is the
  backend's explicit per-slice collective schedule (repro.core.backends).

This module never branches on mode names: the backend registry supplies
state layouts (``state_specs``), the optimizer application
(``apply_update``) and the step family (``manual``), so adding a mode is
one new backend module and zero launcher edits.

Serve steps (prefill / decode) always run under GSPMD — inference has no
gradient traffic, which is the paper's scope; the cache/batch sharding
rules live in launch/sharding.py.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.core import backends as backends_mod
from repro.core import tac
from repro.core.backends import UpdateContext, get_backend
from repro.models import api
from repro.models.layers import no_shard
from repro.optim import adamw
from repro.optim import flat as flat_opt
from repro.launch.sharding import (batch_sharding, cache_shardings,
                                   make_shard_fn, param_shardings)

PyTree = Any

# packed-flat optimizer helpers kept under their historical names (tests
# and notebooks import them from here)
_decay_mask_flat = flat_opt.decay_mask_flat
_decay_mask_traced = flat_opt.decay_mask_traced
_flat_adamw_update = flat_opt.flat_adamw_update
tac_scatter_size = backends_mod.scatter_group_size


class TrainState(NamedTuple):
    params: PyTree
    opt: adamw.AdamState          # tree moments (gspmd/ddp) or flat shards (zero1)
    step: jax.Array
    ef: Optional[PyTree] = None   # error-feedback (TAC compression): one
    #                               array keyed to the global ring plan, or
    #                               a per-bucket pytree (overlap modes) —
    #                               every leaf carries a leading ring dim


# ---------------------------------------------------------------------------
# Shared pieces
# ---------------------------------------------------------------------------


def _loss_fn(cfg: ModelConfig, shard_fn):
    def f(params, batch):
        l, aux = api.loss(params, batch, cfg, shard_fn)
        return l, aux
    return f


def _microbatches(batch: PyTree, n: int) -> PyTree:
    """(B, ...) -> (n, B/n, ...) for gradient accumulation."""
    def split(x):
        b = x.shape[0]
        assert b % n == 0, (b, n)
        return x.reshape(n, b // n, *x.shape[1:])
    return jax.tree.map(split, batch)


def _accumulate_grads(loss_fn, params, batch, n_micro: int):
    """Mean loss/grads over ``n_micro`` sequential microbatches."""
    if n_micro == 1:
        (l, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return l, aux, grads
    micro = _microbatches(batch, n_micro)

    def body(carry, mb):
        acc, lsum = carry
        (l, _aux), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
        acc = jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)
        return (acc, lsum + l), None

    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    (gacc, lsum), _ = jax.lax.scan(body, (zeros, jnp.zeros((), jnp.float32)),
                                   micro)
    inv = 1.0 / n_micro
    grads = jax.tree.map(lambda g: g * inv, gacc)
    return lsum * inv, {}, grads


# ---------------------------------------------------------------------------
# GSPMD production step (2D sharded: FSDP + TP + SP)
# ---------------------------------------------------------------------------


def init_train_state(rng: jax.Array, run: RunConfig) -> TrainState:
    params = api.init(rng, run.model)
    return TrainState(params=params, opt=adamw.init(params),
                      step=jnp.zeros((), jnp.int32))


def abstract_train_state(run: RunConfig) -> TrainState:
    """ShapeDtypeStruct state for the dry-run (no allocation)."""
    params = api.abstract(run.model)
    specs = get_backend("gspmd").state_specs(run, 1)
    return TrainState(params=params, opt=specs.opt,
                      step=jax.ShapeDtypeStruct((), jnp.int32))


def train_state_shardings(mesh, run: RunConfig, *, fsdp: bool = True):
    """NamedSharding tree matching :func:`abstract_train_state`."""
    specs = api.specs(run.model)
    ps = param_shardings(mesh, specs, fsdp=fsdp)
    scalar = NamedSharding(mesh, P())
    return TrainState(params=ps,
                      opt=adamw.AdamState(mu=ps, nu=ps, count=scalar),
                      step=scalar)


def make_train_step_gspmd(run: RunConfig, mesh):
    """Returns (step_fn, state_shardings, batch_shardings_fn).

    ``step_fn(state, batch) -> (state, metrics)`` — jit with the returned
    shardings; XLA/GSPMD owns all collectives (the "kernel network stack"
    baseline at 2D scale).
    """
    cfg = run.model
    shard_fn = make_shard_fn(mesh)
    loss_fn = _loss_fn(cfg, shard_fn)

    def step_fn(state: TrainState, batch: dict):
        l, aux, grads = _accumulate_grads(loss_fn, state.params, batch,
                                          run.microbatches)
        new_params, new_opt, metrics = adamw.update(
            grads, state.opt, state.params, run)
        metrics = dict(metrics, loss=l)
        return TrainState(new_params, new_opt, state.step + 1), metrics

    return step_fn, train_state_shardings(mesh, run), batch_sharding


# ---------------------------------------------------------------------------
# TAC step (paper's technique): fully-manual DP ring over every mesh axis
# ---------------------------------------------------------------------------


def abstract_tac_state(run: RunConfig, n_shards: int,
                       pod_size: int = 1) -> TrainState:
    """State for the TAC step: the backend owns the optimizer / error
    feedback layout (``CommBackend.state_specs``). ``n_shards`` is the
    TOTAL ring size; ``pod_size`` > 1 makes zero1 scatter groups in-pod
    (see backends.scatter_group_size)."""
    params = api.abstract(run.model)
    specs = get_backend(run.comm.mode).state_specs(run, n_shards, pod_size)
    return TrainState(params=params, opt=specs.opt,
                      step=jax.ShapeDtypeStruct((), jnp.int32), ef=specs.ef)


def init_tac_state(rng: jax.Array, run: RunConfig, n_shards: int,
                   pod_size: int = 1) -> TrainState:
    sds = abstract_tac_state(run, n_shards, pod_size)
    params = api.init(rng, run.model)
    zeros = lambda s: jnp.zeros(s.shape, s.dtype)
    return TrainState(params=params,
                      opt=adamw.AdamState(jax.tree.map(zeros, sds.opt.mu),
                                          jax.tree.map(zeros, sds.opt.nu),
                                          jnp.zeros((), jnp.int32)),
                      step=jnp.zeros((), jnp.int32),
                      ef=None if sds.ef is None
                      else jax.tree.map(zeros, sds.ef))


def make_train_step_tac(run: RunConfig, mesh):
    """Returns (step_fn, state_shardings, batch_shardings_fn).

    Fully-manual shard_map over every mesh axis: one flattened DP ring of
    ``n_shards`` peers ("connections"). Params replicated; batch sharded on
    dim 0; gradient sync is the registered backend's collective schedule.
    zero1 backends additionally shard the optimizer moments as flat slices.
    """
    cfg = run.model
    comm = run.comm
    backend = get_backend(comm.mode)
    backend.validate(comm)
    axes = tuple(mesh.axis_names)
    n_shards = int(np.prod([mesh.shape[a] for a in axes]))
    pod_size = mesh.shape.get("pod", 1)
    pod_axis = "pod" if pod_size > 1 else None
    data_axes = tuple(a for a in axes if a != "pod") if pod_axis else axes
    eff_shards = tac_scatter_size(n_shards, pod_size, comm)
    uctx = UpdateContext(axes=axes, n_shards=n_shards,
                         eff_shards=eff_shards)
    loss_fn = _loss_fn(cfg, no_shard)   # manual region: compute is local

    def body(state: TrainState, batch: dict):
        # local loss scaled so psum'd grads are the global-mean grads
        def scaled_loss(p, b):
            l, aux = loss_fn(p, b)
            return l / n_shards, aux

        l, _aux, grads = _accumulate_grads(scaled_loss, state.params, batch,
                                           run.microbatches)

        # local residual: strip the leading ring dim from every EF leaf
        # (one array for global-ring keying, a pytree for per-bucket)
        ef = None if state.ef is None \
            else jax.tree.map(lambda e: e[0], state.ef)
        res = tac.sync_grads(grads, comm, data_axis=data_axes,
                             pod_axis=pod_axis, ef=ef)
        new_ef = None if res.ef is None \
            else jax.tree.map(lambda e: e[None], res.ef)

        # loss epilogue AFTER the sync emission: overlap-style backends'
        # early-slice collectives precede it in the program
        loss = jax.lax.psum(l, axes)

        new_params, new_opt, metrics = backend.apply_update(
            state.params, state.opt, res, run, uctx)
        metrics = dict(metrics, loss=loss)
        return TrainState(new_params, new_opt, state.step + 1,
                          new_ef), metrics

    # ---- shard_map plumbing -------------------------------------------
    state_sds = abstract_tac_state(run, n_shards, pod_size)
    replicated = P()
    batch_spec = P(axes)          # dim 0 over the flattened ring

    if backend.zero1:
        # flat moment shards carry the explicit leading ring dim
        opt_specs = adamw.AdamState(mu=batch_spec, nu=batch_spec,
                                    count=replicated)
    else:
        opt_specs = jax.tree.map(lambda _: replicated, state_sds.opt)
    state_specs = TrainState(
        params=jax.tree.map(lambda _: replicated, state_sds.params),
        opt=opt_specs,
        step=replicated,
        ef=None if state_sds.ef is None
        else jax.tree.map(lambda _: batch_spec, state_sds.ef))
    batch_specs_fn = lambda b: jax.tree.map(lambda _: batch_spec, b)

    def step_fn(state: TrainState, batch: dict):
        bspecs = batch_specs_fn(batch)
        # metrics take a replicated PREFIX spec: whatever dict the
        # backend's apply_update returns works without launcher edits
        out = jax.shard_map(
            body, mesh=mesh,
            in_specs=(state_specs, bspecs),
            out_specs=(state_specs, replicated),
            check_vma=False)(state, batch)
        return out

    def shardings(b=None):
        ns = lambda spec: NamedSharding(mesh, spec)
        ss = jax.tree.map(ns, state_specs)
        return ss

    def batch_shardings(mesh_, batch_tree):
        return jax.tree.map(lambda _: NamedSharding(mesh_, batch_spec),
                            batch_tree)

    return step_fn, shardings(), batch_shardings


def make_train_step(run: RunConfig, mesh):
    """Dispatch on the registered backend's step family (the transparent
    boundary: callers never change, and no mode names appear here)."""
    backend = get_backend(run.comm.mode)
    backend.validate(run.comm)
    if backend.manual:
        return make_train_step_tac(run, mesh)
    return make_train_step_gspmd(run, mesh)


# ---------------------------------------------------------------------------
# Serve steps (GSPMD)
# ---------------------------------------------------------------------------


def make_prefill_step(run: RunConfig, mesh):
    cfg = run.model
    shard_fn = make_shard_fn(mesh)

    def prefill_fn(params, batch):
        return api.prefill(params, batch, cfg, shard_fn)

    return prefill_fn


def make_decode_step(run: RunConfig, mesh):
    """``serve_step``: one new token against a KV cache of seq_len."""
    cfg = run.model
    shard_fn = make_shard_fn(mesh)

    def decode_fn(params, cache, batch):
        logits, new_cache = api.decode_step(params, cache, batch, cfg,
                                            shard_fn)
        return logits, new_cache
    return decode_fn


def serve_specs(run: RunConfig, shape: ShapeConfig, mesh):
    """(abstract params, abstract cache, inputs, shardings) for decode
    cells. The cache length is the cell's seq_len (sliding-window archs
    cap at the window — that is the sub-quadratic property)."""
    cfg = run.model
    params = api.abstract(cfg)
    cache = api.cache_specs(cfg, shape.global_batch, shape.seq_len)
    inputs = api.input_specs(cfg, shape)
    pshard = param_shardings(mesh, api.specs(cfg), fsdp=True)
    cshard = cache_shardings(mesh, cache)
    ishard = batch_sharding(mesh, inputs)
    return params, cache, inputs, pshard, cshard, ishard
