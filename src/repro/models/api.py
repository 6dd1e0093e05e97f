"""Unified model API — every family exposes the same four entry points:

  specs(cfg)                                  -> ParamSpec pytree
  loss(params, batch, cfg, shard_fn)          -> (loss, aux-dict)
  prefill(params, batch, cfg, shard_fn)       -> (last-token logits, cache)
  decode_step(params, cache, batch, cfg, ...) -> (logits, new cache)

plus shape builders for the dry-run:

  input_specs(cfg, shape)        -> {name: ShapeDtypeStruct} (model inputs)
  cache_specs(cfg, batch, max_len)-> cache pytree of ShapeDtypeStruct

``batch`` is a dict: train/prefill {"tokens", "labels"?, ("frames"|"patches")?};
decode {"token": (B,), "pos": ()}. The modality frontends (whisper conv/mel,
llava vision tower) are STUBS per the brief — inputs arrive as precomputed
embeddings.
"""
from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.models import attention as att
from repro.models import hybrid as hyb
from repro.models import rwkv6 as rwkv
from repro.models import transformer as tfm
from repro.models import whisper as whi
from repro.models.common import ParamSpec, abstract_params, init_params
from repro.models.layers import (ShardFn, apply_norm, cross_entropy,
                                 embedding_specs, embed_tokens, lm_logits,
                                 no_shard, norm_specs)

PyTree = Any


# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


def specs(cfg: ModelConfig) -> PyTree:
    if cfg.family == "encdec":
        return whi.whisper_specs(cfg)
    base = {
        "embed": embedding_specs(cfg.vocab_size, cfg.d_model,
                                 cfg.tie_embeddings),
        "ln_f": norm_specs(cfg.d_model, cfg.norm_kind),
    }
    if cfg.family == "ssm":
        base["layers"] = rwkv.rwkv_stack_specs(cfg)
        base["ln_in"] = norm_specs(cfg.d_model, "layernorm")
    elif cfg.family == "hybrid":
        base["layers"] = hyb.hybrid_stack_specs(cfg)
    elif cfg.family == "moe":
        base["layers"] = tfm.stack_specs(cfg, "moe")
    else:  # dense, vlm
        base["layers"] = tfm.stack_specs(cfg, "dense")
    return base


def init(rng: jax.Array, cfg: ModelConfig) -> PyTree:
    return init_params(rng, specs(cfg), cfg.param_dtype)


def abstract(cfg: ModelConfig) -> PyTree:
    return abstract_params(specs(cfg), cfg.param_dtype)


# ---------------------------------------------------------------------------
# Embedding / trunk helpers
# ---------------------------------------------------------------------------


def _embed_inputs(params, batch, cfg: ModelConfig, dtype):
    """Token (+ prefix) embeddings and the number of prefix positions."""
    x = embed_tokens(params["embed"], batch["tokens"], dtype)
    prefix = 0
    if cfg.family == "vlm":
        patches = batch["patches"].astype(dtype)
        x = jnp.concatenate([patches, x], axis=1)
        prefix = patches.shape[1]
    return x, prefix


def _trunk(params, x, cfg: ModelConfig, *, mode, shard_fn,
           cache=None, pos=None, q_positions=None, expert_fn=None):
    """Dispatch to the family stack. Returns (x, new_cache, aux).
    ``expert_fn`` replaces the MoE expert-compute stage
    (:func:`repro.models.moe.apply_moe`) — ignored by expert-free
    families."""
    if cfg.family == "ssm":
        x = apply_norm(params["ln_in"], x, "layernorm")
        x, st = rwkv.apply_rwkv_stack(params["layers"], x, cfg, mode=mode,
                                      shard_fn=shard_fn, state=cache)
        return x, st, jnp.zeros((), jnp.float32)
    if cfg.family == "hybrid":
        return hyb.apply_hybrid_stack(params["layers"], x, cfg, mode=mode,
                                      shard_fn=shard_fn, cache=cache, pos=pos,
                                      q_positions=q_positions)
    kind = "moe" if cfg.family == "moe" else "dense"
    return tfm.apply_stack(params["layers"], x, cfg, kind=kind, mode=mode,
                           shard_fn=shard_fn, cache=cache, pos=pos,
                           q_positions=q_positions, expert_fn=expert_fn)


# ---------------------------------------------------------------------------
# Train loss
# ---------------------------------------------------------------------------


def loss(params: PyTree, batch: dict, cfg: ModelConfig,
         shard_fn: ShardFn = no_shard):
    dtype = jnp.dtype(cfg.compute_dtype)
    if cfg.family == "encdec":
        enc_out = whi.encode(params, batch["frames"].astype(dtype), cfg,
                             shard_fn)
        cross_k, cross_v = whi._cross_kv(params, enc_out, cfg)
        x = embed_tokens(params["embed"], batch["tokens"], dtype)
        x = x + params["pos_dec"].astype(dtype)[None, :x.shape[1]]
        x, _ = whi.decode_stack(params, x, cfg, mode="train",
                                cross_k=cross_k, cross_v=cross_v,
                                shard_fn=shard_fn)
        with jax.named_scope("lm_head"):
            logits = lm_logits(params["embed"], x, shard_fn)
            l = cross_entropy(logits, batch["labels"],
                              batch.get("loss_mask"))
        return l, {"xent": l}

    with jax.named_scope("embed"):
        x, prefix = _embed_inputs(params, batch, cfg, dtype)
        x = shard_fn(x, ("batch", "seq", None))
    x, _, aux = _trunk(params, x, cfg, mode="train", shard_fn=shard_fn)
    with jax.named_scope("lm_head"):
        x = apply_norm(params["ln_f"], x, cfg.norm_kind)
        if prefix:
            x = x[:, prefix:]
        logits = lm_logits(params["embed"], x, shard_fn)
        xent = cross_entropy(logits, batch["labels"],
                             batch.get("loss_mask"))
    total = xent + aux
    return total, {"xent": xent, "aux": aux}


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def prefill(params: PyTree, batch: dict, cfg: ModelConfig,
            shard_fn: ShardFn = no_shard, logits_fn=None, expert_fn=None):
    """``logits_fn`` overrides the LM head (signature of
    :func:`repro.models.layers.lm_logits`) — the serving dispatch layer
    passes a tensor-parallel head whose partial-logit reduction flows
    through the registered CommBackend wire (serving/dispatch.py).
    ``expert_fn`` likewise overrides the MoE expert-compute stage (the
    expert-parallel all-to-all path); expert-free families ignore it."""
    head = logits_fn or lm_logits
    dtype = jnp.dtype(cfg.compute_dtype)
    if cfg.family == "encdec":
        enc_out = whi.encode(params, batch["frames"].astype(dtype), cfg,
                             shard_fn)
        cross_k, cross_v = whi._cross_kv(params, enc_out, cfg)
        x = embed_tokens(params["embed"], batch["tokens"], dtype)
        x = x + params["pos_dec"].astype(dtype)[None, :x.shape[1]]
        x, cache = whi.decode_stack(params, x, cfg, mode="prefill",
                                    cross_k=cross_k, cross_v=cross_v,
                                    shard_fn=shard_fn)
        cache = {"self": cache, "cross_k": cross_k, "cross_v": cross_v}
        with jax.named_scope("lm_head"):
            logits = head(params["embed"], x[:, -1:], shard_fn)[:, 0]
        return logits, cache

    with jax.named_scope("embed"):
        x, _ = _embed_inputs(params, batch, cfg, dtype)
        x = shard_fn(x, ("batch", "seq", None))
    x, cache, _ = _trunk(params, x, cfg, mode="prefill", shard_fn=shard_fn,
                         expert_fn=expert_fn)
    with jax.named_scope("lm_head"):
        x = apply_norm(params["ln_f"], x, cfg.norm_kind)
        if "last_pos" in batch:     # per-request prompt end (serving)
            b_idx = jnp.arange(x.shape[0])
            x_last = x[b_idx, batch["last_pos"]][:, None]
        else:
            x_last = x[:, -1:]
        logits = head(params["embed"], x_last, shard_fn)[:, 0]
    return logits, cache


# ---------------------------------------------------------------------------
# Decode step
# ---------------------------------------------------------------------------


def decode_step(params: PyTree, cache: PyTree, batch: dict, cfg: ModelConfig,
                shard_fn: ShardFn = no_shard, logits_fn=None, expert_fn=None):
    """One token for the whole batch. batch: {"token": (B,), "pos": ()}.
    ``logits_fn`` and ``expert_fn`` override the LM head / MoE expert
    stage exactly as in :func:`prefill`."""
    head = logits_fn or lm_logits
    dtype = jnp.dtype(cfg.compute_dtype)
    pos = batch["pos"]
    tok = batch["token"][:, None]                        # (B,1)
    if cfg.family == "encdec":
        x = embed_tokens(params["embed"], tok, dtype)
        if jnp.ndim(pos):
            pe = jnp.take(params["pos_dec"], pos, axis=0)[:, None]  # (B,1,D)
        else:
            pe = jax.lax.dynamic_slice_in_dim(
                params["pos_dec"], pos, 1, axis=0)[None]
        x = x + pe.astype(dtype)
        x, new_self = whi.decode_stack(params, x, cfg, mode="decode",
                                       cross_k=cache["cross_k"],
                                       cross_v=cache["cross_v"],
                                       shard_fn=shard_fn,
                                       cache=cache["self"], pos=pos)
        with jax.named_scope("lm_head"):
            logits = head(params["embed"], x, shard_fn)[:, 0]
        new_cache = dict(cache, self=new_self)
        return logits, new_cache

    with jax.named_scope("embed"):
        x = embed_tokens(params["embed"], tok, dtype)
    if cfg.family == "vlm":
        pos = pos + cfg.num_patches   # cache slots 0..P-1 hold the prefix
    x, new_cache, _ = _trunk(params, x, cfg, mode="decode",
                             shard_fn=shard_fn, cache=cache, pos=pos,
                             expert_fn=expert_fn)
    with jax.named_scope("lm_head"):
        x = apply_norm(params["ln_f"], x, cfg.norm_kind)
        logits = head(params["embed"], x, shard_fn)[:, 0]
    return logits, new_cache


# ---------------------------------------------------------------------------
# Shape builders (dry-run: ShapeDtypeStruct only, no allocation)
# ---------------------------------------------------------------------------


def cache_specs(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    dt = cfg.compute_dtype
    if cfg.family == "ssm":
        return rwkv.init_state_specs(cfg, batch, dt)
    if cfg.family == "hybrid":
        return hyb.hybrid_cache_specs(cfg, batch, dt)
    if cfg.family == "encdec":
        self_len = min(max_len, whi.WHISPER_MAX_POS)
        kv = (cfg.num_layers, batch, self_len, cfg.num_kv_heads, cfg.head_dim)
        xv = (cfg.num_layers, batch, cfg.num_frames, cfg.num_kv_heads,
              cfg.head_dim)
        return {
            "self": {"k": jax.ShapeDtypeStruct(kv, jnp.dtype(dt)),
                     "v": jax.ShapeDtypeStruct(kv, jnp.dtype(dt))},
            "cross_k": jax.ShapeDtypeStruct(xv, jnp.dtype(dt)),
            "cross_v": jax.ShapeDtypeStruct(xv, jnp.dtype(dt)),
        }
    eff = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    eff += cfg.num_patches            # vlm: prefix occupies leading slots
    return att.kv_cache_specs(cfg.num_layers, batch, eff, cfg.num_kv_heads,
                              cfg.head_dim, dt)


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> PyTree:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_specs(cfg, batch, max_len))


def grow_cache(cfg: ModelConfig, cache: PyTree, max_len: int) -> PyTree:
    """Pad prefill KV caches (sized to the prompt) to ``max_len`` decode
    slots. Rolling-window and recurrent states are already fixed-size.
    Decoding past a prefill cache's length without this is an error (the
    slot write clamps) — the serving engine and tests both route here."""
    if cfg.family in ("ssm", "hybrid"):
        return cache
    window = cfg.sliding_window
    tgt = (min(max_len, window) if window else max_len) + cfg.num_patches

    def grow(x):
        # KV caches: (..., S, KV, Dh)
        if x.ndim >= 4 and x.shape[-2] == cfg.num_kv_heads \
                and x.shape[-3] < tgt:
            pad = [(0, 0)] * x.ndim
            pad[x.ndim - 3] = (0, tgt - x.shape[-3])
            return jnp.pad(x, pad)
        return x

    if cfg.family == "encdec":
        return dict(cache, self=jax.tree.map(grow, cache["self"]))
    return jax.tree.map(grow, cache)


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """Model inputs for one cell (the dry-run's ShapeDtypeStruct stand-ins)."""
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32
    dt = jnp.dtype(cfg.compute_dtype)
    if shape.kind == "train":
        out = {"tokens": jax.ShapeDtypeStruct((b, s), i32),
               "labels": jax.ShapeDtypeStruct((b, s), i32)}
        if cfg.family == "encdec":
            out["frames"] = jax.ShapeDtypeStruct((b, cfg.num_frames,
                                                  cfg.d_model), dt)
        if cfg.family == "vlm":
            out["patches"] = jax.ShapeDtypeStruct((b, cfg.num_patches,
                                                   cfg.d_model), dt)
        return out
    if shape.kind == "prefill":
        out = {"tokens": jax.ShapeDtypeStruct((b, s), i32)}
        if cfg.family == "encdec":
            out["frames"] = jax.ShapeDtypeStruct((b, cfg.num_frames,
                                                  cfg.d_model), dt)
        if cfg.family == "vlm":
            out["patches"] = jax.ShapeDtypeStruct((b, cfg.num_patches,
                                                   cfg.d_model), dt)
        return out
    # decode: one new token against a cache of length seq_len
    return {"token": jax.ShapeDtypeStruct((b,), i32),
            "pos": jax.ShapeDtypeStruct((), i32)}
