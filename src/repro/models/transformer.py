"""Decoder-only transformer stack (dense / MoE / hybrid share this).

Layers are stacked along a leading ``layers`` dim and iterated with
``lax.scan`` (compact HLO; FSDP all-gathers land inside the loop body —
verified in DESIGN.md §4). Training remats each block.

``mode``:
  train   — full sequence, causal (optionally windowed), no cache.
  prefill — full sequence, returns per-layer KV cache.
  decode  — one token per call against the cache.
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as att
from repro.models import moe as moe_mod
from repro.models.common import ParamSpec, stacked
from repro.models.layers import (ShardFn, apply_mlp, apply_norm, mlp_specs,
                                 no_shard, norm_specs)


def depth_scale(cfg: ModelConfig) -> float:
    return 1.0 / (2.0 * max(cfg.num_layers, 1)) ** 0.5


# ---------------------------------------------------------------------------
# One transformer block (pre-norm attention + pre-norm MLP/MoE)
# ---------------------------------------------------------------------------


def block_specs(cfg: ModelConfig, kind: str = "dense") -> dict:
    s = {
        "ln1": norm_specs(cfg.d_model, cfg.norm_kind),
        "ln2": norm_specs(cfg.d_model, cfg.norm_kind),
        "attn": att.attention_specs(cfg.d_model, cfg.num_heads,
                                    cfg.num_kv_heads, cfg.head_dim,
                                    cfg.qkv_bias, depth_scale(cfg)),
    }
    if kind == "moe":
        s["moe"] = moe_mod.moe_specs(cfg)
    else:
        s["mlp"] = mlp_specs(cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                             depth_scale(cfg))
    return s


def apply_block(p: dict, x: jax.Array, cfg: ModelConfig, *, kind: str,
                mode: str, shard_fn: ShardFn, window: int,
                cache_k: Optional[jax.Array] = None,
                cache_v: Optional[jax.Array] = None,
                pos: Optional[jax.Array] = None,
                q_positions: Optional[jax.Array] = None,
                expert_fn=None):
    """Returns (x, new_cache_k, new_cache_v, aux_loss). The two
    sub-blocks, norm to residual, run under the ``attn`` and ``mlp``
    scopes (op_name metadata for the profiler's device ops)."""
    b, s, _ = x.shape
    with jax.named_scope("attn"):
        h = apply_norm(p["ln1"], x, cfg.norm_kind)
        h = shard_fn(h, ("batch", "seq_gather", None))   # SP: one AG
        if q_positions is None:
            if mode != "decode":
                q_positions = jnp.arange(s)
            else:
                # scalar pos -> (s,); per-request (B,) pos -> (B, s)
                base = pos[..., None] if jnp.ndim(pos) else pos
                q_positions = base + jnp.zeros((s,), jnp.int32)
        q, k, v = att.project_qkv(p["attn"], h, h, q_positions,
                                  q_positions, cfg.rope_theta, shard_fn)
        new_k = new_v = None
        if mode == "decode":
            out, new_k, new_v = att.decode_attend(
                q, cache_k, cache_v, k, v, pos,
                num_heads=cfg.num_heads, window=window, shard_fn=shard_fn)
        else:
            kx = att.expand_kv(k, cfg.num_heads)
            vx = att.expand_kv(v, cfg.num_heads)
            out = att.attend_chunked(q, kx, vx, causal=True, window=window)
            if mode == "prefill":
                if window > 0:   # rolling layout for windowed decode caches
                    new_k = att.to_rolling(k, window)
                    new_v = att.to_rolling(v, window)
                else:
                    new_k, new_v = k, v
        x = x + att.out_project(p["attn"], out, shard_fn)
        x = shard_fn(x, ("batch", "seq", None))

    with jax.named_scope("mlp"):
        h = apply_norm(p["ln2"], x, cfg.norm_kind)
        h = shard_fn(h, ("batch", "seq_gather", None))   # SP: one AG
        aux = jnp.zeros((), jnp.float32)
        if kind == "moe":
            y, aux = moe_mod.apply_moe(p["moe"], h, cfg, shard_fn,
                                       expert_fn=expert_fn)
        else:
            y = apply_mlp(p["mlp"], h, cfg.mlp_kind, shard_fn)
        x = x + y
        x = shard_fn(x, ("batch", "seq", None))
    return x, new_k, new_v, aux


# ---------------------------------------------------------------------------
# Stack
# ---------------------------------------------------------------------------


def stack_specs(cfg: ModelConfig, kind: str = "dense") -> dict:
    one = block_specs(cfg, kind)
    return jax.tree.map(lambda s: stacked(s, cfg.num_layers), one,
                        is_leaf=lambda x: isinstance(x, ParamSpec))


def apply_stack(params: dict, x: jax.Array, cfg: ModelConfig, *, kind: str,
                mode: str, shard_fn: ShardFn = no_shard,
                cache: Optional[dict] = None,
                pos: Optional[jax.Array] = None,
                q_positions: Optional[jax.Array] = None,
                expert_fn=None):
    """Scan the block over stacked params, under the ``layers`` scope
    (the loop's own work: slicing the stacked params and caches, and
    stacking each layer's outputs and gradients).

    Returns (x, new_cache, aux_sum). ``cache`` is {"k","v"}: (L,B,S,KV,Dh)
    for prefill/decode; None in train mode.
    """
    window = cfg.sliding_window

    def body(carry, xs):
        x = carry
        if mode == "decode":
            p, ck, cv = xs
            x, nk, nv, aux = apply_block(
                p, x, cfg, kind=kind, mode=mode, shard_fn=shard_fn,
                window=window, cache_k=ck, cache_v=cv, pos=pos,
                q_positions=q_positions, expert_fn=expert_fn)
            return x, (nk, nv, aux)
        p = xs
        x, nk, nv, aux = apply_block(
            p, x, cfg, kind=kind, mode=mode, shard_fn=shard_fn,
            window=window, pos=pos, q_positions=q_positions,
            expert_fn=expert_fn)
        if mode == "prefill":
            return x, (nk, nv, aux)
        return x, aux

    from repro.models.unroll import scan_or_unroll
    L = cfg.num_layers
    with jax.named_scope("layers"):
        if mode == "train":
            x, aux = scan_or_unroll(jax.checkpoint(body), x, params, L)
            return x, None, jnp.sum(aux)
        if mode == "prefill":
            x, (ks, vs, aux) = scan_or_unroll(body, x, params, L)
            return x, {"k": ks, "v": vs}, jnp.sum(aux)
        x, (ks, vs, aux) = scan_or_unroll(
            body, x, (params, cache["k"], cache["v"]), L)
        return x, {"k": ks, "v": vs}, jnp.sum(aux)
