"""Attention: GQA projections + chunked online-softmax attention.

Four execution regimes:

* ``attend_chunked`` — train/prefill. Outer python loop over query chunks
  (static per-chunk KV prefix => causal FLOPs ~= S^2/2, not S^2), inner
  ``lax.scan`` over KV chunks with online softmax (flash-style; bounded
  VMEM/HBM working set). Sliding windows slice a static band per q-chunk.
* ``attend_direct`` — short sequences (one-chunk prefill, cross-attention).
* ``decode_attend`` — one token against the KV cache. Each kv head's
  query group is contracted against the cache as stored, (B, S, KV, Dh);
  the cache is never expanded to the query heads.
* ``kernels/flash_attention.py`` — the Pallas TPU production kernel; this
  module is its jnp oracle and the CPU/dry-run path.

KV caches: full-attention caches are (B, S_max, KV, Dh) written at ``pos``;
windowed caches are rolling (slot = pos % window).
"""
from __future__ import annotations

import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.common import ParamSpec
from repro.models.layers import ShardFn, no_shard, rope

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Projections
# ---------------------------------------------------------------------------


def attention_specs(d: int, num_heads: int, num_kv: int, head_dim: int,
                    bias: bool, depth_scale: float) -> dict:
    s: dict = {
        "wq": ParamSpec((d, num_heads, head_dim), ("embed", "heads", None)),
        "wk": ParamSpec((d, num_kv, head_dim), ("embed", "kv_heads", None)),
        "wv": ParamSpec((d, num_kv, head_dim), ("embed", "kv_heads", None)),
        "wo": ParamSpec((num_heads, head_dim, d), ("heads", None, "embed"),
                        scale=depth_scale),
    }
    if bias:
        s["bq"] = ParamSpec((num_heads, head_dim), ("heads", None), init="zeros")
        s["bk"] = ParamSpec((num_kv, head_dim), ("kv_heads", None), init="zeros")
        s["bv"] = ParamSpec((num_kv, head_dim), ("kv_heads", None), init="zeros")
    return s


def project_qkv(p: dict, xq: jax.Array, xkv: jax.Array,
                q_positions: jax.Array, kv_positions: jax.Array,
                rope_theta: float, shard_fn: ShardFn = no_shard):
    """Returns q (B,Sq,H,Dh), k/v (B,Skv,KV,Dh); RoPE applied to q and k."""
    dt = xq.dtype
    q = jnp.einsum("bsd,dhk->bshk", xq, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", xkv, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", xkv, p["wv"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    q = rope(q, q_positions, rope_theta)
    k = rope(k, kv_positions, rope_theta)
    q = shard_fn(q, ("batch", None, "heads", None))
    k = shard_fn(k, ("batch", None, "kv_heads", None))
    v = shard_fn(v, ("batch", None, "kv_heads", None))
    return q, k, v


def out_project(p: dict, attn: jax.Array, shard_fn: ShardFn = no_shard):
    out = jnp.einsum("bshk,hkd->bsd", attn, p["wo"].astype(attn.dtype))
    return shard_fn(out, ("batch", None, "embed"))


def expand_kv(k: jax.Array, num_heads: int) -> jax.Array:
    """(B,S,KV,Dh) -> (B,S,H,Dh) by broadcasting each kv head over its
    query group (query head h reads kv head h // (H // KV)); used by the
    train, prefill and cross-attention paths. XLA need not fuse this
    broadcast into the dot that reads it: on a TPU it wrote the decode
    cache out as f32 at H heads, so ``decode_attend`` groups the query
    heads instead of calling this."""
    b, s, kv, dh = k.shape
    g = num_heads // kv
    if g == 1:
        return k
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kv, g, dh))
    return k.reshape(b, s, num_heads, dh)


# ---------------------------------------------------------------------------
# Core attention
# ---------------------------------------------------------------------------


def _scores_mask(qpos: jax.Array, kpos: jax.Array, causal: bool,
                 window: int, valid_len: Optional[int] = None) -> jax.Array:
    """(..., Sq, Skv) boolean validity from absolute positions."""
    m = kpos[..., None, :] >= 0
    if valid_len is not None:
        m &= kpos[..., None, :] < valid_len
    if causal:
        m &= kpos[..., None, :] <= qpos[..., :, None]
    if window > 0:
        m &= (qpos[..., :, None] - kpos[..., None, :]) < window
    return m


def attend_direct(q: jax.Array, k: jax.Array, v: jax.Array,
                  qpos: jax.Array, kpos: jax.Array, *,
                  causal: bool, window: int = 0) -> jax.Array:
    """q: (B,Sq,H,Dh); k/v: (B,Skv,H,Dh) (already expanded)."""
    dh = q.shape[-1]
    scale = 1.0 / math.sqrt(dh)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    mask = _scores_mask(qpos, kpos, causal, window)      # (B?,Sq,Skv) or (Sq,Skv)
    while mask.ndim < s.ndim:
        mask = mask[..., None, :, :] if mask.ndim == s.ndim - 1 else mask[None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.astype(q.dtype)


class _OnlineState(NamedTuple):
    m: jax.Array    # (B,H,Sq) running max, f32
    l: jax.Array    # (B,H,Sq) running denom, f32
    acc: jax.Array  # (B,H,Sq,Dh) running numerator, f32


def _online_block(state: _OnlineState, q: jax.Array, kc: jax.Array,
                  vc: jax.Array, qpos: jax.Array, kpos: jax.Array,
                  causal: bool, window: int,
                  valid_len: Optional[int] = None) -> _OnlineState:
    """One KV chunk of online softmax. q: (B,Sq,H,Dh); kc/vc: (B,Kc,H,Dh)."""
    dh = q.shape[-1]
    scale = 1.0 / math.sqrt(dh)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kc,
                   preferred_element_type=jnp.float32) * scale
    mask = _scores_mask(qpos, kpos, causal, window, valid_len)[None, None]
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(state.m, jnp.max(s, axis=-1))
    corr = jnp.exp(state.m - m_new)
    p = jnp.exp(s - m_new[..., None])
    l_new = state.l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bhqk,bkhd->bhqd", p.astype(vc.dtype), vc,
                    preferred_element_type=jnp.float32)
    acc_new = state.acc * corr[..., None] + pv
    return _OnlineState(m_new, l_new, acc_new)


def attend_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: int = 0,
                   q_chunk: int = 512, kv_chunk: int = 512) -> jax.Array:
    """Flash-style chunked attention over already-expanded k/v.

    q: (B,S,H,Dh), k/v: (B,S,H,Dh), positions are 0..S-1 (self-attention).
    Outer python loop over q-chunks keeps each chunk's KV extent *static*:
    full-causal chunk i sees prefix [0, (i+1)*qc); windowed chunk i sees the
    band [i*qc - ceil(W/kc)*kc, (i+1)*qc). HLO FLOPs are therefore the true
    causal/banded cost, which keeps the roofline compute term honest.
    """
    b, s_valid, h, dh = q.shape
    assert k.shape == (b, s_valid, h, dh), (q.shape, k.shape)
    from repro.models.unroll import unroll_enabled
    if unroll_enabled():
        # dry-run cost accounting: avoid inner KV scans (loop bodies are
        # counted once by cost_analysis) — use one direct block per q-chunk
        kv_chunk = max(kv_chunk, s_valid)
    if s_valid <= q_chunk:
        pos = jnp.arange(s_valid)
        return attend_direct(q, k, v, pos, pos, causal=causal, window=window)
    # pad to a q_chunk multiple; padded keys are masked via valid_len,
    # padded queries produce zeros (l == 0 guard) and are sliced off.
    s = -(-s_valid // q_chunk) * q_chunk
    if s != s_valid:
        pad = [(0, 0)] * 4
        pad[1] = (0, s - s_valid)
        q = jnp.pad(q, pad)
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
    nq = s // q_chunk

    outs = []
    for i in range(nq):
        q_i = jax.lax.slice_in_dim(q, i * q_chunk, (i + 1) * q_chunk, axis=1)
        qpos = i * q_chunk + jnp.arange(q_chunk)
        if causal and window <= 0:
            kv_start, kv_end = 0, (i + 1) * q_chunk
        elif window > 0:
            lo = i * q_chunk - (-(-window // kv_chunk)) * kv_chunk
            kv_start, kv_end = max(0, lo), (i + 1) * q_chunk
        else:
            kv_start, kv_end = 0, s
        k_i = jax.lax.slice_in_dim(k, kv_start, kv_end, axis=1)
        v_i = jax.lax.slice_in_dim(v, kv_start, kv_end, axis=1)
        span = kv_end - kv_start

        state = _OnlineState(
            m=jnp.full((b, h, q_chunk), NEG_INF, jnp.float32),
            l=jnp.zeros((b, h, q_chunk), jnp.float32),
            acc=jnp.zeros((b, h, q_chunk, dh), jnp.float32),
        )
        if span <= kv_chunk:
            kpos = kv_start + jnp.arange(span)
            state = _online_block(state, q_i, k_i, v_i, qpos, kpos,
                                  causal, window, s_valid)
        else:
            nk = -(-span // kv_chunk)
            pad = nk * kv_chunk - span
            if pad:
                cfgpad = [(0, 0)] * 4
                cfgpad[1] = (pad, 0)     # left-pad; padded kpos < 0 masked out
                k_i = jnp.pad(k_i, cfgpad)
                v_i = jnp.pad(v_i, cfgpad)
            k_i = k_i.reshape(b, nk, kv_chunk, h, dh).transpose(1, 0, 2, 3, 4)
            v_i = v_i.reshape(b, nk, kv_chunk, h, dh).transpose(1, 0, 2, 3, 4)
            base = kv_start - pad

            def body(st, inp):
                j, kc, vc = inp
                kpos = base + j * kv_chunk + jnp.arange(kv_chunk)
                return _online_block(st, q_i, kc, vc, qpos, kpos,
                                     causal, window, s_valid), None

            state, _ = jax.lax.scan(body, state,
                                    (jnp.arange(nk), k_i, v_i))
        out_i = state.acc / jnp.maximum(state.l, 1e-30)[..., None]
        outs.append(out_i.transpose(0, 2, 1, 3).astype(q.dtype))  # (B,qc,H,Dh)
    out = jnp.concatenate(outs, axis=1)
    return out[:, :s_valid] if s != s_valid else out


# ---------------------------------------------------------------------------
# Decode-step attention against a cache
# ---------------------------------------------------------------------------


def to_rolling(k: jax.Array, window: int) -> jax.Array:
    """Convert a chronological prefill cache (B,S,KV,Dh) into the rolling
    layout decode expects for windowed attention: fixed length ``window``,
    position p stored at slot p % window. Pads when S < window."""
    b, s, kv, dh = k.shape
    if s >= window:
        tail = jax.lax.slice_in_dim(k, s - window, s, axis=1)
        return jnp.roll(tail, s % window, axis=1)
    pad = [(0, 0)] * 4
    pad[1] = (0, window - s)
    return jnp.pad(k, pad)


def init_kv_cache(num_layers: int, batch: int, max_len: int, num_kv: int,
                  head_dim: int, dtype) -> dict:
    return {
        "k": jnp.zeros((num_layers, batch, max_len, num_kv, head_dim), dtype),
        "v": jnp.zeros((num_layers, batch, max_len, num_kv, head_dim), dtype),
    }


def kv_cache_specs(num_layers: int, batch: int, max_len: int, num_kv: int,
                   head_dim: int, dtype) -> dict:
    sh = (num_layers, batch, max_len, num_kv, head_dim)
    return {"k": jax.ShapeDtypeStruct(sh, jnp.dtype(dtype)),
            "v": jax.ShapeDtypeStruct(sh, jnp.dtype(dtype))}


def decode_attend(q: jax.Array, cache_k: jax.Array, cache_v: jax.Array,
                  new_k: jax.Array, new_v: jax.Array, pos: jax.Array, *,
                  num_heads: int, window: int = 0,
                  shard_fn: ShardFn = no_shard):
    """Single-token decode. q: (B,1,H,Dh); cache_k/v: (B,S_max,KV,Dh);
    new_k/v: (B,1,KV,Dh) (already roped at ``pos``). Returns (out, k, v).

    ``pos`` may be a scalar (whole batch at one position — the dry-run
    cells) or a ``(B,)`` vector (the serving engine's mixed-length
    batches). Full attention writes slot ``pos``; windowed caches are
    rolling (slot = pos % window, S_max == window).

    Grouped heads: q is reshaped to (B, KV, G, Dh), G = H // KV, and each
    group is contracted in the cache dtype (f32 accumulation) against its
    kv head of the (B, S_max, KV, Dh) cache, so no H-headed copy of the
    cache is made. G == 1
    (MHA) is the same contraction.

    Sharding (§Perf B2, flash-decoding layout): when kv-heads don't
    divide the model axis, the cache shards its LENGTH dim over
    ``model``; q is pinned replicated (tiny), scores stay length-sharded
    (softmax max/sum become small psums), and the output is resharded to
    heads late — so no cache-sized gather ever materializes."""
    s_max = cache_k.shape[1]
    cache_k = shard_fn(cache_k, ("batch", "seq_model", "rep", "rep"))
    cache_v = shard_fn(cache_v, ("batch", "seq_model", "rep", "rep"))
    slot = pos % s_max if window > 0 else pos
    if jnp.ndim(pos) == 0:
        cache_k = jax.lax.dynamic_update_slice_in_dim(cache_k, new_k, slot,
                                                      axis=1)
        cache_v = jax.lax.dynamic_update_slice_in_dim(cache_v, new_v, slot,
                                                      axis=1)
    else:
        b_idx = jnp.arange(q.shape[0])
        cache_k = cache_k.at[b_idx, slot].set(new_k[:, 0])
        cache_v = cache_v.at[b_idx, slot].set(new_v[:, 0])

    b, _, kv, dh = cache_k.shape
    g = num_heads // kv
    # query head h reads kv head h // g, the grouping expand_kv uses
    qg = shard_fn(q.reshape(b, kv, g, dh), ("batch", "rep", "rep", "rep"))
    scale = 1.0 / math.sqrt(dh)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, cache_k,
                   preferred_element_type=jnp.float32) * scale
    s = shard_fn(s, ("batch", "rep", "rep", "seq_model"))
    j = jnp.arange(s_max)
    if window > 0:
        valid = ((pos[..., None] - j) % s_max) <= pos[..., None]   # rolling
    else:
        valid = j <= pos[..., None]
    # scalar pos -> (S,); vector pos -> (B,S)
    valid = valid[None, None, None, :] if valid.ndim == 1 \
        else valid[:, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p.astype(cache_v.dtype), cache_v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    out = out.reshape(b, 1, num_heads, dh)
    out = shard_fn(out, ("batch", None, "heads", None))   # late reshard
    return out, cache_k, cache_v
