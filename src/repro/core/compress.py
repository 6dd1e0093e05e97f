"""Gradient compression for TAC slices (beyond-paper, DESIGN.md §8).

bf16:    cast slices to bf16 on the wire, fp32 error feedback (the
         truncation residual is re-injected next step, so the update is
         unbiased over time).
int8_ef: per-slice max-abs int8 quantization, summed via all-gather +
         local reduction (wire bytes per device = shards x S/4 vs ring
         all-reduce's ~2S for bf16 — wins only for small, latency-bound
         slices; the benchmark sweeps this).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def bf16_compress(slices: jax.Array, ef: jax.Array | None):
    """slices: (n, S) f32. Returns (wire bf16, new error-feedback f32)."""
    if ef is not None:
        slices = slices + ef
    wire = slices.astype(jnp.bfloat16)
    # XLA:TPU folds an f32->bf16->f32 convert pair inside a fusion, which
    # would zero the residual; reduce_precision rounds exactly as the cast
    new_ef = slices - jax.lax.reduce_precision(slices, exponent_bits=8,
                                               mantissa_bits=7)
    return wire, new_ef


def int8_quantize(slices: jax.Array, ef: jax.Array | None):
    """Returns (q int8, scale f32 (n,1), new_ef)."""
    if ef is not None:
        slices = slices + ef
    amax = jnp.max(jnp.abs(slices), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(slices / scale), -127, 127).astype(jnp.int8)
    new_ef = slices - q.astype(jnp.float32) * scale
    return q, scale, new_ef


def int8_allreduce(q: jax.Array, scale: jax.Array, axes) -> jax.Array:
    """Sum int8 shards across ``axes`` via all-gather + local dequant-sum.
    q: (n, S) int8; scale: (n, 1) f32. Returns f32 (n, S) sum."""
    qg = q
    sg = scale
    for ax in (axes if isinstance(axes, (tuple, list)) else (axes,)):
        qg = jax.lax.all_gather(qg, ax, axis=0)       # (shards, ..., n, S)
        sg = jax.lax.all_gather(sg, ax, axis=0)
    qg = qg.reshape(-1, *q.shape)                      # (total_shards, n, S)
    sg = sg.reshape(-1, *scale.shape)
    return jnp.sum(qg.astype(jnp.float32) * sg, axis=0)
