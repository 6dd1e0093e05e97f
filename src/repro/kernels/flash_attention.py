"""Blockwise (flash-style) causal attention kernel for TPU.

The 32k-prefill cells are compute-dominated by attention; the jnp oracle
(models/attention.attend_chunked) materializes (bq, skv) score tiles in
HBM between ops. This kernel keeps the online-softmax state (m, l, acc)
in VMEM scratch across KV blocks, so each (q-block, kv-block) tile does
two MXU matmuls with no HBM round trip for intermediates.

Layout: q/k/v arrive as (BH, S, Dh) (heads pre-expanded/fused with batch
by ops.py). Grid = (BH, n_q_blocks, n_kv_blocks) with the KV dim
innermost and sequential ('arbitrary'): scratch carries (m, l, acc) per
q-block; the normalized output is written on the last KV block.

Masking: causal (kv_pos <= q_pos), optional sliding window
(q_pos - kv_pos < window), and a validity bound ``s_valid`` so ops.py can
pad S to block multiples. Fully-masked tiles short-circuit via pl.when.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30

DEFAULT_BQ = 512
DEFAULT_BK = 512


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *,
                  bq: int, bk: int, n_kv: int, causal: bool, window: int,
                  s_valid: int, scale: float):
    iq = pl.program_id(1)
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < s_valid
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= (qpos - kpos) < window

    # skip tiles that the causal/window structure fully masks
    q_lo, q_hi = iq * bq, (iq + 1) * bq - 1
    k_lo, k_hi = ik * bk, (ik + 1) * bk - 1
    live = k_lo < s_valid
    if causal:
        live &= k_lo <= q_hi
    if window > 0:
        live &= (q_lo - k_hi) < window

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)          # (bq, dh)
        k = k_ref[0].astype(jnp.float32)          # (bk, dh)
        v = v_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_sc[...] = l_sc[...] * corr + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(p.astype(v.dtype), v,
                                 (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_sc[...] = acc_sc[...] * corr[:, None] + pv
        m_sc[...] = m_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_sc[...], 1e-30)[:, None]
        o_ref[0] = (acc_sc[...] / denom).astype(o_ref.dtype)


def flash_attention_kernel(q: jax.Array, k: jax.Array, v: jax.Array, *,
                           causal: bool = True, window: int = 0,
                           s_valid: int | None = None,
                           bq: int = DEFAULT_BQ, bk: int = DEFAULT_BK,
                           interpret: bool = False) -> jax.Array:
    """q/k/v: (BH, S, Dh), S a multiple of max(bq, bk). Returns (BH, S, Dh).
    ``s_valid``: number of real (unpadded) positions."""
    bh, s, dh = q.shape
    assert k.shape == (bh, s, dh) and v.shape == (bh, s, dh)
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    n_q, n_kv = s // bq, s // bk
    if s_valid is None:
        s_valid = s
    scale = 1.0 / math.sqrt(dh)

    kernel = functools.partial(
        _flash_kernel, bq=bq, bk=bk, n_kv=n_kv, causal=causal,
        window=window, s_valid=s_valid, scale=scale)

    grid = (bh, n_q, n_kv)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dh), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dh), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out
