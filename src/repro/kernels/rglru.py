"""RG-LRU linear-recurrence kernel (recurrentgemma) for TPU.

The gate/decay computation (sigmoids, per-block matmuls) is dense
elementwise work XLA already fuses well; the *sequential* part —

    h_t = a_t * h_{t-1} + b_t

— is what needs a kernel: lax.associative_scan materializes O(log T)
full-size intermediates in HBM, while this kernel streams (C, Wb) tiles
through VMEM with the running state in scratch, one HBM read + write per
element.

Grid = (B, n_w_blocks, n_chunks) with the chunk dim innermost and
sequential; scratch holds h (Wb,) per (batch, width-block) and is
re-initialized from ``h0`` at chunk 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_CHUNK = 128
DEFAULT_WBLOCK = 512


def _rglru_kernel(a_ref, b_ref, h0_ref, y_ref, h_out_ref, h_sc, *,
                  chunk: int, n_chunks: int):
    jc = pl.program_id(2)

    @pl.when(jc == 0)
    def _init():
        h_sc[...] = h0_ref[0]

    a = a_ref[0]                       # (C, Wb) f32
    b = b_ref[0]
    h = h_sc[...]                      # (Wb,)

    def step(t, carry):
        h, y = carry
        at = jax.lax.dynamic_slice_in_dim(a, t, 1, axis=0)   # (1, Wb)
        bt = jax.lax.dynamic_slice_in_dim(b, t, 1, axis=0)
        h = at[0] * h + bt[0]
        y = jax.lax.dynamic_update_slice_in_dim(y, h[None], t, axis=0)
        return h, y

    h, y = jax.lax.fori_loop(
        0, chunk, step, (h, jnp.zeros((chunk, a.shape[1]), jnp.float32)))
    y_ref[0] = y
    h_sc[...] = h

    @pl.when(jc == n_chunks - 1)
    def _final():
        h_out_ref[0] = h_sc[...]


def rglru_kernel(a: jax.Array, b: jax.Array, h0: jax.Array, *,
                 chunk: int = DEFAULT_CHUNK, wblock: int = DEFAULT_WBLOCK,
                 interpret: bool = False):
    """a/b: (B, T, W) f32 (decay and gated input); h0: (B, W) f32.
    T % chunk == 0 and W % wblock == 0 (ops.py pads: a=1, b=0).
    Returns (h_seq (B, T, W), h_final (B, W))."""
    bsz, t, w = a.shape
    wblock = min(wblock, w)
    chunk = min(chunk, t)
    assert t % chunk == 0 and w % wblock == 0, (t, chunk, w, wblock)
    n_chunks = t // chunk

    kernel = functools.partial(_rglru_kernel, chunk=chunk,
                               n_chunks=n_chunks)
    seq_spec = pl.BlockSpec((1, chunk, wblock), lambda i, k, j: (i, j, k))
    vec_spec = pl.BlockSpec((1, wblock), lambda i, k, j: (i, k))
    y, h_final = pl.pallas_call(
        kernel,
        grid=(bsz, w // wblock, n_chunks),
        in_specs=[seq_spec, seq_spec, vec_spec],
        out_specs=(seq_spec, vec_spec),
        out_shape=(
            jax.ShapeDtypeStruct((bsz, t, w), jnp.float32),
            jax.ShapeDtypeStruct((bsz, w), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((wblock,), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b, h0)
    return y, h_final
