"""Chunked WKV6 recurrence kernel (RWKV-6 time mix) for TPU.

The recurrence (models/rwkv6.py)::

    S_t = diag(w_t) S_{t-1} + k_t v_t^T
    y_t = r_t (S_{t-1} + diag(u) k_t v_t^T)

is split per chunk of C steps: the *history* contribution is one MXU
matmul, the *intra-chunk* part is a C-step VPU loop entirely in VMEM:

    la_t   = cumsum(log w)_t              (la_0 = log w_1 ... within chunk)
    y_t    = (r_t . exp(la_{t-1})) @ S_in        # history, (C,hs)@(hs,hs)
           + r_t (L_{t-1} + diag(u) k_t v_t^T)   # local loop, L_0 = 0
    S_out  = exp(la_C) * S_in + L_C

All decay factors used are exp of non-positive numbers — numerically safe
for any w in (0,1) (no 1/A blowup; see DESIGN.md hardware-adaptation).

Grid = (B*H, n_chunks), chunk dim sequential; the running state lives in
a (hs, hs) f32 VMEM scratch. Inputs are (BH, T, hs) f32 (ops.py reshapes
from the model's (B,T,H,hs)); u is (H, hs) indexed by bh % H via BlockSpec
index_map (a free modular broadcast, no gather).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_CHUNK = 32


def _wkv6_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                 y_ref, s_out_ref, state_sc, *, chunk: int, n_chunks: int,
                 hs: int):
    jc = pl.program_id(1)

    @pl.when(jc == 0)
    def _init():
        state_sc[...] = s0_ref[0]

    r = r_ref[0]                      # (C, hs) f32
    k = k_ref[0]
    v = v_ref[0]
    w = w_ref[0]
    u = u_ref[0]                      # (hs,)
    s_in = state_sc[...]              # (hs, hs)

    logw = jnp.log(w)
    la = jnp.cumsum(logw, axis=0)                       # (C, hs), <= 0
    la_prev = la - logw                                  # cum through t-1

    # history: y_hist[t] = (r_t * exp(la_prev_t)) @ S_in
    r_tilde = r * jnp.exp(la_prev)
    y_hist = jax.lax.dot_general(r_tilde, s_in, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)

    # intra-chunk: sequential rank-1 updates on the local state
    def step(t, carry):
        s_loc, y_acc = carry
        rt = jax.lax.dynamic_slice_in_dim(r, t, 1, axis=0)   # (1, hs)
        kt = jax.lax.dynamic_slice_in_dim(k, t, 1, axis=0)
        vt = jax.lax.dynamic_slice_in_dim(v, t, 1, axis=0)
        wt = jax.lax.dynamic_slice_in_dim(w, t, 1, axis=0)
        kv = kt.T * vt                                       # (hs, hs)
        y_t = (rt @ s_loc) + (rt * u[None, :]) @ kv          # (1, hs)
        y_acc = jax.lax.dynamic_update_slice_in_dim(y_acc, y_t, t, axis=0)
        s_loc = wt.T * s_loc + kv
        return s_loc, y_acc

    s_loc, y_local = jax.lax.fori_loop(
        0, chunk, step,
        (jnp.zeros((hs, hs), jnp.float32), jnp.zeros((chunk, hs),
                                                     jnp.float32)))

    y_ref[0] = y_hist + y_local
    state_sc[...] = jnp.exp(la[-1])[:, None] * s_in + s_loc

    @pl.when(jc == n_chunks - 1)
    def _final():
        s_out_ref[0] = state_sc[...]


def wkv6_kernel(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
                u: jax.Array, s0: jax.Array, *,
                chunk: int = DEFAULT_CHUNK,
                interpret: bool = False):
    """r/k/v/w: (BH, T, hs) f32; u: (H, hs); s0: (BH, hs, hs) f32.
    T must be a multiple of ``chunk`` (ops.py pads with w=1, k=v=0).
    Returns (y (BH, T, hs), s_final (BH, hs, hs))."""
    bh, t, hs = r.shape
    h = u.shape[0]
    assert t % chunk == 0, (t, chunk)
    n_chunks = t // chunk

    kernel = functools.partial(_wkv6_kernel, chunk=chunk,
                               n_chunks=n_chunks, hs=hs)
    seq_spec = pl.BlockSpec((1, chunk, hs), lambda b, j: (b, j, 0))
    y, s_final = pl.pallas_call(
        kernel,
        grid=(bh, n_chunks),
        in_specs=[
            seq_spec, seq_spec, seq_spec, seq_spec,
            pl.BlockSpec((1, hs), lambda b, j: (b % h, 0)),
            pl.BlockSpec((1, hs, hs), lambda b, j: (b, 0, 0)),
        ],
        out_specs=(
            seq_spec,
            pl.BlockSpec((1, hs, hs), lambda b, j: (b, 0, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, t, hs), jnp.float32),
            jax.ShapeDtypeStruct((bh, hs, hs), jnp.float32),
        ),
        scratch_shapes=[pltpu.VMEM((hs, hs), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u, s0)
    return y, s_final
