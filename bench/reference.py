"""Plain reference of the dense decoder family, in float32.

Straight ``jax.numpy`` from the published block description: pre-norm
(RMSNorm, or LayerNorm with a shift), grouped-query attention with RoPE
(rotate-half), optional q/k/v biases and an optional sliding window, a
gated SiLU MLP or a plain tanh-GELU MLP with biases, a final norm and an
LM head that is the embedding transposed when tied. Attention takes a
block of queries at a time against the keys they can see, so that long
sequences fit; every configuration takes that one path. No cache, no
batching tricks, no kernels, and nothing imported from the system under
test. Weights are kept in the dtype the configuration stores them in and
widened to float32 layer by layer; every matmul runs at ``highest``
precision.

``prec="fp8"`` is the control: each matmul's operands, and in training
their cotangents, are rounded to float8 e4m3 with a per-tensor scale, the
step below bfloat16 that a later change might be tempted to take.
Accumulation stays float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.model import sliding_window

F32 = jnp.float32
FP8_MAX = 448.0                       # largest finite float8 e4m3fn
QUERY_BLOCK = 512                     # queries per block of attention


def _round8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


@jax.custom_vjp
def _fp8(x):
    """x rounded to float8 e4m3 under a per-tensor scale; its cotangent is
    rounded the same way, so the backward matmuls see fp8 too."""
    return _round8(x)


_fp8.defvjp(lambda x: (_round8(x), None), lambda _, g: (_round8(g),))


def _mm(spec: str, a, b, prec: str):
    if prec == "fp8":
        a, b = _fp8(a), _fp8(b)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _norm(p, x, cfg):
    if cfg["norm_type"] == "rms_norm":
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(var + cfg["rms_norm_eps"]) * p["scale"]
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + cfg["norm_epsilon"]) * p["scale"] \
        + p["bias"]


def _rope(x, theta: float):
    """x: (B, S, heads, dh); rotate-half RoPE at positions 0..S-1."""
    s, dh = x.shape[1], x.shape[-1]
    half = dh // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv          # (S, half)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def attend(q, k, v, window: int, prec: str):
    """Causal softmax attention; q, k, v: (B, S, heads, dh), one kv head
    per query head. With ``window`` > 0 a query at q sees the keys at k
    with 0 <= q - k < window. Computed ``QUERY_BLOCK`` queries at a time
    against the keys that block can see, so that no S x S score matrix is
    made."""
    s, dh = q.shape[1], q.shape[-1]
    outs = []
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        k_lo = max(0, lo - window + 1) if window else 0
        qp = jnp.arange(lo, hi)[:, None]
        kp = jnp.arange(k_lo, hi)[None, :]
        seen = kp <= qp
        if window:
            seen &= qp - kp < window
        scores = _mm("bqhd,bkhd->bhqk", q[:, lo:hi], k[:, k_lo:hi],
                     prec) / math.sqrt(dh)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        outs.append(_mm("bhqk,bkhd->bqhd", probs, v[:, k_lo:hi], prec))
    return jnp.concatenate(outs, axis=1)


def _layer(p, x, cfg, prec):
    """One decoder block; x: (B, S, d) float32."""
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    a = p["attn"]
    y = _norm(p["ln1"], x, cfg)
    q = _mm("bsd,dhk->bshk", y, a["wq"], prec)
    k = _mm("bsd,dhk->bshk", y, a["wk"], prec)
    v = _mm("bsd,dhk->bshk", y, a["wv"], prec)
    if cfg["attention_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    k = jnp.repeat(k, h // kv, axis=2)          # head i reads kv head i//g
    v = jnp.repeat(v, h // kv, axis=2)
    att = attend(q, k, v, sliding_window(cfg), prec)
    x = x + _mm("bshk,hkd->bsd", att, a["wo"], prec)
    m = p["mlp"]
    y = _norm(p["ln2"], x, cfg)
    if cfg["mlp"] == "gated":
        u = jax.nn.silu(_mm("bsd,df->bsf", y, m["wg"], prec)) \
            * _mm("bsd,df->bsf", y, m["wi"], prec)
        return x + _mm("bsf,fd->bsd", u, m["wo"], prec)
    u = _mm("bsd,df->bsf", y, m["wi"], prec)
    if cfg["mlp_bias"]:
        u = u + m["bi"]
    out = _mm("bsf,fd->bsd", _gelu_tanh(u), m["wo"], prec)
    return x + (out + m["bo"] if cfg["mlp_bias"] else out)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(F32), tree)


def hidden(params, tokens, cfg, prec="f32"):
    """Final-normed hidden states (B, S, d) for token rows (B, S)."""
    x = jnp.take(params["embed"]["tok"], tokens, axis=0).astype(F32)

    @jax.checkpoint                       # keeps a layer's activations
    def body(x, layer):                   # out of the backward's memory
        return _layer(_f32(layer), x, cfg, prec), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    return _norm(_f32(params["ln_f"]), x, cfg)


def head(params, x, cfg, prec="f32"):
    """Logits for hidden states x (..., d)."""
    e = params["embed"]
    w = e["tok"].astype(F32).T if cfg["tie_word_embeddings"] \
        else e["out"].astype(F32)
    return _mm("...d,dv->...v", x, w, prec)


def loss(params, batch, cfg, prec="f32"):
    """Token-mean next-token cross entropy over every position."""
    x = hidden(params, batch["tokens"], cfg, prec)
    logits = head(params, x, cfg, prec)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, batch["labels"][..., None], -1)[..., 0]
    return jnp.mean(lse - gold)


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _loss_and_grad(params, batch, cfg_items, prec, block_rows):
    cfg = dict(cfg_items)
    p32 = _f32(params)
    rows = batch["tokens"].shape[0]
    n = rows // block_rows
    blocks = jax.tree.map(
        lambda a: a.reshape(n, block_rows, *a.shape[1:]), batch)

    def body(carry, blk):
        l, g = jax.value_and_grad(loss)(p32, blk, cfg, prec)
        return (carry[0] + l, jax.tree.map(jnp.add, carry[1], g)), None

    init = (jnp.zeros((), F32), jax.tree.map(jnp.zeros_like, p32))
    (l, g), _ = jax.lax.scan(body, init, blocks)
    return l / n, jax.tree.map(lambda x: x / n, g)


def loss_and_grad(params, batch, cfg, prec="f32", block_rows=2):
    """Mean loss and float32 gradient over every row of ``batch``, taken
    ``block_rows`` rows at a time (equal blocks, so the mean of the block
    means is the row mean). ``params`` stay in their stored dtype."""
    rows = batch["tokens"].shape[0]
    if rows % block_rows:
        raise ValueError(f"{rows} rows do not split into {block_rows}s")
    return _loss_and_grad(params, batch, _items(cfg), prec, block_rows)


def _items(cfg: dict) -> tuple:
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if not isinstance(v, (list, dict))))


# -- AdamW, as the training mix states it -------------------------------

def lr_at(opt: dict, count: int) -> float:
    warm = min(count / max(opt["warmup_steps"], 1), 1.0)
    prog = min(max((count - opt["warmup_steps"])
                   / max(opt["total_steps"] - opt["warmup_steps"], 1), 0.0),
               1.0)
    return opt["lr"] * warm * (0.1 + 0.9 * 0.5 * (1.0 + math.cos(math.pi
                                                                 * prog)))


@functools.partial(jax.jit, static_argnums=(5,),
                   donate_argnums=(0, 1, 2, 3))
def _adamw(p, g, m, v, scalars, decay):
    lr, b1, b2, eps, wd, c1, c2, max_norm = scalars
    norm = jnp.sqrt(sum(jnp.sum(x * x) for x in g))
    scale = jnp.minimum(1.0, max_norm / jnp.maximum(norm, 1e-12))
    out_p, out_m, out_v = [], [], []
    for pi, gi, mi, vi, dec in zip(p, g, m, v, decay):
        gi = gi * scale
        mi = b1 * mi + (1 - b1) * gi
        vi = b2 * vi + (1 - b2) * gi * gi
        step = (mi / c1) / (jnp.sqrt(vi / c2) + eps)
        pf = pi.astype(F32)
        if dec:
            step = step + wd * pf
        out_p.append((pf - lr * step).astype(pi.dtype))
        out_m.append(mi)
        out_v.append(vi)
    return out_p, out_m, out_v, scale


def adamw_step(params, grads, mu, nu, count: int, opt: dict, decay):
    """One AdamW step (``count`` is 1-based) after clipping by the global
    norm. ``params`` keep their stored dtype; moments and arithmetic are
    float32. ``decay`` marks the leaves that take weight decay (matrices,
    not norms or biases). Consumes its array arguments; returns (params,
    mu, nu, the clip's scale)."""
    b1, b2 = opt["beta1"], opt["beta2"]
    scalars = (lr_at(opt, count), b1, b2, opt["eps"], opt["weight_decay"],
               1 - b1 ** count, 1 - b2 ** count, opt["grad_clip"])
    p, treedef = jax.tree.flatten(params)
    new_p, new_m, new_v, scale = _adamw(
        p, jax.tree.leaves(grads), jax.tree.leaves(mu), jax.tree.leaves(nu),
        scalars, tuple(jax.tree.leaves(decay)))
    un = functools.partial(jax.tree.unflatten, treedef)
    return un(new_p), un(new_m), un(new_v), float(scale)
