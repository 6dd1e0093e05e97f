"""A configuration file as the system under test runs it, and its weights.

The weights are the benchmark's own: drawn on the device from the seed in
one jitted call, in the dtype the configuration states, laid out as the
program's parameter tree (``repro.models.api.specs`` for the dense family)
so that they can be handed to it. The plain reference draws the same
weights again from the same seed; it never reads the program's copy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# configuration file key -> repro ModelConfig field (widths the file fixes)
WIDTHS = {
    "num_hidden_layers": "num_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
}


def dtype_of(cfg: dict):
    return jnp.dtype(cfg["torch_dtype"])


def program_config(cfg: dict):
    """The repo's ModelConfig for ``cfg["arch"]``, checked against every
    width, the block's kind and the dtype the file states."""
    from repro.configs.registry import get_config
    mc = get_config(cfg["arch"])
    want = {field: cfg[key] for key, field in WIDTHS.items()}
    want.update(
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        qkv_bias=cfg["attention_bias"],
        mlp_kind="swiglu" if cfg["mlp"] == "gated" else "gelu",
        norm_kind="rmsnorm" if cfg["norm_type"] == "rms_norm"
        else "layernorm",
        tie_embeddings=cfg["tie_word_embeddings"],
        rope_theta=cfg["rope_theta"],
        sliding_window=cfg.get("sliding_window") or 0
        if cfg.get("use_sliding_window", True) else 0,
        param_dtype=cfg["torch_dtype"],
        compute_dtype=cfg["torch_dtype"])
    got = {k: getattr(mc, k) for k in want}
    if got != want or mc.family != "dense":
        raise ValueError(
            f"{cfg['name']}: the repo's {cfg['arch']} differs from the "
            f"configuration file: repo {got}, file {want}")
    return mc


def weight_shapes(cfg: dict) -> dict:
    """The dense family's parameter tree: stacked layers, leading dim L."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    n, h, kv = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                cfg["num_key_value_heads"])
    dh, v = d // h, cfg["vocab_size"]
    norm = {"scale": (d,)}
    if cfg["norm_type"] == "layer_norm":
        norm["bias"] = (d,)
    attn = {"wq": (d, h, dh), "wk": (d, kv, dh), "wv": (d, kv, dh),
            "wo": (h, dh, d)}
    if cfg["attention_bias"]:
        attn.update(bq=(h, dh), bk=(kv, dh), bv=(kv, dh))
    if cfg["mlp"] == "gated":
        mlp = {"wi": (d, f), "wg": (d, f), "wo": (f, d)}
    else:
        mlp = {"wi": (d, f), "wo": (f, d)}
        if cfg["mlp_bias"]:
            mlp.update(bi=(f,), bo=(d,))
    layer = {"ln1": norm, "ln2": norm, "attn": attn, "mlp": mlp}
    stacked = jax.tree.map(lambda s: (n,) + s, layer,
                           is_leaf=lambda x: isinstance(x, tuple))
    embed = {"tok": (v, d)}
    if not cfg["tie_word_embeddings"]:
        embed["out"] = (d, v)
    return {"embed": embed, "ln_f": dict(norm), "layers": stacked}


def _leaves(shapes: dict, prefix: str = ""):
    for k in sorted(shapes):
        path = f"{prefix}.{k}" if prefix else k
        if isinstance(shapes[k], dict):
            yield from _leaves(shapes[k], path)
        else:
            yield path, shapes[k]


def _set(tree: dict, path: str, value) -> None:
    *head, last = path.split(".")
    for k in head:
        tree = tree.setdefault(k, {})
    tree[last] = value


@functools.lru_cache(maxsize=None)
def _init_fn(cfg_items: tuple):
    cfg = dict(cfg_items)
    shapes = weight_shapes(cfg)
    dt = dtype_of(cfg)
    std = cfg["initializer_range"]

    def init(seed_key):
        out: dict = {}
        for i, (path, shape) in enumerate(_leaves(shapes)):
            key = jax.random.fold_in(seed_key, i)
            noise = jax.random.normal(key, shape, dt)
            name = path.rsplit(".", 1)[-1]
            if name == "scale":       # norm gains around 1
                value = 1.0 + 0.1 * noise
            else:                     # matrices, biases and norm shifts
                value = std * noise
            _set(out, path, value.astype(dt))
        return out

    return jax.jit(init)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number up to 2**63 (the driver's seeds
    exceed 32 bits)."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def items(cfg: dict) -> tuple:
    """A configuration's scalar keys as a hashable key for jit caches."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if not isinstance(v, (list, dict))))


def init_weights(cfg: dict, seed: int):
    """The weights for ``seed``, made on the default device in one call."""
    return _init_fn(items(cfg))(seed_key(seed))
