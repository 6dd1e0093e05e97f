"""The benchmark's own count of a dense decoder's model FLOPs.

Forward FLOPs of one token are 2 x (matmul parameters it passes through)
plus 4 x H x d_head x span x L for attention: 2 for the scores and 2 for
the weighted sum over the ``span`` keys it attends to: min(i + 1, W) for
the token at position i under a sliding window of W, i + 1 without one.
The LM head counts ``vocab x d_model`` parameters, tied or not. Training
costs 3x forward (forward, and backward through activations and weights);
recomputation is not counted. Norms, biases, RoPE, softmax and the
embedding lookup are left out, as is usual for model FLOPs.
"""
from __future__ import annotations

from bench.model import sliding_window


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    h = cfg["num_attention_heads"]
    return (d, cfg["intermediate_size"], cfg["num_hidden_layers"], h,
            cfg["num_key_value_heads"], cfg["vocab_size"], d // h)


def layer_matmul_params(cfg: dict) -> int:
    """Matmul parameters of all decoder layers (projections and MLP)."""
    d, f, n_layers, h, kv, _, dh = _dims(cfg)
    attn = d * h * dh + 2 * d * kv * dh + h * dh * d
    mlp = (3 if cfg["mlp"] == "gated" else 2) * d * f
    return n_layers * (attn + mlp)


def head_params(cfg: dict) -> int:
    """The LM head's ``vocab x d_model``, counted whether or not it is
    tied to the embedding."""
    return cfg["vocab_size"] * cfg["hidden_size"]


def attention_flops(cfg: dict, keys: float) -> float:
    """Scores plus weighted sum for one query over ``keys`` keys, all
    layers."""
    d, _, n_layers, h, _, _, dh = _dims(cfg)
    return 4.0 * h * dh * keys * n_layers


def keys_seen(cfg: dict, pos: int) -> int:
    """Keys the token at position ``pos`` (0-based) attends to."""
    w = sliding_window(cfg)
    return min(pos + 1, w) if w else pos + 1


def prompt_keys(cfg: dict, n: int) -> float:
    """Keys attended to over positions 0..n-1: the sum of ``keys_seen``."""
    w = sliding_window(cfg) or n
    m = min(n, w)
    return m * (m + 1) / 2 + (n - m) * w


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """3 x forward, with the LM head on every position and the mean span
    over the row (``(seq_len + 1) / 2`` without a window)."""
    fwd = 2.0 * (layer_matmul_params(cfg) + head_params(cfg)) \
        + attention_flops(cfg, prompt_keys(cfg, seq_len) / seq_len)
    return 3.0 * fwd


def prefill_flops(cfg: dict, prompt_len: int) -> float:
    """Forward over a prompt of ``prompt_len`` real tokens, with the LM
    head only on the last position (the only logits that are used)."""
    p = prompt_len
    return (2.0 * layer_matmul_params(cfg) * p + 2.0 * head_params(cfg)
            + attention_flops(cfg, prompt_keys(cfg, p)))


def decode_flops(cfg: dict, pos: int) -> float:
    """One decoded token at position ``pos`` (0-based), LM head
    included."""
    return (2.0 * (layer_matmul_params(cfg) + head_params(cfg))
            + attention_flops(cfg, keys_seen(cfg, pos)))


def request_flops(cfg: dict, prompt_len: int, n_generated: int) -> float:
    """Useful FLOPs of one served request: its prefill (which yields the
    first token) and one decode step for each further token. Padding rows,
    pad positions and idle slots are not counted."""
    total = prefill_flops(cfg, prompt_len)
    for j in range(max(n_generated - 1, 0)):
        total += decode_flops(cfg, prompt_len + j)
    return total
