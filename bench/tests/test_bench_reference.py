"""The plain reference against the repo's model at a reduced size, in
float32 on the CPU, for both block variants of the dense family, with and
without a sliding window."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, model, reference
from bench.tests import cells

REDUCED = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=128, vocab_size=256,
               torch_dtype="float32")


def reduced(name, **settings):
    return cells.shrink(harness.load_json(harness.BENCH / "configs"
                                          / f"{name}.json"),
                        **REDUCED, **settings)


@pytest.mark.parametrize("name", ["qwen2-0.5b", "starcoder2-3b"])
def test_reference_matches_program_prefill(name):
    from repro.models import api
    cfg = reduced(name)
    mc = cells.small_program_config(cfg)
    params = model.init_weights(cfg, 77)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 21), 0, 256)
    last = jnp.asarray([20, 13])
    got, _ = api.prefill(params, {"tokens": toks, "last_pos": last}, mc)
    with jax.default_matmul_precision("highest"):
        h = reference.hidden(params, toks, cfg)
        want = reference.head(params, h[jnp.arange(2), last], cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("name", ["qwen2-0.5b", "starcoder2-3b"])
def test_reference_matches_program_decode_through_cache(name):
    from repro.models import api
    cfg = reduced(name)
    mc = cells.small_program_config(cfg)
    params = model.init_weights(cfg, 78)
    toks = jax.random.randint(jax.random.PRNGKey(4), (1, 12), 0, 256)
    _, cache = api.prefill(params, {"tokens": toks[:, :8]}, mc)
    cache = api.grow_cache(mc, cache, 16)
    want_h = reference.hidden(params, toks, cfg)
    for t in range(8, 12):
        got, cache = api.decode_step(
            params, cache, {"token": toks[:, t], "pos": jnp.asarray(t)}, mc)
        want = reference.head(params, want_h[:, t], cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_reference_loss_and_grad_blocks_agree():
    cfg = reduced("qwen2-0.5b")
    params = model.init_weights(cfg, 5)
    rng = np.random.default_rng(0)
    t = rng.integers(0, 256, (4, 17)).astype(np.int32)
    batch = {"tokens": t[:, :-1], "labels": t[:, 1:]}
    l1, g1 = reference.loss_and_grad(params, batch, cfg, block_rows=1)
    l4, g4 = reference.loss_and_grad(params, batch, cfg, block_rows=4)
    assert float(l1) == pytest.approx(float(l4), rel=1e-5)
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g4)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_fp8_control_departs_from_float32():
    cfg = reduced("starcoder2-3b")
    params = model.init_weights(cfg, 6)
    toks = jax.random.randint(jax.random.PRNGKey(5), (1, 16), 0, 256)
    f32 = reference.head(params, reference.hidden(params, toks, cfg), cfg)
    fp8 = reference.head(params, reference.hidden(params, toks, cfg, "fp8"),
                         cfg, "fp8")
    rel = float(jnp.linalg.norm(fp8 - f32) / jnp.linalg.norm(f32))
    assert 1e-3 < rel < 0.5


def attend_by_query(q, k, v, window):
    """The same attention one query at a time, in numpy float64."""
    q, k, v = (np.asarray(a, np.float64) for a in (q, k, v))
    out = np.zeros_like(q)
    for i in range(q.shape[1]):
        lo = max(0, i - window + 1) if window else 0
        s = np.einsum("bhd,bkhd->bhk", q[:, i], k[:, lo:i + 1]) \
            / math.sqrt(q.shape[-1])
        p = np.exp(s - s.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        out[:, i] = np.einsum("bhk,bkhd->bhd", p, v[:, lo:i + 1])
    return out


@pytest.mark.parametrize("block", [7, 16, 512])
@pytest.mark.parametrize("window", [0, 1, 16])
def test_blocked_attention_equals_unblocked(window, block, monkeypatch):
    q, k, v = jax.random.normal(jax.random.PRNGKey(window), (3, 2, 45, 4, 8))
    monkeypatch.setattr(reference, "QUERY_BLOCK", 45)
    whole = reference.attend(q, k, v, window, "f32")
    monkeypatch.setattr(reference, "QUERY_BLOCK", block)
    got = reference.attend(q, k, v, window, "f32")
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got),
                               attend_by_query(q, k, v, window),
                               rtol=1e-5, atol=1e-6)


def windowed(name):
    """``name`` at the reduced widths with a window of 16, its ModelConfig,
    and the reference's hidden states in blocks of 16 queries."""
    cfg = reduced(name, sliding_window=16, use_sliding_window=True)
    mc = cells.small_program_config(cfg)
    assert mc.sliding_window == 16
    return cfg, mc


@pytest.mark.parametrize("name", ["qwen2-0.5b", "starcoder2-3b"])
def test_windowed_reference_matches_program_through_rolling_cache(
        name, monkeypatch):
    """Window 16 over 48 tokens: the prefill of 40, then 8 decode steps
    through the rolling cache, against the reference's forward pass in
    blocks of 16 queries."""
    from repro.models import api
    cfg, mc = windowed(name)
    params = model.init_weights(cfg, 79)
    toks = jax.random.randint(jax.random.PRNGKey(6), (1, 48), 0, 256)
    with jax.default_matmul_precision("highest"):
        full_h = reference.hidden(params, toks,
                                  dict(cfg, use_sliding_window=False))
        monkeypatch.setattr(reference, "QUERY_BLOCK", 16)
        want_h = reference.hidden(params, toks, cfg)
    assert float(jnp.max(jnp.abs(want_h[:, 47] - full_h[:, 47]))) > 1e-2
    got, cache = api.prefill(params, {"tokens": toks[:, :40]}, mc)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(reference.head(params, want_h[:, 39],
                                                   cfg)),
        rtol=2e-4, atol=2e-4)
    cache = api.grow_cache(mc, cache, 64)
    for t in range(40, 48):
        got, cache = api.decode_step(
            params, cache, {"token": toks[:, t], "pos": jnp.asarray(t)}, mc)
        want = reference.head(params, want_h[:, t], cfg)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


# Not strict: the repair lands in the program, outside this file, and an
# XPASS then shows it without failing the run.
@pytest.mark.xfail(strict=False, reason="PERF.md open question 1: the "
                   "program's rolling prefill takes the padded length, "
                   "not each row's")
@pytest.mark.parametrize("name", ["starcoder2-3b"])
def test_windowed_reference_matches_program_on_a_padded_batch(name):
    """Rows of 40 and 30 tokens in one prefill padded to 40, both past a
    window of 16, then 4 decode steps: each row against the reference
    over that row alone."""
    from repro.models import api
    cfg, mc = windowed(name)
    params = model.init_weights(cfg, 80)
    toks = jax.random.randint(jax.random.PRNGKey(7), (2, 44), 0, 256)
    lens = [40, 30]
    with jax.default_matmul_precision("highest"):
        want = [reference.head(params, reference.hidden(
            params, toks[b:b + 1, :n + 4], cfg)[0, n - 1:], cfg)
            for b, n in enumerate(lens)]
    got, cache = api.prefill(
        params, {"tokens": toks[:, :40],
                 "last_pos": jnp.asarray(lens) - 1}, mc)
    cache = api.grow_cache(mc, cache, 64)
    outs = [got]
    for t in range(4):
        pos = jnp.asarray(lens) + t
        got, cache = api.decode_step(
            params, cache, {"token": toks[jnp.arange(2), pos], "pos": pos},
            mc)
        outs.append(got)
    for b in range(2):
        np.testing.assert_allclose(
            np.stack([np.asarray(o[b]) for o in outs]),
            np.asarray(want[b]), rtol=2e-4, atol=2e-4)
