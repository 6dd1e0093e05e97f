"""The plain reference against the repo's model at a reduced size, in
float32 on the CPU, for both block variants of the dense family."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import harness, model, reference
from bench.tests import cells

REDUCED = dict(num_hidden_layers=3, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=128, vocab_size=256,
               torch_dtype="float32")


def reduced(name):
    return cells.shrink(harness.load_json(harness.BENCH / "configs"
                                          / f"{name}.json"), **REDUCED)


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
