"""Attention-path unit tests: chunked vs direct, decode vs full, rolling
windows, grouped (GQA) decode, RoPE."""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.models import attention as att
from repro.models.layers import rope


def mk(rng, i, shape):
    return jax.random.normal(jax.random.fold_in(rng, i), shape)


@pytest.mark.parametrize("s", [8, 64, 130, 257])
@pytest.mark.parametrize("window", [0, 32])
def test_chunked_equals_direct(s, window, rng):
    b, h, dh = 2, 2, 16
    q, k, v = (mk(rng, i, (b, s, h, dh)) for i in range(3))
    pos = jnp.arange(s)
    o1 = att.attend_chunked(q, k, v, causal=True, window=window,
                            q_chunk=64, kv_chunk=32)
    o2 = att.attend_direct(q, k, v, pos, pos, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=2e-5, rtol=2e-4)


def test_gqa_expand():
    b, s, kv, g, dh = 1, 3, 2, 3, 4
    k = mk(jax.random.PRNGKey(0), 0, (b, s, kv, dh))
    kx = att.expand_kv(k, kv * g)
    assert kx.shape == (b, s, kv * g, dh)
    for i in range(kv * g):
        np.testing.assert_array_equal(np.asarray(kx[:, :, i]),
                                      np.asarray(k[:, :, i // g]))


GROUPS = [(2, 2), (14, 2), (4, 1)]     # (query heads, kv heads)


@pytest.mark.parametrize("h,kv", GROUPS)
def test_decode_matches_direct_full(h, kv, rng):
    """Decoding token t against a cache equals direct attention over the
    full prefix, with each query head reading its group's kv head."""
    b, s, dh = 2, 9, 8
    q = mk(rng, 0, (b, s, h, dh))
    k, v = mk(rng, 1, (b, s, kv, dh)), mk(rng, 2, (b, s, kv, dh))
    pos_all = jnp.arange(s)
    full = att.attend_direct(q, att.expand_kv(k, h), att.expand_kv(v, h),
                             pos_all, pos_all, causal=True)
    cache_k = jnp.zeros((b, 16, kv, dh))
    cache_v = jnp.zeros((b, 16, kv, dh))
    for t in range(s):
        out, cache_k, cache_v = att.decode_attend(
            q[:, t:t + 1], cache_k, cache_v, k[:, t:t + 1], v[:, t:t + 1],
            jnp.asarray(t), num_heads=h)
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(full[:, t]),
                                   atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize("h,kv", GROUPS)
def test_decode_vector_pos_matches_scalar(h, kv, rng):
    """A batch at mixed per-slot positions equals each slot decoded alone
    at its own scalar position."""
    b, dh, smax = 3, 8, 16
    ck, cv = mk(rng, 1, (b, smax, kv, dh)), mk(rng, 2, (b, smax, kv, dh))
    q = mk(rng, 3, (b, 1, h, dh))
    nk, nv = mk(rng, 4, (b, 1, kv, dh)), mk(rng, 5, (b, 1, kv, dh))
    pos = jnp.asarray([2, 5, 11])
    o_v, k_v, v_v = att.decode_attend(q, ck, cv, nk, nv, pos, num_heads=h)
    for i in range(b):
        one = slice(i, i + 1)
        o_s, k_s, v_s = att.decode_attend(q[one], ck[one], cv[one], nk[one],
                                          nv[one], pos[i], num_heads=h)
        np.testing.assert_allclose(np.asarray(o_s[0]), np.asarray(o_v[i]),
                                   atol=1e-6)
        np.testing.assert_allclose(np.asarray(k_s[0]), np.asarray(k_v[i]),
                                   atol=0)
        np.testing.assert_allclose(np.asarray(v_s[0]), np.asarray(v_v[i]),
                                   atol=0)


def test_decode_never_expands_the_cache():
    """One grouped decode step (qwen2's 14 query / 2 kv heads) lowers to
    no value as large as the cache broadcast to every query head."""
    b, smax, h, kv, dh = 3, 48, 14, 2, 64
    args = (jax.ShapeDtypeStruct((b, 1, h, dh), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, smax, kv, dh), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, smax, kv, dh), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, 1, kv, dh), jnp.bfloat16),
            jax.ShapeDtypeStruct((b, 1, kv, dh), jnp.bfloat16),
            jax.ShapeDtypeStruct((b,), jnp.int32))
    text = jax.jit(lambda *a: att.decode_attend(*a, num_heads=h)).lower(
        *args).as_text()
    expanded = b * smax * h * dh
    shapes = [tuple(int(d) for d in m.split("x")[:-1])
              for m in re.findall(r"tensor<([0-9x]+[a-z0-9]+)>", text)]
    assert any(smax in sh for sh in shapes), "cache not found in the text"
    too_big = [sh for sh in shapes if np.prod(sh, dtype=np.int64) >= expanded]
    assert not too_big, too_big


@pytest.mark.parametrize("s,w", [(5, 8), (8, 8), (13, 8)])
def test_to_rolling_layout(s, w, rng):
    k = mk(rng, 0, (1, s, 1, 4))
    r = att.to_rolling(k, w)
    assert r.shape == (1, w, 1, 4)
    # position p (for p in the live window) sits at slot p % w
    for p in range(max(0, s - w), s):
        np.testing.assert_array_equal(np.asarray(r[0, p % w]),
                                      np.asarray(k[0, p]))


@pytest.mark.parametrize("h,kv", GROUPS)
def test_windowed_decode_matches_full_band(h, kv, rng):
    """Rolling-cache decode == direct banded attention, beyond one wrap."""
    b, dh, w = 1, 8, 4
    s = 11
    q = mk(rng, 0, (b, s, h, dh))
    k, v = mk(rng, 1, (b, s, kv, dh)), mk(rng, 2, (b, s, kv, dh))
    pos_all = jnp.arange(s)
    full = att.attend_direct(q, att.expand_kv(k, h), att.expand_kv(v, h),
                             pos_all, pos_all, causal=True, window=w)
    ck = jnp.zeros((b, w, kv, dh))
    cv = jnp.zeros((b, w, kv, dh))
    for t in range(s):
        out, ck, cv = att.decode_attend(
            q[:, t:t + 1], ck, cv, k[:, t:t + 1], v[:, t:t + 1],
            jnp.asarray(t), num_heads=h, window=w)
        np.testing.assert_allclose(np.asarray(out[:, 0]),
                                   np.asarray(full[:, t]),
                                   atol=2e-5, rtol=2e-4, err_msg=f"t={t}")


def test_rope_rotation_property(rng):
    """RoPE inner products depend only on relative position."""
    h, dh = 1, 16
    q = mk(rng, 0, (1, 1, h, dh))
    k = mk(rng, 1, (1, 1, h, dh))

    def score(pq, pk):
        qr = rope(q, jnp.asarray([pq])[None], 10000.0)
        kr = rope(k, jnp.asarray([pk])[None], 10000.0)
        return float(jnp.sum(qr * kr))

    assert abs(score(3, 1) - score(7, 5)) < 1e-4
    assert abs(score(3, 1) - score(4, 1)) > 1e-6   # actually rotates
