"""Per-kernel shape/dtype sweeps: Pallas (interpret mode on CPU) vs the
pure-jnp oracles in kernels/ref.py (brief deliverable (c))."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


def rel_close(a, b, atol, rtol):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32),
                               atol=atol, rtol=rtol)


# -- ring pack ---------------------------------------------------------------


@pytest.mark.parametrize("n,s", [(1, 512), (3, 1024), (5, 8192)])
@pytest.mark.parametrize("wire", ["bfloat16", "float32"])
def test_pack_slices(n, s, wire, np_rng):
    flat = jnp.asarray(np_rng.normal(size=(n * s,)), jnp.float32)
    ef = jnp.asarray(np_rng.normal(size=(n, s)) * 0.01, jnp.float32)
    w1, e1 = ops.pack_slices(flat, ef, n_slices=n, slice_elems=s,
                             wire_dtype=wire)
    w2, e2 = ref.pack_slices(flat, ef, n_slices=n, slice_elems=s,
                             wire_dtype=wire)
    rel_close(w1, w2, 0, 0)
    rel_close(e1, e2, 0, 0)
    rel_close(ops.unpack_slices(w1), ref.unpack_slices(w2), 0, 0)


@pytest.mark.parametrize("n,s", [(3, 4608), (5, 1536), (7, 2560),
                                 (1, 512 * 11), (3, 512 * 129)])
@pytest.mark.parametrize("wire", ["bfloat16", "float32"])
def test_pack_slices_odd_alignment(n, s, wire, np_rng):
    """Odd slice counts and 512-aligned slice lengths that are no
    multiple of 4096, including a buffer of more rows than one tile whose
    last tile is partial: pallas (interpret on CPU) must match the jnp
    oracle bit-for-bit."""
    assert s % (8 * 128 * 4) != 0
    flat = jnp.asarray(np_rng.normal(size=(n * s,)), jnp.float32)
    ef = jnp.asarray(np_rng.normal(size=(n, s)) * 0.01, jnp.float32)
    w1, e1 = ops.pack_slices(flat, ef, n_slices=n, slice_elems=s,
                             wire_dtype=wire)
    w2, e2 = ref.pack_slices(flat, ef, n_slices=n, slice_elems=s,
                             wire_dtype=wire)
    rel_close(w1, w2, 0, 0)
    rel_close(e1, e2, 0, 0)
    rel_close(ops.unpack_slices(w1), ref.unpack_slices(w2), 0, 0)


def test_pack_slices_no_ef(np_rng):
    flat = jnp.asarray(np_rng.normal(size=(2 * 512,)), jnp.float32)
    w1, e1 = ops.pack_slices(flat, None, n_slices=2, slice_elems=512,
                             with_ef=False)
    w2, _ = ref.pack_slices(flat, None, n_slices=2, slice_elems=512,
                            with_ef=False)
    assert e1 is None
    rel_close(w1, w2, 0, 0)


def test_pack_ef_telescopes(np_rng):
    """Error feedback property: sum of wire values + final residual equals
    the sum of inputs exactly (per element, over steps)."""
    n, s = 2, 512
    ef = None
    total_wire = np.zeros((n, s), np.float32)
    total_in = np.zeros((n, s), np.float32)
    for step in range(4):
        flat = jnp.asarray(np_rng.normal(size=(n * s,)), jnp.float32)
        total_in += np.asarray(flat).reshape(n, s)
        wire, ef = ops.pack_slices(flat, ef, n_slices=n, slice_elems=s)
        total_wire += np.asarray(wire, np.float32)
    np.testing.assert_allclose(total_wire + np.asarray(ef), total_in,
                               atol=1e-5)


# -- flash attention ---------------------------------------------------------


@pytest.mark.parametrize("b,s,h,dh", [(2, 128, 2, 64), (1, 257, 3, 32),
                                      (1, 64, 1, 128), (2, 96, 4, 16)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 48),
                                           (False, 0)])
def test_flash_attention(b, s, h, dh, causal, window, rng):
    q = jax.random.normal(jax.random.fold_in(rng, 1), (b, s, h, dh))
    k = jax.random.normal(jax.random.fold_in(rng, 2), (b, s, h, dh))
    v = jax.random.normal(jax.random.fold_in(rng, 3), (b, s, h, dh))
    o1 = ops.flash_attention(q, k, v, causal=causal, window=window,
                             bq=64, bk=64)
    o2 = ref.flash_attention(q, k, v, causal=causal, window=window)
    rel_close(o1, o2, 2e-4, 2e-3)


def test_flash_attention_bf16(rng):
    b, s, h, dh = 1, 128, 2, 64
    mk = lambda i: jax.random.normal(jax.random.fold_in(rng, i),
                                     (b, s, h, dh)).astype(jnp.bfloat16)
    q, k, v = mk(1), mk(2), mk(3)
    o1 = ops.flash_attention(q, k, v, bq=64, bk=64)
    o2 = ref.flash_attention(q, k, v)
    assert o1.dtype == jnp.bfloat16
    rel_close(o1, o2, 3e-2, 5e-2)


def test_flash_matches_model_attention(rng):
    """The kernel agrees with the model's chunked online-softmax path."""
    from repro.models.attention import attend_chunked
    b, s, h, dh = 1, 160, 2, 32
    q = jax.random.normal(jax.random.fold_in(rng, 1), (b, s, h, dh))
    k = jax.random.normal(jax.random.fold_in(rng, 2), (b, s, h, dh))
    v = jax.random.normal(jax.random.fold_in(rng, 3), (b, s, h, dh))
    o1 = ops.flash_attention(q, k, v, bq=64, bk=64)
    o2 = attend_chunked(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    rel_close(o1, o2, 2e-4, 2e-3)


# -- WKV6 --------------------------------------------------------------------


@pytest.mark.parametrize("b,t,h,hs", [(2, 64, 2, 16), (1, 37, 3, 32),
                                      (1, 128, 1, 64)])
@pytest.mark.parametrize("chunk", [16, 32])
def test_wkv6(b, t, h, hs, chunk, rng):
    f = lambda i, sh: jax.random.normal(jax.random.fold_in(rng, i), sh)
    r, k, v = f(1, (b, t, h, hs)), f(2, (b, t, h, hs)), f(3, (b, t, h, hs))
    w = jax.nn.sigmoid(f(4, (b, t, h, hs))) * 0.85 + 0.1
    u = f(5, (h, hs)) * 0.1
    s0 = f(6, (b, h, hs, hs)) * 0.1
    y1, sf1 = ops.wkv6(r, k, v, w, u, s0, chunk=chunk)
    y2, sf2 = ref.wkv6(r, k, v, w, u, s0)
    rel_close(y1, y2, 2e-3, 2e-3)
    rel_close(sf1, sf2, 2e-3, 2e-3)


def test_wkv6_extreme_decay(rng):
    """Numerical safety: near-zero and near-one decays (the log-space
    formulation must not overflow)."""
    b, t, h, hs = 1, 32, 1, 16
    f = lambda i, sh: jax.random.normal(jax.random.fold_in(rng, i), sh)
    r, k, v = f(1, (b, t, h, hs)), f(2, (b, t, h, hs)), f(3, (b, t, h, hs))
    w = jnp.concatenate([jnp.full((b, t // 2, h, hs), 1e-6),
                         jnp.full((b, t - t // 2, h, hs), 1.0 - 1e-6)], 1)
    u = f(5, (h, hs)) * 0.1
    s0 = jnp.zeros((b, h, hs, hs))
    y1, sf1 = ops.wkv6(r, k, v, w, u, s0, chunk=16)
    y2, sf2 = ref.wkv6(r, k, v, w, u, s0)
    assert np.isfinite(np.asarray(y1)).all()
    rel_close(y1, y2, 5e-3, 5e-3)


# -- RG-LRU ------------------------------------------------------------------


@pytest.mark.parametrize("b,t,w", [(2, 64, 128), (1, 100, 65), (3, 16, 512)])
def test_rglru(b, t, w, rng):
    a = jax.nn.sigmoid(jax.random.normal(jax.random.fold_in(rng, 1),
                                         (b, t, w))) * 0.95
    bb = jax.random.normal(jax.random.fold_in(rng, 2), (b, t, w))
    h0 = jax.random.normal(jax.random.fold_in(rng, 3), (b, w))
    y1, hf1 = ops.rglru(a, bb, h0, chunk=32, wblock=64)
    y2, hf2 = ref.rglru(a, bb, h0)
    rel_close(y1, y2, 2e-4, 2e-4)
    rel_close(hf1, hf2, 2e-4, 2e-4)


def test_rglru_matches_model(rng):
    """Kernel output matches the model's associative-scan RG-LRU core."""
    from repro.models.hybrid import _rglru
    b, t, lw, nb = 1, 48, 64, 4
    p = {
        "wa": jax.random.normal(jax.random.fold_in(rng, 1),
                                (nb, lw // nb, lw // nb)) * 0.1,
        "ba": jnp.zeros((lw,)),
        "wx": jax.random.normal(jax.random.fold_in(rng, 2),
                                (nb, lw // nb, lw // nb)) * 0.1,
        "bx": jnp.zeros((lw,)),
        "lam": jnp.ones((lw,)),
    }
    y = jax.random.normal(jax.random.fold_in(rng, 3), (b, t, lw))
    h0 = jax.random.normal(jax.random.fold_in(rng, 4), (b, lw)) * 0.1
    hs_model, hlast_model = _rglru(y, p, h0, nb, lw // nb)

    # rebuild (a, gated) exactly as the model does, then run the kernel
    from repro.models.hybrid import RGLRU_C
    yb = y.reshape(b, t, nb, lw // nb)
    r = jax.nn.sigmoid(
        jnp.einsum("btni,nij->btnj", yb, p["wa"]).reshape(b, t, lw)
        + p["ba"])
    i = jax.nn.sigmoid(
        jnp.einsum("btni,nij->btnj", yb, p["wx"]).reshape(b, t, lw)
        + p["bx"])
    a = jnp.exp(-RGLRU_C * jax.nn.softplus(p["lam"]) * r)
    gated = jnp.sqrt(jnp.maximum(1 - a**2, 1e-12)) * (i * y)
    hs_kern, hlast_kern = ops.rglru(a, gated, h0, chunk=16, wblock=64)
    rel_close(hs_kern, hs_model, 2e-4, 2e-4)
    rel_close(hlast_kern, hlast_model, 2e-4, 2e-4)
