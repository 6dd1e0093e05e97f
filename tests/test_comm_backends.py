"""The pluggable CommBackend layer (docs/COMM_BACKENDS.md).

Single-device coverage of the registry contract, the cross-backend
numerical parity of ``sync_grads``, and the emission structure of the
beyond-paper ``hadronio_overlap`` mode (independent collectives emitted
before the loss epilogue). Multi-device numerics are exercised by
tests/distributed/check_tac_modes.py / check_steps.py.
"""
import re

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.base import CommConfig, RunConfig, ShapeConfig
from repro.configs.registry import get_config
from repro.core import aggregation as agg
from repro.core import tac
from repro.core.backends import (CommBackend, available_modes, get_backend,
                                 register, scatter_group_size)
from repro.core.backends.hadronio_overlap import make_buckets
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh

ALL_MODES = ("gspmd", "sockets", "vma", "hadronio", "hadronio_rs",
             "hadronio_overlap", "hadronio_overlap_rs")


# ---------------------------------------------------------------------------
# Registry contract
# ---------------------------------------------------------------------------


def test_registry_roundtrip():
    """Every registered mode resolves, lists, and self-identifies."""
    modes = available_modes()
    for m in ALL_MODES:
        assert m in modes, m
    for m in modes:
        b = get_backend(m)
        assert isinstance(b, CommBackend)
        assert b.name == m
        # singletons: repeated lookup is the same object
        assert get_backend(m) is b


def test_registry_unknown_mode():
    with pytest.raises(KeyError, match="hadronio"):   # lists known modes
        get_backend("carrier_pigeon")


def test_registry_rejects_duplicate_names():
    with pytest.raises(ValueError, match="already registered"):
        @register("hadronio")
        class Dupe(CommBackend):   # pragma: no cover - never instantiated
            def sync(self, grads, ctx):
                raise NotImplementedError


def test_config_validation_derives_from_registry():
    for m in available_modes():
        assert CommConfig(mode=m).mode == m
    with pytest.raises(AssertionError, match="registered"):
        CommConfig(mode="nope")


def test_capability_flags():
    assert not get_backend("gspmd").manual
    for m in ALL_MODES[1:]:
        assert get_backend(m).manual, m
    assert get_backend("hadronio_rs").zero1
    assert get_backend("hadronio_overlap_rs").zero1
    for m in ("sockets", "vma", "hadronio", "hadronio_overlap"):
        assert not get_backend(m).zero1, m


def test_scatter_group_size():
    hier = CommConfig(mode="hadronio_rs", hierarchical=True)
    flat = CommConfig(mode="hadronio_rs", hierarchical=False)
    assert scatter_group_size(8, 2, hier) == 4     # in-pod group
    assert scatter_group_size(8, 2, flat) == 8
    assert scatter_group_size(8, 1, hier) == 8


def test_overlap_supports_compression():
    """Per-bucket EF keying (ISSUE 2): the overlap modes now accept wire
    compression — validate() passes and the backend declares EF state."""
    for mode in ("hadronio_overlap", "hadronio_overlap_rs"):
        for compress in ("bf16", "int8_ef"):
            comm = CommConfig(mode=mode, compress=compress,
                              hierarchical=False)
            get_backend(mode).validate(comm)     # must not raise
            assert get_backend(mode).needs_ef(comm)


def test_comm_config_rejects_bad_values():
    """Clear errors for the enum/range fields (ISSUE 2 satellite)."""
    with pytest.raises(ValueError, match="channels"):
        CommConfig(mode="hadronio", channels=0, hierarchical=False)
    with pytest.raises(ValueError, match="channels"):
        CommConfig(mode="hadronio", channels=-3, hierarchical=False)
    with pytest.raises(ValueError, match="compress"):
        CommConfig(mode="hadronio", compress="fp4", hierarchical=False)
    with pytest.raises(ValueError, match="pack"):
        CommConfig(mode="hadronio", pack="cuda", hierarchical=False)
    with pytest.raises(ValueError, match="aggregate"):
        CommConfig(mode="hadronio", aggregate="tensor", hierarchical=False)


def test_unsupported_compress_rejected_at_validate():
    """Strategies that cannot honor a codec say so instead of silently
    ignoring it."""
    for mode, compress in [("sockets", "bf16"), ("sockets", "int8_ef"),
                           ("vma", "int8_ef"), ("gspmd", "bf16")]:
        comm = CommConfig(mode=mode, compress=compress, hierarchical=False)
        with pytest.raises(ValueError, match="compress"):
            get_backend(mode).validate(comm)


def test_overlap_bucketing():
    # 4-byte items; 3 leaves of 100/200/50 elems, 512B buckets, reverse order
    buckets = make_buckets([100, 200, 50], 512 // 4)
    assert buckets[0][0] == 2                      # last leaf first
    flat = [i for b in buckets for i in b]
    assert sorted(flat) == [0, 1, 2]               # exact partition
    for b in buckets[:-1]:
        assert sum(100 if i == 0 else 200 if i == 1 else 50
                   for i in b) <= 512 // 4 or len(b) == 1
    # one oversized leaf still gets a bucket
    assert make_buckets([10_000], 64) == [[0]]


# ---------------------------------------------------------------------------
# Cross-backend parity (1-device ring: psum == identity, so every mode
# must return the input gradients exactly — pack/slice/bucket roundtrips
# included)
# ---------------------------------------------------------------------------


def _model_grads():
    cfg = get_config("qwen2-0.5b-reduced")
    from repro.models import api
    return api.init(jax.random.PRNGKey(0), cfg)


@pytest.mark.parametrize("mode", ["sockets", "vma", "hadronio",
                                  "hadronio_overlap", "hadronio_rs",
                                  "hadronio_overlap_rs"])
def test_cross_backend_parity_small_model(mode):
    grads = _model_grads()
    comm = CommConfig(mode=mode, slice_bytes=64 * 1024, hierarchical=False)
    mesh = make_mesh((1,), ("data",))

    def body(g):
        r = tac.sync_grads(g, comm, data_axis=("data",))
        # zero1: reconstruct via the backend's own gather epilogue
        return get_backend(mode).gathered_grads(r, g)

    out = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                out_specs=P(), check_vma=False))(grads)
    flat_in, _ = jax.tree.flatten(grads)
    flat_out, treedef_out = jax.tree.flatten(out)
    assert jax.tree.structure(grads) == treedef_out
    for a, b in zip(flat_in, flat_out):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# Emission structure of the beyond-paper overlap mode
# ---------------------------------------------------------------------------

_AR_RE = re.compile(
    r'%(\S+)\s*=\s*"?stablehlo\.all_reduce"?\s*\(([^)]*)\)')


def _lower_tac_step(mode: str, slice_bytes: int = 16 * 1024):
    cfg = get_config("qwen2-0.5b-reduced")
    run = RunConfig(model=cfg, shape=ShapeConfig("t", "train", 16, 4),
                    comm=CommConfig(mode=mode, slice_bytes=slice_bytes,
                                    hierarchical=False))
    mesh = make_mesh((1,), ("data",))
    with jax.set_mesh(mesh):
        step_fn, state_sh, _ = steps_mod.make_train_step(run, mesh)
        state = steps_mod.init_tac_state(jax.random.PRNGKey(0), run, 1)
        batch = {"tokens": jnp.zeros((4, 16), jnp.int32),
                 "labels": jnp.zeros((4, 16), jnp.int32)}
        return jax.jit(step_fn).lower(state, batch).as_text()


def test_overlap_emits_independent_collectives():
    """The overlap backend must emit >= 2 all-reduces that do not feed
    each other (independence is what the latency-hiding scheduler needs),
    and the gradient collectives must precede the scalar loss epilogue."""
    text = _lower_tac_step("hadronio_overlap")
    matches = list(_AR_RE.finditer(text))
    assert len(matches) >= 2, f"expected >=2 all_reduce, got {len(matches)}"
    results = {m.group(1) for m in matches}
    for m in matches:
        operands = {o.strip().lstrip("%") for o in m.group(2).split(",")}
        assert not (operands & results), \
            f"all_reduce feeds another all_reduce: {m.group(0)}"
    # the loss epilogue (scalar f32 all-reduce) comes after at least one
    # gradient-bucket collective in emission order
    scalar = [i for i, m in enumerate(matches)
              if "tensor<f32>" in text[m.start():m.start() + 400]]
    assert scalar and scalar[-1] > 0, \
        "scalar loss all-reduce should follow gradient collectives"


def test_overlap_matches_bucket_count():
    """One all-reduce per bucket (+1 for the loss) — send-call count, the
    paper's messages axis."""
    cfg = get_config("qwen2-0.5b-reduced")
    from repro.models import api
    params = api.abstract(cfg)
    leaves = jax.tree.leaves(params)
    sizes = [int(np.prod(l.shape)) if l.shape else 1 for l in leaves]
    slice_bytes = 16 * 1024
    n_buckets = len(make_buckets(sizes, slice_bytes))
    assert n_buckets >= 2       # the config is small but multi-bucket
    text = _lower_tac_step("hadronio_overlap", slice_bytes)
    n_ar = len(_AR_RE.findall(text))
    assert n_ar == n_buckets + 1, (n_ar, n_buckets)


def test_channel_count_is_a_real_lever():
    """comm.channels bounds in-flight collectives: with fewer channels
    than slices, same-channel collectives are chained through
    optimization_barrier (visible in the emitted HLO); numerics are
    unchanged either way."""
    grads = _model_grads()
    mesh = make_mesh((1,), ("data",))
    outs = {}
    for n_ch in (1, 64):
        comm = CommConfig(mode="hadronio", slice_bytes=16 * 1024,
                          channels=n_ch, hierarchical=False)
        f = jax.jit(jax.shard_map(
            lambda g: tac.sync_grads(g, comm, data_axis=("data",)).grads,
            mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False))
        outs[n_ch] = f(grads)
        text = f.lower(grads).as_text()
        n_barriers = text.count("stablehlo.optimization_barrier")
        if n_ch == 1:
            assert n_barriers > 0, "serialized channel must chain ops"
        else:
            assert n_barriers == 0, "independent slices need no chaining"
    for a, b in zip(jax.tree.leaves(outs[1]), jax.tree.leaves(outs[64])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_hadronio_op_count_matches_plan():
    """hadronio emits exactly one collective per ring-buffer slice (+1
    loss) — the gathering-write invariant, now routed via channels."""
    cfg = get_config("qwen2-0.5b-reduced")
    from repro.models import api
    comm = CommConfig(mode="hadronio", slice_bytes=16 * 1024,
                      hierarchical=False)
    plan = agg.make_plan(api.abstract(cfg), comm)
    text = _lower_tac_step("hadronio", 16 * 1024)
    n_ar = len(_AR_RE.findall(text))
    assert n_ar == plan.n_slices + 1, (n_ar, plan.n_slices)


def test_overlap_rs_emits_one_reduce_scatter_per_bucket():
    """The bucketed ZeRO-1 mode: one reduce-scatter per bucket in the
    lowered step (the overlap property on the scatter path), ahead of
    the loss epilogue's all-reduce."""
    cfg = get_config("qwen2-0.5b-reduced")
    from repro.models import api
    from repro.core.backends.hadronio_overlap_rs import rs_bucket_plan
    slice_bytes = 16 * 1024
    comm = CommConfig(mode="hadronio_overlap_rs", slice_bytes=slice_bytes,
                      hierarchical=False)
    plan = rs_bucket_plan(api.abstract(cfg), comm, 1)
    text = _lower_tac_step("hadronio_overlap_rs", slice_bytes)
    n_rs = text.count("stablehlo.reduce_scatter")
    assert n_rs == plan.n_buckets, (n_rs, plan.n_buckets)


# ---------------------------------------------------------------------------
# The pack stage (comm.pack): pallas fused kernel vs jnp reference
# ---------------------------------------------------------------------------


def _pack_comm(compress, pack):
    return CommConfig(mode="hadronio", compress=compress, pack=pack,
                      hierarchical=False)


def test_pack_stage_identical_wire_bytes(np_rng):
    """comm.pack='pallas' and 'jnp' must produce bit-identical wire
    bytes (and residuals): the fused kernel is a copy-path optimization,
    never a numerics change."""
    from repro.core.backends import pipeline
    slices = jnp.asarray(np_rng.normal(size=(3, 1536)), jnp.float32)
    ef = jnp.asarray(np_rng.normal(size=(3, 1536)) * 0.01, jnp.float32)
    for compress in ("none", "bf16"):
        outs = {}
        for pack in ("jnp", "pallas"):
            e = ef if compress == "bf16" else None
            wire, new_ef, scale = pipeline.pack_wire(
                slices, e, _pack_comm(compress, pack))
            assert scale is None
            outs[pack] = (wire, new_ef)
        wj, ej = outs["jnp"]
        wp, ep = outs["pallas"]
        assert wj.dtype == wp.dtype
        np.testing.assert_array_equal(
            np.asarray(wj).view(np.uint8), np.asarray(wp).view(np.uint8))
        if compress == "bf16":
            np.testing.assert_array_equal(np.asarray(ej), np.asarray(ep))


def test_bf16_residual_exact_under_jit(np_rng):
    """The jitted jnp pack stage's residual is x - f32(bf16(x)) exactly.
    XLA:TPU folds an f32->bf16->f32 convert pair inside a fusion (the
    residual came back all zeros on a v5e); the CPU compiler keeps the
    pair, so the lowering must round through reduce_precision instead."""
    import ml_dtypes
    from repro.core.backends import pipeline
    slices = jnp.asarray(np_rng.normal(size=(2, 1024)), jnp.float32)
    ef = jnp.asarray(np_rng.normal(size=(2, 1024)) * 0.01, jnp.float32)
    f = jax.jit(lambda s, e: pipeline.pack_wire(
        s, e, _pack_comm("bf16", "jnp"))[:2])
    assert "reduce_precision" in f.lower(slices, ef).as_text()
    x = np.asarray(slices) + np.asarray(ef)
    _, new_ef = f(slices, ef)
    want = x - x.astype(ml_dtypes.bfloat16).astype(np.float32)
    np.testing.assert_array_equal(np.asarray(new_ef), want)
    assert np.count_nonzero(want) > want.size // 2


def test_pack_stage_int8_always_jnp(np_rng):
    """int8 needs an amax reduction the kernel does not fuse: both pack
    settings take the identical jnp path."""
    from repro.core.backends import pipeline
    slices = jnp.asarray(np_rng.normal(size=(2, 512)), jnp.float32)
    q1, e1, s1 = pipeline.pack_wire(slices, None, _pack_comm("int8_ef",
                                                             "jnp"))
    q2, e2, s2 = pipeline.pack_wire(slices, None, _pack_comm("int8_ef",
                                                             "pallas"))
    np.testing.assert_array_equal(np.asarray(q1), np.asarray(q2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(e1), np.asarray(e2))


def test_unpack_stage_identical_outputs(np_rng):
    """The unpack stage (scattering read) mirrors the pack-stage harness
    discipline: pallas and jnp implementations produce bit-identical f32
    outputs from the same wire bytes; a wire already in the target dtype
    is returned untouched (no copy pass)."""
    from repro.core.backends import pipeline
    src = jnp.asarray(np_rng.normal(size=(3, 1536)), jnp.float32)
    wire = src.astype(jnp.bfloat16)
    outs = {p: pipeline.unpack_wire(wire, _pack_comm("bf16", p))
            for p in ("jnp", "pallas")}
    for p, o in outs.items():
        assert o.dtype == jnp.float32 and o.shape == wire.shape, p
    np.testing.assert_array_equal(np.asarray(outs["jnp"]),
                                  np.asarray(outs["pallas"]))
    # bf16 -> f32 widening is exact: the unpack stage loses nothing
    np.testing.assert_array_equal(np.asarray(outs["jnp"]),
                                  np.asarray(wire, np.float32))
    for p in ("jnp", "pallas"):
        assert pipeline.unpack_wire(src, _pack_comm("none", p)) is src


# ---------------------------------------------------------------------------
# Channel-count autotune (benchmarks/latency.py, ROADMAP item)
# ---------------------------------------------------------------------------


def test_channel_autotune_smoke():
    """The sweep runs on the live mesh and returns a channel count from
    the swept set, plus the recommended-default row for the CSV."""
    from benchmarks.latency import autotune_channels
    best, rows = autotune_channels(msg_size=1024, channels=(1, 2), iters=1)
    assert best in (1, 2)
    rec = [r for r in rows if r.metric == "recommended_channels"]
    assert len(rec) == 1 and rec[0].value == best
    assert CommConfig(mode="hadronio", channels=best,
                      hierarchical=False).channels == best


def test_autotune_rows_carry_mode_label():
    """The autotune rows thread the ACTUAL mode name into the CSV (they
    used to hard-code "hadronio"), so sweeps over the overlap modes stay
    distinguishable."""
    from benchmarks.latency import autotune_channels
    _, rows = autotune_channels(msg_size=1024, channels=(1,), iters=1,
                                mode="hadronio_overlap_rs")
    assert rows and all(r.mode == "hadronio_overlap_rs" for r in rows)


def test_slice_bytes_autotune_smoke():
    """The slice-granularity sweep (ROADMAP follow-up) runs the LIVE wire
    pipeline on this mesh, returns a granularity from the swept set, and
    derives the recommended-default row from the already-measured points
    (no re-measurement)."""
    from benchmarks.latency import autotune_slice_bytes
    best, rows = autotune_slice_bytes(payload_bytes=64 * 1024,
                                      slice_sizes=(4096, 16384),
                                      channels=2, iters=1)
    assert best in (4096, 16384)
    measured = [r for r in rows if r.metric == "sweep_slice_goodput"]
    assert len(measured) == 2 and all(r.kind == "measured"
                                      for r in measured)
    rec = [r for r in rows if r.metric == "recommended_slice_bytes"]
    assert len(rec) == 1 and rec[0].value == best and rec[0].kind == "derived"
    assert CommConfig(mode="hadronio", slice_bytes=best,
                      hierarchical=False).slice_bytes == best


def test_slice_bytes_autotune_sweeps_aggregate_axis():
    """The same sweep parameterizes over the new aggregate axis — the
    channel-flush pipeline is measurable per mesh too."""
    from benchmarks.latency import autotune_slice_bytes
    best, rows = autotune_slice_bytes(payload_bytes=64 * 1024,
                                      slice_sizes=(16384,), channels=2,
                                      aggregate="channel", iters=1)
    assert best == 16384 and rows
