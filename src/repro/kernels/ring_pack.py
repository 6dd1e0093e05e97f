"""Ring-buffer pack kernel — the paper's gathering-write copy path on TPU.

hadroNIO's hot spot is the memcpy of many small buffers into one
contiguous ring-buffer region (paper §III-C). The TPU reading: the packed
flat gradient must be (a) carved into ring slices, (b) cast to the wire
dtype and (c) error-feedback-corrected — three elementwise passes that
naive jnp code issues as separate HBM round trips. This kernel fuses them
into ONE HBM read + one write per element, tiled through VMEM.

    wire[i]   = cast(flat[i] + ef[i], wire_dtype)
    new_ef[i] = (flat[i] + ef[i]) - f32(wire[i])

Block layout: every pass is elementwise, so the ``(n_slices,
slice_elems)`` buffer is viewed as ``(rows, LANES)`` — 128-lane rows in
memory order — and tiled by row blocks: the whole row count when it fits
one tile (a block equal to the array always lowers), otherwise
``MAX_BLOCK_ROWS``, a multiple of 16, which meets Mosaic's (8, 128) f32
and (16, 128) bf16 tiling rule; a partial last tile is masked by the
pipeline. Slices are 512-aligned by the plan
(aggregation.make_plan; ring_buffer.plan_slices rounds to 512 BYTES — at
least 128 f32 lanes), so every buffer is a whole number of rows.

``unpack_slices_kernel`` is the scattering-read counterpart — the live
unpack stage of the wire pipeline (backends/pipeline.unpack_wire): one
fused cast-from-wire-dtype + re-slice pass over the stacked collective
results, replacing a per-slice ``.astype`` epilogue.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
# (1024, 128) f32 = 512 KiB per buffer; the EF pack moves four buffers,
# double-buffered: 4 MiB of VMEM, inside the default scoped limit
MAX_BLOCK_ROWS = 1024


def _rows(total: int) -> int:
    if total % LANES:
        raise ValueError(
            f"ring_pack buffers must hold a multiple of {LANES} elements "
            f"(got {total}); the slice plan keeps slices 512-byte aligned")
    return total // LANES


def _grid_spec(rows: int):
    blk = min(rows, MAX_BLOCK_ROWS)
    return pl.cdiv(rows, blk), pl.BlockSpec((blk, LANES), lambda i: (i, 0))


def _pack_kernel(flat_ref, ef_ref, wire_ref, new_ef_ref):
    x = flat_ref[...].astype(jnp.float32)
    if ef_ref is not None:
        x = x + ef_ref[...]
    w = x.astype(wire_ref.dtype)
    wire_ref[...] = w
    if new_ef_ref is not None:
        new_ef_ref[...] = x - w.astype(jnp.float32)


def _unpack_kernel(wire_ref, out_ref):
    out_ref[...] = wire_ref[...].astype(out_ref.dtype)


def pack_slices_kernel(flat: jax.Array, ef, n_slices: int,
                       slice_elems: int, wire_dtype,
                       *, interpret: bool = False, with_ef: bool = True):
    """flat: (n_slices * slice_elems,) f32. Returns (wire (n, S) of
    wire_dtype, new_ef (n, S) f32 or None)."""
    assert flat.shape == (n_slices * slice_elems,), flat.shape
    rows = _rows(flat.shape[0])
    grid, spec = _grid_spec(rows)
    x2 = flat.reshape(rows, LANES)
    wire_shape = jax.ShapeDtypeStruct((rows, LANES), jnp.dtype(wire_dtype))

    def back(a):
        return a.reshape(n_slices, slice_elems)

    if with_ef:
        if ef is None:
            ef = jnp.zeros((rows, LANES), jnp.float32)
        wire, new_ef = pl.pallas_call(
            _pack_kernel, grid=(grid,), in_specs=[spec, spec],
            out_specs=(spec, spec),
            out_shape=(wire_shape,
                       jax.ShapeDtypeStruct((rows, LANES), jnp.float32)),
            interpret=interpret, name="ring_pack_ef")(
                x2, ef.reshape(rows, LANES))
        return back(wire), back(new_ef)

    def kernel_no_ef(flat_ref, wire_ref):
        _pack_kernel(flat_ref, None, wire_ref, None)

    wire = pl.pallas_call(
        kernel_no_ef, grid=(grid,), in_specs=[spec], out_specs=spec,
        out_shape=wire_shape, interpret=interpret, name="ring_pack")(x2)
    return back(wire), None


def unpack_slices_kernel(wire: jax.Array, out_dtype=jnp.float32,
                         *, interpret: bool = False) -> jax.Array:
    """(n, S) wire -> (n * S,) of out_dtype (one fused cast+copy pass)."""
    rows = _rows(wire.size)
    grid, spec = _grid_spec(rows)
    out = pl.pallas_call(
        _unpack_kernel, grid=(grid,), in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.dtype(out_dtype)),
        interpret=interpret, name="ring_unpack")(wire.reshape(rows, LANES))
    return out.reshape(-1)
