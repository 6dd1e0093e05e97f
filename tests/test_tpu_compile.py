"""Ahead-of-time compiles for a described (not attached) TPU v5e chip.

Interpret-mode tests cannot see Mosaic's tiling rules, scoped-VMEM limits
or a program that does not fit HBM; the TPU compiler can, and it is
installed here. Each test lowers a main-path program at its real size and
compiles it for ``topo.devices[0]`` of a described ``v5e:2x2`` host.

The topology is described inside a module-scoped fixture, never at import
time: only one process may load the TPU library, and the suite runs under
several workers that all import this file.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs.base import CommConfig
from repro.configs.registry import get_config
from repro.kernels import ring_pack as rp
from repro.models import api
from repro.serving import dispatch

SLICE_BYTES = 4 * 1024 * 1024      # the training default (--slice-bytes)
N_SLICES = 8
SLICE_ELEMS = SLICE_BYTES // 4


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 — skip reason
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


@pytest.mark.parametrize("wire", ["bfloat16", "float32"])
def test_ring_pack_with_ef_compiles(one_chip, wire):
    flat = jax.ShapeDtypeStruct((N_SLICES * SLICE_ELEMS,), jnp.float32,
                                sharding=one_chip)
    ef = jax.ShapeDtypeStruct((N_SLICES, SLICE_ELEMS), jnp.float32,
                              sharding=one_chip)
    _compile(lambda x, e: rp.pack_slices_kernel(
        x, e, N_SLICES, SLICE_ELEMS, jnp.dtype(wire)), flat, ef)


def test_ring_pack_without_ef_compiles(one_chip):
    flat = jax.ShapeDtypeStruct((N_SLICES * SLICE_ELEMS,), jnp.float32,
                                sharding=one_chip)
    _compile(lambda x: rp.pack_slices_kernel(
        x, None, N_SLICES, SLICE_ELEMS, jnp.float32, with_ef=False), flat)


def test_ring_unpack_compiles(one_chip):
    wire = jax.ShapeDtypeStruct((N_SLICES, SLICE_ELEMS), jnp.bfloat16,
                                sharding=one_chip)
    _compile(lambda w: rp.unpack_slices_kernel(w, jnp.float32), wire)


def test_qwen2_serve_decode_step_compiles(topo):
    """One decode step of full-width qwen2-0.5b through the hadronio
    serve wire on a one-chip mesh, at the batch and cache length the
    chip smoke serves with."""
    cfg = get_config("qwen2-0.5b")
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    comm = CommConfig(mode="hadronio", channels=4)
    step = dispatch.make_serve_step(cfg, comm, mesh, channel_indices=(0, 1))
    batch, max_len = 4, 512
    compiled = step.decode.lower(
        api.abstract(cfg), api.cache_specs(cfg, batch, max_len),
        {"token": jax.ShapeDtypeStruct((batch,), jnp.int32),
         "pos": jax.ShapeDtypeStruct((batch,), jnp.int32)}).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16 * 2**30, total
