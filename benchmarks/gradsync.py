"""Gradient-sync table (new — the paper's technique applied to its real
target): per-mode HLO collective op count + bytes for REAL model
gradients, plus measured step time on the host mesh.

This is the end-to-end restatement of Figs. 4/6/8: the "messages" are a
model's gradient tensors (hundreds of small buffers), the "flush" is the
TAC pack, and the op-count column is exactly the paper's send-call count.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import (Row, block, derived_collective_time,
                               percentile_rows, timeit_samples)
from repro.core.backends import available_modes, get_backend
from repro.configs.base import CommConfig, RunConfig, ShapeConfig
from repro.configs.registry import get_config
from repro.data import DataConfig, SyntheticSource, batch_at
from repro.launch import hlo_analysis as hlo
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh

# the paper's four modes in presentation order, then every other
# registered manual mode (e.g. hadronio_overlap) — registry-derived so a
# newly registered backend lands in the table without edits here
PAPER_MODES = ("sockets", "vma", "hadronio", "hadronio_rs")
MODES = PAPER_MODES + tuple(m for m in available_modes()
                            if get_backend(m).manual and m not in PAPER_MODES)


def run(mesh=None, *, arch: str = "qwen1.5-4b-reduced",
        seq_len: int = 64, modes=MODES, slice_bytes: int = 256 * 1024,
        iters: int = 5, flush_evidence: bool = True):
    if mesh is None:
        n = len(jax.devices())
        mesh = make_mesh((n,), ("data",))
    n_dev = int(np.prod(list(mesh.shape.values())))
    cfg = get_config(arch)
    shape = ShapeConfig("bench", "train", seq_len, n_dev)
    src = SyntheticSource(cfg.vocab_size, 0)
    batch_np = batch_at(src, DataConfig(seq_len, n_dev), 0)
    n_grads = len(jax.tree.leaves(
        __import__("repro.models.api", fromlist=["specs"]).specs(cfg)))

    rows = []
    with jax.set_mesh(mesh):
        for mode in modes:
            run_cfg = RunConfig(
                model=cfg, shape=shape,
                comm=CommConfig(mode=mode, slice_bytes=slice_bytes,
                                hierarchical=False))
            step_fn, state_sh, batch_sh_fn = steps_mod.make_train_step(
                run_cfg, mesh)
            state = jax.device_put(
                steps_mod.init_tac_state(jax.random.PRNGKey(0), run_cfg,
                                         n_dev)
                if get_backend(mode).manual else
                steps_mod.init_train_state(jax.random.PRNGKey(0), run_cfg),
                state_sh)
            batch = jax.device_put(batch_np, batch_sh_fn(mesh, batch_np))
            jitted = jax.jit(step_fn)
            lowered = jitted.lower(state, batch)
            emitted = hlo.stablehlo_collective_stats(lowered.as_text())
            compiled = lowered.compile()
            stats = hlo.collective_stats(compiled.as_text())

            def one():
                nonlocal state
                state, m = jitted(state, batch)
                jax.block_until_ready(m["loss"])

            samples = timeit_samples(one, warmup=1, iters=iters)
            t = float(np.median(samples))
            rows.append(Row("gradsync", "table-gradsync", mode, 0, n_dev,
                            "emitted_collective_ops", emitted.total_ops,
                            "ops", "derived"))
            rows.append(Row("gradsync", "table-gradsync", mode, 0, n_dev,
                            "emitted_collective_bytes",
                            emitted.total_bytes, "B", "derived"))
            rows.append(Row("gradsync", "table-gradsync", mode, 0, n_dev,
                            "collective_ops", stats.total_ops, "ops",
                            "derived"))
            rows.append(Row("gradsync", "table-gradsync", mode, 0, n_dev,
                            "collective_bytes", stats.total_bytes, "B",
                            "derived"))
            rows.append(Row("gradsync", "table-gradsync", mode, 0, n_dev,
                            "step_time", t * 1e3, "ms", "measured"))
            rows.extend(percentile_rows("gradsync", "table-gradsync", mode,
                                        0, n_dev, samples,
                                        metric="step_time", unit="ms",
                                        scale=1e3))
            rows.append(Row("gradsync", "table-gradsync", mode, 0, n_dev,
                            "sync_v5e_model",
                            derived_collective_time(stats) * 1e3, "ms",
                            "derived"))
            rows.append(Row("gradsync", "table-gradsync", mode, 0, n_dev,
                            "n_grad_tensors", n_grads, "tensors",
                            "derived"))

        if flush_evidence:
            rows.extend(_flush_evidence_rows(mesh, cfg, shape, n_dev,
                                             slice_bytes))
    return rows


def _flush_evidence_rows(mesh, cfg, shape, n_dev: int,
                         slice_bytes: int) -> list:
    """The flush-axis evidence table: for the overlap modes under
    ``aggregate="channel"`` with fewer channels than buckets, compare
    ``flush="step"`` vs ``"ready"`` on the EMITTED program — collective
    op count (same sync flushes either way; for ``hadronio_overlap_rs``
    the count DROPS under ``ready`` because the ZeRO-1 update epilogue
    legitimately merges its all-gathers per channel flush,
    ``gather_flush_groups``) and the position of the first collective
    among all emitted ops
    (``hlo_analysis.first_collective_position``): the readiness-driven
    schedule emits the first gathering write before the later buckets'
    pack ops, which is the overlap the ROADMAP follow-up asked for."""
    rows = []
    overlap_modes = [m for m in MODES if m.startswith("hadronio_overlap")]
    for mode in overlap_modes:
        for flush in ("step", "ready"):
            run_cfg = RunConfig(
                model=cfg, shape=shape,
                comm=CommConfig(mode=mode, slice_bytes=slice_bytes,
                                channels=2, aggregate="channel",
                                flush=flush, hierarchical=False))
            step_fn, state_sh, batch_sh_fn = steps_mod.make_train_step(
                run_cfg, mesh)
            state_sds = steps_mod.abstract_tac_state(run_cfg, n_dev)
            batch_sds = {
                "tokens": jax.ShapeDtypeStruct(
                    (n_dev, shape.seq_len), jnp.int32),
                "labels": jax.ShapeDtypeStruct(
                    (n_dev, shape.seq_len), jnp.int32)}
            text = jax.jit(step_fn).lower(state_sds, batch_sds).as_text()
            emitted = hlo.stablehlo_collective_stats(text)
            pos = hlo.first_collective_position(text)
            rows.append(Row("gradsync", "flush-evidence", mode, 0, 2,
                            f"emitted_collective_ops:{flush}",
                            emitted.total_ops, "ops", "derived"))
            if pos is not None:          # None = no collectives emitted
                first, total = pos
                rows.append(Row("gradsync", "flush-evidence", mode, 0, 2,
                                f"first_collective_pos:{flush}",
                                first / max(total, 1), "frac", "derived"))
    return rows
