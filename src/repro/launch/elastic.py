"""Elastic scaling: continue a run on a different device count / mesh.

Checkpoints are mesh-agnostic (full host arrays per leaf — see
checkpoint/store.py), so elasticity reduces to: build the new mesh, derive
the new shardings from the same logical rules, restore, continue. The two
things that must be re-derived on a scale change:

* ``CommConfig``-dependent state — the ZeRO-1 modes keep *flat,
  ring-sharded* optimizer moments whose shard length depends on the
  device count. The owning backend's ``reshard_flat_shards`` hook
  re-slices them for the new ring (the global flat vector is an
  invariant; the segment layout — ring slices vs overlap buckets — is
  backend-owned).
* data order — the pipeline is addressed by (step, global index), so a
  different host count reads the same global batch (DataConfig.host_*).

Straggler/eviction policy (documented for the 1000-node deployment): a
persistently slow host is evicted by the cluster manager; the survivors
restart from LATEST via this module onto the shrunken mesh. Synchronous
SGD semantics are preserved exactly — only wall-clock is lost.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from repro.configs.base import RunConfig
from repro.core.backends import get_backend
from repro.checkpoint import CheckpointStore
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.optim import adamw


def reshard_tac_opt(flat_mu: np.ndarray, flat_nu: np.ndarray,
                    old_shards: int, new_shards: int, n_slices: int):
    """Re-slice hadronio_rs-style flat moment shards for a new ring size
    (thin wrapper over :func:`repro.optim.flat.reshard_ring_segments`,
    which owns the segment-major re-slice rule — the live restore path
    goes through the backend's ``reshard_flat_shards`` hook).

    Saved checkpoints hold the *global* stacked shards (old_shards,
    shard_len); the global flat layout is n_slices equal segments.
    Returns (new_mu, new_nu) of shape (new_shards, new_shard_len)."""
    from repro.optim.flat import reshard_ring_segments
    seg = [flat_mu.shape[1] * old_shards // n_slices] * n_slices
    return (reshard_ring_segments(flat_mu, old_shards, new_shards, seg),
            reshard_ring_segments(flat_nu, old_shards, new_shards, seg))


def reshard_event_loops(serve, new_loops: int):
    """Elastic reshard of the SERVING fleet: the same continue-on-a-
    different-shape contract applied to event loops instead of devices.
    Returns a re-validated :class:`~repro.configs.base.ServeConfig` with
    ``event_loops=new_loops`` (``dataclasses.replace`` re-runs the config
    invariants — a loop count the channel pool cannot feed raises here,
    not mid-request); ``leader_loops`` is clamped so the leader lanes
    always keep an owning loop. Served tokens are invariant to the
    resize: affinity changes emission structure, never logits (the
    conformance invariant), so a group rebuilt with the new config at a
    flush boundary continues bit-identically — the recovery property the
    chaos harness's reshard-mid-request scenario asserts."""
    import dataclasses as _dc
    return _dc.replace(serve, event_loops=new_loops,
                       leader_loops=min(serve.leader_loops, new_loops))


def _minimal_regroup(n_channels: int, old_groups: tuple, new_loops: int):
    """Minimal-migration repartition for the FLAT fabric. Shrink: the
    surviving loops keep their runs and the removed TAIL loops' channels
    coalesce onto the last survivor — only the removed loops' channels
    change owner. Grow by ``k``: each added loop takes exactly ONE
    channel from the pool tail (added loop ``i`` gets channel
    ``n-k+i``); donors keep their prefixes. Returns None when the
    minimal move would violate an ownership invariant (a donor emptied,
    or a non-contiguous run) — the caller falls back to a full
    recompute. Balance-to-within-one is deliberately NOT preserved:
    fewer owner changes means fewer serve-step recompiles (the affinity
    keys the step cache), which is the whole point of an in-flight
    resize."""
    old_k = len(old_groups)
    if new_loops == old_k:
        return old_groups
    if new_loops < old_k:
        groups = [list(g) for g in old_groups[:new_loops]]
        tail = sorted(c for g in old_groups[new_loops:] for c in g)
        groups[-1] = sorted(groups[-1] + tail)
    else:
        add = new_loops - old_k
        donate = set(range(n_channels - add, n_channels))
        groups = [[c for c in g if c not in donate] for g in old_groups]
        if any(not g for g in groups):
            return None               # a donor would own nothing
        groups += [[c] for c in sorted(donate)]
    for g in groups:                  # contiguous runs only
        if list(g) != list(range(min(g), max(g) + 1)):
            return None
    if sorted(c for g in groups for c in g) != list(range(n_channels)):
        return None                   # disjoint + covering
    return tuple(tuple(g) for g in groups)


def reshard_affinity(n_channels: int, old_groups, new_loops: int, *,
                     n_pods: int = 1, leaders: int = 0,
                     leader_loops: int = 1):
    """Re-derive the channel-affinity partition for a resized fleet and
    report the migration: ``(new_groups, moved)`` where ``moved`` is the
    sorted tuple of channel ids whose owning loop index changed — the
    connections that must be handed to a different worker thread on a
    netty-style rebalance. Ownership stays disjoint, contiguous and
    covering in both partitions (``channel_affinity`` invariants).

    The FLAT fabric (no leader lanes, one pod) migrates MINIMALLY
    (:func:`_minimal_regroup`): channels only move off removed loops on
    a shrink, and only onto added loops on a grow — survivors keep
    their serve steps warm across the resize. The TOPOLOGY form
    (``leaders > 0`` or ``n_pods > 1``) always recomputes
    ``channel_affinity``: pod alignment and leader pinning are
    correctness constraints worth the extra migrations."""
    from repro.serving.event_loop import channel_affinity
    old_groups = tuple(tuple(g) for g in old_groups)
    if new_loops > n_channels:
        # raise the standard ownership error
        channel_affinity(n_channels, new_loops)
    new_groups = None
    if leaders <= 0 and n_pods <= 1:
        new_groups = _minimal_regroup(n_channels, old_groups, new_loops)
    if new_groups is None:
        new_groups = channel_affinity(n_channels, new_loops, n_pods=n_pods,
                                      leaders=leaders,
                                      leader_loops=leader_loops)
    old_owner = {c: i for i, g in enumerate(old_groups) for c in g}
    moved = tuple(sorted(
        c for i, g in enumerate(new_groups) for c in g
        if old_owner.get(c) != i))
    return new_groups, moved


def make_on_mismatch(run: RunConfig):
    """Shape-mismatch resolver for elastic restores. Ring-sized state is
    backend-owned, so the re-slice rule is the backend's
    ``reshard_flat_shards`` hook (zero1 flat moments — including the
    replan-and-reinit path a non-power-of-two scatter group takes, where
    even the total flat length changes); error-feedback residuals are
    per-peer and keyed to the ring/bucket layout, so any mismatch resets
    them to zero (one uncompensated step of truncation — the EF
    telescoping restarts cleanly). Leaves are told apart by their
    checkpoint path name (``.ef...`` vs ``.opt_...``, see
    checkpoint/store._leaf_files), not by shape: an overlap bucket's
    residual and a flat moment shard are both 2-D."""
    backend = get_backend(run.comm.mode)
    if not backend.zero1 and run.comm.compress == "none":
        return None

    def on_mismatch(name: str, arr: np.ndarray, ref) -> np.ndarray:
        want = tuple(ref.shape)
        if name.startswith(".ef") and arr.ndim == len(want):
            return np.zeros(want, np.float32)
        if arr.ndim == 2 and len(want) == 2:
            out = backend.reshard_flat_shards(run, arr, want[0])
            if tuple(out.shape) != want:
                raise ValueError(
                    f"{name}: backend resharded {arr.shape} -> {out.shape},"
                    f" expected {want}")
            return out
        if arr.ndim == len(want) and arr.shape[1:] == want[1:]:
            # leading ring dim changed on a per-peer residual: reset
            return np.zeros(want, np.float32)
        raise ValueError(f"{name}: cannot reshard {arr.shape}->{want}")

    return on_mismatch


def restore_elastic(store: CheckpointStore, run: RunConfig, mesh,
                    step: Optional[int] = None):
    """Restore the latest (or given) checkpoint onto ``mesh`` — the mesh
    may have a different shape/size than the one that saved. Returns
    (state, step)."""
    s = store.latest_step() if step is None else step
    if s is None:
        raise FileNotFoundError(f"no checkpoint under {store.dir}")
    n_shards = int(np.prod(list(mesh.shape.values())))
    with jax.set_mesh(mesh):
        _, state_sh, _ = steps_mod.make_train_step(run, mesh)
        if get_backend(run.comm.mode).manual:
            like = steps_mod.abstract_tac_state(run, n_shards,
                                                mesh.shape.get("pod", 1))
        else:
            like = steps_mod.abstract_train_state(run)
        state = store.restore(s, like, state_sh,
                              on_mismatch=make_on_mismatch(run))
    return state, s
