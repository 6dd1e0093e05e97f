import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax, jax.numpy as jnp
from repro.configs.base import CommConfig, RunConfig
from repro.configs.registry import get_config, get_shape
from repro.launch import steps
from repro.launch.mesh import make_mesh
from repro.launch.sharding import batch_sharding
from repro.models import api

print("jax.shard_map:", hasattr(jax, "shard_map"))
print("set_mesh:", hasattr(jax, "set_mesh"))

cfg = get_config("qwen1.5-4b-reduced")
B, S = 8, 32
shape = get_shape("train_4k")
rng = jax.random.PRNGKey(0)
batch = {"tokens": jax.random.randint(rng, (B, S), 0, cfg.vocab_size),
         "labels": jax.random.randint(rng, (B, S), 0, cfg.vocab_size)}

mesh = make_mesh((4, 2), ("data", "model"))

# --- GSPMD path ---
run = RunConfig(model=cfg, shape=shape, comm=CommConfig(mode="gspmd"))
with jax.set_mesh(mesh):
    step_fn, state_sh, batch_sh_fn = steps.make_train_step(run, mesh)
    state = jax.device_put(steps.init_train_state(rng, run), state_sh)
    jitted = jax.jit(step_fn, in_shardings=(state_sh, batch_sh_fn(mesh, batch)),
                     out_shardings=(state_sh, None), donate_argnums=(0,))
    state1, metrics = jitted(state, batch)
    print("gspmd loss:", float(metrics["loss"]), "gnorm:", float(metrics["grad_norm"]))
    state2, m2 = jitted(state1, batch)
    print("gspmd loss2:", float(m2["loss"]))
    assert float(m2["loss"]) < float(metrics["loss"]), "loss should drop"

# --- TAC paths ---
losses = {}
for mode in ("sockets", "vma", "hadronio", "hadronio_overlap", "hadronio_rs"):
    run = RunConfig(model=cfg, shape=shape,
                    comm=CommConfig(mode=mode, slice_bytes=256 * 1024,
                                    ring_capacity_bytes=16 * 1024 * 1024,
                                    hierarchical=False))
    with jax.set_mesh(mesh):
        step_fn, state_sh, batch_sh_fn = steps.make_train_step(run, mesh)
        state = jax.device_put(steps.init_tac_state(rng, run, 8), state_sh)
        jitted = jax.jit(step_fn, in_shardings=(state_sh, batch_sh_fn(mesh, batch)),
                         out_shardings=(state_sh, None), donate_argnums=(0,))
        state1, metrics = jitted(state, batch)
        state2, m2 = jitted(state1, batch)
        losses[mode] = (float(metrics["loss"]), float(m2["loss"]))
        print(f"{mode}: loss {losses[mode][0]:.6f} -> {losses[mode][1]:.6f}")

# transparency: all modes produce the same loss trajectory (within fp tolerance)
vals0 = [v[0] for v in losses.values()]
vals1 = [v[1] for v in losses.values()]
assert max(vals0) - min(vals0) < 1e-4, vals0
assert max(vals1) - min(vals1) < 1e-3, vals1
print("transparency check OK")

# microbatching
run = RunConfig(model=cfg, shape=shape, comm=CommConfig(mode="hadronio", hierarchical=False),
                microbatches=2)
batch16 = {"tokens": jax.random.randint(rng, (16, S), 0, cfg.vocab_size),
           "labels": jax.random.randint(rng, (16, S), 0, cfg.vocab_size)}
with jax.set_mesh(mesh):
    step_fn, state_sh, batch_sh_fn = steps.make_train_step(run, mesh)
    state = jax.device_put(steps.init_tac_state(rng, run, 8), state_sh)
    s1, m = jax.jit(step_fn, in_shardings=(state_sh, batch_sh_fn(mesh, batch16)),
                    out_shardings=(state_sh, None))(state, batch16)
    print("microbatch hadronio loss:", float(m["loss"]))

# compression state threading
run = RunConfig(model=cfg, shape=shape,
                comm=CommConfig(mode="hadronio", compress="bf16", hierarchical=False))
with jax.set_mesh(mesh):
    step_fn, state_sh, batch_sh_fn = steps.make_train_step(run, mesh)
    state = jax.device_put(steps.init_tac_state(rng, run, 8), state_sh)
    s1, m = jax.jit(step_fn, in_shardings=(state_sh, batch_sh_fn(mesh, batch)),
                    out_shardings=(state_sh, None))(state, batch)
    print("bf16-compressed hadronio loss:", float(m["loss"]), "ef shape:", s1.ef.shape)
print("ALL OK")

# --- hierarchical TAC on a (pod, data, model) mesh: trajectories must match
mesh3 = make_mesh((2, 2, 2), ("pod", "data", "model"))
batch3 = {"tokens": jax.random.randint(rng, (8, S), 0, cfg.vocab_size),
          "labels": jax.random.randint(rng, (8, S), 0, cfg.vocab_size)}
tr3 = {}
for mode, hier in (("sockets", False), ("hadronio", True),
                   ("hadronio_rs", True), ("hadronio_rs", False)):
    run = RunConfig(model=cfg, shape=shape,
                    comm=CommConfig(mode=mode, slice_bytes=256 * 1024,
                                    hierarchical=hier))
    with jax.set_mesh(mesh3):
        step_fn, state_sh, batch_sh_fn = steps.make_train_step(run, mesh3)
        state = jax.device_put(steps.init_tac_state(rng, run, 8, 2),
                               state_sh)
        jitted = jax.jit(step_fn, in_shardings=(state_sh,
                                                batch_sh_fn(mesh3, batch3)),
                         out_shardings=(state_sh, None))
        losses = []
        for _ in range(3):
            state, m = jitted(state, batch3)
            losses.append(float(m["loss"]))
        tr3[(mode, hier)] = losses
        print(f"pod-mesh {mode:12s} hier={hier}: {['%.5f' % l for l in losses]}")
ref3 = np.array(tr3[("sockets", False)])
for k, v in tr3.items():
    assert np.max(np.abs(np.array(v) - ref3)) < 2e-3, (k, v)
print("hierarchical pod-mesh trajectory equivalence OK")
print("ALL OK")
