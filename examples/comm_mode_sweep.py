"""The paper's experiment, end to end: train the SAME model under each
communication stack and show (a) identical loss trajectories — the
transparency claim — and (b) the collective-op schedule each mode emits —
the performance claim (hadroNIO's aggregation = fewer, larger sends).

  PYTHONPATH=src python examples/comm_mode_sweep.py
"""
import jax
import numpy as np

from repro.configs.base import CommConfig, RunConfig, ShapeConfig
from repro.core.backends import available_modes, get_backend
from repro.configs.registry import get_config
from repro.data import DataConfig, SyntheticSource, batch_at
from repro.launch import hlo_analysis as hlo
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_mesh
from repro.launch.train import Trainer

# every registered manual mode, paper order first (registry-derived:
# a new backend shows up here and in the parity assertion automatically)
PAPER = ("sockets", "vma", "hadronio", "hadronio_rs")
MODES = PAPER + tuple(m for m in available_modes()
                      if get_backend(m).manual and m not in PAPER)


def main():
    cfg = get_config("qwen1.5-4b-reduced")
    shape = ShapeConfig("sweep", "train", seq_len=64, global_batch=4)
    mesh = make_mesh((len(jax.devices()),), ("data",))
    n_dev = len(jax.devices())

    print(f"{'mode':12s} {'final loss':>10s} {'coll ops':>9s} "
          f"{'coll bytes':>12s}  trajectory")
    trajs = {}
    for mode in MODES:
        run = RunConfig(model=cfg, shape=shape,
                        comm=CommConfig(mode=mode, slice_bytes=128 * 1024,
                                        hierarchical=False),
                        lr=1e-3, total_steps=8, warmup_steps=2)
        # collective schedule from the compiled step
        with jax.set_mesh(mesh):
            step_fn, state_sh, batch_sh_fn = steps_mod.make_train_step(
                run, mesh)
            state = jax.device_put(
                steps_mod.init_tac_state(jax.random.PRNGKey(0), run, n_dev),
                state_sh)
            batch = batch_at(SyntheticSource(cfg.vocab_size, 0),
                             DataConfig(64, 4), 0)
            batch = jax.device_put(batch, batch_sh_fn(mesh, batch))
            stats = hlo.stablehlo_collective_stats(
                jax.jit(step_fn).lower(state, batch).as_text())

        out = Trainer(run, mesh, log_every=100,
                      log_fn=lambda s: None).run_loop()
        trajs[mode] = out["losses"]
        print(f"{mode:12s} {out['final_loss']:10.4f} "
              f"{stats.total_ops:9d} {stats.total_bytes:12d}  "
              f"{['%.3f' % l for l in out['losses'][:4]]}")

    ref = np.array(trajs["sockets"])
    for mode, t in trajs.items():
        assert np.max(np.abs(np.array(t) - ref)) < 2e-3, mode
    print("\nall modes: identical trajectories (transparency), "
          "different collective schedules (the paper's point).")


if __name__ == "__main__":
    main()
