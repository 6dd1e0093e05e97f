import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
# The two lines above MUST run before any other import (jax locks the
# device count on first init). Everything below is ordinary code.
"""Multi-pod dry-run driver (brief: MULTI-POD DRY-RUN).

For each (architecture × input shape × mesh) cell: build the step function,
``jax.jit(...).lower(**abstract inputs)``, ``.compile()``, and record
memory/cost/collective analysis into a JSON artifact. No arrays are ever
allocated — state and inputs are ShapeDtypeStructs.

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  python -m repro.launch.dryrun --all                 # every runnable cell
  python -m repro.launch.dryrun --all --mesh multipod # 2 pods = 512 chips
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k \
      --mode hadronio                                 # paper-faithful step
"""
import argparse
import json
import time
import traceback

import jax
import numpy as np

from repro.configs.base import CommConfig, RunConfig
from repro.core.backends import available_modes, get_backend
from repro.configs.registry import SHAPES, ARCH_IDS, cell_skip_reason, \
    get_config, get_shape
from repro.launch import hlo_analysis as hlo
from repro.launch import steps
from repro.launch.mesh import make_production_mesh
from repro.launch.sharding import batch_sharding
from repro.models import api

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__),
                            "..", "..", "..", "artifacts")


def _mesh_chips(mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


def _lower_cell(cfg, shape, mesh, mode: str, microbatches: int):
    """Build + lower one cell's step. Returns the lowered computation."""
    run = RunConfig(model=cfg, shape=shape, comm=CommConfig(mode=mode),
                    microbatches=microbatches)
    if shape.kind == "train":
        step_fn, state_shardings, batch_sh_fn = steps.make_train_step(
            run, mesh)
        if get_backend(mode).manual:
            state = steps.abstract_tac_state(run, _mesh_chips(mesh),
                                             mesh.shape.get("pod", 1))
        else:
            state = steps.abstract_train_state(run)
        inputs = api.input_specs(cfg, shape)
        in_sh = (state_shardings, batch_sh_fn(mesh, inputs))
        jitted = jax.jit(step_fn, in_shardings=in_sh,
                         out_shardings=(state_shardings, None),
                         donate_argnums=(0,))
        return jitted.lower(state, inputs)
    if shape.kind == "prefill":
        fn = steps.make_prefill_step(run, mesh)
        params, cache, inputs, psh, csh, ish = steps.serve_specs(
            run, shape, mesh)
        jitted = jax.jit(fn, in_shardings=(psh, ish))
        return jitted.lower(params, inputs)
    fn = steps.make_decode_step(run, mesh)
    params, cache, inputs, psh, csh, ish = steps.serve_specs(
        run, shape, mesh)
    jitted = jax.jit(fn, in_shardings=(psh, csh, ish),
                     out_shardings=(None, csh), donate_argnums=(1,))
    return jitted.lower(params, cache, inputs)


def _variant_cfg(cfg, groups: int):
    """A ``groups``-deep variant of cfg for the unrolled cost probe."""
    import dataclasses
    pat = len(cfg.block_pattern) if cfg.block_pattern else 1
    kw = {"num_layers": groups * pat}
    if cfg.family == "encdec":
        kw["encoder_layers"] = groups
    return dataclasses.replace(cfg, **kw)


def _units_full(cfg) -> float:
    """Full depth in variant-group units (see unroll.py / EXPERIMENTS.md)."""
    pat = len(cfg.block_pattern) if cfg.block_pattern else 1
    return cfg.num_layers / pat


def _costs_of(lowered) -> dict:
    compiled = lowered.compile()
    cost = hlo.flops_and_bytes(compiled)
    coll = hlo.collective_stats(compiled.as_text())
    return {"flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes_accessed", 0.0),
            "coll_bytes": float(coll.total_bytes),
            "coll_ops": float(coll.total_ops)}


def scan_corrected_costs(cfg, shape, mesh, mode: str) -> dict:
    """Two-point extrapolation of per-layer HLO costs.

    cost_analysis counts loop bodies once (see models/unroll.py), so the
    full-depth lowering under-reports. We lower UNROLLED 1-group and
    2-group variants and extrapolate: cost(L) = overhead + L * per_group.
    """
    from repro.models.unroll import unrolled_layers
    with unrolled_layers():
        c1 = _costs_of(_lower_cell(_variant_cfg(cfg, 1), shape, mesh, mode, 1))
        c2 = _costs_of(_lower_cell(_variant_cfg(cfg, 2), shape, mesh, mode, 1))
    units = _units_full(cfg)
    out = {}
    for k in c1:
        per_group = c2[k] - c1[k]
        overhead = c1[k] - per_group
        out[k] = max(0.0, overhead + per_group * units)
    out["variant_units"] = units
    return out


def dryrun_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                mode: str = "gspmd", microbatches: int = 1,
                correct_scans: bool = True,
                extra: dict | None = None) -> dict:
    """Lower + compile one cell; return the artifact dict."""
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    skip = cell_skip_reason(cfg, shape)
    if skip:
        return {"arch": arch, "shape": shape_name, "mesh":
                "multipod" if multi_pod else "pod", "mode": mode,
                "status": "skip", "reason": skip}

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    with jax.set_mesh(mesh):
        lowered = _lower_cell(cfg, shape, mesh, mode, microbatches)
        compiled = lowered.compile()
        t1 = time.time()
        corrected = None
        if correct_scans:
            try:
                corrected = scan_corrected_costs(cfg, shape, mesh, mode)
            except Exception as e:       # noqa: BLE001 — probe is optional
                corrected = {"error": f"{type(e).__name__}: {e}"}

    text = compiled.as_text()
    coll = hlo.collective_stats(text)
    cost = hlo.flops_and_bytes(compiled)
    memory = hlo.memory_stats(compiled)
    n_chips = _mesh_chips(mesh)
    mf = hlo.model_flops(cfg, shape)
    ab = hlo.analytic_hbm_bytes(cfg, shape, n_chips,
                                tp=mesh.shape.get("model", 1),
                                dp=mesh.shape.get("data", 1))
    # roofline terms: compute from analytic MODEL_FLOPS (exact), memory
    # from the analytic traffic model, collective from the scan-corrected
    # parsed HLO (falls back to raw when the probe failed).
    coll_bytes = (corrected or {}).get("coll_bytes", coll.total_bytes) \
        if isinstance(corrected, dict) and "error" not in (corrected or {}) \
        else coll.total_bytes
    terms = hlo.roofline_terms(flops=mf, hbm_bytes=ab,
                               collective_bytes=coll_bytes,
                               n_chips=n_chips, flops_are_global=True,
                               hbm_is_global=False)
    raw_terms = hlo.roofline_terms(
        flops=cost.get("flops", 0.0),
        hbm_bytes=cost.get("bytes_accessed", 0.0),
        collective_bytes=coll.total_bytes,
        n_chips=n_chips, flops_are_global=False)
    art = {
        "arch": arch, "shape": shape_name,
        "mesh": "multipod" if multi_pod else "pod",
        "mode": mode, "status": "ok",
        "n_chips": n_chips,
        "compile_seconds": round(t1 - t0, 2),
        "collectives": coll.as_dict(),
        "cost_analysis": cost,
        "scan_corrected": corrected,
        "memory_analysis": memory,
        "analytic_hbm_bytes_per_chip": ab,
        "roofline": terms,
        "roofline_raw_hlo": raw_terms,
        "model_flops_global": mf,
        "model_flops_per_chip": mf / n_chips,
        "hlo_flops_corrected_per_chip":
            (corrected or {}).get("flops") if isinstance(corrected, dict)
            else None,
        "useful_flops_ratio":
            (mf / n_chips) / corrected["flops"]
            if isinstance(corrected, dict) and corrected.get("flops")
            else None,
        "param_count": cfg.param_count(),
        "active_param_count": cfg.active_param_count(),
    }
    if extra:
        art.update(extra)
    return art


def artifact_path(arch: str, shape: str, mesh: str, mode: str,
                  out_dir: str) -> str:
    safe = arch.replace("/", "_").replace(".", "_")
    return os.path.join(out_dir, f"dryrun_{safe}_{shape}_{mesh}_{mode}.json")


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", choices=list(ARCH_IDS))
    p.add_argument("--shape", choices=list(SHAPES))
    p.add_argument("--mesh", choices=["pod", "multipod"], default="pod")
    p.add_argument("--mode", default="gspmd",
                   choices=list(available_modes()))
    p.add_argument("--all", action="store_true",
                   help="run every (arch x shape) cell for --mesh/--mode")
    p.add_argument("--no-correct", action="store_true",
                   help="skip the unrolled scan-correction probe "
                        "(multipod runs: pass/fail + memory only)")
    p.add_argument("--out", default=os.path.normpath(ARTIFACT_DIR))
    p.add_argument("--skip-existing", action="store_true")
    args = p.parse_args()

    os.makedirs(args.out, exist_ok=True)
    cells = ([(a, s) for a in ARCH_IDS for s in SHAPES] if args.all
             else [(args.arch, args.shape)])
    failures = 0
    for arch, shape in cells:
        path = artifact_path(arch, shape, args.mesh, args.mode, args.out)
        if args.skip_existing and os.path.exists(path):
            with open(path) as f:
                if json.load(f).get("status") in ("ok", "skip"):
                    print(f"[cached] {arch} x {shape}")
                    continue
        try:
            art = dryrun_cell(arch, shape, multi_pod=args.mesh == "multipod",
                              mode=args.mode,
                              correct_scans=not args.no_correct)
        except Exception as e:
            art = {"arch": arch, "shape": shape, "mesh": args.mesh,
                   "mode": args.mode, "status": "fail",
                   "error": f"{type(e).__name__}: {e}",
                   "traceback": traceback.format_exc()}
            failures += 1
        with open(path, "w") as f:
            json.dump(art, f, indent=1)
        status = art["status"]
        if status == "ok":
            r = art["roofline"]
            print(f"[ok]   {arch} x {shape} ({args.mesh},{args.mode}): "
                  f"compile {art['compile_seconds']}s, "
                  f"bottleneck={r['bottleneck']}, "
                  f"coll={art['collectives']['total_bytes']/1e9:.2f}GB, "
                  f"mem_temp={art['memory_analysis'].get('temp_size_in_bytes', 0)/1e9:.2f}GB")
        elif status == "skip":
            print(f"[skip] {arch} x {shape}: {art['reason'][:60]}")
        else:
            print(f"[FAIL] {arch} x {shape}: {art['error']}")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
