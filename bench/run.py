"""Run one benchmark cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, its configuration under
``bench/configs/``, its traffic mix under ``bench/traffic/`` (whose
``driver`` names the module under ``bench/drivers/`` that runs it), its
correctness limits under ``bench/limits/`` and, with ``--trace 1``, one
reader per per-layer metric under ``bench/metrics/``. Prints one JSON line
last on stdout: ``correct``, ``attempted``, ``failed``, ``metrics`` (the
cell's end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``, each
compared number beside its limit. Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import importlib  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, model, peaks  # noqa: E402


@dataclasses.dataclass
class Context:
    """What a driver needs to run one cell."""
    cell: str
    cfg: dict              # the configuration file
    mc: object             # the repo's ModelConfig for it
    mix: dict              # the traffic mix file
    seed: int
    seconds: float
    trace: bool
    devs: list
    peaks: dict
    t_start: float
    fault: object = None   # tests and studies only: plants a fault

    def window_seconds(self) -> float:
        """``seconds``; a traced run measures at most the mix's
        ``trace_seconds``, which keeps its trace small."""
        return min(self.seconds, self.mix["trace_seconds"]) if self.trace \
            else self.seconds


def result_of(out: dict, ctx: Context, bench: dict, limits: dict) -> dict:
    chk = harness.checks(out["readings"], limits)
    res = {"correct": harness.passed(chk), "attempted": out["attempted"],
           "failed": out["failed"]}
    if ctx.trace:
        rec = out["record"]
        metrics = {}
        for m in harness.per_layer_for(bench, ctx.cell):
            v = harness.metric_reader(m["name"])(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        tr = rec["trace"]
        device = dict(out["device"], busy_s=tr["busy_ns"] * 1e-9,
                      window_s=tr["window_ns"] * 1e-9)
        res.update(metrics=metrics, device=device,
                   breakdown={"device_ops": tr["top_ops"],
                              "idle_gaps": tr["idle_gaps"]})
    else:
        names = {m["name"]: m["unit"]
                 for m in harness.end_to_end_for(bench, ctx.cell)}
        res.update(metrics={n: {"value": v, "unit": names[n]}
                            for n, v in out["end_to_end"].items()
                            if n in names},
                   device=out["device"])
    res["checks"] = chk
    return res


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)

    bench = harness.benchmark()
    cell = harness.cell_of(bench, args.workload)
    cfg = harness.config_of(bench, cell["config"])
    mix = harness.traffic_of(cell["traffic"])
    limits = harness.limits_of(cell["name"])
    try:
        devs = harness.chips(cell["chips"])
    except harness.NoChip as e:
        print(f"bench: {e}; this benchmark runs only on TPU chips",
              file=sys.stderr)
        return 2
    pk = peaks.peaks_for(devs[0].device_kind)
    harness.enable_compile_cache()
    ctx = Context(cell=cell["name"], cfg=cfg, mc=model.program_config(cfg),
                  mix=mix, seed=args.seed, seconds=args.seconds,
                  trace=bool(args.trace), devs=devs, peaks=pk,
                  t_start=T_START)
    driver = importlib.import_module(f"bench.drivers.{mix['driver']}")
    out = driver.run(ctx)
    harness.emit(result_of(out, ctx, bench, limits))
    return 0


if __name__ == "__main__":
    sys.exit(main())
