"""What every cell shares: finding files by name, the chip, the compile
cache, the measured window, the trace, the checks and the result line."""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


# -- files found by name -------------------------------------------------

def load_json(path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell_of(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                   f"{[w['name'] for w in bench['workloads']]}")


def config_of(bench: dict, name: str) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return load_json(ROOT / c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic_of(name: str) -> dict:
    return load_json(BENCH / "traffic" / f"{name}.json")


def limits_of(cell: str) -> dict:
    return load_json(BENCH / "limits" / f"{cell}.json")


def metric_reader(name: str):
    """``bench/metrics/<name>.py``'s ``read(record)``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def end_to_end_for(bench: dict, cell: str) -> list:
    return [m for m in bench["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer_for(bench: dict, cell: str) -> list:
    e2e = {m["name"] for m in end_to_end_for(bench, cell)}
    return [m for m in bench["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in e2e)]


# -- the chip -------------------------------------------------------------

def chips(n: int) -> list:
    """The first ``n`` TPU devices; NoChip when there are fewer, or none."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devs[0].platform!r})")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips, JAX finds {len(devs)}")
    return devs[:n]


def device_info(devs) -> dict:
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def enable_compile_cache() -> str:
    """``$JAX_COMPILATION_CACHE_DIR`` when set, else the fixed
    ``.jax_cache/`` at the root of the checkout; every program is cached,
    however quick its compile."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def settle() -> None:
    """End set-up: collect, then move every object that set-up left (the
    imported modules, the program, its state) out of the collector's
    reach, so that a collection in the window walks only what the window
    makes. ``unsettle`` undoes it."""
    gc.collect()
    gc.freeze()


def unsettle() -> None:
    gc.unfreeze()


class CompileCounter:
    """Counts XLA compiles and persistent-cache loads (each is one backend
    compile request) while ``active``."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        from jax import monitoring
        self.active = False
        self.count = 0
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **_):
        if self.active and event == self.EVENT:
            self.count += 1


# -- the traced window ----------------------------------------------------

class Tracer:
    """With ``on``, records a ``jax.profiler`` trace of the window (host
    Python tracing off) into a temporary directory, and reduces it."""

    def __init__(self, on: bool):
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="bench_trace_") if on else None
        self.reduced = None

    @contextlib.contextmanager
    def window(self):
        import jax
        if not self.on:
            yield
            return
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                yield
        finally:
            jax.profiler.stop_trace()

    def reduce(self) -> dict:
        from bench import trace_reduce
        try:
            planes = trace_reduce.read_planes(
                trace_reduce.xplane_file(self.dir))
            self.reduced = trace_reduce.reduce(planes)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return self.reduced


# -- numbers ---------------------------------------------------------------

def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default)."""
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q))


def checks(readings: dict, limits: dict) -> dict:
    """{name: {"value", "limit"}} for every limited number; a missing
    (None) or non-finite reading is over its limit."""
    out = {}
    for name, lim in limits["checks"].items():
        v = readings.get(name)
        if v is not None and not math.isfinite(v):
            v = None
        out[name] = {"value": v, "limit": lim["limit"]}
    return out


def passed(chk: dict) -> bool:
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in chk.values())


def emit(result: dict) -> None:
    """Checks as the last lines of stderr; the result as the last line of
    stdout, with ``checks`` its last key."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
