"""The program's own spans and scopes in a ``jax.profiler`` trace.

    python3 bench/program_trace.py --workload <cell> --seed <n> [--recorder 1]

Runs one cell as ``bench/run.py --trace 1`` does (or, with ``--trace 0
--recorder 1``, untraced with the program's span recorder on, which is
what tracing costs) and prints the run's result line, then one more JSON
line: the reduction below of the traced window, and the per-layer numbers
read from it. The benchmark's runs never run this; it is the reduction a
per-layer metric of the engine's spans or the model's scopes would read.

The program marks its work twice (``docs/OBSERVABILITY.md``):

* host spans: while ``repro.obs.trace`` records, every span is also a
  ``jax.profiler.TraceAnnotation`` named ``repro.<kind>``, a ``/host:CPU``
  event whose stats are the span's args (``decode`` carries ``active``, a
  finished request's zero-length ``request`` event ``uid``, ``ttft_ms``,
  ``done_ms`` and ``tokens``);
* device scopes: ``jax.named_scope`` names in each HLO op's ``op_name``
  (``.../layers/while/body/attn/dot_general``; backward ops as
  ``transpose(jvp(lm_head))/...``). An op counts for the innermost
  registered scope in its path; ``comm.flush.ch<i>`` and
  ``comm.leader.l<j>`` count as ``comm``. A TPU op event carries no
  ``op_name`` (its stats are its device offset and duration): it comes
  from the optimized HLO that XLA dumps during the run
  (``--xla_dump_to``), by module and instruction name.

:func:`reduce_program` gives ``program`` (per span kind: count, median and
p95 of the durations inside the window; the ``request`` events' args;
``idle_by_span``, chip 0's idle ns keyed by the innermost ``repro.*`` span
over it) and ``scopes`` (device self ns per top-level scope, chip 0).
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import shutil
import sys
import tempfile
from collections import defaultdict
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]

SCOPES = ("embed", "attn", "mlp", "lm_head", "layers", "optimizer", "comm")
_WRAP = re.compile(r"^(?:[\w]+\()+([^()]*)\)+$")     # transpose(jvp(x))
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?"
                    r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)")


def scope_of(op_name: str):
    """The innermost registered scope in an op_name path, or None."""
    best = None
    for part in op_name.split(";")[0].split("/"):
        m = _WRAP.match(part)
        part = m.group(1) if m else part
        if part.startswith("comm."):
            part = "comm"
        if part in SCOPES:
            best = part
    return best


def hlo_op_names(dump_dir: str) -> dict:
    """{(module, instruction): op_name} from the optimized HLO that XLA
    dumped; an instruction whose name repeats with another op_name across
    modules of one name maps to None."""
    out: dict = {}
    for path in glob.glob(os.path.join(dump_dir, "*after_optimizations.txt")):
        module = None
        with open(path) as f:
            for line in f:
                if module is None:
                    m = _MODULE.match(line)
                    module = m.group(1) if m else None
                    continue
                m = _INSTR.match(line)
                if m:
                    key = (module, m.group(1))
                    if out.get(key, m.group(2)) != m.group(2):
                        out[key] = None
                    else:
                        out[key] = m.group(2)
    return out


def read_program(path: str, hlo_names: dict) -> dict:
    """{"host": [(start, end, name)] every host event, "spans": [(start,
    end, kind, stats)] the ``repro.*`` ones, "ops": {plane:
    [(start, end, op_name or "")]} each TPU plane's ``XLA Ops``}. A TPU
    op event carries no ``op_name``: its module is the ``XLA Modules``
    event it runs in (``jit_step(<fingerprint>)``), its instruction the
    head of its name, and ``hlo_names`` maps the pair."""
    import jax
    from bench.trace_reduce import short_name
    pd = jax.profiler.ProfileData.from_file(path)
    host, spans, ops = [], [], {}
    for plane in pd.planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                for ev in line.events:
                    host.append((ev.start_ns, ev.end_ns, ev.name))
                    if ev.name.startswith("repro."):
                        spans.append((ev.start_ns, ev.end_ns, ev.name[6:],
                                      dict(ev.stats)))
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            mods = sorted((ev.start_ns, ev.name.split("(")[0])
                          for ev in lines.get("XLA Modules", []))
            starts = [m[0] for m in mods]
            evs = []
            for ev in lines.get("XLA Ops", []):
                k = bisect.bisect_right(starts, ev.start_ns) - 1
                key = (mods[k][1] if k >= 0 else "", short_name(ev.name))
                evs.append((ev.start_ns, ev.end_ns, hlo_names.get(key) or ""))
            ops[plane.name] = evs
    return {"host": host, "spans": spans, "ops": ops}


def _pct(values, q):
    import numpy as np
    return float(np.percentile(np.asarray(values, float), q)) \
        if values else None


def scope_self_ns(ops) -> dict:
    """Self ns per scope (None for unscoped) of one line's ops
    [(start, end, op_name)]: an op's duration less the ops it encloses."""
    out: dict = defaultdict(float)
    stack: list = []                # [end, scope, duration, child_ns]

    def close(top):
        out[top[1]] += top[2] - top[3]

    for s, e, name in sorted(ops, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
        stack.append([e, scope_of(name), e - s, 0.0])
    while stack:
        close(stack.pop())
    return dict(out)


def idle_by_span(idle, spans) -> dict:
    """Idle ns keyed by the innermost (shortest) ``repro.*`` span over
    each stretch of ``idle`` (sorted disjoint intervals); "" where none."""
    marks = []
    for k, (s, e, _, _) in enumerate(spans):
        if e > s:
            marks += [(s, 1, k), (e, -1, k)]
    for s, e in idle:
        marks += [(s, 2, -1), (e, -2, -1)]
    marks.sort(key=lambda m: (m[0], -abs(m[1])))
    out: dict = defaultdict(float)
    live: set = set()
    idle_on, last = False, None
    for t, what, k in marks:
        if idle_on and last is not None and t > last:
            inner = min(live, key=lambda j: spans[j][1] - spans[j][0],
                        default=None)
            out[spans[inner][2] if inner is not None else ""] += t - last
        last = t
        if what == 1:
            live.add(k)
        elif what == -1:
            live.discard(k)
        else:
            idle_on = what == 2
    return dict(out)


def _innermost(spans, s, e) -> list:
    """[kind, name] of the shortest span that covers [s, e] whole, else
    of the one that overlaps it most; ["", ""] where none touches it."""
    cover = [sp for sp in spans if sp[0] <= s and sp[1] >= e]
    if cover:
        sp = min(cover, key=lambda x: x[1] - x[0])
    else:
        sp = max(spans, key=lambda x: min(x[1], e) - max(x[0], s),
                 default=None)
        if sp is None or min(sp[1], e) <= max(sp[0], s):
            return ["", ""]
    return [sp[2], str(sp[3].get("name", ""))]


def reduce_program(prog: dict, window_name: str = "bench.window",
                   top: int = 10) -> dict:
    from bench import trace_reduce as tr
    lo, hi = tr.window_of(prog["host"], window_name)
    inside = [sp for sp in prog["spans"] if sp[0] >= lo and sp[1] <= hi]
    by_kind: dict = defaultdict(list)
    for s, e, kind, _ in inside:
        by_kind[kind].append((e - s) * 1e-6)
    kinds = {k: {"count": len(v), "median_ms": _pct(v, 50),
                 "p95_ms": _pct(v, 95)} for k, v in sorted(by_kind.items())}
    requests = [st for _, _, kind, st in inside if kind == "request"]
    out = {"window_ns": float(hi - lo), "kinds": kinds,
           "requests": requests, "idle_by_span": {}, "idle_gaps": [],
           "scopes": {}, "busy_ns": None, "ops_named": None}
    if prog["ops"]:
        first = min(prog["ops"], key=lambda n: int(n.rsplit(":", 1)[1]))
        ops = tr._clip(prog["ops"][first], lo, hi)
        busy = tr.union((s, e) for s, e, _ in ops)
        out["busy_ns"] = tr.length(busy)
        over = [sp for sp in prog["spans"] if sp[1] > lo and sp[0] < hi]
        idle = tr.gaps(busy, lo, hi)
        out["idle_by_span"] = idle_by_span(idle, over)
        longest = sorted(idle, key=lambda g: g[0] - g[1])[:top]
        out["idle_gaps"] = [[(e - s) * 1e-9, *_innermost(over, s, e)]
                            for s, e in longest]
        out["scopes"] = {str(k): v for k, v in scope_self_ns(ops).items()}
        out["ops_named"] = sum(1 for _, _, n in ops if n) / max(len(ops), 1)
    return out


def metrics(red: dict, kind: str) -> dict:
    """The per-layer numbers a ``serve`` or ``train`` cell's reduction
    gives: step and host-step medians, TTFT p95, and scope shares. Each
    is None where the trace holds no span or no scoped op to read."""
    k, busy = red["kinds"], red["busy_ns"]
    scoped = busy and any(s != "None" for s in red["scopes"])
    share = (lambda s: 100.0 * red["scopes"].get(s, 0.0) / busy
             if scoped else None)
    if kind == "train":
        return {"lm_head_share.train": share("lm_head")}
    ttft = [r["ttft_ms"] for r in red["requests"] if "ttft_ms" in r]
    return {"decode_step_ms.serve": k.get("decode", {}).get("median_ms"),
            "host_step_ms.serve": k.get("host_step", {}).get("median_ms"),
            "ttft_p95_ms.serve": _pct(ttft, 95),
            "attention_share.serve": share("attn")}


def main(argv=None) -> int:
    import argparse
    import json
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=51)
    p.add_argument("--trace", type=int, choices=(0, 1), default=1)
    p.add_argument("--recorder", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    scratch = tempfile.mkdtemp(prefix="bench_program_")
    dump = os.path.join(scratch, "hlo")
    if args.trace:
        # a fresh compile cache: every program compiles in this process,
        # so XLA dumps each one
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                                   + f" --xla_dump_to={dump}"
                                   " --xla_dump_hlo_as_text")
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(scratch,
                                                               "cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness, run, trace_reduce
    found: dict = {}

    class Tracer(harness.Tracer):
        def reduce(self):
            path = trace_reduce.xplane_file(self.dir)
            found["red"] = reduce_program(read_program(
                path, hlo_op_names(dump)))
            return super().reduce()

    harness.Tracer = Tracer
    if args.recorder and not args.trace:
        from repro.obs import trace as obs_trace
        obs_trace.enable(capacity=1 << 20)
    try:
        rc = run.main(["--workload", args.workload, "--seed",
                       str(args.seed), "--seconds", str(args.seconds),
                       "--trace", str(args.trace)])
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    if "red" in found:
        red = found["red"]
        cell = harness.cell_of(harness.benchmark(), args.workload)
        kind = harness.traffic_of(cell["traffic"])["driver"]
        red["metrics"] = metrics(red, "train" if kind == "train"
                                 else "serve")
        print(json.dumps({"program": red}), flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
