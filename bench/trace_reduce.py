"""Reduce a ``jax.profiler`` trace of the measured window to numbers.

The trace holds one plane per chip (``/device:TPU:<n>``) whose ``XLA Ops``
line has an event per executed HLO op (control-flow ops such as ``while``
enclose the ops of their body), an ``Async XLA Ops`` line with the spans of
asynchronous ops, and a ``/host:CPU`` plane whose lines are host threads;
the harness's ``jax.profiler.TraceAnnotation`` spans land there, among
them the one that marks the window. Times are nanoseconds on one clock.

Per chip:

* busy — the union of the intervals in which any op ran;
* collective — the union of collective ops (all-reduce, all-gather,
  reduce-scatter, all-to-all, collective-permute and their async halves);
* exposed collective — collective time during which no compute op ran
  (compute ops are the non-collective ops that enclose no other op).

Besides: each op's self time (its duration less the ops it encloses),
and the idle gaps of the first chip, each named by the host event that
overlapped it most.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute", "collective-broadcast", "send", "recv")
_SHORT = re.compile(r"^%?([\w.\-]+)")
_TYPE = re.compile(r"=\s*(\(?[a-z0-9]+\[[^\]]*\])")


def short_name(name: str) -> str:
    """``%fusion.3 = bf16[8]{0} fusion(...)`` -> ``fusion.3``."""
    m = _SHORT.match(name)
    return m.group(1) if m else name


def op_label(name: str) -> str:
    """A readable op label: its short name and first result type."""
    t = _TYPE.search(name)
    return f"{short_name(name)} {t.group(1).lstrip('(')}" if t \
        else short_name(name)


def is_collective(name: str) -> bool:
    base = re.sub(r"\.\d+$", "", short_name(name))
    return any(base == c or base.startswith(c + "-") for c in COLLECTIVES)


def union(intervals) -> list:
    out: list = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return float(sum(e - s for s, e in intervals))


def subtract(a, b) -> list:
    """Union ``a`` minus union ``b`` (both sorted and disjoint)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo: float, hi: float) -> list:
    """The complement of ``busy`` inside [lo, hi]."""
    return subtract([(lo, hi)], busy)


def self_times(events) -> tuple:
    """(self ns per label, leaf intervals) for one line's events
    [(start, end, name)]: an op's self time is its duration less that of
    the ops it encloses; leaves enclose none."""
    selft: dict = defaultdict(float)
    leaves = []
    stack: list = []            # [start, end, name, child_ns, has_child]

    def close(top):
        selft[op_label(top[2])] += (top[1] - top[0]) - top[3]
        if not top[4]:
            leaves.append((top[0], top[1], top[2]))

    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            close(stack.pop())
        if stack:
            stack[-1][3] += e - s
            stack[-1][4] = True
        stack.append([s, e, name, 0.0, False])
    while stack:
        close(stack.pop())
    return dict(selft), leaves


def xplane_file(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}: {files}")
    return files[0]


def read_planes(path: str) -> dict:
    """{"devices": {plane: {line: [(start, end, name)]}}, "host":
    [(start, end, name)]} from one ``.xplane.pb``."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices: dict = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            devices[plane.name] = {
                line.name: [(ev.start_ns, ev.end_ns, ev.name)
                            for ev in line.events]
                for line in plane.lines}
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host += [(ev.start_ns, ev.end_ns, ev.name)
                         for ev in line.events]
    return {"devices": devices, "host": host}


def _clip(events, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi), n) for s, e, n in events
            if e > lo and s < hi]


def window_of(host, name: str) -> tuple:
    spans = [(s, e) for s, e, n in host if n == name]
    if not spans:
        raise RuntimeError(f"no host span {name!r} in the trace")
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(planes: dict, window_name: str = "bench.window",
           top: int = 10) -> dict:
    lo, hi = window_of(planes["host"], window_name)
    per_chip = []
    op_time: dict = defaultdict(float)
    first_busy = None
    for name in sorted(planes["devices"],
                       key=lambda n: int(n.rsplit(":", 1)[1])):
        lines = planes["devices"][name]
        ops = _clip(lines.get("XLA Ops", []), lo, hi)
        asyn = _clip(lines.get("Async XLA Ops", []), lo, hi)
        selft, leaves = self_times(ops)
        for k, v in selft.items():
            op_time[k] += v
        busy = union((s, e) for s, e, _ in ops)
        coll = union((s, e) for s, e, n in ops + asyn if is_collective(n))
        compute = union((s, e) for s, e, n in leaves if not is_collective(n))
        per_chip.append({
            "device": name, "busy_ns": length(busy), "ops": len(ops),
            "collective_ns": length(coll),
            "exposed_collective_ns": length(subtract(coll, compute))})
        if first_busy is None:
            first_busy = busy
    if not per_chip:
        raise RuntimeError("the trace holds no TPU device plane")
    n = len(per_chip)
    top_ops = sorted(((k, v / n * 1e-9) for k, v in op_time.items()),
                     key=lambda kv: -kv[1])[:top]
    idle = sorted(gaps(first_busy, lo, hi), key=lambda g: g[0] - g[1])[:top]
    return {
        "window_ns": float(hi - lo),
        "chips": per_chip,
        "busy_ns": sum(c["busy_ns"] for c in per_chip) / n,
        "exposed_collective_ns":
            sum(c["exposed_collective_ns"] for c in per_chip) / n,
        "top_ops": [[k, v] for k, v in top_ops],
        "idle_gaps": [[name_gap(planes["host"], s, e, window_name),
                       (e - s) * 1e-9] for s, e in idle],
    }


def name_gap(host, s: float, e: float, window_name: str) -> str:
    """``<harness span> > <host event>`` for the gap [s, e]: the longest
    ``bench.*`` span over it, and the runtime event that overlapped it
    most (the shortest on a tie). A gap named by the harness span alone
    passed in host Python, which the trace does not record."""
    best, best_key, outer = None, None, None
    for hs, he, name in host:
        ov = min(he, e) - max(hs, s)
        if ov <= 0 or name == window_name:
            continue
        if name.startswith("bench."):
            if outer is None or he - hs > outer[1]:
                outer = (name, he - hs)
            continue
        key = (ov, -(he - hs))
        if best_key is None or key > best_key:
            best, best_key = name, key
    parts = [p for p in (outer and outer[0], best) if p]
    return (" > ".join(parts) or "no host event")[:120]
