"""The trace reduction: busy union, idle share, exposed collective time,
self times and named idle gaps."""
import json
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.json")


def op(name, shape="bf16[8]{0}"):
    return f"%{name} = {shape} {name.split('.')[0]}(...)"


def synthetic():
    ops = [(0, 100, op("while.1")), (10, 40, op("fusion.1")),
           (50, 90, op("fusion.2")), (120, 125, op("fusion.4"))]
    asyn = [(95, 130, op("all-reduce-start.3", "f32[4]{0}"))]
    host = [(0, 200, "bench.window"), (0, 119, "bench.step"),
            (98, 119, "PjitFunction(step)"), (130, 200, "bench.step")]
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "Async XLA Ops": asyn}},
            "host": host}


def test_interval_algebra():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.gaps([(2, 3)], 0, 5) == [(0, 2), (3, 5)]
    assert tr.length([(0, 10), (20, 25)]) == 15


def test_names():
    name = "%fusion.307 = (bf16[896]{0}, f32[8]{0}) fusion(x), kind=kOutput"
    assert tr.short_name(name) == "fusion.307"
    assert tr.op_label(name) == "fusion.307 bf16[896]"
    assert tr.is_collective("%all-reduce.5 = f32[] all-reduce(x)")
    assert tr.is_collective("%all-gather-start.2 = f32[] x")
    assert not tr.is_collective("%fusion.2 = f32[] fusion(all-reduce.1)")


def test_busy_idle_exposed_on_a_synthetic_trace():
    r = tr.reduce(synthetic())
    assert r["window_ns"] == 200
    assert r["busy_ns"] == 105                 # [0, 100] and [120, 125]
    # the collective [95, 130] less the compute leaves [120, 125]
    assert r["chips"][0]["collective_ns"] == 35
    assert r["exposed_collective_ns"] == 30
    top = dict(r["top_ops"])
    # while.1 encloses 30 + 40 ns of its body
    assert top["while.1 bf16[8]"] == pytest.approx(30e-9)
    assert top["fusion.2 bf16[8]"] == pytest.approx(40e-9)
    gaps = dict(r["idle_gaps"])
    assert gaps["bench.step"] == pytest.approx(75e-9)       # [125, 200]
    assert gaps["bench.step > PjitFunction(step)"] == pytest.approx(20e-9)


def test_window_is_required():
    t = synthetic()
    t["host"] = t["host"][1:]
    with pytest.raises(RuntimeError):
        tr.reduce(t)


def test_recorded_trace_matches_a_brute_force_count():
    """A trace recorded on a TPU v5e: three calls of a small jitted
    program inside ``bench.window``."""
    import numpy as np
    with open(DATA) as f:
        planes = json.load(f)
    r = tr.reduce(planes)
    lo, hi = tr.window_of(planes["host"], "bench.window")
    mask = np.zeros(int(hi - lo), bool)
    for s, e, _ in planes["devices"]["/device:TPU:0"]["XLA Ops"]:
        mask[int(max(s, lo) - lo):int(min(e, hi) - lo)] = True
    assert r["window_ns"] == hi - lo
    assert r["busy_ns"] == mask.sum() > 0
    assert sum(g for _, g in r["idle_gaps"]) == pytest.approx(
        (hi - lo - mask.sum()) * 1e-9)       # all 8 gaps are listed
    assert r["exposed_collective_ns"] == 0       # one chip: no collective
    assert all(name.startswith("bench.") for name, _ in r["idle_gaps"][:3])
    assert sum(t for _, t in r["top_ops"]) == pytest.approx(
        r["busy_ns"] * 1e-9)
