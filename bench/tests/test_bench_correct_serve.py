"""``correct`` of a serving cell on the CPU at a small size (its own
limits, ``cells.SMALL_LIMITS``): the program passes, the fp8 control
fails, and so does every planted fault."""
import importlib

import pytest

from bench import faults, harness, traffic
from bench.drivers import serve_closed
from bench.run import result_of
from bench.tests import cells

CELLS = [w["name"] for w in cells.BENCH["workloads"]
         if harness.traffic_of(w["traffic"])["driver"] == "serve_closed"]


def run(ctx):
    out = importlib.import_module("bench.drivers.serve_closed").run(ctx)
    return result_of(out, ctx, cells.BENCH, cells.limits(ctx))


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    res = run(cells.small_context(cell, 2**32 + 21))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_fp8_control_is_not_correct(cell):
    ctx = cells.small_context(cell, 2**32 + 22)
    sess = serve_closed.Session(ctx.mc, ctx.cfg, ctx.mix, ctx.devs, ctx.seed)
    results, sent, _ = sess.round(traffic.round_requests(
        ctx.mix, ctx.cfg["vocab_size"], ctx.seed, 0))
    done = {u: (q.prompt, results[u].tokens) for u, q in sent.items()}
    gap = serve_closed.reference_gap(ctx.cfg, ctx.mix, ctx.seed, done,
                                     pick="fp8")
    chk = harness.checks({"logit_gap": gap}, cells.limits(ctx))
    assert not harness.passed(chk), chk


@pytest.mark.parametrize("fault", sorted(faults.SERVE))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault):
    res = run(cells.small_context(cell, 2**32 + 23,
                                  fault=faults.SERVE[fault]))
    assert not res["correct"], res["checks"]
