"""``correct`` of a training cell on the CPU at a small size (its own
limits, ``cells.SMALL_LIMITS``): the program passes, the fp8 control
fails, and so does every planted fault."""
import importlib

import pytest

from bench import faults, harness
from bench.drivers import train
from bench.run import result_of
from bench.tests import cells

CELLS = [w["name"] for w in cells.BENCH["workloads"]
         if harness.traffic_of(w["traffic"])["driver"] == "train"]


def run(ctx):
    out = importlib.import_module("bench.drivers.train").run(ctx)
    return result_of(out, ctx, cells.BENCH, cells.limits(ctx))


@pytest.mark.parametrize("cell", CELLS)
def test_program_is_correct(cell):
    res = run(cells.small_context(cell, 2**32 + 11))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_fp8_control_is_not_correct(cell):
    ctx = cells.small_context(cell, 2**32 + 12)
    rows = ctx.mix["rows_per_chip"]
    ref = train.reference_readings(ctx.cfg, ctx.mix, ctx.seed, rows)
    ctl = train.reference_readings(ctx.cfg, ctx.mix, ctx.seed, rows, "fp8")
    chk = harness.checks(train.compare(ctl, ref), cells.limits(ctx))
    assert not harness.passed(chk), chk


@pytest.mark.parametrize("fault", sorted(faults.TRAIN))
@pytest.mark.parametrize("cell", CELLS)
def test_fault_is_not_correct(cell, fault):
    res = run(cells.small_context(cell, 2**32 + 13,
                                  fault=faults.TRAIN[fault]))
    assert not res["correct"], res["checks"]
