"""The reduction of the program's own spans and scopes in a trace, and
what it leaves alone: the benchmark's reduction of the same trace."""
import json
import os

import pytest

from bench import harness
from bench import program_trace as pt
from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "small_trace.json")


def test_scope_of_takes_the_innermost_registered_scope():
    assert pt.scope_of("jit(step)/layers/while/body/closed_call/"
                       "checkpoint/attn/dot_general") == "attn"
    assert pt.scope_of("jit(step)/transpose(jvp(lm_head))/mul;"
                       "jit(step)/transpose(jvp(lm_head))/bcast") == "lm_head"
    assert pt.scope_of("jit(step)/transpose(jvp())/while/body/"
                       "dynamic_update_slice") is None
    assert pt.scope_of("jit(d)/layers/while/body/dynamic_slice") == "layers"
    assert pt.scope_of("jit(d)/lm_head/comm.flush.ch3/psum") == "comm"
    assert pt.scope_of("jit(b)/comm.flush.ch2/comm.leader.l0/psum") == "comm"
    assert pt.scope_of("jit(s)/optimizer/mul") == "optimizer"
    assert pt.scope_of("") is None


def synthetic():
    """Two decode steps and a host step of one loop over chip 0's ops."""
    host = [(0, 1000, "bench.window"), (900, 905, "np.asarray")]
    spans = [(0, 400, "decode", {"name": "step1", "active": 3}),
             (400, 500, "host_step", {"name": "step1"}),
             (500, 900, "decode", {"name": "step2", "active": 2}),
             (900, 900, "request", {"uid": 7, "ttft_ms": 5.0,
                                    "done_ms": 9.0, "tokens": 2}),
             (990, 1100, "decode", {"name": "step3"})]
    ops = [(10, 300, "jit(d)/layers/while/body/attn/dot_general"),
           (20, 100, "jit(d)/layers/while/body/attn/broadcast"),
           (300, 380, "jit(d)/lm_head/dot_general"),
           (520, 880, "jit(d)/transpose(jvp(mlp))/dot_general"),
           (880, 890, "")]
    return {"host": host + [(s, e, "repro." + k) for s, e, k, _ in spans],
            "spans": spans, "ops": {"/device:TPU:0": ops}}


def test_reduce_program_on_a_synthetic_trace():
    r = pt.reduce_program(synthetic())
    assert r["window_ns"] == 1000
    # step3 ends after the window: it is not counted, but its idle is
    assert r["kinds"]["decode"]["count"] == 2
    assert r["kinds"]["decode"]["median_ms"] == pytest.approx(400e-6)
    assert r["kinds"]["host_step"]["count"] == 1
    assert r["requests"] == [{"uid": 7, "ttft_ms": 5.0, "done_ms": 9.0,
                              "tokens": 2}]
    # busy [10, 380] and [520, 890]: 740 ns
    assert r["busy_ns"] == 740
    assert r["scopes"] == {"attn": 290.0, "lm_head": 80.0, "mlp": 360.0,
                           "None": 10.0}
    # idle [0, 10] decode; [380, 400] decode, [400, 500] host_step,
    # [500, 520] decode; [890, 900] decode, [900, 990] none, [990, 1000]
    # decode (step3)
    assert r["idle_by_span"] == {"decode": 70.0, "host_step": 100.0,
                                 "": 90.0}
    assert sum(r["idle_by_span"].values()) == 1000 - r["busy_ns"]
    top = r["idle_gaps"][0]
    assert top[0] == pytest.approx(140e-9) and top[1:] == ["host_step",
                                                           "step1"]


def test_program_metrics_per_cell_kind():
    r = pt.reduce_program(synthetic())
    serve = pt.metrics(r, "serve")
    assert serve["decode_step_ms.serve"] == pytest.approx(400e-6)
    assert serve["host_step_ms.serve"] == pytest.approx(100e-6)
    assert serve["ttft_p95_ms.serve"] == 5.0
    assert serve["attention_share.serve"] == pytest.approx(100 * 290 / 740)
    assert pt.metrics(r, "train") == {
        "lm_head_share.train": pytest.approx(100 * 80 / 740)}
    assert "lm_head_share.train" not in serve
    # a trace without the program's spans (the parent's) reads nothing
    bare = synthetic()
    bare["spans"] = []
    bare["ops"] = {"/device:TPU:0": [(s, e, "jit(d)/while/body/dot")
                                     for s, e, _ in bare["ops"][
                                         "/device:TPU:0"]]}
    empty = pt.reduce_program(bare)
    assert all(v is None for v in pt.metrics(empty, "serve").values())
    assert pt.metrics(empty, "train") == {"lm_head_share.train": None}


def test_hlo_op_names_from_a_dump(tmp_path):
    (tmp_path / "module_0005.jit_f.tpu_after_optimizations.txt").write_text(
        "HloModule jit_f, entry_computation_layout={()->f32[]}\n"
        '  %fusion.3 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, '
        'metadata={op_name="jit(f)/attn/dot_general" stack_frame_id=1}\n'
        "  ROOT %copy.1 = f32[8]{0} copy(%fusion.3)\n")
    (tmp_path / "module_0005.jit_f.before_optimizations.txt").write_text(
        "HloModule jit_f\n"
        '  %x = f32[8]{0} add(%p), metadata={op_name="jit(f)/mlp/x"}\n')
    assert pt.hlo_op_names(str(tmp_path)) == {
        ("jit_f", "fusion.3"): "jit(f)/attn/dot_general"}


SMALL_TRACE_REDUCED = {
    "busy_ns": 48411.0,
    "chips": [{"busy_ns": 48411.0, "collective_ns": 0.0,
               "device": "/device:TPU:0", "exposed_collective_ns": 0.0,
               "ops": 8}],
    "exposed_collective_ns": 0.0,
    "idle_gaps": [["bench.step > PjitFunction(<lambda>)",
                   0.004314467000000001],
                  ["bench.step > PjitFunction(<lambda>)",
                   0.0032541870000000004],
                  ["bench.step > PjitFunction(<lambda>)", 0.002051226],
                  ["no host event", 2e-09], ["no host event", 2e-09],
                  ["no host event", 2e-09], ["no host event", 1e-09],
                  ["no host event", 1e-09]],
    "top_ops": [["fusion bf16[1024,1024]", 2.5233e-05],
                ["convolution_tanh_fusion bf16[1024,1024]", 2.3146e-05],
                ["copy-start bf16[1024,1024]", 2.6e-08],
                ["copy-done bf16[1024,1024]", 6.000000000000001e-09]],
    "window_ns": 9668299.0}


def test_benchmark_reduction_of_the_recorded_trace_is_unchanged():
    """Every key the benchmark's reduction returns on the recorded v5e
    trace, and every per-layer reading of it, byte for byte."""
    with open(DATA) as f:
        planes = json.load(f)
    red = tr.reduce(planes)
    assert json.dumps(red, sort_keys=True) == json.dumps(
        SMALL_TRACE_REDUCED, sort_keys=True)
    idle = harness.metric_reader("device_idle.serve")(
        {"kind": "serve", "trace": red})
    assert repr(idle) == repr(100.0 * (1.0 - 48411.0 / 9668299.0))
    assert harness.metric_reader("device_idle.train")(
        {"kind": "serve", "trace": red}) is None


def test_tpu_ops_named_through_their_module_and_the_dump():
    """A trace recorded on a TPU v5e: one call of a jitted step (a scanned
    ``attn``/``mlp`` layer pair with remat, its gradient, an ``lm_head``
    loss and an ``optimizer`` update). The op events carry no op_name;
    the module event they run in and the step's optimized HLO name them."""
    names = pt.hlo_op_names(os.path.join(os.path.dirname(DATA), "tpu_dump"))
    prog = pt.read_program(os.path.join(os.path.dirname(DATA),
                                        "tpu_scoped_step.xplane.pb"), names)
    ops = prog["ops"]["/device:TPU:0"]
    assert len(ops) == 62 and sum(1 for _, _, n in ops if n) == 48
    scopes = pt.scope_self_ns(ops)
    assert set(scopes) == {None, "attn", "mlp", "lm_head", "optimizer"}
    assert scopes["mlp"] > scopes["attn"] > scopes["optimizer"] > 0
    assert [sp[2] for sp in prog["spans"]] == ["decode"]
    assert prog["spans"][0][3] == {"step": 3, "active": 16}
