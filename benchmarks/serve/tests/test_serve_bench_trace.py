"""The trace reduction, on a trace recorded on one TPU v5e: one decode step
of `qwen2-1.5b.decode` (64 active slots), traced by the harness with its
own host spans (`data/decode_step.xplane.pb`, 412 KiB; recorded by
`Harness(cell, 77, 4.0, trace=True)` with the traffic's `trace_seconds`
set to 0.2 and the trace directory kept)."""

import os

import pytest

from serve_bench_util import DATA, ROOT

import costs
import harness
import readings
import trace_reduce

FIXTURE = os.path.join(DATA, "decode_step.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    return trace_reduce.reduce_trace(FIXTURE)


def test_op_and_module_names():
    assert trace_reduce.op_name(
        "%fused_gemv.65 = f32[64,256]{1,0} custom-call(bf16[64,1536] %fusion.53)") == "fused_gemv"
    assert trace_reduce.op_name("%dynamic-slice_bitcast_fusion.25 = bf16[2]") == \
        "dynamic-slice_bitcast_fusion"
    assert trace_reduce.module_name("jit_decode(8639500160314716719)") == "jit_decode"


def test_busy_idle_and_programs(summary):
    assert summary.devices == 1
    assert summary.window_s == pytest.approx(0.096217755, rel=1e-6)
    assert summary.busy_s == pytest.approx(0.09219654, rel=1e-6)
    assert 0 < summary.busy_s <= summary.window_s
    assert summary.module_runs["jit_decode"] == 1
    # Busy time is leaf ops' time: loops' own events are not counted again,
    # and their bookkeeping between iterations is microseconds of a step.
    leaves = sum(summary.op_s.values())
    assert 0.999 * summary.busy_s <= leaves <= summary.busy_s


def test_kernel_time_by_name_and_program(summary):
    assert summary.op_s["paged_decode_attention"] == pytest.approx(0.044999319, rel=1e-6)
    assert summary.kernel_s({"fused_gemv"}, "jit_decode") == pytest.approx(0.01150042, rel=1e-6)
    assert summary.kernel_s({"fused_gemv"}, "jit_prefill") == 0.0
    top = summary.breakdown()["device_ops"]
    assert top[0][0] == "paged_decode_attention" and len(top) <= 10


def test_idle_gaps_are_attributed_to_host_spans(summary):
    idle = summary.idle_by_host
    assert sum(idle.values()) == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    assert set(idle) <= {"prefill dispatch", "decode dispatch", "step outside both",
                         "between steps"}
    assert idle["step outside both"] == pytest.approx(0.004021209, rel=1e-5)


def test_decode_roofline_on_the_recorded_step(summary):
    """One traced decode call of 64 rows: its projections' least time (all
    28 layers' weights read once, memory-bound) over the fused GEMV's time."""
    cell = harness.load_cell(ROOT, "qwen2-1.5b.decode")
    run = harness.Run(cell=cell, seed=0, seconds=0.0, trace=True)
    run.summary = summary
    run.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    run.calls = [harness.Call("decode", 0, True, rows=64)]
    got = readings.matmul_roofline(run, "decode", "jit_decode", ("fused_gemv",))
    ops, byts = costs.projection_cost(cell.config, 64)
    least, bound = costs.least_time(ops, byts, costs.peaks("TPU v5 lite"))
    assert bound == "memory"
    assert got == pytest.approx(100 * least / 0.01150042, rel=1e-6)
    assert 0 < got < 100
