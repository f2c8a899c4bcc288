"""The readers of the engine's own spans and counters, on a CPU rehearsal:
the tiny closed cell run through the harness with a trace, as the
rehearsal runs it.  Each reader finds a number; the counters read inside
the program agree exactly with the same quantities read from outside it,
and the step span lies inside the harness's clock around `Engine.step`."""

import pytest

from serve_bench_util import tiny_checkout

import harness

SEED = 2**33 + 11
ENGINE_METRICS = ("step_host_ms.decode", "admit_host_ms.decode", "decode_step_ms.engine",
                  "batch_occupancy.engine", "kv_pages_peak_frac.engine")


@pytest.fixture(scope="module")
def layer(tmp_path_factory):
    root = tiny_checkout(str(tmp_path_factory.mktemp("spans")))
    cell = harness.load_cell(root, "tiny.closed")
    h = harness.Harness(cell, SEED, 2.0, trace=True)
    run = h.execute()
    out = harness.read_metrics(run, cell.metric_names(True))
    h.release()
    return out


@pytest.mark.parametrize("name", ENGINE_METRICS)
def test_engine_reader_reads_a_number(layer, name):
    assert name in layer, sorted(layer)
    assert layer[name]["value"] > 0


@pytest.mark.parametrize("name", ["batch_occupancy", "kv_pages_peak_frac"])
def test_counters_inside_equal_the_outside_readings(layer, name):
    assert layer[name + ".engine"]["value"] == layer[name]["value"]


def test_engine_step_span_lies_inside_the_harness_clock(layer):
    assert layer["decode_step_ms.engine"]["value"] <= layer["decode_step_ms"]["value"]
    assert layer["step_host_ms.decode"]["value"] <= layer["decode_step_ms.engine"]["value"]
