"""CPU rehearsal of the harness: a tiny configuration and traffic file run
through its internals with the Pallas kernels interpreted; a configuration,
a traffic mix and a per-layer metric added as new files and entries only;
the entry point's refusal of a platform that is not a TPU; and the
agreement of BENCHMARK.json with the metric files it names."""

import json
import os

import pytest

from serve_bench_util import BENCH, DATA, ROOT, tiny_checkout

import harness
import run as entry

SEED = 2**33 + 7  # wider than 32 bits, as a run's seed may be

NEW_METRIC = '''"""Test metric: steps in the window."""

LAYER = "scheduler"
UNIT = "steps"
MOVES = "out_tok_s"


def read(run):
    return float(len(run.steps)) or None
'''


@pytest.fixture(scope="module")
def closed(tmp_path_factory):
    root = tiny_checkout(str(tmp_path_factory.mktemp("closed")), metrics=[{
        "name": "steps_in_window", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "scheduler", "moves": "out_tok_s"}])
    with open(os.path.join(root, "benchmarks", "serve", "metrics", "steps_in_window.py"), "w") as f:
        f.write(NEW_METRIC)
    cell = harness.load_cell(root, "tiny.closed")
    h = harness.Harness(cell, SEED, 2.0, trace=True)
    run = h.execute()
    layer = harness.read_metrics(run, cell.metric_names(True))
    e2e = harness.read_metrics(run, cell.metric_names(False))
    h.release()
    return {"cell": cell, "run": run, "layer": layer, "e2e": e2e,
            "numbers": harness.check(run, h.mix)[0]}


def test_rehearsal_is_correct_and_compiles_nothing_in_the_window(closed):
    run, numbers = closed["run"], closed["numbers"]
    assert run.window_compiles == []
    assert harness.is_correct(numbers), numbers
    assert numbers["served_tokens_checked"]["value"] > 0
    assert run.warm["admissions"] > 0
    assert run.device["platform"] == "cpu"


def test_rehearsal_reports_end_to_end_metrics(closed):
    e2e = closed["e2e"]
    assert set(e2e) == {"itl_p95_ms", "out_tok_s", "setup_s"}
    assert all(v["value"] > 0 for v in e2e.values())
    assert e2e["out_tok_s"]["unit"] == "tokens/s"


def test_new_files_run_by_name(closed):
    """The tiny configuration, traffic mix and the steps_in_window metric
    exist only as new files plus new entries, and the harness ran them."""
    layer, run = closed["layer"], closed["run"]
    assert layer["steps_in_window"]["value"] == len(run.steps) > 0
    assert closed["cell"].config["hidden_size"] == 128


def test_device_metrics_stay_silent_off_a_tpu(closed):
    layer = closed["layer"]
    for name in ("batch_occupancy", "kv_pages_peak_frac", "decode_step_ms"):
        assert name in layer, name
    assert 0 < layer["batch_occupancy"]["value"] <= 1
    assert 0 < layer["kv_pages_peak_frac"]["value"] <= 1
    for name in ("decode_matmul_roofline", "mfu.decode", "device_idle.decode"):
        assert name not in layer, name


def test_open_loop_rehearsal_times_from_due(tmp_path):
    root = tiny_checkout(str(tmp_path), name="tiny.open",
                         traffic_file=os.path.join(DATA, "tiny_open.json"))
    cell = harness.load_cell(root, "tiny.open")
    h = harness.Harness(cell, SEED, 1.5)
    run = h.execute()
    sent = [r for r in run.requests if run.in_window(r.submit)]
    assert sent and all(r.submit >= r.due for r in sent)
    firsts = [r for r in run.requests if run.in_window(r.first)]
    assert firsts and all(r.first > r.submit for r in firsts)
    # The open loop's schedule is the generator's, from the window's start.
    sched = harness.traffic_lib.generator(cell.traffic, h.mix).schedule(len(run.requests))
    assert [r.due - run.window_t0 for r in run.requests] == pytest.approx(sched)


def test_entry_point_refuses_a_platform_that_is_not_a_tpu(capsys):
    rc = entry.main(["--workload", "qwen2-1.5b.decode", "--seed", str(SEED),
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc == 2
    assert out.out == ""
    assert "not a TPU" in out.err


def test_a_metric_file_is_loaded_by_name():
    cell = harness.load_cell(ROOT, "qwen2-1.5b.decode")
    mod = cell.metric("decode_step_ms")
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == ("model step", "ms", "itl_p95_ms")


def test_benchmark_json_agrees_with_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        b = json.load(f)
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    cells = {w["name"]: w for w in b["workloads"]}
    e2e = {m["name"]: m for m in b["end_to_end"]}
    reported = {c: {n for n, m in e2e.items() if "workloads" not in m or c in m["workloads"]}
                for c in cells}
    for m in b["end_to_end"]:
        mod = harness.load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"), "m")
        assert mod.UNIT == m["unit"]
    for m in b["per_layer"]:
        mod = harness.load_module(os.path.join(BENCH, "metrics", m["name"] + ".py"), "m")
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (m["layer"], m["unit"], m["moves"])
        for c in m["workloads"]:
            assert m["moves"] in reported[c], (m["name"], c)
    for c, w in cells.items():
        assert "setup_s" in reported[c] and len(reported[c]) >= 2
        assert any(c in m["workloads"] for m in b["per_layer"])
        assert os.path.exists(os.path.join(BENCH, "traffic", w["traffic"] + ".json"))
        assert os.path.exists(os.path.join(BENCH, "limits", c + ".json"))
    for c in b["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert sorted(c["reduced"]) == sorted(cfg["reduced"])
        for key, published in cfg["published"].items():
            assert (cfg[key] != published) == (key in c["reduced"])
