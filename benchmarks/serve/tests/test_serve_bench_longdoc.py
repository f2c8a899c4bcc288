"""CPU rehearsal of a long-document cell at a toy size: the 14b's traits
(`data/tiny_longdoc.json`) under a closed loop of prompts longer than their
answers (`data/tiny_longdoc_traffic.json`), traced, through the harness
with the Pallas kernels interpreted.  The run is correct and compiles
nothing in its window; `prefill_pad_frac` agrees with a count from the
admitted lengths; the readers of the device trace and of the chip's peak
read nothing off a TPU."""

import os

import pytest

from serve_bench_util import DATA, add_cell, checkout

import harness

SEED = 2**33 + 29
CELL = "tiny14b.tiny_longdoc"
DEVICE_READERS = ("prefill_ms_per_ktok", "prefill_matmul_roofline",
                  "flash_prefill_attention_roofline", "mfu.longdoc", "device_idle.longdoc")


@pytest.fixture(scope="module")
def longdoc(tmp_path_factory):
    root = checkout(str(tmp_path_factory.mktemp("longdoc")))
    add_cell(root, CELL, config="tiny14b", traffic="tiny_longdoc", limit=0.5,
             config_file=os.path.join(DATA, "tiny_longdoc.json"),
             traffic_file=os.path.join(DATA, "tiny_longdoc_traffic.json"))
    cell = harness.load_cell(root, CELL)
    h = harness.Harness(cell, SEED, 2.0, trace=True)
    run = h.execute()
    layer = harness.read_metrics(run, cell.metric_names(True))
    e2e = harness.read_metrics(run, cell.metric_names(False))
    h.release()
    return {"run": run, "layer": layer, "e2e": e2e, "block": h.settings["block_size"],
            "max_seq": h.settings["max_seq"], "numbers": harness.check(run, h.mix)[0]}


def test_longdoc_rehearsal_is_correct_and_compiles_nothing_in_the_window(longdoc):
    run, numbers = longdoc["run"], longdoc["numbers"]
    assert harness.is_correct(numbers), numbers
    assert numbers["served_tokens_checked"]["value"] >= 24
    assert run.window_compiles == []
    assert set(longdoc["e2e"]) == {"itl_p95_ms", "out_tok_s", "setup_s"}


def test_prefill_pad_frac_matches_the_admitted_lengths(longdoc):
    """Each admitting step prefills its requests as one batch padded to the
    next power of two of its longest prompt, at least one block."""
    run, bs = longdoc["run"], longdoc["block"]
    cap = -(-longdoc["max_seq"] // bs) * bs
    by_step = {}
    for r in run.requests:
        if r.admit_step is not None and r.admit_step >= 0:
            by_step.setdefault(r.admit_step, []).append(r.prompt_len)
    real = sum(sum(lens) for lens in by_step.values())
    padded = sum(len(lens) * max(bs, min(1 << (max(lens) - 1).bit_length(), cap))
                 for lens in by_step.values())
    assert len(by_step) > 3 and padded > real
    assert longdoc["layer"]["prefill_pad_frac"]["value"] == pytest.approx(1 - real / padded)


def test_device_readers_read_nothing_off_a_tpu(longdoc):
    assert longdoc["run"].device["platform"] == "cpu"
    for name in DEVICE_READERS:
        assert name not in longdoc["layer"], name
    assert "prefill_pad_frac" in longdoc["layer"]
