"""`correct` comes out false when the timed path is broken underneath the
harness (its look for a chip skipped, the rest of a run driven as the
benchmark drives it), and the control (the reference in float8) fails the
comparison that the program passes.

Faults a one-chip serving cell can have: a token altered where it is
produced (the decode step's sampled tokens), and a step that returns its
state unchanged (the prefill's cache, so the prompt's K/V never lands).
"""

import numpy as np
import pytest

from serve_bench_util import tiny_checkout

import harness

SEED = 2**34 + 3
VOCAB = 2048


def run_cell(tmp, fault=None, limit=0.5):
    root = tiny_checkout(str(tmp), limit=limit)
    cell = harness.load_cell(root, "tiny.closed")
    h = harness.Harness(cell, SEED, 1.5)
    if fault is not None:
        fault(h.eng)
    run = h.execute()
    h.release()
    return run, h.mix


def alter_tokens(eng):
    decode = eng.decode_fn

    def altered(*args, **kw):
        nxt, logits, caches = decode(*args, **kw)
        return (nxt + 1) % VOCAB, logits, caches

    eng.decode_fn = altered


def keep_prefill_state(eng):
    prefill = eng.prefill_fn

    def unchanged(params, tokens, caches, *rest):
        logits, _ = prefill(params, tokens, caches, *rest)
        return logits, caches

    eng.prefill_fn = unchanged


@pytest.mark.parametrize("fault", [alter_tokens, keep_prefill_state],
                         ids=["token_altered", "state_unchanged"])
def test_fault_makes_correct_false(tmp_path, fault):
    run, mix = run_cell(tmp_path, fault)
    numbers, _ = harness.check(run, mix)
    assert numbers["served_tokens_checked"]["value"] > 0
    assert not harness.is_correct(numbers), numbers
    assert numbers["mean_logit_gap"]["value"] > numbers["mean_logit_gap"]["limit"]


@pytest.mark.parametrize("seed", [2**34 + 3, 13])
def test_control_fails_where_the_program_passes(tmp_path, seed):
    """Twelve fixed requests of 12 tokens, served by the engine to the end
    (no window, so the sample does not depend on timing), then the
    harness's own comparison of the program's tokens and of the float8
    control against the tiny cell's limits file.  Read on the CPU at this
    size over seeds 2**34+3, 11, 12, 13, 14: the program 0 to 0.00024, the
    control 0.0013 to 0.0027; the limit, 0.0005, lies between.  The cells'
    limits are set from readings at their own widths on the chip."""
    root = tiny_checkout(str(tmp_path), limit=0.0005)
    cell = harness.load_cell(root, "tiny.closed")
    h = harness.Harness(cell, seed, 1.0)
    reqs = [h.engine_lib.Request(uid=i, prompt=h.mix.prompt(i), max_new_tokens=12)
            for i in range(12)]
    for r in reqs:
        assert h.eng.submit(r)
    h.eng.run()
    seqs = [(h.mix.prompt(r.uid), np.asarray(r.generated, np.int32)) for r in reqs]
    degraded = len(h.eng.degraded)
    h.release()
    program, _ = harness.compare(cell, seed, seqs, degraded)
    control, _ = harness.compare(cell, seed, seqs, degraded, low=True)
    assert program["mean_logit_gap"]["limit"] == 0.0005
    assert harness.is_correct(program), program
    assert not harness.is_correct(control), control
