#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 benchmarks/serve/control.py --workload qwen2-1.5b.decode \
        --seeds 11,12,13 --seconds 30

For each seed, in one process: one run of the cell as `run.py` makes it
(warm-up, window, then the float32 reference over a sample of the requests
it finished), and on the same prompts and served tokens the control: the
reference computed in float8 e4m3, reading the gap of the token that it
puts first.  Both go through the harness's own comparison against the
cell's limits file.  Prints one JSON line per seed with both mean gaps,
both widest gaps and both verdicts; the program's has to be true and the
control's false.  Exits 1 where a control came out correct.  The
benchmark's own runs never run the control.
"""

from __future__ import annotations

import json
import os
import sys
import time

import run as entry


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [entry.HERE, os.path.join(entry.ROOT, "src")]
    import jax

    import harness

    if jax.devices()[0].platform != "tpu":
        entry.log("not a TPU; no readings")
        return 2
    entry.enable_compile_cache(jax)
    cell = harness.load_cell(entry.ROOT, args.workload)
    clock = harness.CompileClock()
    control_passed = 0
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        h = harness.Harness(cell, seed, args.seconds, clock=clock, t_start=t0)
        run = h.execute()
        h.release()
        program, program_max = harness.check(run, h.mix)
        control, control_max = harness.check(run, h.mix, low=True)
        control_passed += harness.is_correct(control)
        print(json.dumps({
            "seed": seed, "program": program.get("mean_logit_gap", {}).get("value"),
            "control": control.get("mean_logit_gap", {}).get("value"),
            "program_max": program_max, "control_max": control_max,
            "tokens": program["served_tokens_checked"]["value"],
            "limit": program.get("mean_logit_gap", {}).get("limit"),
            "correct": harness.is_correct(program),
            "control_correct": harness.is_correct(control), "setup_s": run.setup_s,
            "window_compiles": len(run.window_compiles),
            "seconds": time.perf_counter() - t0}), flush=True)
    return 1 if control_passed else 0


if __name__ == "__main__":
    sys.exit(main())
