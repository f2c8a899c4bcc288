#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of this machine.

    python3 benchmarks/serve/run.py --workload qwen2-1.5b.decode \
        --seed 12345 --seconds 10 --trace 0

The cell, its configuration, traffic, metrics and limits are found by name
from BENCHMARK.json at the checkout's root.  `--trace 0` reports the cell's
end-to-end metrics, `--trace 1` its per-layer metrics from a run that also
traces a few seconds of the window.  The last line of standard output is
one JSON object: correct, attempted, failed, metrics, device (and with
`--trace 1` breakdown), and last `check`, each number compared with its
limit; the same numbers are the last lines of standard error.

Exits 2, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.  JAX's persistent compilation cache is the one
JAX_COMPILATION_CACHE_DIR names, else <checkout>/.jax_cache.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def enable_compile_cache(jax) -> str:
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # Every program, however quick to compile, so that only a checkout's
    # first run compiles.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def result_line(run, metrics: dict) -> dict:
    window = [r for r in run.requests if run.in_window(r.submit)]
    device = dict(run.device)
    out = {
        "correct": None,
        "attempted": len(window),
        "failed": sum(r.status not in ("ok", "running") for r in window),
        "metrics": metrics,
        "device": device,
    }
    if run.trace and run.summary is not None and run.summary.devices:
        device["busy_s"] = run.summary.busy_s
        device["window_s"] = run.summary.window_s
        out["breakdown"] = run.summary.breakdown()
    return out


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
    import jax

    import harness

    cell = harness.load_cell(ROOT, args.workload)
    devices = jax.devices()
    chips = int(cell.workload["chips"])
    if devices[0].platform != "tpu":
        log(f"platform {devices[0].platform!r} is not a TPU; no result")
        return 2
    if len(devices) < chips:
        log(f"{cell.name} needs {chips} chips, JAX finds {len(devices)}; no result")
        return 2
    log(f"compile cache {enable_compile_cache(jax)}")
    h = harness.Harness(cell, args.seed, args.seconds, trace=bool(args.trace), t_start=T_START)
    run = h.execute()
    log(f"devices {run.device}")
    log(f"warm-up {run.warm}; set-up {run.setup_s:.3f} s, of it compiling "
        f"{run.setup_compile_s:.3f} s")
    log(f"set-up compiles by function {h.clock.top(0.0, run.window_t0)}; "
        f"persistent cache {h.clock.cache}")
    log(f"window {run.window_s:.3f} s, {len(run.steps)} steps; compiles in window "
        f"{len(run.window_compiles)} ({sum(d for _, d in run.window_compiles):.3f} s) "
        f"{sorted({f for f, _ in run.window_compiles})}; first-call work in window "
        f"{run.window_first_call_s:.3f} s")
    lag = [r.submit - r.due for r in run.requests if run.in_window(r.submit)]
    if lag:
        log(f"load generator lag: max {1e3 * max(lag):.3f} ms over {len(lag)} sends")
    dec = sorted(1e3 * (st.t1 - st.t0) for st in run.steps if st.admitted == 0 and st.emitted)
    adm = [1e3 * (st.t1 - st.t0) for st in run.steps if st.admitted]
    if dec:
        log(f"decode-only steps {len(dec)}: p10 {dec[len(dec) // 10]:.2f} p50 "
            f"{dec[len(dec) // 2]:.2f} p90 {dec[9 * len(dec) // 10]:.2f} ms; admitting steps "
            f"{len(adm)}: mean {sum(adm) / max(len(adm), 1):.2f} ms")
    held = [st.bytes_in_use for st in run.steps if st.bytes_in_use]
    pages = [st.pages_in_use for st in run.steps if st.pages_in_use is not None]
    if held and pages:
        import costs

        page_b = costs.kv_bytes_per_token(cell.config) * h.settings.get("block_size", 16)
        log(f"window device memory: bytes_in_use max {max(held)}, min {min(held)}; "
            f"weights {2 * costs.param_count(cell.config)} B; live KV at the peak "
            f"pages_in_use {max(pages)} x {page_b} B = {max(pages) * page_b} B")
    metrics = harness.read_metrics(run, cell.metric_names(bool(args.trace)))
    out = result_line(run, metrics)
    h.release()
    t_ref = time.perf_counter()
    numbers, widest = harness.check(run, h.mix)
    log(f"reference check {time.perf_counter() - t_ref:.3f} s; widest single gap {widest}")
    out["correct"] = harness.is_correct(numbers)
    out["check"] = numbers
    for name, v in metrics.items():
        log(f"metric {name} = {v['value']} {v['unit']}")
    for name, v in numbers.items():
        print(f"[check] {name} {v['value']} limit {v['limit']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
