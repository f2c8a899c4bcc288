# One function per paper table. Prints ``name,us_per_call,derived`` CSV.
#
#   table1_parity      — paper Table 1 (accuracy parity HF vs 10x-IREE,
#                        plus the Llama.cpp-style w8a8/w4a8 columns)
#   table2_throughput  — paper Table 2 (prefill/decode tokens/s per path)
#                        + the decode fast-path bench (BENCH_decode.json)
#   kernel_bench       — per-microkernel correctness + timing (Figs 1-2 analog)
#   roofline           — §Roofline terms from the dry-run (TPU projection),
#                        emitted when results/dryrun/ exists.
#
# ``--quick``: smoke mode — only the decode fast-path + paged-cache benches,
# tiny shapes and step counts, finishes in seconds (CI / local sanity).
#
# A failing bench section does not abort the others, but ANY failure makes the
# process exit nonzero — CI's bench-smoke job treats bench breakage as red
# (benchmarks/check_regression.py separately gates on the emitted numbers).

from __future__ import annotations

import os
import sys
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _run_sections(sections) -> int:
    failures = []
    for name, fn in sections:
        try:
            fn()
        except Exception as exc:  # propagate as nonzero exit, keep going
            traceback.print_exc()
            print(f"{name}/FAILED,0,{exc!r}")
            failures.append(name)
    if failures:
        print(f"run/FAILED_SECTIONS,{len(failures)},{';'.join(failures)}")
        return 1
    return 0


def main() -> int:
    from benchmarks import ablation_tiles, kernel_bench, table1_parity, table2_throughput
    from repro.launch import compile_cache

    compile_cache.enable()
    print("name,us_per_call_or_value,derived")
    if "--quick" in sys.argv[1:]:
        return _run_sections([
            ("table2_quick", lambda: table2_throughput.main(quick=True)),
        ])

    sections = [
        ("table1", table1_parity.main),
        ("table2", table2_throughput.main),
        ("kernel_bench", kernel_bench.main),
        ("ablation_tiles", ablation_tiles.main),
    ]
    if os.path.isdir("results/dryrun") and os.listdir("results/dryrun"):
        from benchmarks import roofline

        sections.append(("roofline", roofline.main))
    else:
        print("roofline/SKIPPED,0,run repro.launch.dryrun first")
    return _run_sections(sections)


if __name__ == "__main__":
    sys.exit(main())
