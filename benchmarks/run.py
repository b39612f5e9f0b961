"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Usage:

    PYTHONPATH=src python -m benchmarks.run             # everything
    PYTHONPATH=src python -m benchmarks.run --only fig18,table4
    PYTHONPATH=src python -m benchmarks.run --list      # what exists, where
                                                        # each suite writes

See docs/BENCHMARKS.md for what each suite measures and the current numbers.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from repro.runtime.compile_cache import enable_compile_cache

# (name, module, output artifact or None) — artifacts land in the repo root
# and are what CI gates on; suites without one only emit CSV rows.
SUITES = [
    ("fig4_breakdown", "bench_breakdown", "BENCH_obs_overhead.json"),
    ("fig5_pace", "bench_pace", None),
    ("table1_grid_sizes", "bench_grid_sizes", None),
    ("table2_update_freq", "bench_update_freq", "BENCH_update_freq.json"),
    ("table4_algo", "bench_algo", None),
    ("pipeline_compaction", "bench_pipeline", "BENCH_pipeline.json"),
    ("fused_path_kernel", "bench_fused_path", "BENCH_fused_path.json"),
    ("adaptive_sampler", "bench_sampler", "BENCH_sampler.json"),
    ("serve3d_service", "bench_serve3d", "BENCH_serve3d.json"),
    ("serve3d_robustness", "bench_robustness", "BENCH_robustness.json"),
    ("fig8_10_access_patterns", "bench_access_patterns", None),
    ("fig16_18_kernels", "bench_kernels", None),
]


def list_suites() -> None:
    width = max(len(name) for name, _, _ in SUITES)
    mwidth = max(len(f"benchmarks.{m}") for _, m, _ in SUITES)
    print(f"{'name':<{width}}  {'module':<{mwidth}}  artifact")
    for name, module, artifact in SUITES:
        print(f"{name:<{width}}  {f'benchmarks.{module}':<{mwidth}}  {artifact or '-'}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated name substrings")
    ap.add_argument("--list", action="store_true",
                    help="print registered suites with their output artifacts "
                         "and exit (run nothing)")
    args = ap.parse_args()
    if args.list:
        list_suites()
        return
    only = args.only.split(",") if args.only else None
    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)

    print("name,us_per_call,derived")
    failures = []
    for name, module, _artifact in SUITES:
        if only and not any(o in name for o in only):
            continue
        t0 = time.time()
        try:
            mod = __import__(f"benchmarks.{module}", fromlist=["run"])
            mod.run()
            print(f"# {name} done in {time.time()-t0:.0f}s", file=sys.stderr)
        except Exception:
            failures.append(name)
            print(f"# {name} FAILED:\n{traceback.format_exc()}", file=sys.stderr)
    if failures:
        print(f"# FAILED suites: {failures}", file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
