"""Readings that the limits of `correct` are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 12 --control-seeds 3 \
        [--first-seed N] [--out calib.jsonl]

For each seed: the cell's set-up as a run makes it (the same driver, the
same timed call and feed), then the numbers `correct` compares, program
against reference.  On the first `--control-seeds` seeds also the control,
the reference computed in the nearest precision below the configuration's
(three bfloat16 passes for float32 at highest), put in the program's place,
and each fault the driver plants in the reference (for training, the mean
taken over half of the batch).  A render cell serves `check_views` views a
seed outside any window.  One JSON line per seed and reading.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))

# the nearest precision below the one a configuration states
CONTROL = {"highest": "high"}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_000_000_000)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from bench import harness

    out = open(args.out, "a") if args.out else None
    for k in range(args.seeds):
        seed = args.first_seed + 7919 * k
        cell = harness.load_cell(args.workload, seed)
        if k == 0:
            harness.devices_for(cell.workload["chips"], True)
            harness.configure_jax(cell.config)
        drv = harness.driver(cell)
        t0 = time.perf_counter()
        drv.setup()
        drv.serve_for_check()
        t1 = time.perf_counter()
        prog = drv.program_readings()
        ref = drv.reference_readings()
        t2 = time.perf_counter()
        rows = [("program", drv.numbers(prog, ref))]
        if k < args.control_seeds:
            lower = CONTROL[cell.config["matmul_precision"]]
            rows.append((f"control:{lower}", drv.numbers(drv.reference_readings(lower), ref)))
            rows += [(f"fault:{f}", drv.numbers(drv.reference_readings(fault=f), ref))
                     for f in drv.faults]
        for what, nums in rows:
            rec = {"cell": args.workload, "seed": seed, "reading": what, "numbers": nums,
                   "setup_s": t1 - t0, "reference_s": t2 - t1}
            line = json.dumps(rec, default=str)
            print(line, flush=True)
            if out:
                out.write(line + "\n")
                out.flush()
    print(f"calibrate: {args.workload} done in {time.perf_counter() - T_START:.1f}s",
          file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
