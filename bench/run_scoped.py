"""`bench/run.py`, with the traced window's device time by program stage.

    python3 bench/run_scoped.py --workload <cell> --seed <n> --seconds <s> --trace 1

The same run and result line as `bench/run.py`; a traced run's `breakdown`
gains `scopes`: device seconds per chip by the outermost and innermost named
stage on each op's program path (`bench/scopes.py`), the top 15, with the
Pallas MLP kernels named apart, and the seconds of the rest.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]   # in place of bench/ itself
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

from bench import harness, scopes  # noqa: E402


def keeping_runs(runs: list):
    """`harness.reader`, keeping the `Run` the readers are handed."""
    read = harness.reader

    def reader(metric):
        fn = read(metric)

        def keep(run):
            if not runs:
                runs.append(run)
            return fn(run)
        return keep
    return reader


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    runs: list = []
    harness.reader = keeping_runs(runs)
    try:
        cell = harness.load_cell(args.workload, args.seed)
        line = harness.execute(cell, args.seconds, bool(args.trace), t_start=T_START,
                               log=lambda s: print(f"bench: {s}", file=sys.stderr, flush=True))
    except harness.NoAccelerator as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    if runs and "breakdown" in line:
        line["breakdown"]["scopes"] = scopes.breakdown(runs[0])
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())
