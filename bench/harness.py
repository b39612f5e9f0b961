"""The harness: one run of one cell, from `BENCHMARK.json` and the files it names.

Everything a cell is made of is found by name, so a cell, a configuration
or a metric is added by adding files:

* `BENCHMARK.json` — the cell's configuration, traffic and chips, and the
  metrics that apply to it;
* `bench/configs/<config>.json` — sizes and settings, with source and cuts;
* `bench/traffic/<traffic>.json` — the mix, and the driver that runs it;
* `bench/drivers/<driver>.py` — the entry point the window drives, and
  what the readers and the comparison need to know of its work;
* `bench/metrics/<metric>.py` — one reader per metric;
* `bench/limits/<cell>.json` — the limit of each number `correct` compares.

A run: set-up (jax start, traffic and weights from the seed, the program's
objects, warm-up of exactly the cell's programs), the window, the peak of
device memory, then, with the program's state freed, the comparison with
the plain reference.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


class NoAccelerator(RuntimeError):
    pass


@dataclass
class Cell:
    name: str
    seed: int
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    limits: dict


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", metric["name"]) in reported


def load_cell(name: str, seed: int, bench: dict | None = None) -> Cell:
    from bench import compare, traffic

    bench = bench or json.loads((ROOT / "BENCHMARK.json").read_text())
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r}; have {sorted(work)}")
    w = work[name]
    cfg = json.loads((HERE / "configs" / f"{w['config']}.json").read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, {m["name"]})]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _applies(m, name, names)]
    return Cell(name, int(seed), w, cfg, traffic.load(w["traffic"]), e2e, per_layer,
                compare.limits(name))


def _module(kind: str, name: str):
    path = HERE / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench.{kind}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(metric: str):
    """The reader of a metric: `read(run) -> float | None` in bench/metrics/<metric>.py."""
    return _module("metrics", metric).read


def driver(cell: Cell):
    """The cell's driver: `Driver(cell)` from bench/drivers/<traffic's driver>.py."""
    return _module("drivers", cell.traffic["driver"]).Driver(cell)


@dataclass
class Run:
    """What the metric readers read."""
    cell: Cell
    driver: object = None
    setup_s: float = 0.0
    window: dict = field(default_factory=dict)
    spans: list = field(default_factory=list)
    trace: object = None
    device_kind: str = ""


def devices_for(chips: int, require: bool):
    import jax
    devs = jax.devices()
    if require and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(f"the cell needs {chips} TPU chip(s); jax reports "
                            f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def configure_jax(cfg: dict):
    """The compile cache's directory comes from JAX_COMPILATION_CACHE_DIR,
    which `run.py` points into the checkout before jax is imported."""
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])


class CompileLog:
    """Lowerings, XLA compiles and persistent-cache hits, with their times."""

    EVENTS = {"/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
              "/jax/core/compile/backend_compile_duration": "compile"}

    def __init__(self):
        import jax
        self.events: list = []
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in self.EVENTS:
            self.events.append((self.EVENTS[event], time.perf_counter(), float(secs)))

    def _event(self, event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            self.events.append((event.rsplit("_", 1)[-1], time.perf_counter(), 0.0))

    def between(self, t0: float, t1: float) -> dict:
        out: dict = {}
        for kind, t, secs in self.events:
            if t0 <= t <= t1:
                n, s = out.get(kind, (0, 0.0))
                out[kind] = (n + 1, s + secs)
        return out


def execute(cell: Cell, seconds: float, trace: bool, *, t_start: float,
            require_chips: bool = True, log=print) -> dict:
    """One run of `cell`; returns the result line's object."""
    import jax
    from bench import compare
    from repro.obs import trace as obs

    devs = devices_for(cell.workload["chips"], require_chips)
    configure_jax(cell.config)
    compiles = CompileLog()
    t_jax = time.perf_counter()
    drv = driver(cell)
    run = Run(cell, drv, device_kind=devs[0].device_kind)

    obs.clear()
    obs.configure(enabled=True, jax_annotations=False)
    drv.setup()
    t_setup = time.perf_counter()
    run.setup_s = t_setup - t_start
    obs.configure(enabled=trace, jax_annotations=trace)
    log(f"setup {run.setup_s:.2f}s (jax {t_jax - t_start:.2f}s); compiles "
        f"{compiles.between(t_start, t_setup)}")

    tdir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0        # spans and runtime events, not every call
        jax.profiler.start_trace(tdir, profiler_options=opts)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation("bench/window"):
            run.window = drv.window(seconds)
    finally:
        if trace:
            jax.profiler.stop_trace()
    t1 = time.perf_counter()
    obs.configure(enabled=False, jax_annotations=False)
    run.spans = obs.events()
    in_window = compiles.between(t0, t1)
    stats = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs]
    slowest = sorted(run.window.get("unit_seconds", []))[-3:]
    log(f"window {run.window['units']} {drv.unit}s in {run.window['seconds']:.3f}s, slowest "
        f"{[round(u, 4) for u in slowest]}; compiles in window {in_window}")

    if trace:
        from bench import devtrace
        try:
            run.trace = devtrace.reduce(devtrace.load(tdir), [str(d.id) for d in devs])
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    drv.free()
    t_ref = time.perf_counter()
    numbers = drv.numbers(drv.program_readings(), drv.reference_readings())
    correct, rows = compare.judge(numbers, cell.limits)
    if in_window.get("lower") or in_window.get("compile"):
        correct = False
        rows.append(("window_compiles", float(sum(n for n, _ in in_window.values())), 0.0))
    log(f"reference {time.perf_counter() - t_ref:.1f}s; numbers "
        f"{ {k: v for k, v in numbers.items()} }")

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs),
              "memory_peak_bytes": int(max(stats))}
    line = {"correct": bool(correct), "attempted": int(run.window["attempted"]),
            "failed": int(run.window["failed"]), "metrics": metrics, "device": device}
    if trace and run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    line["check"] = {k: {"value": v, "limit": lim} for k, v, lim in rows}
    for k, v, lim in rows:
        print(f"check {k} {v!r} limit {lim!r}", file=sys.stderr)
    return line


def main(argv=None, t_start: float | None = None) -> int:
    import argparse
    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        cell = load_cell(args.workload, args.seed)
        line = execute(cell, args.seconds, bool(args.trace), t_start=t_start,
                       log=lambda s: print(f"bench: {s}", file=sys.stderr, flush=True))
    except NoAccelerator as e:
        print(f"bench: {e}; nothing was run", file=sys.stderr)
        return 3
    print(json.dumps(line))
    return 0
