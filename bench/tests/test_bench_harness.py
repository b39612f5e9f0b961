"""The harness finds cells, configurations, traffic, limits and metric readers
by name; its result line has the keys the benchmark's contract fixes; and a
run with no TPU exits non-zero without a result."""
import json
import os
import re
import shutil
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import jax
import pytest

from bench import compare, harness, traffic
from bench.tests import bench_tiny

ROOT = Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_benchmark_file_keeps_to_its_format():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    names = [c["name"] for c in BENCH["configs"]] + [w["name"] for w in BENCH["workloads"]]
    names += [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names)) and all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == c["reduced"]
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    layers = {}
    for m in BENCH["per_layer"]:
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
        layers.setdefault(m["layer"], m["layer"])
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_is_found_by_name(cell):
    c = harness.load_cell(cell, 1)
    drv = harness.driver(c)
    for attr in ("unit", "faults", "numbers", "setup", "window", "free", "serve_for_check",
                 "points_per_unit", "kernel_rows", "window_flops", "program_readings",
                 "reference_readings"):
        assert hasattr(drv, attr), attr
    assert drv.points_per_unit() == 196_608
    e2e = {m["name"] for m in c.end_to_end}
    assert "setup_s" in e2e and len(e2e) >= 2 and c.per_layer
    assert all(m["moves"] in e2e for m in c.per_layer)
    for m in c.end_to_end + c.per_layer:
        assert callable(harness.reader(m["name"]))
    assert c.limits and all(v > 0 for v in c.limits.values())


def test_a_cell_config_traffic_and_metric_are_added_by_adding_files(tmp_path, monkeypatch):
    tree = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, tree / sub)
    cfg = json.loads((tree / "configs" / "instant3d.json").read_text())
    cfg.update(name="instant3d-big", reduced=[])
    cfg["field"]["log2_table_color"] = 17
    (tree / "configs" / "instant3d-big.json").write_text(json.dumps(cfg))
    t = json.loads((tree / "traffic" / "train-dense.json").read_text())
    (tree / "traffic" / "train-wide.json").write_text(json.dumps(dict(t, rays=8192)))
    (tree / "limits" / "big-wide.json").write_text(
        json.dumps({"numbers": {"loss_gap": {"limit": 1e-5}}}))
    (tree / "metrics" / "rays_per_step.py").write_text(
        "def read(run):\n    return run.cell.traffic['rays']\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "big-wide", "config": "instant3d-big",
                               "traffic": "train-wide", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "rays_per_step", "unit": "rays", "better": "higher",
                               "source": "program_counter", "layer": "trainer",
                               "moves": "train_step_ms", "workloads": ["big-wide"]})
    for mod in (harness, compare, traffic):
        monkeypatch.setattr(mod, "HERE", tree)
    c = harness.load_cell("big-wide", 5, bench=bench)
    assert c.config["field"]["log2_table_color"] == 17 and c.traffic["rays"] == 8192
    assert c.limits == {"loss_gap": 1e-5}
    assert [m["name"] for m in c.per_layer][-1] == "rays_per_step"
    assert harness.reader("rays_per_step")(harness.Run(c)) == 8192


ECHO_DRIVER = textwrap.dedent('''
    class Driver:
        unit = "call"
        faults = ()

        def __init__(self, cell):
            self.cell = cell

        def setup(self):
            pass

        def window(self, seconds):
            return {"units": 4, "seconds": 1.0, "attempted": 4, "failed": 0}

        def free(self):
            pass

        def serve_for_check(self):
            pass

        def points_per_unit(self):
            return 1

        def kernel_rows(self):
            return 1

        def window_flops(self, window):
            return 0.0

        def program_readings(self):
            return {"x": 1.0}

        def reference_readings(self, precision="highest", fault=None):
            return {"x": 1.0 + (fault == "off")}

        @staticmethod
        def numbers(prog, ref):
            return {"x_gap": abs(prog["x"] - ref["x"])}
''')


def test_a_driver_is_added_by_adding_one_file(tmp_path, monkeypatch):
    tree = tmp_path / "bench"
    for sub in ("configs", "traffic", "limits", "metrics", "drivers"):
        shutil.copytree(ROOT / "bench" / sub, tree / sub,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (tree / "drivers" / "echo.py").write_text(ECHO_DRIVER)
    (tree / "traffic" / "echo.json").write_text(json.dumps({"driver": "echo"}))
    (tree / "limits" / "instant3d-echo.json").write_text(
        json.dumps({"numbers": {"x_gap": {"limit": 0.0}}}))
    (tree / "metrics" / "call_ms.py").write_text(
        "def read(run):\n    return 1e3 * run.window['seconds'] / run.window['units']\n")
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "instant3d-echo", "config": "instant3d",
                               "traffic": "echo", "chips": 1, "why": "test"})
    bench["end_to_end"].append({"name": "call_ms", "unit": "ms", "better": "lower",
                                "bound": 0.01, "source": "host_clock",
                                "workloads": ["instant3d-echo"]})
    for mod in (harness, compare, traffic):
        monkeypatch.setattr(mod, "HERE", tree)
    c = harness.load_cell("instant3d-echo", 5, bench=bench)
    line = harness.execute(c, 0.1, False, t_start=time.perf_counter(), require_chips=False,
                           log=lambda s: None)
    assert line["correct"] and line["check"]["x_gap"] == {"value": 0.0, "limit": 0.0}
    assert set(line["metrics"]) == {"setup_s", "call_ms"}
    assert line["metrics"]["call_ms"]["value"] == 250.0
    drv = harness.driver(c)
    assert not compare.judge(drv.numbers(drv.program_readings(),
                                         drv.reference_readings(fault="off")), c.limits)[0]


def test_result_line_holds_the_contract_keys():
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        line = bench_tiny.run("instant3d-render")
    finally:
        jax.config.update("jax_enable_compilation_cache", True)
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "check"]
    assert set(line["metrics"]) == {"setup_s", "render_ms"}
    assert all(set(v) == {"value", "unit"} for v in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert all(set(v) == {"value", "limit"} for v in line["check"].values())


def _run_cli(cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "instant3d-render", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_the_run_fails_and_prints_no_result():
    p = _run_cli(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "TPU" in p.stderr


def test_with_only_the_benchmark_files_the_run_fails(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0 and p.stdout == ""
