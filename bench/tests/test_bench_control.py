"""The control: the reference computed in the nearest precision below the
configuration's (three bfloat16 passes for float32 at highest), put in the
program's place, has to come out not correct.  Here at a small size on the
CPU; `bench/calibrate.py` reads it on the chip at the cells' own sizes."""
import jax
import pytest

from bench import compare, harness
from bench.calibrate import CONTROL
from bench.tests import bench_tiny


@pytest.fixture(autouse=True)
def no_compile_cache():
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", True)


@pytest.mark.parametrize("name", ["instant3d-train-dense", "ngp-train-dense",
                                  "instant3d-render"])
def test_control_fails_the_limits(name):
    cell = bench_tiny.cell(name, size="control")
    drv = harness.driver(cell)
    drv.setup()
    drv.serve_for_check()
    ref = drv.reference_readings()
    lower = CONTROL[cell.config["matmul_precision"]]
    numbers = drv.numbers(drv.reference_readings(lower), ref)
    assert compare.judge(drv.numbers(drv.program_readings(), ref), cell.limits)[0]
    ok, rows = compare.judge(numbers, cell.limits)
    assert not ok, rows
