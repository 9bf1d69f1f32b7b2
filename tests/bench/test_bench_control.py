"""The control, the reference in float8 put in the program's place, fails.

At a size a test run holds (the smoke sizes, a tiny cohort), each cell's
limits must judge the reference computed with float8 (e4m3) matmul
operands not correct against the float32 reference. ``bench/calibrate.py``
makes the same comparison on the chip at the cells' own sizes.
"""
import bench_cells
import jax.numpy as jnp
import pytest

from bench import check, harness, spec
from bench.reference import nn

CELLS = {"fedlm100m-fedpa": ("fedlm-100m", {}),
         "fedlm100m-fedavg": ("fedlm-100m", {"algorithm": "fedavg",
                                             "burn-in-rounds": 0})}


@pytest.mark.parametrize("real", sorted(CELLS))
def test_float8_control_is_not_correct(tmp_path, real):
    arch, flags = CELLS[real]
    name = bench_cells.make_tree(tmp_path, arch,
                                 limits=bench_cells.real_limits(real),
                                 **flags)
    cell = spec.load_cell(tmp_path, name)
    ref = harness.reference_readings(cell, 7)
    control = harness.reference_readings(
        cell, 7, nn.rounding_to(jnp.float8_e4m3fn))
    correct, table = check.judge(check.numbers(control, ref), cell.limits)
    assert correct is False, table
