"""A run with the timed path broken underneath comes out not correct.

Each fault of ``bench/faults.py`` is planted in the program of a tiny cell
that carries the limits of a real cell; the harness runs it past its chip
check, and the comparison with the reference must say ``correct: false``.
The same run with nothing planted must say ``correct: true``. One file per
real cell, so that the runs spread over the test workers.
"""
import bench_cells
import pytest

from bench import faults

REAL, ARCH, FLAGS = "xlstm125m-fedpa", "xlstm-125m", {}


@pytest.mark.parametrize("fault", [None] + sorted(faults.FAULTS))
def test_fault_makes_the_run_not_correct(tmp_path, fault):
    bench_cells.check_fault(tmp_path, REAL, ARCH, FLAGS, fault)
