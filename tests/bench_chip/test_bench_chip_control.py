"""The control of the check at a size a test run holds: the plain
reference computed on float8 operands, put in the program's place, fails
against the float32 reference, on a 2-layer model of the cell's kind on
the CPU, under the limits of that size (test_bench_chip_check.LIMITS)."""
import dataclasses
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                os.pardir))

from benchmarks.chip import readings, spec  # noqa: E402
from test_bench_chip_check import CELL, LIMITS  # noqa: E402


@pytest.fixture(scope="module")
def tiny():
    c = spec.load_cell(CELL)
    m = dataclasses.replace(c.model, layers=2, d=64, heads=4, kv_heads=4,
                            head_dim=16, ff=128, vocab=256)
    return dataclasses.replace(
        c, model=m, traffic=dict(c.traffic, seq_len=256, mean_doc_len=64),
        limits=LIMITS)


@pytest.mark.parametrize("seed", [5, 2**31 + 7])
def test_the_control_is_not_correct(tiny, seed):
    assert not readings.control(tiny, seed)["correct"]
