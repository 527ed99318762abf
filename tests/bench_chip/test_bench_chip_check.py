"""The comparison that decides `correct`, at a size a test run holds.

A run of the harness, its look for a chip skipped, on a 2-layer model of
the cell's kind on the CPU: sound, it comes out correct; with the timed
path broken underneath it comes out not correct, once for each fault a
one-chip training cell can have.

The limits are this size's own, set as the cell's are: on the CPU, sound
runs over eight seeds read at most loss 3.8e-4, grad 2.7e-3, change
4.0e-3; the float8 control over three seeds at least loss 2.0e-3, grad
1.2e-2 (change 1.0e-2 is under three times the sound reading and sets no
upper end); half the batch left out reads change 0.19."""
import dataclasses
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                os.pardir))

from benchmarks.chip import check, harness, spec  # noqa: E402
from benchmarks.chip.faults import FAULTS  # noqa: E402

CELL = "gpt7b-16k-1chip"
LIMITS = {"loss_gap": 1e-3, "grad_gap": 6e-3, "change_gap": 0.03}


@pytest.fixture(scope="module")
def tiny():
    """The cell at 2 layers of width 64 and 256 tokens."""
    c = spec.load_cell(CELL)
    m = dataclasses.replace(c.model, layers=2, d=64, heads=4, kv_heads=4,
                            head_dim=16, ff=128, vocab=256)
    return dataclasses.replace(
        c, model=m, traffic=dict(c.traffic, seq_len=256, mean_doc_len=64),
        limits=LIMITS, per_layer=())


_REFERENCE = harness.reference_readings
_MEMO = {}


def _reference_once(cell, seed, steps, precision="float32"):
    """The reference's readings, computed once per seed for all the runs
    of this module (they share one tiny cell)."""
    key = (seed, steps, precision)
    if key not in _MEMO:
        _MEMO[key] = _REFERENCE(cell, seed, steps, precision)
    return _MEMO[key]


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    monkeypatch.setattr(harness, "use_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "reference_readings", _reference_once)


def _run(cell, wrap=None, seed=2**31 + 3):
    return harness.run(cell, seed, 0.2, False, t_start=time.perf_counter(),
                       require_tpu=False, wrap_step=wrap,
                       log=lambda *_: None)


def test_sound_run_is_correct(tiny):
    out = _run(tiny)
    assert out["correct"], check.lines(out["checks"])
    assert out["failed"] == 0 and out["attempted"] > tiny.checked_steps
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_step_is_not_correct(tiny, fault):
    out = _run(tiny, FAULTS[fault])
    assert not out["correct"], check.lines(out["checks"])
