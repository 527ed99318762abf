"""Smoke tests for the runnable examples (argv-driven --fast mode), so the
examples can't rot silently.  Each main() returns its result object, which
the tests assert on — a crash or a NaN loss fails tier-1, not just the
reader's afternoon."""
import math
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "examples"))


def test_offload_ablation_fast(eight_devices, capsys):
    import offload_ablation

    led = offload_ablation.main(["--fast"])
    assert led.peak_bytes > 0
    assert led.runtime_coverage_ok()
    out = capsys.readouterr().out
    for variant in ("sppo_executed", "sppo_xla_policy", "no_offload",
                    "full_recompute"):
        assert variant in out
    assert "memledger" in out


def test_long_context_training_fast(eight_devices):
    import long_context_training

    history = long_context_training.main(["--fast"])
    assert len(history) == 3
    losses = [h["loss"] for h in history]
    assert all(math.isfinite(l) for l in losses)


@pytest.mark.skipif(os.environ.get("REPRO_ATTENTION") == "interpret",
                    reason="quickstart is covered by the jnp leg")
def test_examples_are_argv_driven():
    """Both examples accept argv lists (the CI smoke contract)."""
    import long_context_training
    import offload_ablation

    for mod in (offload_ablation, long_context_training):
        assert mod.main.__code__.co_argcount >= 1
