"""End-to-end behaviour tests: the train driver learns, resumes, and the
serve driver decodes — on a reduced config through the public entry points."""
import json
import os
import subprocess
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
           XLA_FLAGS="--xla_force_host_platform_device_count=8")


def _run(args, timeout=540):
    return subprocess.run([sys.executable, "-m"] + args, env=ENV,
                          capture_output=True, text=True, timeout=timeout)


def test_train_loss_decreases(tmp_path):
    metrics = tmp_path / "m.json"
    r = _run(["repro.launch.train", "--arch", "starcoder2-3b", "--reduced",
              "--steps", "30", "--seq", "256", "--batch", "8",
              "--mesh", "1x1", "--n-chunks", "2",
              "--metrics-out", str(metrics)])
    assert r.returncode == 0, r.stderr[-2000:]
    hist = json.loads(metrics.read_text())
    assert hist[-1]["loss"] < hist[0]["loss"] - 0.5
    assert all(np.isfinite(h["loss"]) for h in hist)


def test_train_distributed_with_restart(tmp_path):
    ck = tmp_path / "ckpt"
    r1 = _run(["repro.launch.train", "--arch", "qwen2-7b", "--reduced",
               "--steps", "8", "--seq", "256", "--batch", "8",
               "--mesh", "4x2", "--pp", "2", "--n-chunks", "2",
               "--ckpt-dir", str(ck), "--ckpt-every", "4"])
    assert r1.returncode == 0, r1.stderr[-2000:]
    r2 = _run(["repro.launch.train", "--arch", "qwen2-7b", "--reduced",
               "--steps", "12", "--seq", "256", "--batch", "8",
               "--mesh", "4x2", "--pp", "2", "--n-chunks", "2",
               "--ckpt-dir", str(ck), "--resume", "auto"])
    assert r2.returncode == 0, r2.stderr[-2000:]
    assert "resumed from step 8" in (r2.stderr + r2.stdout)


def test_serve_decodes():
    r = _run(["repro.launch.serve", "--arch", "qwen2-7b", "--reduced",
              "--mesh", "2x2", "--prompt-len", "128", "--batch", "4",
              "--decode-steps", "4"])
    assert r.returncode == 0, r.stderr[-2000:]
    assert "decoded 4 tokens/seq" in (r.stderr + r.stdout)


def test_train_steps_compile_nothing():
    """The reduced 1x1 trainer with host-resident moments compiles its step
    once, before step 0: three steps then compile nothing (step 1 used to
    compile again — the fresh step counter is uncommitted, and the CPU
    backend returns host-annotated moments in device memory).  The
    persistent cache is off so that a compile cannot hide as a cache hit."""
    import jax

    from repro.launch.train import main

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        report = {}
        hist = main(["--arch", "sppo-gpt-7b", "--reduced", "--steps", "3",
                     "--seq", "128", "--batch", "1", "--mesh", "1x1",
                     "--offload-moments"], report=report)
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
    assert report["compiles"] >= 1
    assert [h["compiles"] for h in hist] == [0, 0, 0]
    assert all(np.isfinite(h["loss"]) for h in hist)


def test_loss_only_matches_step_zero_loss():
    """``main(loss_only=True)`` is the forward-only loss of step 0's batch on
    the initial parameters: the loss step 0 reports."""
    from repro.launch.train import main

    args = ["--arch", "qwen2-7b", "--reduced", "--steps", "1", "--seq", "64",
            "--batch", "2", "--mesh", "1x1"]
    step0 = main(args)[0]["loss"]
    fwd = main(args, loss_only=True)[0]["loss"]
    np.testing.assert_allclose(fwd, step0, rtol=1e-6)


def test_peak_table_keyed_by_device_kind():
    from types import SimpleNamespace

    import pytest

    from repro.runtime.metrics import Meter, peak_flops

    v5e = SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert peak_flops(v5e) == 197e12
    assert peak_flops(SimpleNamespace(platform="cpu", device_kind="cpu")) \
        is None
    with pytest.raises(ValueError, match="TPU v9"):
        peak_flops(SimpleNamespace(platform="tpu", device_kind="TPU v9"))
    meter = Meter(n_chips=1, tokens_per_step=10, n_active_params=5)
    meter.start()
    assert meter.stop(0, 1.0)["mfu"] is None


def test_host_memory_kind_raises_without_pinned_host():
    from types import SimpleNamespace

    import pytest

    from repro.runtime import hostmem

    dev = SimpleNamespace(device_kind="fake", addressable_memories=lambda: [
        SimpleNamespace(kind="device")])
    with pytest.raises(RuntimeError, match="pinned_host"):
        hostmem.host_memory_kind(dev)
    assert hostmem.host_memory_kind() == "pinned_host"


def test_attention_backend_defaults_and_names():
    import pytest

    from repro.kernels import ops as kops

    # off a TPU the default is the jnp reference; interpret is only ever
    # chosen explicitly (REPRO_ATTENTION or set_backend)
    assert kops.get_backend() == os.environ.get("REPRO_ATTENTION", "jnp")
    with pytest.raises(ValueError):
        kops.set_backend("pallas-interpret")
