#!/usr/bin/env python3
"""Smoke test of the SPPO trainer on a TPU, through its normal entry point.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # a 2x2 mesh: pp=2 x sp=2 vs one chip

The model is sppo-gpt-7b (the paper's GPT-7B) at its published widths —
d_model 4096, 32 heads of 128, d_ff 16384, vocab 51200 — cut in depth only,
to 4 layers.  Weights are random from the trainer's fixed seed and the
batch is the trainer's seeded synthetic data.  Everything runs in this one
process, which holds the chip(s); nothing is spawned.

One chip: batch 1 x 16384 tokens on a 1x1 mesh, activation offload and
host-resident AdamW moments on, 5 steps on the Pallas attention kernels.
Fails unless the platform is a TPU, the host memory kind is pinned_host
and the moments live there after the run, the compiled step holds Pallas
kernels (tpu_custom_call), every loss is finite, no step compiles, and
step 0's loss agrees with the same batch's loss through the blockwise-jnp
reference attention (forward only), computed first in this process.

--four-chips: only the same model on a 2x2 mesh (--pp 2, sp 2) for 3
steps, and its step-0 loss against the one-chip pp=1 loss (forward only,
Pallas) on the same parameters and batch.

Prints the device, the plan, the host kind, compile time, each step's
time and loss and the device's peak memory, then as the last line of
standard output, only when every check passed:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
The compile cache is $JAX_COMPILATION_CACHE_DIR when set, else
<repo>/.jax_cache.
"""
import argparse
import gc
import json
import math
import os
import sys

REPO = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(REPO, "src")

ARCH = "sppo-gpt-7b"
LAYERS = 4
# 32768 does not fit: the TPU compiler refuses that step (24.0 GiB of the
# v5e's 15.75 GiB of HBM); the 16384-token step peaks at 6.9 GiB
SEQ = 16384
STEPS = 5
FOUR_CHIP_STEPS = 3
# Step 0's loss through the Pallas kernels vs a reference: the model runs in
# bf16, and the two attention paths round their outputs to bf16 at
# different points (2**-8 ~ 0.4% relative per element).  The loss is a mean
# over 16384 tokens, which averages such rounding down, so 1% relative
# bounds an honest difference with margin while a wrong mask or a wrong
# softmax moves the loss by far more.
LOSS_RTOL = 1e-2


def fail(msg: str):
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def say(msg: str):
    print(f"chip_smoke: {msg}", flush=True)


def train_args(mesh: str, steps: int, *extra: str) -> list:
    return ["--arch", ARCH, "--layers", str(LAYERS), "--mesh", mesh,
            "--batch", "1", "--seq", str(SEQ), "--offload-moments",
            "--steps", str(steps), "--log-every", "1", *extra]


def check_close(name: str, got: float, want: float):
    rel = abs(got - want) / abs(want)
    say(f"{name}: {got:.6f} vs {want:.6f} (relative difference {rel:.3e}, "
        f"limit {LOSS_RTOL:g})")
    if not rel <= LOSS_RTOL:
        fail(f"{name} differs by {rel:.3e} > {LOSS_RTOL:g}")


def run_training(train, argv: list) -> tuple:
    """One training run; checks what every run must show, prints it."""
    report = {}
    hist = train.main(argv, report=report)
    say(f"plan: chunks {report['chunks']} alphas {report['alphas']}")
    say(f"host kind {report.get('host_kind')}, moments in "
        f"{report['moment_kinds']}")
    say(f"step compiled in {report['compile_s']:.1f} s "
        f"({report['compiles']} programs, {report['cache_hits']} of them "
        f"from the persistent cache; {report['pallas_kernels']} "
        f"tpu_custom_call)")
    for rec in hist:
        say(f"step {rec['step']}: {rec['dt']:.3f} s  loss {rec['loss']:.6f}  "
            f"compiles {rec['compiles']}")
    say(f"peak_bytes_in_use {report['peak_bytes_in_use']}")
    if report.get("host_kind") != "pinned_host":
        fail(f"host memory kind is {report.get('host_kind')!r}, "
             f"not 'pinned_host'")
    if report["moment_kinds"] != ["pinned_host"]:
        fail(f"AdamW moments ended in {report['moment_kinds']}")
    if report["pallas_kernels"] == 0:
        fail("the compiled step holds no tpu_custom_call (Pallas kernel)")
    if not all(math.isfinite(rec["loss"]) for rec in hist):
        fail(f"non-finite loss: {[rec['loss'] for rec in hist]}")
    compiled = [rec["step"] for rec in hist if rec["compiles"]]
    if compiled:
        fail(f"steps {compiled} compiled")
    return hist, report


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 pp=2 x sp=2 path and its "
                         "one-chip comparison")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        fail(f"the repro package is not at {SRC}")
    sys.path.insert(0, SRC)

    import jax

    devices = jax.devices()
    dev = devices[0]
    say(f"device {dev.platform} {dev.device_kind!r} x {len(devices)}")
    if dev.platform != "tpu":
        fail(f"no TPU found: JAX runs on {dev.platform}")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        fail(f"needs {want} chips, found {len(devices)}")

    import logging

    from repro.kernels import ops as kops
    from repro.launch import train

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    say(f"compile cache {train.use_compile_cache()}")
    if kops.get_backend() != "pallas":
        fail(f"attention backend on the TPU is {kops.get_backend()!r}")

    if args.four_chips:
        one = train.main(train_args("1x1", 1), loss_only=True)[0]["loss"]
        say(f"one chip pp=1 loss of batch 0 (forward, Pallas): {one:.6f}")
        gc.collect()
        hist, _ = run_training(train, train_args("2x2", FOUR_CHIP_STEPS,
                                                 "--pp", "2"))
        check_close("2x2 pp=2 step-0 loss vs one chip pp=1",
                    hist[0]["loss"], one)
    else:
        with kops.backend("jnp"):
            ref = train.main(train_args("1x1", 1), loss_only=True)[0]["loss"]
        say(f"reference loss of batch 0 (forward, jnp attention): {ref:.6f}")
        gc.collect()
        hist, _ = run_training(train, train_args("1x1", STEPS))
        check_close("step-0 loss vs jnp reference", hist[0]["loss"], ref)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
