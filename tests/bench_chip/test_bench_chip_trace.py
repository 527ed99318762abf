"""The reduction from a trace to per-layer numbers, on hand-made events in
the neutral form ``tracefile.load`` returns: the union of device busy
time, kernel and collective time by instruction, idle gaps named by the
host span open in them, and the HLO classes the readers match on."""
import os
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
sys.path.insert(0, ROOT)

from benchmarks.chip import harness, tracefile  # noqa: E402
from benchmarks.chip.metrics import (device_idle_share,  # noqa: E402
                                     host_copy_ms_per_step)

HAND = {
    "window": [0, 100],
    "devices": {
        "/device:TPU:0": [["fusion.1", 0, 10], ["k.1", 5, 20],
                          ["copy-start.2", 40, 5], ["all-reduce.3", 60, 10],
                          ["fusion.1", 95, 20]],
        "/device:TPU:1": [["fusion.1", 0, 50], ["all-reduce.3", 50, 30]],
    },
    "host": [["bench.step", 0, 100], ["bench.block", 35, 65]],
}
HLO = """\
  %k.1 = (f32[4]{0}) custom-call(%p), custom_call_target="tpu_custom_call", backend_config={"custom_call_config":{"body":"QUJDAGhlbHBlcl9rZXJuZWwAbWluZV9rZXJuZWwAWFla"}}
  %copy-start.2 = (f32[8]{0:S(5)}, f32[8]{0}, u32[]) copy-start(f32[8]{0:S(5)} %h)
  %copy.4 = f32[8]{0} copy(f32[8]{0} %x)
  %all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %y), to_apply=%sum
"""


def test_union_of_intervals_and_clipping():
    ev = HAND["devices"]["/device:TPU:0"]
    # [0,25] + [40,45] + [60,70] + [95,100]: overlaps merged, clipped
    assert tracefile.busy_ns(ev, 0, 100) == 25 + 5 + 10 + 5
    assert tracefile.op_ns(ev, {"all-reduce.3"}, 0, 100) == 10
    assert tracefile.op_ns(ev, {"fusion.1"}, 0, 100) == 10 + 5


def test_idle_gaps_are_named_by_the_open_host_span():
    gaps = tracefile.idle_gaps(HAND)
    assert gaps[0] == ["bench.block", 25e-9]          # 70..95
    assert ["bench.step", 15e-9] in gaps               # 25..40
    assert sum(g[1] for g in gaps) == pytest.approx(55e-9)


def test_hlo_classification():
    ops = tracefile.hlo_ops(HLO)
    # the last *_kernel name of the body's string table is the kernel's
    assert ops["kernels"] == {"k.1": "mine_kernel"}
    assert ops["host_copies"] == {"copy-start.2"}
    assert ops["collectives"] == {"all-reduce.3"}


def test_idle_share_is_the_mean_over_devices():
    r = harness.Readings(cell=None, peak=None, chips=2, steps=1,
                         window_s=1e-7, compiles_in_window=0, trace=HAND,
                         hlo=tracefile.hlo_ops(HLO), scopes={})
    # device 0 busy 45 of 100, device 1 busy 80 of 100
    assert device_idle_share.read(r) == pytest.approx(100 * (1 - 0.625))
    assert host_copy_ms_per_step.read(r) == pytest.approx(5 / 2 / 1e6)


def test_a_trace_with_no_device_ops_is_refused(tmp_path):
    """A trace whose planes hold no TPU op line (here the CPU's) fails
    loudly, naming what it did hold, rather than leaving the device
    metrics silently out."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.StepTraceAnnotation("bench.step", step_num=0):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    path, = tmp_path.glob("**/*.xplane.pb")
    with pytest.raises(ValueError, match="no 'XLA Ops' events.*host:CPU"):
        tracefile.load(str(path))


RECORDED = os.path.join(ROOT, "benchmarks", "chip", "testdata",
                        "gpt7b-16k-1chip.trace.json.gz")
# what the reduction read from it when it was recorded
RECORDED_PLANE = "/device:TPU:0"
RECORDED_IDLE = 0.09027114575785866
RECORDED_KERNEL_NS = {"_flash_partial_kernel": 1781557570,
                      "_flash_bwd_dkv_kernel": 959756435,
                      "_flash_bwd_dq_kernel": 664593670}
RECORDED_COPY_MS = 4864.627842
RECORDED_FWD = 2.506399140382451
RECORDED_BWD = 5.497945730104592


@pytest.fixture(scope="module")
def recorded():
    """One traced step of the cell on a TPU v5e, in the neutral form, with
    the HLO classes of its compiled step (the instructions that the step's
    events name)."""
    import gzip
    import json

    from benchmarks.chip import spec

    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    hlo = rec["hlo"]
    hlo = {"kernels": hlo["kernels"], "host_copies": set(hlo["host_copies"]),
           "collectives": set(hlo["collectives"])}
    cell = spec.load_cell("gpt7b-16k-1chip", ROOT)
    return harness.Readings(cell=cell, peak=harness.peak_of("TPU v5 lite"),
                            chips=1, steps=rec["steps"],
                            window_s=rec["window_s"], compiles_in_window=0,
                            trace=rec["trace"], hlo=hlo, scopes={})


def test_recorded_chip_trace_reduces_to_fixed_numbers(recorded):
    from benchmarks.chip.metrics import attn_bwd_roofline, attn_fwd_roofline

    r = recorded
    t0, t1 = r.trace["window"]
    ev, = r.trace["devices"].values()
    assert list(r.trace["devices"]) == [RECORDED_PLANE]
    assert device_idle_share.read(r) == pytest.approx(RECORDED_IDLE, rel=1e-9)
    kernels = {}
    for op, k in r.hlo["kernels"].items():
        kernels.setdefault(k, set()).add(op)
    got = {k: tracefile.op_ns(ev, ops, t0, t1) for k, ops in kernels.items()}
    assert got == RECORDED_KERNEL_NS
    assert host_copy_ms_per_step.read(r) == pytest.approx(RECORDED_COPY_MS,
                                                          rel=1e-9)
    assert tracefile.op_ns(ev, r.hlo["collectives"], t0, t1) == 0
    assert attn_fwd_roofline.read(r) == pytest.approx(RECORDED_FWD, rel=1e-9)
    assert attn_bwd_roofline.read(r) == pytest.approx(RECORDED_BWD, rel=1e-9)
    assert 0 < RECORDED_FWD <= 100 and 0 < RECORDED_BWD <= 100


def test_hlo_names_are_read_from_the_event_text():
    class Ev:
        stats = [("hlo_op", "copy-done.3")]

        def __init__(self, name):
            self.name = name

    text = "%copy-start.3 = (bf16[8]{0:S(5)}) copy-start(bf16[8]{0} %x)"
    assert tracefile._op_name(Ev(text)) == "copy-start.3"
    assert tracefile._op_name(Ev("copy")) == "copy-done.3"


def test_the_hbm_peak_counts_reserved_temporaries():
    stats = {"peak_bytes_in_use": 2511667200,
             "peak_bytes_reserved": 12320948224}
    assert harness.hbm_peak(stats) == 2511667200 + 12320948224
    assert harness.hbm_peak({}) == 0
