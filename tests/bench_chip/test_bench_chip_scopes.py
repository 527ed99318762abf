"""The program's layer scopes and the readers of device time by layer.

A tiny sppo-gpt train step (2 layers, 2 chunks, activation offload, AdamW
moments on the host, SPPO recomputation) is compiled on the CPU, and every
layer scope has to reach its compiled HLO where that layer does work: the
forward, the backward and the recomputed forward for the layers inside the
checkpointed slot, forward and backward for the embedding and the head, the
update for the optimizer.  The readers are then checked on hand-made events
and on one recorded step of the cell on a TPU v5e.
"""
import dataclasses
import gzip
import json
import os
import re
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
sys.path.insert(0, ROOT)

from benchmarks.chip import flops, harness, scopes, spec, tracefile  # noqa: E402

METRICS = ("attn_ms_per_step", "mlp_ms_per_step", "head_loss_ms_per_step",
           "optimizer_ms_per_step", "offload_wait_ms_per_step",
           "recompute_ms_per_step")


def _read(name, r):
    return harness.read_metric(name, r)


def test_the_benchmark_and_the_program_name_the_same_layers():
    from repro.runtime import scopes as program_scopes

    assert scopes.LAYERS == program_scopes.LAYERS


# ---------------------------------------------------------------------------
# the compiled step
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def step_hlo():
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from repro.configs.base import ShapeConfig, get_config
    from repro.launch.mesh import make_test_mesh
    from repro.models.model_zoo import build_model
    from repro.optim import adamw
    from repro.parallel import specs as SP
    from repro.parallel.runner import (batch_struct, make_train_step,
                                       resolve_cell)

    cfg = dataclasses.replace(get_config("sppo-gpt-7b").reduced(),
                              n_layers=2)
    mdef = build_model(cfg)
    cell = resolve_cell(mdef, ShapeConfig("scopes", 256, 1, "train"),
                        data_size=1, model_size=1,
                        overrides={"n_chunks": 2, "offload_moments": True})
    assert cell.plan.offload and cell.plan.remat == "sppo"
    assert cell.alphas[0] > 0
    mesh = make_test_mesh(1, 1)
    struct, specs = SP.param_struct_and_specs(mdef, 1, 1, cell.dtype)
    shard = jax.tree.map(lambda s: NamedSharding(mesh, s), specs)

    def sds(a, s, dtype=None):
        return jax.ShapeDtypeStruct(a.shape, dtype or a.dtype, sharding=s)

    params = jax.tree.map(sds, struct, shard)
    moments = jax.tree.map(
        lambda a, s: sds(a, s.with_memory_kind("pinned_host"), jnp.float32),
        struct, shard)
    step0 = jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(
        mesh, jax.sharding.PartitionSpec()))
    bstruct, bspecs = batch_struct(cell)
    batch = {k: sds(v, NamedSharding(mesh, bspecs[k]))
             for k, v in bstruct.items()}
    step = jax.jit(make_train_step(cell, mesh), donate_argnums=(0, 1))
    lowered = step.lower(params, adamw.AdamWState(step0, moments, moments),
                         batch)
    return lowered.compile().as_text()


def _phase(op_name):
    if scopes.is_recompute(op_name):
        return "recompute"
    if "transpose(" in op_name:
        return "backward"
    if "jvp(" in op_name:
        return "forward"
    return "update"


# where each layer does work in a train step with SPPO recomputation
WHERE = {
    scopes.EMBED: {"forward", "backward"},
    scopes.NORM: {"forward", "backward", "recompute"},
    scopes.ATTN: {"forward", "backward", "recompute"},
    scopes.MLP: {"forward", "backward", "recompute"},
    scopes.HEAD_LOSS: {"forward", "backward"},
    scopes.ADAMW: {"update"},
    scopes.OFFLOAD: {"forward", "backward", "recompute"},
}


def test_every_layer_scope_reaches_the_compiled_step(step_hlo):
    found = {}
    for op_name in scopes.hlo_scopes(step_hlo).values():
        layer = scopes.layer_of(op_name)
        if layer is not None:
            found.setdefault(layer, set()).add(_phase(op_name))
    assert set(found) == set(scopes.LAYERS)
    for layer, phases in WHERE.items():
        assert phases <= found[layer], (layer, found[layer])
    # the update is not differentiated, and nothing outside it is a layer
    # of the optimizer
    assert found[scopes.ADAMW] == {"update"}


def test_hlo_scopes_leave_out_containers(step_hlo):
    found = scopes.hlo_scopes(step_hlo)
    named = re.compile(r"\s*(?:ROOT\s+)?%(\S+) = .*\) "
                       r"(while|conditional|call)\(.*op_name=")
    containers = [m.group(1) for m in map(named.match,
                                          step_hlo.splitlines()) if m]
    assert containers, "the step's scans compile to loops with op_names"
    assert not set(containers) & set(found)
    # the HLO classes the harness hands every reader are left as they were
    assert set(tracefile.hlo_ops(step_hlo)) == {"kernels", "host_copies",
                                                "collectives"}


# ---------------------------------------------------------------------------
# the readers, on hand-made events
# ---------------------------------------------------------------------------


def test_layer_of_takes_the_innermost_scope():
    assert scopes.layer_of(
        "jit(train_step)/jvp()/while/body/closed_call/sppo.attn/"
        "sppo.offload/slice") == scopes.OFFLOAD
    assert scopes.layer_of("jit(train_step)/jvp(sppo.head_loss)/exp") \
        == scopes.HEAD_LOSS
    assert scopes.layer_of(
        "jit(train_step)/transpose(jvp(sppo.attn/sppo.norm))/mul") \
        == scopes.NORM
    assert scopes.layer_of("jit(train_step)/transpose(jvp())/while/body/"
                           "sppo.mlp/dot_general") == scopes.MLP
    assert scopes.layer_of("jit(train_step)/while/body/dynamic_slice") is None
    assert scopes.layer_of("jit(f)/sppo.other/add") is None
    assert scopes.layer_of(None) is None


def test_is_recompute_reads_the_remat_component():
    assert scopes.is_recompute(
        "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
        "rematted_computation/sppo.mlp/dot_general")
    assert scopes.is_recompute("transpose(jvp(rematted_computation))/add")
    assert not scopes.is_recompute(
        "jit(train_step)/jvp()/while/body/closed_call/checkpoint/sppo.mlp/"
        "dot_general")
    assert not scopes.is_recompute("jit(f)/not_rematted_computation_x/add")
    assert not scopes.is_recompute(None)


FWD = "jit(train_step)/jvp()/while/body/closed_call"
BWD = "jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint"
HAND_HLO = f"""\
  %fusion.1 = bf16[8]{{0}} fusion(%a), kind=kOutput, metadata={{op_name="{FWD}/sppo.attn/dot_general" source_file="x.py" source_line=1}}
  %fusion.2 = bf16[8]{{0}} fusion(%a), metadata={{op_name="jit(train_step)/transpose(jvp(sppo.mlp))/dot_general"}}
  %fusion.3 = bf16[8]{{0}} fusion(%a), metadata={{op_name="{BWD}/rematted_computation/sppo.attn/sppo.offload/concatenate"}}
  %fusion.9 = bf16[8]{{0}} fusion(%a), metadata={{op_name="jit(train_step)/jvp(sppo.head_loss)/exp"}}
  %fusion.10 = f32[8]{{0}} fusion(%a), metadata={{op_name="jit(train_step)/sppo.adamw/mul"}}
  %fusion.11 = bf16[8]{{0}} fusion(%a), metadata={{op_name="{BWD}/rematted_computation/sppo.mlp/dot_general"}}
  %copy-done.4 = f32[8]{{0:S(5)}} copy-done(%copy-start.4)
  %copy-done.5 = bf16[8]{{0}} copy-done(%copy-start.5), metadata={{op_name="{BWD}/sppo.offload/device_put"}}
  %copy-done.6 = f32[8]{{0}} copy-done(%copy-start.6), metadata={{op_name="jit(train_step)/sppo.adamw/device_put"}}
  %copy-start.7 = (bf16[8]{{0:S(5)}}, bf16[8]{{0}}, u32[]) copy-start(bf16[8]{{0}} %x), metadata={{op_name="{FWD}/sppo.offload/device_put"}}
  %copy-start.5 = (bf16[8]{{0}}, bf16[8]{{0:S(5)}}, u32[]) copy-start(bf16[8]{{0:S(5)}} %y)
  %copy-start.6 = (f32[8]{{0}}, f32[8]{{0:S(5)}}, u32[]) copy-start(f32[8]{{0:S(5)}} %z)
  %copy-done.13 = f32[8]{{0}} copy-done(%copy-start.13)
  %copy-start.13 = (f32[8]{{0}}, f32[8]{{0:S(1)}}, u32[]) copy-start(f32[8]{{0:S(1)}} %w)
  %while.8 = (s32[], bf16[8]{{0}}) while(%t), condition=%c, body=%b, metadata={{op_name="{FWD}/while"}}
  %dynamic-slice.12 = bf16[8]{{0}} dynamic-slice(%a, %i), dynamic_slice_sizes={{8}}
"""
# two devices, window [0, 100]: each event [op, start, duration]
HAND = {
    "window": [0, 100],
    "devices": {
        "/device:TPU:0": [["while.8", 0, 100], ["fusion.1", -5, 15],
                          ["fusion.2", 10, 10], ["fusion.3", 20, 4],
                          ["copy-start.7", 24, 1], ["copy-done.4", 25, 5],
                          ["copy-done.5", 30, 3], ["copy-done.6", 33, 7],
                          ["fusion.9", 40, 10], ["fusion.10", 50, 6],
                          ["fusion.11", 56, 4], ["dynamic-slice.12", 60, 2],
                          ["copy-done.13", 62, 1],
                          ["fusion.1", 95, 20]],
        "/device:TPU:1": [["fusion.1", 0, 30], ["copy-done.4", 30, 9]],
    },
    "async": {},
    "host": [["bench.step", 0, 100]],
}
# ns per device: fusion.1 10+5 (clipped) and 30
HAND_NS = {
    "attn_ms_per_step": ((10 + 5) + 30) / 2,
    "mlp_ms_per_step": (10 + 4) / 2,
    "head_loss_ms_per_step": 10 / 2,
    "optimizer_ms_per_step": (7 + 6) / 2,
    # copy-done.4 (no scope) and .5 (offload, a reload: matched by its
    # start); not .6 (adamw), .13 (not a host copy) or a start
    "offload_wait_ms_per_step": ((5 + 3) + 9) / 2,
    "recompute_ms_per_step": (4 + 4) / 2,
}


def _hand_readings(steps=1, text=HAND_HLO, trace=HAND, names=None):
    """What a reader gets for a step of HLO ``text``: its classes and, where
    ``names`` does not stand in for them, its op_names."""
    return harness.Readings(cell=None, peak=None, chips=2, steps=steps,
                            window_s=1e-7, compiles_in_window=0, trace=trace,
                            hlo=tracefile.hlo_ops(text),
                            scopes=(scopes.hlo_scopes(text) if names is None
                                    else names))


def test_hand_hlo_scopes():
    got = scopes.hlo_scopes(HAND_HLO)
    assert "while.8" not in got
    assert "copy-done.4" not in got
    assert got["fusion.1"] == f"{FWD}/sppo.attn/dot_general"
    # a reload's done has no S(5) shape of its own
    assert tracefile.hlo_ops(HAND_HLO)["host_copies"] == {
        "copy-done.4", "copy-start.7", "copy-start.5", "copy-start.6"}


@pytest.mark.parametrize("metric", METRICS)
def test_each_layer_metric_reads_its_scope(metric):
    assert _read(metric, _hand_readings()) == pytest.approx(
        HAND_NS[metric] / 1e6, rel=1e-12)
    # per step: two steps in the window halve every reading
    assert _read(metric, _hand_readings(steps=2)) == pytest.approx(
        HAND_NS[metric] / 2e6, rel=1e-12)


@pytest.mark.parametrize("metric", METRICS)
def test_each_layer_metric_reads_the_live_step(metric):
    """A reader takes the op_names handed to it with the step
    (``Readings.scopes``) and no others: the same trace read against
    another program's op_names reads as a program without scopes."""
    other = "  %fusion.1 = f32[8]{0} fusion(%a), metadata={op_name=\"jit(g)/add\"}\n"
    r = _hand_readings()
    assert r.scopes == scopes.hlo_scopes(HAND_HLO)
    assert _read(metric, r) == pytest.approx(HAND_NS[metric] / 1e6,
                                             rel=1e-12)
    # only the host copies, with no layer in either, read alike
    got = _read(metric, _hand_readings(names=scopes.hlo_scopes(other)))
    if metric == "offload_wait_ms_per_step":
        assert got == pytest.approx(UNSCOPED_NS[metric] / 1e6, rel=1e-12)
    else:
        assert got is None


def test_the_live_step_is_found_in_the_runtime():
    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope(scopes.MLP):
            y = jnp.tanh(x @ x)
        with jax.named_scope(scopes.HEAD_LOSS):
            return jnp.sum(jnp.exp(y) * x, axis=0)

    x = jnp.ones((16, 16), jnp.float32)
    text = jax.jit(f).lower(x).compile().as_text()
    want = scopes.hlo_scopes(text)
    named = {op: v for op, v in want.items() if scopes.layer_of(v)}
    assert {scopes.layer_of(v) for v in named.values()} == {scopes.MLP,
                                                            scopes.HEAD_LOSS}
    trace = {"window": [0, 100], "async": {}, "host": [],
             "devices": {"/device:TPU:0": [[op, 10 * i, 5] for i, op
                                           in enumerate(sorted(named))]}}
    r = _hand_readings(text=text, trace=trace)
    assert r.scopes == want
    assert scopes.ms_per_step(r, scopes.in_layer(r, scopes.MLP)) \
        == pytest.approx(5e-6 * sum(scopes.layer_of(v) == scopes.MLP
                                    for v in named.values()))


def test_the_harness_readers_find_the_live_step(monkeypatch):
    """A traced run of the harness on the CPU, at a tiny size of the cell:
    the trace is a stand-in (the CPU has no TPU plane) whose events name
    the step's instructions.  The readers are handed the op_names of the
    step's HLO text, and read the same numbers as from that text."""
    import time

    c = spec.load_cell("gpt7b-16k-1chip", ROOT)
    m = dataclasses.replace(c.model, layers=2, d=64, heads=4, kv_heads=4,
                            head_dim=16, ff=128, vocab=256)
    cell = dataclasses.replace(
        c, model=m, traffic=dict(c.traffic, seq_len=256, mean_doc_len=64),
        per_layer=METRICS)
    seen = {}
    hlo_ops = tracefile.hlo_ops

    def keep_text(text):
        seen["text"] = text
        return hlo_ops(text)

    def stand_in(path):
        ops = sorted(set(re.findall(r"^\s*(?:ROOT\s+)?%([^\s=]+)\s*=",
                                    seen["text"], re.M)))
        events = [[op, 10 * i, 5] for i, op in enumerate(ops)]
        seen["trace"] = {"window": [0, 10 * len(ops)], "async": {},
                         "host": [], "devices": {"/device:TPU:0": events}}
        return seen["trace"]

    read = harness.read_metric

    def read_metric(name, r):
        seen["r"] = r
        return read(name, r)

    monkeypatch.setattr(tracefile, "hlo_ops", keep_text)
    monkeypatch.setattr(tracefile, "load", stand_in)
    monkeypatch.setattr(harness, "read_metric", read_metric)
    monkeypatch.setattr(harness, "use_compile_cache", lambda: None)
    out = harness.run(cell, 2**31 + 7, 0.2, True, t_start=time.perf_counter(),
                      require_tpu=False, log=lambda *_: None)
    assert seen["r"].scopes == scopes.hlo_scopes(seen["text"])
    assert any(map(scopes.layer_of, seen["r"].scopes.values()))
    got = {k: v["value"] for k, v in out["metrics"].items()}
    # the CPU keeps host-memory values on the device: no S(5) copy to wait on
    assert set(got) == set(METRICS) - {"offload_wait_ms_per_step"}
    given = dataclasses.replace(seen["r"], **{
        k: getattr(_hand_readings(text=seen["text"]), k)
        for k in ("hlo", "scopes")})
    for metric, value in got.items():
        assert value == pytest.approx(read(metric, given), rel=1e-12)


# what each reader finds in a program that opens no layer scope (an older
# one): only the host copies, as activation transfers, and JAX's own marks
# of recomputation
UNSCOPED_NS = {"offload_wait_ms_per_step": (5 + 3 + 7 + 9) / 2,
               "recompute_ms_per_step": (4 + 4) / 2}


@pytest.mark.parametrize("metric", METRICS)
def test_each_layer_metric_is_silent_without_scoped_events(metric):
    """The readers return None rather than raise where there is nothing to
    read: no layer scope, no op_names handed over, no device events."""
    got = _read(metric, _hand_readings(
        text=HAND_HLO.replace("sppo.", "other.")))
    if metric in UNSCOPED_NS:
        assert got == pytest.approx(UNSCOPED_NS[metric] / 1e6, rel=1e-12)
    else:
        assert got is None
    assert _read(metric, _hand_readings(names={})) is None
    empty = harness.Readings(cell=None, peak=None, chips=1, steps=0,
                             window_s=0.0, compiles_in_window=0,
                             trace={"window": None, "devices": {},
                                    "async": {}, "host": []},
                             hlo=tracefile.hlo_ops(HAND_HLO),
                             scopes=scopes.hlo_scopes(HAND_HLO))
    assert _read(metric, empty) is None


def test_ms_per_step_clips_to_the_window_and_averages_devices():
    r = _hand_readings()
    # device 0: fusion.1 at [-5, 10] -> 10 and [95, 115] -> 5; device 1: 30
    assert scopes.ms_per_step(r, scopes.in_layer(r, scopes.ATTN)) \
        == pytest.approx(45 / 2 / 1e6)
    assert scopes.ms_per_step(r, lambda op: False) is None
    # the loop spans everything and is never taken
    assert scopes.ms_per_step(r, lambda op: True) == pytest.approx(
        (15 + 10 + 4 + 1 + 5 + 3 + 7 + 10 + 6 + 4 + 2 + 1 + 30 + 9) / 2
        / 1e6)


def test_host_copies_take_the_layer_of_what_they_move():
    found = scopes.hlo_scopes(HAND_HLO)
    # a start takes its done's op_name; the unnamed copy-done.4 moves a
    # value with no op_name here, and copy-done.13 is no host copy
    assert found["copy-start.5"] == found["copy-done.5"]
    assert scopes.layer_of(found["copy-start.6"]) == scopes.ADAMW
    assert "copy-done.4" not in found
    r = _hand_readings()
    assert scopes.layer(r, "copy-done.4") == scopes.OFFLOAD
    assert scopes.layer(r, "copy-done.13") is None
    assert scopes.is_host_copy(r, "copy-done.5")
    assert not scopes.is_host_copy(r, "copy-done.13")
    moved = scopes.hlo_scopes("""\
  %fusion.1 = f32[8]{0} fusion(%p), metadata={op_name="jit(f)/sppo.adamw/mul"}
  %copy-start.2 = (f32[8]{0:S(5)}, f32[8]{0}, u32[]) copy-start(%fusion.1)
  %copy-done.2 = f32[8]{0:S(5)} copy-done(%copy-start.2)
  %copy-start.3 = (f32[8]{0}, f32[8]{0:S(5)}, u32[]) copy-start(%copy-done.2)
  %copy-done.3 = f32[8]{0} copy-done(%copy-start.3)
  %add.4 = f32[8]{0} add(%copy-done.3, %q), metadata={op_name="jit(f)/sppo.mlp/add"}
  %copy-start.5 = (f32[8]{0}, f32[8]{0:S(5)}, u32[]) copy-start(%p)
  %copy-done.5 = f32[8]{0} copy-done(%copy-start.5)
  %tuple.6 = (f32[8]{0}) tuple(%copy-done.5)
""")
    # the write-back takes the value's; the reload, what it feeds
    assert moved["copy-start.2"] == moved["copy-done.2"] == "jit(f)/sppo.adamw/mul"
    assert moved["copy-start.3"] == moved["copy-done.3"] == "jit(f)/sppo.mlp/add"
    assert "copy-done.5" not in moved and "copy-start.5" not in moved


def test_unscoped_share_and_top_unscoped():
    r = _hand_readings()
    # in no layer: dynamic-slice.12 (2) and copy-done.13 (1), on device 0
    layers = scopes.by_layer(r)
    assert layers["unscoped"] == pytest.approx((2 + 1) / 2 / 1e6)
    assert layers[scopes.OFFLOAD] == pytest.approx((4 + 1 + 5 + 3 + 9) / 2
                                                   / 1e6)
    busy0 = tracefile.busy_ns(HAND["devices"]["/device:TPU:0"], 0, 100)
    busy1 = tracefile.busy_ns(HAND["devices"]["/device:TPU:1"], 0, 100)
    assert scopes.unscoped_share(r) == pytest.approx(
        100 * (2 + 1) / 2 / ((busy0 + busy1) / 2))
    assert scopes.top_unscoped(r) == [
        ["dynamic-slice.12", pytest.approx(2 / 2 / 1e6)],
        ["copy-done.13", pytest.approx(1 / 2 / 1e6)]]
    plain = HAND_HLO.replace("sppo.", "other.")
    assert scopes.unscoped_share(_hand_readings(text=plain)) is not None
    assert scopes.unscoped_share(_hand_readings(text="")) is None


# ---------------------------------------------------------------------------
# one recorded step of the cell on a TPU v5e
# ---------------------------------------------------------------------------

RECORDED = os.path.join(ROOT, "benchmarks", "chip", "testdata",
                        "gpt7b-16k-1chip.scoped.trace.json.gz")
# what the readers read from it when it was recorded
RECORDED_MS = {"attn_ms_per_step": 3736.864749,
               "mlp_ms_per_step": 410.126152,
               "head_loss_ms_per_step": 234.747162,
               "optimizer_ms_per_step": 866.766356,
               "offload_wait_ms_per_step": 67.343124,
               "recompute_ms_per_step": 978.791014}
RECORDED_UNSCOPED = 2.538290925926916


@pytest.fixture(scope="module")
def recorded():
    """One traced step of the cell (the program with its layer scopes), in
    the neutral form, with the HLO classes and op_names of the instructions
    that the step's events name."""
    with gzip.open(RECORDED, "rt") as f:
        rec = json.load(f)
    hlo = rec["hlo"]
    hlo = {"kernels": hlo["kernels"], "host_copies": set(hlo["host_copies"]),
           "collectives": set(hlo["collectives"])}
    cell = spec.load_cell("gpt7b-16k-1chip", ROOT)
    return harness.Readings(cell=cell, peak=harness.peak_of("TPU v5 lite"),
                            chips=1, steps=rec["steps"],
                            window_s=rec["window_s"], compiles_in_window=0,
                            trace=rec["trace"], hlo=hlo,
                            scopes=rec["hlo"]["scopes"])


@pytest.mark.parametrize("metric", METRICS)
def test_recorded_scoped_trace_reads_fixed_numbers(recorded, metric):
    assert _read(metric, recorded) == pytest.approx(RECORDED_MS[metric],
                                                    rel=1e-9)


# the layers' rooflines read from it: 6·T·N at 197e12 FLOP/s over the
# layer's time, N the MLP's 4·2·4096·16384 and the head's 4096·51200
RECORDED_ROOFLINE = {"mlp_roofline": 65.32168431947638,
                     "head_loss_roofline": 44.57943110049715}
ROOFLINE_OF = {"mlp_roofline": ("mlp_ms_per_step", 4 * 2 * 4096 * 16384),
               "head_loss_roofline": ("head_loss_ms_per_step", 4096 * 51200)}


@pytest.mark.parametrize("metric", sorted(RECORDED_ROOFLINE))
def test_recorded_scoped_trace_reads_layer_rooflines(recorded, metric):
    got = _read(metric, recorded)
    assert got == pytest.approx(RECORDED_ROOFLINE[metric], rel=1e-9)
    assert 0 < got <= 100
    ms, n = ROOFLINE_OF[metric]
    least_s = 6 * n * 16384 / recorded.peak["bf16_flops_per_s"]
    assert got == pytest.approx(100 * least_s / (RECORDED_MS[ms] / 1e3),
                                rel=1e-8)


@pytest.mark.parametrize("metric", sorted(RECORDED_ROOFLINE))
def test_layer_rooflines_are_silent_without_their_layer(recorded, metric):
    # no peak for the device; no op of the layer; no step in the window
    assert _read(metric, _hand_readings()) is None
    other = dataclasses.replace(recorded, scopes={
        op: name.replace("sppo.", "other.")
        for op, name in recorded.scopes.items()})
    assert _read(metric, other) is None
    assert _read(metric, dataclasses.replace(recorded, steps=0)) is None


def test_recorded_scoped_trace_leaves_little_unscoped(recorded):
    r = recorded
    assert scopes.unscoped_share(r) == pytest.approx(RECORDED_UNSCOPED,
                                                     rel=1e-9)
    assert RECORDED_UNSCOPED <= 5
    layers = scopes.by_layer(r)
    assert set(layers) == set(scopes.LAYERS) | {"unscoped"}
    # the layers and the unscoped rest add up to the step's operations
    t0, t1 = r.trace["window"]
    ev, = r.trace["devices"].values()
    ops = {e[0] for e in ev if not scopes._is_container(e[0])}
    assert sum(layers.values()) == pytest.approx(
        tracefile.op_ns(ev, ops, t0, t1) / 1e6, rel=1e-9)
    # every Pallas kernel is attention; two of the four forwards recompute
    kernels = set(r.hlo["kernels"])
    assert {scopes.layer(r, k) for k in kernels} == {scopes.ATTN}
    assert sum(scopes.is_recompute(r.scopes[k]) for k in kernels) == 2


# ---------------------------------------------------------------------------
# MFU: the trainer's meter counts the benchmark's FLOPs
# ---------------------------------------------------------------------------


def test_meter_flops_match_the_benchmark_count():
    from repro.configs.base import get_config
    from repro.models.model_zoo import build_model
    from repro.parallel import specs as SP
    from repro.runtime.metrics import Meter, attention_flops

    cell = spec.load_cell("gpt7b-16k-1chip", ROOT)
    batch, seq = cell.traffic["batch"], cell.traffic["seq_len"]
    assert (cell.model.layers, batch * seq) == (4, 16384)
    cfg = dataclasses.replace(get_config("sppo-gpt-7b"), n_layers=4)
    mdef = build_model(cfg)
    # as launch/train.py builds it
    meter = Meter(n_chips=1, tokens_per_step=batch * seq,
                  n_active_params=SP.count_matmul_params(mdef, 1, 1),
                  attn_flops_per_step=attention_flops(cfg, batch=batch,
                                                      seq=seq))
    want = flops.train_step_flops(cell.model, batch=batch, seq=seq)
    assert meter.flops_per_step == pytest.approx(want, rel=1e-9)
    # 6·N·T alone, the old count, was about a fifth low here
    assert 6 * meter.n_active_params * batch * seq < 0.85 * want
