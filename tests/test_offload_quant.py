"""Compressed host residency tests (DESIGN.md §14).

The bf16/fp32 -> fp8_e4m3/int8 + per-row fp32 scale codec behind
``ParallelPlan.offload_dtype`` / ``moments_dtype`` is *lossy by design*, so
the on/off identity law of the raw offload channel
(tests/test_offload_exec.py, <= 1e-5) is replaced here by pinned drift
tolerances: the forward stays exact under the prefetch-'ahead' capture seam
(the tag is an identity; compression happens on the captured copy), the
backward replay reconstructs within the codec's resolution, and the ledger
accounts the raw device drain, the wire payload, and the device-resident
scales as three separate honest numbers."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.configs.base import ShapeConfig, get_config
from repro.core import costmodel as cm
from repro.core import offload as ofl
from repro.models.model_zoo import build_model
from repro.parallel.ctx import SINGLE
from repro.parallel.runner import resolve_cell, run_pipeline
from repro.runtime import hostmem
from repro.runtime import memledger as ml

ALPHAS = (1.0, 0.7, 0.5, 0.0)   # full / fractional / fractional / reserved

# pinned codec resolutions: fp8_e4m3 has a 3-bit mantissa (worst-case
# relative rounding step 2^-4 per element), int8 symmetric rounds within
# 0.5/127 of the row amax — the row-level reconstruction bounds
ROW_TOL = {"fp8": 0.07, "int8": 0.01}
# one-step gradient drift of a compressed cell against raw residency
GRAD_TOL = {"fp8": 0.05, "int8": 0.03}


# ---------------------------------------------------------------------------
# codec primitives
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("codec", ["fp8", "int8"])
def test_codec_round_trip_within_row_resolution(codec):
    """Per-row reconstruction error stays within the codec's pinned
    resolution, across 6 decades of row magnitude (the per-row scale makes
    the error relative to each row's amax, not the tensor's)."""
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (24, 64), jnp.float32)
    x = x * (10.0 ** jnp.arange(-3, 3).repeat(4))[:, None]
    p, s = hostmem.quantize(x, codec)
    y = hostmem.dequantize(p, s, codec, jnp.float32)
    err = np.max(np.abs(np.asarray(x - y)), axis=-1)
    amax = np.max(np.abs(np.asarray(x)), axis=-1)
    assert np.all(err <= ROW_TOL[codec] * amax), (codec, err / amax)
    assert p.dtype == hostmem.codec_wire_dtype(codec)
    assert s.dtype == jnp.float32 and s.shape == (24, 1)


@settings(max_examples=20, deadline=None)
@given(st.sampled_from(["fp8", "int8"]),
       st.floats(-448.0, 448.0, width=32, allow_subnormal=False,
                 allow_nan=False),
       st.integers(1, 6))
def test_codec_degenerate_constant_rows(codec, val, rows):
    """Constant rows (including all-zero) survive the round trip: no
    NaN/inf from the zero-amax scale guard, zeros reconstruct exactly,
    constants within the codec resolution."""
    x = jnp.full((rows, 16), val, jnp.float32)
    v32 = float(x[0, 0])   # the fp32 value the codec actually sees
    p, s = hostmem.quantize(x, codec)
    y = np.asarray(hostmem.dequantize(p, s, codec, jnp.float32))
    assert np.all(np.isfinite(y))
    if v32 == 0.0:
        assert np.all(y == 0.0) and np.all(np.asarray(s) == 1.0)
    else:
        assert np.all(np.abs(y - v32) <= ROW_TOL[codec] * abs(v32))


def test_codec_zero_rows_exact_among_live_rows():
    """A mixed batch — some rows zero, some not — keeps the zero rows
    bitwise zero under both codecs (per-row scales don't couple rows)."""
    x = jnp.stack([jnp.zeros((8,)), jnp.ones((8,)) * 3.5,
                   jnp.zeros((8,)), jnp.linspace(-2.0, 2.0, 8)])
    for codec in ("fp8", "int8"):
        p, s = hostmem.quantize(x, codec)
        y = np.asarray(hostmem.dequantize(p, s, codec, jnp.float32))
        assert np.all(y[0] == 0.0) and np.all(y[2] == 0.0), codec
        assert np.any(y[1] != 0.0)


def test_int8_transport_bitcast_round_trips_bits():
    """The prefetch seam transports int8 payloads bitcast to the fp8 byte
    container (integer custom_vjp outputs get float0 tangents); the bitcast
    must be bit-exact both ways, and fp8 must pass through untouched."""
    p = jnp.arange(-128, 128, dtype=jnp.int8).reshape(16, 16)
    t = hostmem.to_transport(p, "int8")
    assert t.dtype == jnp.float8_e4m3fn and t.shape == p.shape
    back = hostmem.from_transport(t, "int8")
    assert back.dtype == jnp.int8
    np.testing.assert_array_equal(np.asarray(back), np.asarray(p))
    f = jnp.ones((4,), jnp.float8_e4m3fn)
    assert hostmem.to_transport(f, "fp8") is f
    assert hostmem.from_transport(f, "fp8") is f


def test_unknown_codec_rejected():
    with pytest.raises(ValueError, match="unknown offload codec"):
        hostmem.codec_wire_dtype("fp4")


# ---------------------------------------------------------------------------
# sub-byte accounting (the int4 overcount regression)
# ---------------------------------------------------------------------------


def test_aval_bytes_sub_byte_dtypes_are_bit_exact():
    """numpy reports itemsize 1 for the sub-byte ml_dtypes, so the old
    elems*itemsize walk overcounted int4/fp4 tensors 2x; the bit-width
    table must report packed bytes, rounding odd element counts up."""
    def b(shape, dtype):
        return ml._aval_bytes(jax.ShapeDtypeStruct(shape, dtype))

    assert np.dtype(jnp.int4).itemsize == 1   # the trap this fixes
    assert b((4, 8), jnp.int4) == 16          # 32 elems * 4 bits
    assert b((4, 8), jnp.uint4) == 16
    assert b((3,), jnp.int4) == 2             # (3*4+7)//8: rounds up
    assert b((4, 8), jnp.int8) == 32
    assert b((4, 8), jnp.bfloat16) == 64
    assert b((4, 8), jnp.float8_e4m3fn) == 32
    assert b((), jnp.float32) == 4


def test_tagged_walk_counts_packed_int4_bytes():
    """The jaxpr name-walk behind the ledger inherits the bit-exact
    accounting: a named int4 tensor contributes its packed bytes plus the
    element count the raw-drain reconstruction needs."""
    from jax.ad_checkpoint import checkpoint_name

    def f(x):
        q = x.astype(jnp.int4)
        return checkpoint_name(q, ofl.OFF_NAME + "@c0")

    per = ml.tagged_bytes_from_jaxpr(
        jax.make_jaxpr(f)(jnp.zeros((4, 8), jnp.float32)))
    assert per["@c0"]["off"] == 16
    assert per["@c0"]["off_elems"] == 32


def test_tagged_walk_counts_codec_scale_names():
    """act_scale@… names land in the per-suffix "scale" bucket, next to
    the wire-payload "off" bytes they belong to."""
    from jax.ad_checkpoint import checkpoint_name

    name = ofl.OFF_NAME + "@c0"

    def f(x):
        p, s = hostmem.quantize(x, "fp8")
        p = checkpoint_name(p, name)
        s = checkpoint_name(s, ofl.scale_name_for(name))
        return hostmem.dequantize(p, s, "fp8", x.dtype)

    per = ml.tagged_bytes_from_jaxpr(
        jax.make_jaxpr(f)(jnp.zeros((4, 8), jnp.bfloat16)))
    assert per["@c0"]["off"] == 32         # 32 fp8 payload bytes
    assert per["@c0"]["off_elems"] == 32
    assert per["@c0"]["scale"] == 16       # 4 rows * fp32
    assert ofl.scale_name_for(name) == "act_scale@c0"


# ---------------------------------------------------------------------------
# executed equivalence: compressed vs raw residency, pinned drift
# ---------------------------------------------------------------------------


def _pp1_step(codec, *, prefetch=None, pb=None, doc_lens=None):
    """One pp=1 loss+grad step of the reduced cell under `codec` — uniform
    batch, or a packed variable-length batch when `pb` is given."""
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    B = pb.tokens.shape[0] if pb is not None else 2
    over = dict(n_chunks=4, grad_accum=1, offload=True,
                partition="length", offload_dtype=codec)
    if prefetch:
        over["prefetch"] = prefetch
    cell = resolve_cell(mdef, ShapeConfig("q", 256, B, "train"),
                        data_size=1, model_size=1, overrides=over,
                        doc_lens=doc_lens)
    cell = dataclasses.replace(cell, dtype=jnp.float32,
                               alphas=ALPHAS[:cell.sched.n])
    key = jax.random.PRNGKey(0)
    sp = mdef.init_stage_params(key, 0, 1, jnp.float32)
    g = mdef.init_globals(key, jnp.float32)
    if pb is not None:
        tokens, labels = jnp.asarray(pb.tokens), jnp.asarray(pb.labels)
        ds = jnp.asarray(pb.doc_start)
    else:
        tokens = jax.random.randint(key, (2, 256), 0, cfg.vocab_size)
        labels = jnp.roll(tokens, -1, axis=1)
        ds = None

    def loss(sp_, g_):
        out = run_pipeline(cell, SINGLE, sp_, g_, tokens, labels, None,
                           with_loss=True, doc_start=ds)
        return out["loss"] / jnp.maximum(out["denom"], 1.0)

    l, gr = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(sp, g)
    flat = np.concatenate([np.asarray(x, np.float64).ravel()
                           for x in jax.tree_util.tree_leaves(gr)])
    return float(l), flat


def _drift(a, b):
    loss = abs(a[0] - b[0]) / max(abs(b[0]), 1e-9)
    grad = float(np.linalg.norm(a[1] - b[1])) / max(
        float(np.linalg.norm(b[1])), 1e-12)
    return loss, grad


@pytest.mark.parametrize("codec", ["fp8", "int8"])
def test_pp1_compressed_drift_within_pinned_tolerance(codec):
    """pp=1 chunk loop, alphas covering {0, frac, 1}: the 'ahead' capture
    forward is an identity (loss exact to fp32 noise), the compressed
    backward replay drifts but stays within the pinned bound — and it must
    drift (a zero-drift codec run means the codec never engaged)."""
    comp, raw = _pp1_step(codec), _pp1_step("none")
    loss_d, grad_d = _drift(comp, raw)
    assert loss_d <= 1e-5, (codec, loss_d)
    assert 1e-7 < grad_d <= GRAD_TOL[codec], (codec, grad_d)


def test_pp1_sync_prefetch_compressed_drift():
    """Under prefetch='sync' the quantized reconstruction IS the primal
    (host_round_trip substitutes the dequantized rows), so the loss itself
    drifts — within the codec resolution — and grads stay bounded, though
    looser than the 'ahead' seam (every downstream consumer of the
    reconstruction drifts too; measured ~7e-2 vs ~8e-3 ahead)."""
    comp = _pp1_step("fp8", prefetch="sync")
    raw = _pp1_step("none", prefetch="sync")
    loss_d, grad_d = _drift(comp, raw)
    assert loss_d <= 2e-2, loss_d
    assert 1e-7 < grad_d <= 0.1, grad_d


def test_pp1_varlen_packed_compressed_drift():
    """The packed variable-length cell (DESIGN.md §13) composes with the
    codec: segment-windowed attention over packed rows, compressed
    residency on the offloaded row splits."""
    from repro.data import pipeline as dpipe

    cfg = get_config("qwen2-7b").reduced()
    docs = dpipe.sample_corpus(8, vocab_size=cfg.vocab_size, seed=0,
                               dist="zipf", mean_len=48, max_len=192)
    lens = [len(d) for d in docs]
    pb = dpipe.pack_documents(docs, 256)
    comp = _pp1_step("fp8", pb=pb, doc_lens=lens)
    raw = _pp1_step("none", pb=pb, doc_lens=lens)
    loss_d, grad_d = _drift(comp, raw)
    assert loss_d <= 1e-5, loss_d
    assert 1e-7 < grad_d <= GRAD_TOL["fp8"], grad_d


def _mk_pp2_cell(mdef, codec, *, data_size=4, model_size=2):
    shape = ShapeConfig("q", 256, 4, "train")
    cell = resolve_cell(
        mdef, shape, data_size=data_size, model_size=model_size,
        overrides=dict(pp=2, dp=data_size // 2, n_chunks=len(ALPHAS),
                       grad_accum=1, partition="length", offload=True,
                       offload_dtype=codec))
    return dataclasses.replace(cell, dtype=jnp.float32, alphas=ALPHAS)


@pytest.mark.parametrize("codec", ["fp8", "int8"])
def test_pp2_compressed_drift_within_pinned_tolerance(codec, eight_devices):
    """Same law on the pp=2 tick loop (the prefetch seam transports the
    payload — int8 rides the fp8 bitcast container across the custom_vjp
    cotangent channel)."""
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    key = jax.random.PRNGKey(7)
    tokens = jax.random.randint(key, (4, 256), 0, cfg.vocab_size)
    labels = jnp.roll(tokens, -1, axis=1)

    def step(c):
        fn, args = ml.build_step(c, data_size=4, model_size=2,
                                 tokens=tokens, labels=labels)
        l, gr = jax.jit(fn)(*args)
        flat = np.concatenate([np.asarray(x, np.float64).ravel()
                               for x in jax.tree_util.tree_leaves(gr)])
        return float(l), flat

    comp = step(_mk_pp2_cell(mdef, codec))
    raw = step(_mk_pp2_cell(mdef, "none"))
    loss_d, grad_d = _drift(comp, raw)
    assert loss_d <= 1e-5, (codec, loss_d)
    assert 1e-7 < grad_d <= GRAD_TOL[codec], (codec, grad_d)


# ---------------------------------------------------------------------------
# ledger: raw drain vs wire bytes vs scales, CSV round trip
# ---------------------------------------------------------------------------


def test_compressed_ledger_accounting_and_csv(eight_devices, tmp_path):
    """The measured ledger of a compressed pp=2 cell keeps three honest
    numbers per tick: off_bytes (raw device drain — still satisfies the
    alpha row-split law), off_wire_bytes (the 1-byte payload, itemsize-fold
    smaller), scale_bytes (device-resident fp32 scales); the peak stays
    bracketed by the compression-aware prediction, and everything round
    trips through the CSV."""
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    cell = _mk_pp2_cell(mdef, "fp8")
    led = ml.measure(cell, data_size=4, model_size=2, baseline=False)
    assert led.offload_codec == "fp8"
    itemsize = jnp.dtype(cell.dtype).itemsize
    saw_off = False
    for r in led.ticks:
        acts = r.mat_bytes - r.scale_bytes
        frac = r.off_bytes / acts
        assert abs(frac - r.alpha) < 0.1, (r.tick, frac, r.alpha)
        if r.off_bytes:
            saw_off = True
            # fp32 activations on a 1-byte wire: exactly itemsize-fold
            assert r.off_wire_bytes * itemsize == r.off_bytes, vars(r)
            assert r.scale_bytes > 0
        else:
            assert r.off_wire_bytes == 0 and r.scale_bytes == 0
    assert saw_off
    assert led.off_wire_bytes_total * itemsize == led.off_bytes_total
    assert led.host_bytes == led.off_wire_bytes_total
    predicted = ml.predicted_spmd_peak(cell)
    assert led.peak_bytes <= 1.1 * predicted, (led.peak_bytes, predicted)
    assert led.peak_bytes >= 0.8 * predicted, (led.peak_bytes, predicted)
    # compression strictly cuts the priced reload lane at fixed alphas
    bw = cm.V5E.d2h_bw
    cell_raw = dataclasses.replace(
        cell, plan=dataclasses.replace(cell.plan, offload_dtype="none"))
    led_raw = ml.measure(cell_raw, data_size=4, model_size=2,
                         baseline=False)
    assert led.off_bytes_total == led_raw.off_bytes_total
    assert led.off_wire_bytes_total < led_raw.off_wire_bytes_total
    assert led.price_h2d(bw=bw, prefetch="sync") < led_raw.price_h2d(
        bw=bw, prefetch="sync")

    path = tmp_path / "quant.csv"
    led.to_csv(str(path))
    back = ml.read_csv(str(path))
    assert back["summary"]["offload_codec"] == "fp8"
    assert back["summary"]["off_bytes_total"] == led.off_bytes_total
    assert back["summary"]["off_wire_bytes_total"] == \
        led.off_wire_bytes_total
    assert back["summary"]["scale_bytes_total"] == led.scale_bytes_total
    assert back["summary"]["host_bytes"] == led.host_bytes
    for row, r in zip(back["rows"], led.ticks):
        assert row["off_bytes"] == r.off_bytes
        assert row["off_wire_bytes"] == r.off_wire_bytes
        assert row["scale_bytes"] == r.scale_bytes


def test_uncompressed_ledger_wire_equals_raw(eight_devices, tmp_path):
    """With codec 'none' the wire view collapses onto the raw bytes and the
    scale column is zero — the compressed-channel fields add no drift to
    the existing accounting."""
    cfg = get_config("qwen2-7b").reduced()
    mdef = build_model(cfg)
    cell = _mk_pp2_cell(mdef, "none")
    led = ml.measure(cell, data_size=4, model_size=2, baseline=False)
    assert led.offload_codec == "none"
    for r in led.ticks:
        assert r.off_wire_bytes == r.off_bytes
        assert r.scale_bytes == 0
    path = tmp_path / "raw.csv"
    led.to_csv(str(path))
    back = ml.read_csv(str(path))
    assert back["summary"]["offload_codec"] == "none"
    assert back["summary"]["off_wire_bytes_total"] == led.off_bytes_total


# ---------------------------------------------------------------------------
# compressed moments residency
# ---------------------------------------------------------------------------


def _tiny_params(key):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w": jax.random.normal(k1, (16, 32), jnp.float32) * 0.1,
            "o": jax.random.normal(k2, (32, 16), jnp.float32) * 0.1,
            "b": jax.random.normal(k3, (32,), jnp.float32) * 0.1}


@pytest.mark.optstate
@pytest.mark.parametrize("codec,tol", [("fp8", 1e-2), ("int8", 3e-2)])
def test_compressed_moments_residency_and_drift(codec, tol):
    """moments_dtype residency: host leaves are (payload, scale) pairs in
    the wire dtype, step 1 matches raw exactly (zero moments dequantize to
    zero), and the step-2 parameters — the first step that reads back
    quantized moments — stay within the codec-resolution drift bound
    (measured ~3e-3 fp8 / ~1.3e-2 int8: int8 is coarser than fp8 for the
    *second* moment, whose wide dynamic range favors the float codec)."""
    from repro.optim import adamw

    key = jax.random.PRNGKey(3)
    params = _tiny_params(key)
    grads = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.PRNGKey(9), p.shape,
                                    jnp.float32), params)

    def run(moments_dtype, steps=2):
        state = adamw.init_state(params, jnp.float32, offload_moments=True,
                                 moments_dtype=moments_dtype)
        p = params
        outs = []
        for _ in range(steps):
            p, state, _ = adamw.apply_update(
                p, grads, state, lr=1e-2, offload_moments=True,
                moments_dtype=moments_dtype)
            outs.append(p)
        return outs, state

    (p1_c, p2_c), state_c = run(codec)
    (p1_r, p2_r), _ = run("none")
    for a, b in zip(jax.tree_util.tree_leaves(p1_c),
                    jax.tree_util.tree_leaves(p1_r)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=0, atol=1e-6)
    flat_c = np.concatenate([np.asarray(l, np.float64).ravel()
                             for l in jax.tree_util.tree_leaves(p2_c)])
    flat_r = np.concatenate([np.asarray(l, np.float64).ravel()
                             for l in jax.tree_util.tree_leaves(p2_r)])
    drift = np.linalg.norm(flat_c - flat_r) / np.linalg.norm(flat_r)
    assert 0.0 < drift <= tol, (codec, drift)
    # residency shape: every param leaf became a (payload, scale) pair
    wire = hostmem.codec_wire_dtype(codec)
    n_param_leaves = len(jax.tree_util.tree_leaves(params))
    leaves_m = jax.tree_util.tree_leaves(state_c.m)
    assert len(leaves_m) == 2 * n_param_leaves
    payloads = [l for l in leaves_m if l.dtype == wire]
    scales = [l for l in leaves_m if l.dtype == jnp.float32]
    assert len(payloads) == n_param_leaves == len(scales)


@pytest.mark.optstate
def test_compressed_moments_init_with_last_axis_sharded_params(eight_devices):
    """Regression: a model-sharded (rows, d) param must not hand its
    last-axis partition to the (rows, 1) scale buffer — the singleton axis
    cannot divide by the mesh's model size (train.py --moments-dtype hit
    this at init).  The payload keeps the param's sharding; the scale gets
    it with the trailing axis unpartitioned (hostmem.row_scale_sharding)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_test_mesh
    from repro.optim import adamw

    kind = hostmem.host_memory_kind()
    mesh = make_test_mesh(4, 2)
    # transfer-lint: ok (test fixture, device placement only)
    p = jax.device_put(jnp.ones((64, 32), jnp.float32),
                       NamedSharding(mesh, P(None, "model")))
    state = adamw.init_state({"w": p}, jnp.float32, offload_moments=True,
                             moments_dtype="fp8")
    payload, scale = state.m["w"]
    assert payload.shape == (64, 32) and scale.shape == (64, 1)
    assert hostmem.memory_kind_of(payload) == kind
    assert hostmem.memory_kind_of(scale) == kind
    assert payload.sharding.spec == P(None, "model")
    assert scale.sharding.spec[-1] is None


@pytest.mark.optstate
def test_compressed_moment_bytes_match_closed_form():
    """Measured host-resident moment bytes (payload + scales) equal the
    cost model's compressed closed form over the same shapes."""
    from repro.optim import adamw

    params = _tiny_params(jax.random.PRNGKey(0))
    state = adamw.init_state(params, jnp.float32, offload_moments=True,
                             moments_dtype="fp8")
    measured = sum(int(l.nbytes)
                   for l in jax.tree_util.tree_leaves(state.m)) + \
        sum(int(l.nbytes) for l in jax.tree_util.tree_leaves(state.v))
    shapes = [tuple(l.shape)
              for l in jax.tree_util.tree_leaves(params)]
    assert measured == cm.moment_bytes_from_shapes(shapes, "float32", "fp8")


@pytest.mark.optstate
def test_moments_dtype_requires_explicit_offload():
    from repro.optim import adamw

    params = _tiny_params(jax.random.PRNGKey(0))
    with pytest.raises(AssertionError, match="offload_moments"):
        adamw.init_state(params, jnp.float32, offload_moments=False,
                         moments_dtype="fp8")
    state = adamw.init_state(params, jnp.float32, offload_moments=True,
                             moments_dtype="fp8")
    with pytest.raises(AssertionError, match="offload_moments"):
        adamw.apply_update(params, params, state, lr=1e-3,
                           offload_moments=False, moments_dtype="fp8")


# ---------------------------------------------------------------------------
# analytic side: wire ratio and scale terms
# ---------------------------------------------------------------------------


def test_wire_ratio_and_scale_terms():
    assert cm.offload_wire_ratio("none") == 1.0
    assert cm.offload_wire_ratio("fp8") == 0.5   # 1 byte vs bf16
    assert cm.offload_wire_ratio("int8") == 0.5
    cfg = get_config("qwen2-7b").reduced()
    lens = [64, 64]
    zero = cm.chunk_scale_bytes(cfg, lens, batch=2, pp=1, sp=1)
    assert all(z == 0.0 for z in zero)
    sb = cm.chunk_scale_bytes(cfg, lens, batch=2, pp=1, sp=1,
                              offload_dtype="fp8")
    assert all(b > 0 for b in sb)
    # scales are fp32 per trailing-axis row: strictly smaller than the
    # payload they describe
    acts = cm.chunk_act_bytes(cfg, lens, batch=2, pp=1, sp=1)
    assert all(s < a for s, a in zip(sb, acts))


def test_solver_alpha_grows_under_compression():
    """The alpha planner sees the link at its effective raw-bytes rate
    (wire_ratio halves the bytes per offloaded row), so compressed plans
    offload at least as much as raw plans on every chunk."""
    from repro.core import solver

    cfg = get_config("qwen2-7b")
    _, a_raw, _ = solver.simulate_candidate(
        cfg, 65536, 1, 7_000_000_000, 2, 8, 16)
    _, a_c, _ = solver.simulate_candidate(
        cfg, 65536, 1, 7_000_000_000, 2, 8, 16, offload_dtype="fp8")
    assert all(c >= r for c, r in zip(a_c, a_raw)), (a_c, a_raw)
    assert sum(a_c) >= sum(a_raw)
