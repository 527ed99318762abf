"""Gradient conformance of the attention kernels (the training contract).

Three implementations must agree on dq/dk/dv:
  * the dense ``jnp.einsum`` oracle (`mha_reference`, plain autodiff),
  * the blockwise-jnp reference (`attention_partial_ref`, autodiff of the
    scan with the gradient-frozen max statistic),
  * the Pallas path (`flash_attention_partial`, fused backward kernels via
    custom_vjp, interpret mode on CPU).

Property-tested across causal/non-causal, GQA group sizes, decode (Tq=1),
ragged positions and PAD cache slots, fp32/bf16 — tolerance-tiered per
dtype.  Plus: gradients must flow through the partial-softmax *merge*
(`merge_partials`): the stop_gradient on the max statistic must not freeze
dq/dk for the winning block (finite-difference checked).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.flash_attention import flash_attention_partial
from repro.kernels.ref import (PAD_POS, attention_partial_ref, merge_partials,
                               mha_reference, normalize)

# (Tq, S, n_pad_slots, q_off): ragged block shapes, decode, ragged offsets
SHAPES = [
    (16, 32, 0, 16),
    (17, 33, 5, 8),
    (1, 40, 8, 30),     # decode: Tq=1 padded to a block
    (8, 24, 3, 13),
]

TOL = {jnp.float32: 1e-4, jnp.bfloat16: 6e-2}


def _mk_case(shape_idx, G, Hkv, dtype, seed):
    Tq, S, n_pad, q_off = SHAPES[shape_idx % len(SHAPES)]
    H, hd, hv = G * Hkv, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = jax.random.normal(ks[0], (1, Tq, H, hd), dtype)
    k = jax.random.normal(ks[1], (1, S, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (1, S, Hkv, hv), dtype)
    w = jax.random.normal(ks[3], (1, Tq, H, hv), jnp.float32)
    q_pos = jnp.arange(Tq, dtype=jnp.int32) + q_off
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    if n_pad:
        kv_pos = jnp.where(jnp.arange(S) < S - n_pad, kv_pos, PAD_POS)
    return q, k, v, w, q_pos, kv_pos


def _grads(loss, q, k, v):
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@settings(max_examples=8, deadline=None)
@given(st.sampled_from([1, 4, 8]),          # GQA group size
       st.sampled_from([True, False]),       # causal
       st.sampled_from(["float32", "bfloat16"]),
       st.integers(0, 7))                    # shape pick + rng seed
def test_grad_conformance(G, causal, dtype_name, seed):
    dtype = jnp.dtype(dtype_name).type
    Hkv = 2 if G < 8 else 1
    q, k, v, w, q_pos, kv_pos = _mk_case(seed, G, Hkv, dtype, seed)

    def loss_pallas(q, k, v):
        o, _, l = flash_attention_partial(q, k, v, q_pos, kv_pos,
                                          causal=causal, block_q=16,
                                          block_k=16, interpret=True)
        return jnp.sum(normalize(o, l) * w)

    def loss_ref(q, k, v):
        o, _, l = attention_partial_ref(q, k, v, q_pos, kv_pos,
                                        causal=causal, block_k=16)
        return jnp.sum(normalize(o, l) * w)

    def loss_dense(q, k, v):
        return jnp.sum(mha_reference(q, k, v, q_pos, kv_pos,
                                     causal=causal) * w)

    gp = _grads(loss_pallas, q, k, v)
    gr = _grads(loss_ref, q, k, v)
    gd = _grads(loss_dense, q, k, v)
    tol = TOL[dtype]
    for name, a, b, c in zip("qkv", gp, gr, gd):
        a, b, c = (np.asarray(x, np.float32) for x in (a, b, c))
        np.testing.assert_allclose(a, b, rtol=tol, atol=tol,
                                   err_msg=f"d{name}: pallas vs ref")
        np.testing.assert_allclose(a, c, rtol=tol, atol=tol,
                                   err_msg=f"d{name}: pallas vs dense")


def test_grad_fully_masked_rows_are_zero():
    """Queries that can see no KV (all slots in the future / PAD) must get
    exactly zero gradient — not NaN from exp(NEG_INF - NEG_INF)."""
    q, k, v, w, q_pos, _ = _mk_case(0, 2, 2, jnp.float32, 1)
    kv_pos = jnp.arange(k.shape[1], dtype=jnp.int32) + 10_000

    for fn in (
        lambda q, k, v: flash_attention_partial(
            q, k, v, q_pos, kv_pos, block_q=16, block_k=16, interpret=True),
        lambda q, k, v: attention_partial_ref(
            q, k, v, q_pos, kv_pos, block_k=16),
    ):
        def loss(q, k, v, fn=fn):
            o, _, l = fn(q, k, v)
            return jnp.sum(normalize(o, l) * w)

        gq, gk, gv = _grads(loss, q, k, v)
        for g in (gq, gk, gv):
            assert not np.any(np.isnan(np.asarray(g)))
            np.testing.assert_allclose(np.asarray(g), 0.0)


def test_grad_decode_padded_block():
    """Decode (Tq=1, padded to a kernel block) backward matches dense."""
    q, k, v, w, _, kv_pos = _mk_case(2, 4, 2, jnp.float32, 3)
    q_pos = jnp.full((1,), 30, jnp.int32)

    def loss_pallas(q, k, v):
        o, _, l = flash_attention_partial(q, k, v, q_pos, kv_pos,
                                          block_q=16, block_k=16,
                                          interpret=True)
        return jnp.sum(normalize(o, l) * w)

    def loss_dense(q, k, v):
        return jnp.sum(mha_reference(q, k, v, q_pos, kv_pos) * w)

    gp = _grads(loss_pallas, q, k, v)
    gd = _grads(loss_dense, q, k, v)
    for name, a, b in zip("qkv", gp, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4,
                                   err_msg=f"d{name}")


# (Tq, S, G, causal, dtype): lengths the shape-chosen tiles split into
# several blocks and pad — 512 x 1024 at G 1, 128 x 1024 at G 4 (row cap),
# and decode: 8 x 1024 over 4 kv blocks
AUTO_CASES = [
    (1000, 2000, 1, True, jnp.float32),
    (1000, 2000, 4, True, jnp.float32),
    (300, 600, 2, False, jnp.float32),
    (1000, 2000, 1, True, jnp.bfloat16),
    (1, 4000, 7, True, jnp.float32),
]


@pytest.mark.parametrize("Tq,S,G,causal,dtype", AUTO_CASES)
def test_grad_auto_tiles_match_ref(Tq, S, G, causal, dtype):
    """dq/dk/dv at the tiles the kernel picks itself (several blocks, with
    padding) match the blockwise reference's autodiff."""
    H, Hkv, hd = 2 * G, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(Tq + G), 4)
    q = jax.random.normal(ks[0], (1, Tq, H, hd), dtype)
    k = jax.random.normal(ks[1], (1, S, Hkv, hd), dtype)
    v = jax.random.normal(ks[2], (1, S, Hkv, hd), dtype)
    w = jax.random.normal(ks[3], (1, Tq, H, hd), jnp.float32)
    q_pos = jnp.arange(Tq, dtype=jnp.int32) + (S - Tq)
    kv_pos = jnp.where(jnp.arange(S) < S - 7, jnp.arange(S), PAD_POS)

    def loss(fn):
        def f(q, k, v):
            o, _, l = fn(q, k, v)
            return jnp.sum(normalize(o, l) * w)
        return f

    gp = _grads(loss(lambda q, k, v: flash_attention_partial(
        q, k, v, q_pos, kv_pos, causal=causal, interpret=True)), q, k, v)
    gr = _grads(loss(lambda q, k, v: attention_partial_ref(
        q, k, v, q_pos, kv_pos, causal=causal, block_k=16)), q, k, v)
    tol = TOL[dtype]
    for name, a, b in zip("qkv", gp, gr):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=tol,
                                   atol=tol, err_msg=f"d{name}")


# ---------------------------------------------------------------------------
# Merge gradients: the stop_gradient on the max stat must not freeze anything
# ---------------------------------------------------------------------------


def _merge_setup():
    B, Tq, S, H, Hkv, hd = 1, 8, 32, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (B, Tq, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    # second shard's keys scaled up: its scores dominate, so *it* wins the
    # running max — the regression target for a frozen-winner bug
    k = k.at[:, S // 2:].multiply(3.0)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
    w = jax.random.normal(ks[3], (B, Tq, H, hd), jnp.float32)
    q_pos = jnp.arange(Tq, dtype=jnp.int32) + (S - Tq)
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    return q, k, v, w, q_pos, kv_pos, S // 2


def _merged_loss(q, k, v, w, q_pos, kv_pos, half):
    parts = [attention_partial_ref(q, k[:, sl], v[:, sl], q_pos, kv_pos[sl],
                                   block_k=8)
             for sl in (slice(0, half), slice(half, None))]
    o, _, l = merge_partials(parts)
    return jnp.sum(normalize(o, l) * w)


def test_merge_partials_grads_match_full_attention():
    """Sharded partials + merge must have the *same* gradients as full-KV
    attention — including dk of the shard that wins the max statistic."""
    q, k, v, w, q_pos, kv_pos, half = _merge_setup()

    def loss_merged(q, k, v):
        return _merged_loss(q, k, v, w, q_pos, kv_pos, half)

    def loss_full(q, k, v):
        return jnp.sum(mha_reference(q, k, v, q_pos, kv_pos) * w)

    gm = _grads(loss_merged, q, k, v)
    gf = _grads(loss_full, q, k, v)
    for name, a, b in zip("qkv", gm, gf):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4,
                                   err_msg=f"d{name}")
    # the winning (second) shard's dk is live, not frozen
    dk_win = np.asarray(gm[1])[:, half:]
    assert np.max(np.abs(dk_win)) > 1e-3


def test_merge_partials_grad_finite_difference():
    """Directional finite-difference check of dq through the merge: the
    stop_gradient on the max statistic is a *reparameterization*, not a
    truncation — the analytic derivative must match the numeric one."""
    q, k, v, w, q_pos, kv_pos, half = _merge_setup()

    def loss_q(q):
        return _merged_loss(q, k, v, w, q_pos, kv_pos, half)

    g = jax.grad(loss_q)(q)
    u = jax.random.normal(jax.random.PRNGKey(5), q.shape, jnp.float32)
    u = u / jnp.linalg.norm(u)
    eps = 3e-2
    num = (loss_q(q + eps * u) - loss_q(q - eps * u)) / (2 * eps)
    ana = jnp.sum(g * u)
    np.testing.assert_allclose(float(ana), float(num), rtol=2e-2, atol=2e-3)


@pytest.mark.ring
@settings(deadline=None)  # max_examples inherited: nightly raises it
@given(st.integers(2, 5),        # number of KV shards in the ring
       st.integers(0, 11))       # arrival order: rotation or a shuffle
def test_ring_fold_is_arrival_order_invariant(n_shards, order_seed):
    """The ring schedule's silent dependency (DESIGN.md §15): folding the
    same KV shards in *any* arrival order — each rank sees a different
    rotation of the ring — must give bit-identical (o, m, l) and, through
    them, bit-identical gradients.  fold_arrivals scatters every block into
    its canonical source slot before the single merge, so the merge graph
    never sees the arrival order; this property-checks exactly that."""
    from repro.parallel.ring import fold_arrivals

    B, H, Hkv, hd = 1, 4, 2, 16
    S = 8 * n_shards
    Tq = 8
    ks = jax.random.split(jax.random.PRNGKey(order_seed + 17 * n_shards), 4)
    q = jax.random.normal(ks[0], (B, Tq, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
    w = jax.random.normal(ks[3], (B, Tq, H, hd), jnp.float32)
    q_pos = jnp.arange(Tq, dtype=jnp.int32) + S - Tq  # sees every shard
    kv_pos = jnp.arange(S, dtype=jnp.int32)

    canonical = list(range(n_shards))
    rot = order_seed % n_shards
    order = canonical[rot:] + canonical[:rot]
    if order_seed >= 6:  # beyond rotations: arbitrary permutations too
        rng = np.random.RandomState(order_seed)
        order = list(rng.permutation(n_shards))

    def fold(k, order):
        parts = []
        for s in order:
            sl = slice(s * 8, (s + 1) * 8)
            parts.append(attention_partial_ref(
                q, k[:, sl], v[:, sl], q_pos, kv_pos[sl], causal=True))
        return fold_arrivals(parts, order, n_blocks=n_shards)

    def loss(k, order):
        o, _, l = fold(k, order)
        return jnp.sum(normalize(o, l) * w)

    o_a, m_a, l_a = fold(k, canonical)
    o_b, m_b, l_b = fold(k, order)
    np.testing.assert_array_equal(np.asarray(o_a), np.asarray(o_b))
    np.testing.assert_array_equal(np.asarray(m_a), np.asarray(m_b))
    np.testing.assert_array_equal(np.asarray(l_a), np.asarray(l_b))
    g_a = jax.grad(loss)(k, canonical)
    g_b = jax.grad(loss)(k, order)
    np.testing.assert_array_equal(np.asarray(g_a), np.asarray(g_b))
