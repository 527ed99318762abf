"""The benchmark's FLOP and byte counts against hand counts."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                os.pardir))

from benchmarks.chip import flops, spec  # noqa: E402


def test_causal_pairs_are_the_visible_half():
    # T = S = 4: query i sees i + 1 keys -> 1 + 2 + 3 + 4
    assert flops.attention_pairs(1, 4, 4, causal=True) == 10
    assert flops.attention_pairs(2, 4, 4, causal=False) == 32
    # queries at the end of a longer context see the whole prefix
    assert flops.attention_pairs(1, 2, 6, causal=True) == 5 + 6


def test_forward_counts_by_hand():
    f, b = flops.attention_fwd(B=1, H=2, Hkv=2, T=4, S=4, head_dim=8)
    assert f == 4 * 8 * 2 * 10          # q.k and p.v, 2 FLOPs per MAC
    # Q, K, V, O in bf16 and the float32 log-sum-exp
    assert b == 4 * (1 * 4 * 2 * 8 * 2) + 4 * 1 * 4 * 2


def test_backward_is_twice_the_forward():
    kw = dict(B=2, H=32, Hkv=32, T=16384, S=16384, head_dim=128)
    assert flops.attention_bwd(**kw)[0] == 2 * flops.attention_fwd(**kw)[0]


def test_grouped_kv_heads_leave_flops_unchanged():
    mha = flops.attention_fwd(B=1, H=32, Hkv=32, T=1024, S=1024, head_dim=128)
    gqa = flops.attention_fwd(B=1, H=32, Hkv=2, T=1024, S=1024, head_dim=128)
    assert gqa[0] == mha[0]
    assert gqa[1] < mha[1]               # narrower K and V to read


def test_train_step_flops_of_the_cell():
    m = spec.load_cell("gpt7b-16k-1chip").model
    T = 16384
    n = flops.matmul_params(m)
    # 4 layers of 4·d² + 2·d·ff, plus the head d·V; the embedding is not
    # multiplied
    assert n == 4 * (4 * 4096**2 + 2 * 4096 * 16384) + 4096 * 51200
    attn = 3 * 4 * 128 * 32 * (T * (T + 1) // 2) * 4
    assert flops.train_step_flops(m, batch=1, seq=T) == 6 * n * T + attn
    assert flops.train_step_flops(m, batch=1, seq=T) == pytest.approx(
        1.26e14, rel=0.01)


def test_least_time_takes_the_larger_bound():
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.least_time(1000, 50, peak) == 10.0
    assert flops.least_time(100, 500, peak) == 50.0
