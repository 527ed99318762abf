"""The weights the benchmark draws for the GPT cell, its FLOP count and its
decay set, pinned: reading the leaf table from the reference module left
each of them as it was when the table sat in the harness."""
import dataclasses
import hashlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir,
                                os.pardir))

from benchmarks.chip import flops, references, spec, weights  # noqa: E402

CELL = "gpt7b-16k-1chip"
# sha256 over each leaf's name, dtype, shape and bytes, in table order, for
# the tiny model below at seed 2**31 + 3
DIGEST = "e390c0e9540d55e3e272df5ede75d55de6e4bc69b7f426e9d52047664832ee7c"
ORDER = ["ln1_scale", "ln1_bias", "wq", "wk", "wv", "wo", "ln2_scale",
         "ln2_bias", "w1", "w2", "embed", "head", "lnf_scale", "lnf_bias"]


@pytest.fixture(scope="module")
def model():
    return spec.load_cell(CELL).model


def test_gpt_canonical_weights_are_pinned(model):
    m = dataclasses.replace(model, layers=2, d=64, heads=4, kv_heads=4,
                            head_dim=16, ff=128, vocab=256)
    canon = weights.canonical(references.of(m).leaves(m),
                              weights.seed_key(2**31 + 3))
    assert list(canon) == ORDER
    h = hashlib.sha256()
    for name, x in canon.items():
        h.update(name.encode())
        h.update(str(x.dtype).encode())
        h.update(str(x.shape).encode())
        h.update(np.asarray(x).tobytes())
    assert h.hexdigest() == DIGEST


def test_gpt_train_step_flops_are_pinned(model):
    assert flops.matmul_params(model) == 1015021568
    assert flops.train_step_flops(model, batch=1, seq=16384) \
        == 126170569900032


def test_gpt_decay_set_is_pinned(model):
    table = references.of(model).leaves(model)
    assert {n for n, lf in table.items() if lf.decay} == {
        "wq", "wk", "wv", "wo", "w1", "w2", "embed", "head"}
