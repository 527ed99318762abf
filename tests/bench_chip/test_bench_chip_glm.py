"""A second architecture through the harness, on the CPU, with nothing of
it under benchmarks/chip/: the program's ``glm4-9b`` at 2 layers of width
64, 8 query heads over 2 KV heads and a 256-token vocabulary, with RMSNorm,
SwiGLU, q/k/v biases and rotary on half of each head.  Its reference is the
stand-in ``glm_double``, registered in place of a module under
``references/``, and its cell is built here.

Sound, a run comes out correct; with the timed path broken underneath, not
correct, once for each fault.  The limits are this size's own: on the CPU,
sound runs over eight seeds read at most loss 2.8e-4, grad 6.2e-3, change
1.2e-2; the faults over three seeds each read at least: a state left
unchanged 1 on grad and change, half the batch left out grad 8.2e-2 and
change 0.16, one update doubled loss 2.3e-3 and change 0.997.  The key
bias's passed half moves by round-off alone in the program (its reference
gradient reads 1e-10, the program's 2e-6) and is left out of the change by
the check's rule; read with the rotated half, it read a change gap of 0.097.

``program_config`` holds the program to every architecture field a
configuration file states, and refuses a file that misstates one.
"""
import dataclasses
import json
import math
import os
import sys
import time
import types

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir))
sys.path.insert(0, ROOT)

from benchmarks.chip import check, flops, harness, spec  # noqa: E402
from benchmarks.chip.faults import FAULTS  # noqa: E402

import glm_double  # noqa: E402

REFERENCE = "glm_double"
CONFIG = {
    "name": "glm4-tiny", "reference": REFERENCE, "program_arch": "glm4-9b",
    "num_hidden_layers": 2, "published": {"num_hidden_layers": 40},
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "head_dim": 8, "intermediate_size": 128, "vocab_size": 256,
    "hidden_act": "silu", "norm": "rmsnorm", "norm_eps": 1e-6,
    "rope_theta": 10000.0, "dtype": "bfloat16", "attention_bias": True,
    "partial_rotary_factor": 0.5,
}
LIMITS = {"loss_gap": 1e-3, "grad_gap": 2e-2, "change_gap": 4e-2}
GLM_EPS = 1.5625e-7      # GLM-4's RMSNorm eps; the program runs 1e-6


@pytest.fixture(scope="module")
def tiny():
    """The cell, with the stand-in reference registered for this module."""
    name = f"benchmarks.chip.references.{REFERENCE}"
    sys.modules[name] = glm_double
    gpt = spec.load_cell("gpt7b-16k-1chip", ROOT)
    yield dataclasses.replace(
        gpt, name="glm4-tiny", model=spec.Model.from_file(CONFIG),
        traffic=dict(gpt.traffic, seq_len=256, mean_doc_len=64),
        limits=LIMITS, per_layer=())
    del sys.modules[name]


@pytest.fixture(autouse=True)
def no_cache(monkeypatch):
    monkeypatch.setattr(harness, "use_compile_cache", lambda: None)


def _run(cell, wrap=None, seed=2**31 + 5):
    return harness.run(cell, seed, 0.2, False, t_start=time.perf_counter(),
                       require_tpu=False, wrap_step=wrap,
                       log=lambda *_: None)


def test_sound_run_is_correct(tiny):
    out = _run(tiny)
    assert out["correct"], check.lines(out["checks"])
    assert out["failed"] == 0 and out["attempted"] > tiny.checked_steps


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_broken_step_is_not_correct(tiny, fault):
    out = _run(tiny, FAULTS[fault])
    assert not out["correct"], check.lines(out["checks"])


def test_step_mfu_counts_three_mlp_matrices(tiny):
    m = tiny.model
    d, ff, V = m.d, m.ff, m.vocab
    qd, kvd = m.heads * m.head_dim, m.kv_heads * m.head_dim
    assert (qd, kvd) == (64, 16)
    # per layer: Wq, Wk, Wv, Wo and gate, up, down; the head; no embedding,
    # no bias, no norm
    assert flops.matmul_params(m) == 2 * (d * qd + 2 * d * kvd + qd * d
                                          + 3 * d * ff) + d * V


# GLM-4-9B at published widths, 4 of its 40 layers
GLM_WIDTHS = dict(CONFIG, num_hidden_layers=4, hidden_size=4096,
                  num_attention_heads=32, num_key_value_heads=2, head_dim=128,
                  intermediate_size=13696, vocab_size=151552,
                  norm_eps=GLM_EPS)


def test_layer_rooflines_count_glm_mlp_and_head(monkeypatch):
    from benchmarks.chip.metrics import head_loss_roofline, mlp_roofline

    monkeypatch.setitem(sys.modules,
                        f"benchmarks.chip.references.{REFERENCE}", glm_double)
    m = spec.Model.from_file(GLM_WIDTHS)
    T = 16384
    # the gated MLP's gate, up and down; the head d x 151552
    mlp = 4 * 3 * 4096 * 13696
    head = 4096 * 151552
    assert flops.weights_matmul(m, mlp_roofline.takes, tokens=T) == (
        6 * T * mlp, 2 * mlp)
    assert flops.weights_matmul(m, head_loss_roofline.takes, tokens=T) == (
        6 * T * head, 2 * head)
    # the head is about 37% of the step's model FLOPs
    assert 6 * T * head / flops.train_step_flops(m, batch=1, seq=T) \
        == pytest.approx(0.37, abs=0.01)


def test_program_config_takes_the_file():
    cfg = harness.program_config(spec.Model.from_file(CONFIG))
    assert (cfg.act, cfg.norm, cfg.qkv_bias, cfg.rope_fraction) == (
        "swiglu", "rmsnorm", True, 0.5)
    assert (cfg.n_layers, cfg.d_model, cfg.n_kv_heads) == (2, 64, 2)


def _norm_at(monkeypatch, eps):
    """The program's norms made to run at ``eps``, as a program that passes
    its configuration's eps to ``apply_norm`` would, with the functions'
    defaults left as they are."""
    harness._import_program()
    from repro.models import layers

    rms = layers.rms_norm

    def apply_norm(x, p, kind):
        assert kind == "rmsnorm"
        return rms(x, p["scale"], eps)

    monkeypatch.setattr(layers, "apply_norm", apply_norm)


EPS_CASES = {"rmsnorm": ("rmsnorm", 1e-6, None),
             "layernorm": ("layernorm", 1e-5, None),
             "patched": ("rmsnorm", GLM_EPS, GLM_EPS)}


@pytest.mark.parametrize("case", list(EPS_CASES))
def test_program_norm_eps_is_the_eps_the_norms_run_at(case, monkeypatch):
    # the eps read is measured through the program's model: the norms'
    # defaults (1e-6, 1e-5) where nothing passes another, and the eps a
    # patched norm runs at where one does.  A row whose mean square is the
    # eps read then comes out at 1 / sqrt(2) of its size
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.models import layers

    kind, want, patched = EPS_CASES[case]
    if patched is not None:
        _norm_at(monkeypatch, patched)
    arch = {"rmsnorm": "glm4-9b", "layernorm": "sppo-gpt-7b"}[kind]
    cfg = dataclasses.replace(get_config(arch), d_model=64)
    eps = harness.program_norm_eps(cfg)
    assert eps == pytest.approx(want, rel=1e-4)
    a = math.sqrt(eps)
    x = jnp.array([[a, -a] * 4], jnp.float32)
    p = {"scale": jnp.zeros(8) if kind == "rmsnorm" else jnp.ones(8),
         "bias": jnp.zeros(8)}
    y = np.asarray(layers.apply_norm(x, p, kind))
    np.testing.assert_allclose(np.abs(y), a / math.sqrt(a * a + eps),
                               rtol=1e-5)


def test_program_config_holds_the_measured_eps(monkeypatch):
    glm = dict(CONFIG, norm_eps=GLM_EPS)
    with pytest.raises(ValueError, match="program has norm eps=.*states "
                       "norm_eps=1.5625e-07"):
        harness.program_config(spec.Model.from_file(glm))
    _norm_at(monkeypatch, GLM_EPS)
    harness.program_config(spec.Model.from_file(glm))
    with pytest.raises(ValueError, match="program has norm eps=.*states "
                       "norm_eps=1e-06"):
        harness.program_config(spec.Model.from_file(CONFIG))


with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                       "sppo-gpt-7b.json")) as f:
    GPT_FILE = json.load(f)

# each architecture field a file states, misstated for the GPT program
MISSTATED = [
    ("hidden_act", "silu", "act"),
    ("norm", "rmsnorm", "norm"),
    ("norm_eps", 1e-6, "norm eps"),
    ("attention_bias", True, "qkv_bias"),
    ("mlp_bias", True, "mlp_bias"),
    ("position_embedding_type", "learned", "pos_emb"),
    ("rope_theta", 500000.0, "rope_theta"),
    ("partial_rotary_factor", 0.5, "rope_fraction"),
    ("tie_word_embeddings", True, "tie_embeddings"),
    ("dtype", "float32", "dtype"),
    ("family", "moe", "family"),
]


def test_every_architecture_field_is_checked():
    assert {field for _, _, field in MISSTATED} \
        == set(harness.stated(spec.Model.from_file(GPT_FILE))) \
        - {"rope"} | {"norm eps"}


@pytest.mark.parametrize("key,value,field", MISSTATED,
                         ids=[k for k, _, _ in MISSTATED])
def test_program_config_refuses_a_misstated_field(key, value, field):
    harness.program_config(spec.Model.from_file(GPT_FILE))
    wrong = spec.Model.from_file(dict(GPT_FILE, **{key: value}))
    with pytest.raises(ValueError, match=f"program has {field}=.*"
                       f"states {key}={value!r}"):
        harness.program_config(wrong)
