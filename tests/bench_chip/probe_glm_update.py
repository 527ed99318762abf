"""A probe on the chip, not a test: what the harness's own steps hold on
the device at GLM-4-9B's 4-of-40-layer cut at published widths
(``glm_double``'s table: d 4096, 32 query heads over 2 KV groups of 128,
SwiGLU at 13696, vocabulary 151552, 151552 x 4096 embedding and head).
One phase a process, since a chip's peak never falls:

- ``update``: the reference's AdamW steps with synthetic float32 gradients,
  bfloat16 weights and every gradient on the device, the moments in host
  memory, as the plain reference takes them; with ``--whole`` each leaf is
  updated whole instead of in blocks.
- ``change``: the reference's change norms (``harness.reference_change``)
  over the weights as drawn.
- ``program``: the program's tree for ``glm4-9b`` at that cut (bfloat16
  weights on the device, float32 moments in host memory, as the cell's
  plan holds them) read by ``Program.moment_norms`` and
  ``Program.change_norms``.

It prints one JSON line per step: seconds, the chip's counters
(``bytes_in_use``, ``peak_bytes_in_use``, ``peak_bytes_reserved`` and
``harness.hbm_peak``, their sum) and, for the program's reads, each
compiled program's temporaries.

    python3 tests/bench_chip/probe_glm_update.py --phase update
"""
import argparse
import dataclasses
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, HERE)

GLM = {
    "name": "glm4-9b-probe", "reference": "glm_double",
    "program_arch": "glm4-9b", "num_hidden_layers": 4,
    "published": {"num_hidden_layers": 40}, "hidden_size": 4096,
    "num_attention_heads": 32, "num_key_value_heads": 2, "head_dim": 128,
    "intermediate_size": 13696, "vocab_size": 151552, "hidden_act": "silu",
    "norm": "rmsnorm", "norm_eps": 1.5625e-07, "rope_theta": 10000.0,
    "dtype": "bfloat16", "attention_bias": True,
    "partial_rotary_factor": 0.5,
}
OPT = {"lr_peak": 3e-4, "warmup_steps": 20, "total_steps": 100, "b1": 0.9,
       "b2": 0.95, "eps": 1e-8, "weight_decay": 0.1, "clip_norm": 1.0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--phase", choices=("update", "change", "program"),
                    default="update")
    ap.add_argument("--whole", action="store_true")
    ap.add_argument("--steps", type=int, default=2)
    args = ap.parse_args(argv)

    import jax

    import glm_double
    from benchmarks.chip import harness, spec, weights

    dev, = harness.find_chips(1)
    m = spec.Model.from_file(GLM)
    table = glm_double.leaves(m)
    key = weights.seed_key(2**31 + 16)

    def stats(**extra):
        s = dev.memory_stats() or {}
        return {**extra,
                **{k: s.get(k) for k in ("bytes_in_use", "peak_bytes_in_use",
                                         "peak_bytes_reserved",
                                         "bytes_limit")},
                "peak_bytes": harness.hbm_peak(s)}

    def emit(**line):
        print(json.dumps(line), flush=True)

    emit(phase=args.phase, whole=args.whole,
         params=sum(math.prod(lf.shape) for lf in table.values()),
         **stats())
    phase = {"update": _update, "change": _change, "program": _program}
    try:
        phase[args.phase](args, m, table, key, stats, emit)
    except Exception as e:  # the whole-leaf update runs out of memory
        emit(error=str(e)[:600], **stats())
        return 1
    return 0


def _drawn(table, key):
    import jax

    from benchmarks.chip import weights

    return weights.by_layer(
        jax.jit(lambda k: weights.canonical(table, k))(key), table)


def _update(args, m, table, key, stats, emit):
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import adamw, harness

    params = _drawn(table, key)

    def grad_of(i, shape):
        return jax.jit(lambda k: 1e-3 * jax.random.normal(
            jax.random.fold_in(k, i), shape, jnp.float32))(key)

    leaves, tdef = jax.tree.flatten(params)
    grads = tdef.unflatten([grad_of(i, p.shape)
                            for i, p in enumerate(leaves)])
    jax.block_until_ready(grads)
    emit(drawn="weights and gradients", **stats())
    decayed = {n for n, lf in table.items() if lf.decay}
    if args.whole:
        adamw.SLICE_BYTES = 2**62
    opt = adamw.AdamW(OPT, jnp.bfloat16, lambda path: path[-1].key in decayed)
    sq = jax.jit(lambda g: jnp.sqrt(jnp.sum(jnp.square(g))))
    for step in range(args.steps):
        t = time.perf_counter()
        norms = harness._leaf_norms(grads, table, sq)
        gnorm = math.sqrt(sum(v * v for v in norms.values()))
        t_norm = time.perf_counter() - t
        params = opt.update(params, grads, step, gnorm,
                            keep=step < args.steps - 1)
        jax.block_until_ready(params)
        emit(step=step, seconds=time.perf_counter() - t,
             norm_seconds=t_norm, gnorm=gnorm, **stats())


def _change(args, m, table, key, stats, emit):
    import jax

    from benchmarks.chip import harness

    params = _drawn(table, key)
    jax.block_until_ready(params)
    emit(drawn="weights", **stats())
    t = time.perf_counter()
    change = harness.reference_change(params, table, key)
    emit(step="reference_change", seconds=time.perf_counter() - t,
         largest=max(change.values()), **stats())


def _program(args, m, table, key, stats, emit):
    import jax
    import jax.numpy as jnp

    from benchmarks.chip import harness, weights

    harness._import_program()
    from repro.configs.base import get_config
    from repro.models.model_zoo import build_model
    from repro.optim import adamw as program_adamw
    from repro.parallel import specs as SP

    # the program's glm4-9b at the file's sizes; its norms' eps is not
    # compared here (``program_config`` would refuse 1e-6 against the
    # published 1.5625e-7), as nothing here runs a norm
    cfg = dataclasses.replace(
        get_config(m.program_arch), n_layers=m.layers, d_model=m.d,
        n_heads=m.heads, n_kv_heads=m.kv_heads, head_dim=m.head_dim,
        d_ff=m.ff, vocab_size=m.vocab)
    struct, _ = SP.param_struct_and_specs(build_model(cfg), 1, 1,
                                          jnp.bfloat16)
    params = jax.jit(lambda k: weights.to_program(
        weights.canonical(table, k), table, struct, pp=1, data=1))(key)
    state = program_adamw.init_state(params, jnp.float32,
                                     offload_moments=True)
    jax.block_until_ready((params, state))
    emit(drawn="program weights and host moments", **stats())
    prog = harness.Program.__new__(harness.Program)
    prog.table, prog.slots, prog.key = table, weights.slots(struct), key
    prog.params, prog.opt_state = params, state
    prog._norms = jax.jit(prog._norms_of)
    for name, read, tree, k in (
            ("moment_norms", lambda: prog.moment_norms(OPT["b1"]),
             state.m, None),
            ("change_norms", prog.change_norms, params, key)):
        mem = prog._norms.lower(tree, k).compile().memory_analysis()
        t = time.perf_counter()
        read()
        emit(step=name, seconds=time.perf_counter() - t,
             temp_bytes=getattr(mem, "temp_size_in_bytes", None),
             **stats())


if __name__ == "__main__":
    sys.exit(main())
