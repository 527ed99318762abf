"""One run of one cell: set-up, the first steps the check compares, the
measured window, the reference, and the result line.

The program is driven through its own training path -- ``resolve_cell``,
``make_train_step`` jitted with its state donated, batches laid out by
``shard_batch`` and placed on the mesh -- the path ``launch/train.py``
builds.  Weights come from ``weights.py`` and batches from ``traffic/``,
both from `--seed`; the program gets only the arrays.
"""
from __future__ import annotations

import dataclasses
import gc
import glob
import gzip
import importlib
import json
import math
import os
import shutil
import sys
import tempfile
import time

from . import adamw as ref_adamw
from . import check, references, scopes, spec, tracefile, weights
from .traffic import TokenFeed

CACHE_DIR = os.path.join(spec.ROOT, ".jax_cache")
# the program's name for an activation a configuration file names
PROGRAM_ACT = {"gelu_tanh": "gelu", "silu": "swiglu"}


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def find_chips(n: int, *, require_tpu: bool = True):
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if require_tpu and platform != "tpu":
        raise NoChip(f"no TPU found: JAX runs on {platform}")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} chips; JAX found {len(devices)} "
                     f"{platform} devices")
    return devices[:n]


def use_compile_cache(path: str = CACHE_DIR) -> None:
    """JAX's persistent cache with every program in it, so a second run of
    a cell compiles nothing: at $JAX_COMPILATION_CACHE_DIR where the
    environment sets one (JAX reads it itself), else at a fixed path
    inside the checkout."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Programs compiled or loaded from the persistent cache, counted from
    JAX's own monitoring events (the first fires once per program either
    way, the second once per cache load)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax

        self.programs = self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, *_, **__):
        if event == self.COMPILE:
            self.programs += 1
        elif event == self.CACHE_HIT:
            self.cache_hits += 1


def _import_program():
    src = os.path.join(spec.ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def stated(model) -> dict:
    """{program field: (configuration key, the file's value, the value the
    program has to hold)} for every architecture field a configuration
    states; an absent optional key states its default (``spec.OPTIONAL``)."""
    pos = model.position_embedding_type
    return {
        "act": ("hidden_act", model.act, PROGRAM_ACT.get(model.act)),
        "norm": ("norm", model.norm, model.norm),
        "qkv_bias": ("attention_bias", model.attention_bias,
                     model.attention_bias),
        "mlp_bias": ("mlp_bias", model.mlp_bias, model.mlp_bias),
        "pos_emb": ("position_embedding_type", pos, pos),
        "rope": ("position_embedding_type", pos, pos == "rope"),
        "rope_theta": ("rope_theta", model.rope_theta, model.rope_theta),
        "rope_fraction": ("partial_rotary_factor",
                          model.partial_rotary_factor,
                          model.partial_rotary_factor),
        "tie_embeddings": ("tie_word_embeddings", model.tie_word_embeddings,
                           model.tie_word_embeddings),
        "dtype": ("dtype", model.dtype, model.dtype),
        "family": ("family", model.family, model.family),
    }


def program_norm_eps(cfg) -> float:
    """The eps the program's norms run at, measured: the program's model
    for ``cfg`` (its vocabulary cut to one token) runs its own final norm
    and head (``ModelDef.head_logits``) on a row of mean square v and mean
    0, with the gain the program initialises (1) and a head that reads the
    row's first element x.  The norm gives y = x / sqrt(v + eps), so eps =
    (x / y)^2 - v, in float32 at the highest matmul precision; v is small
    beside any eps a model states, so its rounding does not show.  It runs
    on the host's CPU where JAX has it, whatever the default device."""
    import jax
    import jax.numpy as jnp

    _import_program()
    from repro.models.model_zoo import build_model
    from repro.parallel.ctx import SINGLE

    cfg = dataclasses.replace(cfg, vocab_size=1)
    mdef = build_model(cfg)
    d = cfg.d_model

    def first_of(key, row):
        g = mdef.init_globals(key, jnp.float32)
        name, leaf = (("embed", "table") if cfg.tie_embeddings
                      else ("head", "w"))
        g[name][leaf] = jnp.zeros_like(g[name][leaf]).at[0, 0].set(1.0)
        return mdef.head_logits(g, row[None, None, :], SINGLE)[0, 0, 0]

    # on the host's CPU, so that the chip's memory counters do not see it
    try:
        where = jax.devices("cpu")[0]
    except RuntimeError:        # JAX started without its CPU backend
        where = jax.devices()[0]
    with jax.default_device(where), jax.default_matmul_precision("highest"):
        # +a, -a, ... (and 0 last where d is odd): mean 0, mean square v
        a = float(jnp.float32(1e-6))
        signs = [(-1.0) ** i for i in range(d - d % 2)] + [0.0] * (d % 2)
        v = a * a * (d - d % 2) / d
        row = jnp.asarray(signs, jnp.float32) * a
        y = float(jax.jit(first_of)(jax.random.key(0), row))
    return (a / y) ** 2 - v


def program_config(model):
    """The program's ModelConfig for ``model``: its registered architecture
    with the file's sizes.  Every architecture field the file states has to
    be the program's: a difference is an error naming both values, not an
    override."""
    from repro.configs.base import get_config

    cfg = get_config(model.program_arch)
    cfg = dataclasses.replace(
        cfg, n_layers=model.layers, d_model=model.d, n_heads=model.heads,
        n_kv_heads=model.kv_heads, head_dim=model.head_dim, d_ff=model.ff,
        vocab_size=model.vocab)
    fields = {k: (key, v, want, getattr(cfg, k))
              for k, (key, v, want) in stated(model).items()}
    fields["norm eps"] = ("norm_eps", model.norm_eps, model.norm_eps,
                          program_norm_eps(cfg))
    for k, (key, v, want, have) in fields.items():
        # the eps is measured: equal within its rounding
        same = (math.isclose(have, want, rel_tol=1e-3) if k == "norm eps"
                else have == want)
        if not same:
            raise ValueError(f"{model.program_arch}: the program has {k}="
                             f"{have!r}, the configuration states {key}="
                             f"{v!r}")
    return cfg


class Program:
    """The system under test: the compiled step, its state and its feed."""

    def __init__(self, cell: spec.Cell, seed: int, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding

        _import_program()
        from repro.configs.base import ShapeConfig
        from repro.launch.mesh import make_test_mesh
        from repro.models.model_zoo import build_model
        from repro.optim import adamw
        from repro.parallel import specs as SP
        from repro.parallel.runner import (batch_struct, make_train_step,
                                           resolve_cell)

        m, tr = cell.model, cell.traffic
        self.model = m
        data, width = cell.mesh["data"], cell.mesh["model"]
        self.data = data
        self.mesh = make_test_mesh(data, width, devices=list(devices))
        mdef = build_model(program_config(m))
        overrides = dict(cell.plan)
        if overrides.get("pp", 1) > 1:
            overrides["dp"] = data // overrides["pp"]
        self.cell = resolve_cell(
            mdef, ShapeConfig(cell.name, tr["seq_len"], tr["batch"], "train"),
            data_size=data, model_size=width, overrides=overrides)
        plan = self.cell.plan
        self.pp = plan.pp
        if jnp.dtype(self.cell.dtype).name != m.dtype:
            raise ValueError(f"program runs {self.cell.dtype}, the "
                             f"configuration states {m.dtype}")
        struct, specs = SP.param_struct_and_specs(mdef, plan.pp, data,
                                                  self.cell.dtype)
        pshard = jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs)
        self.slots = weights.slots(struct)
        self.table = table = references.of(m).leaves(m)
        init = jax.jit(lambda key: weights.to_program(
            weights.canonical(table, key), table, struct, pp=plan.pp,
            data=data), out_shardings=pshard)
        self.key = weights.seed_key(seed)
        params = init(self.key)
        opt_state = adamw.init_state(
            params, jnp.float32 if plan.opt_dtype == "float32"
            else jnp.bfloat16, offload_moments=plan.offload_moments,
            moments_dtype=plan.moments_dtype)
        o = cell.optimizer
        step = jax.jit(make_train_step(
            self.cell, self.mesh, lr_kwargs=dict(
                peak=o["lr_peak"], warmup=o["warmup_steps"],
                total=o["total_steps"])), donate_argnums=(0, 1))
        _, bspecs = batch_struct(self.cell)
        self.bshard = {k: NamedSharding(self.mesh, s)
                       for k, s in bspecs.items()}
        # compile before the first step, as the trainer does; the state is
        # then placed where the step returns it, so every step -- the first
        # included -- runs the same executable
        batch0 = self.stage(*TokenFeed(tr, m.vocab, seed).batch(0))
        compiled = step.lower(params, opt_state, batch0).compile()
        out_shard = tuple(compiled.output_shardings[:2])
        if jax.tree.map(lambda a: a.sharding, (params, opt_state)) \
                != out_shard:
            params, opt_state = jax.device_put((params, opt_state), out_shard)
            compiled = step.lower(params, opt_state, batch0).compile()
        self.hlo_text = compiled.as_text()
        self.step = step
        self.params, self.opt_state = params, opt_state
        self._norms = jax.jit(self._norms_of)

    def stage(self, tokens, labels):
        import jax

        from repro.data.pipeline import shard_batch

        batch = shard_batch(tokens, labels, pods=1, data_size=self.data,
                            pp=self.pp)
        return {k: jax.device_put(v, self.bshard[k]) for k, v in batch.items()}

    def _leaves(self, tree):
        for name, lf in self.table.items():
            yield name, lf, weights._get(tree[weights.part(lf)], lf.path)

    def _norms_of(self, tree, key=None):
        """Norm of every weight of a program tree, in canonical layout;
        with ``key``, of its difference from the weights drawn from it.
        Leaf by leaf, each waiting for the one before, so that no more than
        one leaf of a host-resident tree is on the device at a time."""
        import jax
        import jax.numpy as jnp

        out, prev = {}, None
        for name, lf, leaf in self._leaves(tree):
            if prev is not None:
                leaf, _ = jax.lax.optimization_barrier((leaf, prev))
            x = weights.program_rows(jax.device_put(leaf,
                                                    jax.memory.Space.Device),
                                     lf, self.slots)
            x = x.astype(jnp.float32)
            if key is not None:
                x = x - weights.leaf(self.table, key, name).astype(
                    jnp.float32)
            out[name] = prev = weights.norms(x, lf)
        return out

    def _named(self, norms) -> dict:
        out = {}
        for name, v in norms.items():
            out.update(weights.named(name, v, self.table[name].per_layer))
        return out

    def moment_norms(self, b1: float) -> dict:
        """The first gradient as the optimizer got it, read back from the
        first moment after one step (m = (1 - b1) * g)."""
        return {k: v / (1 - b1) for k, v in
                self._named(self._norms(self.opt_state.m)).items()}

    def change_norms(self) -> dict:
        """|params now - params at the seed| per leaf."""
        return self._named(self._norms(self.params, self.key))

    def free(self):
        self.params = self.opt_state = self.step = None
        gc.collect()


def reference_readings(cell: spec.Cell, seed: int, steps: int,
                       precision: str = "float32") -> dict:
    """The plain reference's losses, first clipped gradient norms and the
    parameters' change after ``steps`` steps, from the same seed and
    batches as the program's."""
    import jax
    import jax.numpy as jnp

    m = cell.model
    mod = references.of(m)
    table = mod.leaves(m)
    model = mod.Reference(m, precision=precision)
    feed = TokenFeed(cell.traffic, m.vocab, seed)
    key = weights.seed_key(seed)
    make = jax.jit(lambda k: weights.canonical(table, k))
    params = weights.by_layer(make(key), table)
    decayed = {name for name, lf in table.items() if lf.decay}
    opt = ref_adamw.AdamW(cell.optimizer, jnp.bfloat16,
                          lambda path: path[-1].key in decayed)
    sq = jax.jit(lambda g: jnp.sqrt(jnp.sum(jnp.square(g))))
    losses, grad = [], {}
    for s in range(steps):
        loss, grads = model.loss_and_grads(params, *feed.batch(s))
        losses.append(loss)
        norms = _leaf_norms(grads, table, sq)
        gnorm = math.sqrt(sum(v * v for v in norms.values()))
        if s == 0:
            clip = min(1.0, cell.optimizer["clip_norm"] / max(gnorm, 1e-12))
            grad = {k: v * clip for k, v in norms.items()}
        params = opt.update(params, grads, s, gnorm, keep=s < steps - 1)
        del grads
    return {"loss": losses, "grad": grad,
            "change": reference_change(params, table, key)}


def reference_change(params, table, key) -> dict:
    """|params - params drawn from ``key``| per leaf, of a tree in the
    reference's layout: one leaf on the device at a time, drawn anew in
    float32 beside the held one."""
    import jax
    import jax.numpy as jnp

    change = {}
    for name, lf in table.items():
        d = jax.jit(lambda p, k, n=name, lf=lf: weights.norms(
            (jnp.stack(p) if lf.per_layer else p).astype(jnp.float32)
            - weights.leaf(table, k, n).astype(jnp.float32), lf))
        cur = ([lay[name] for lay in params["layers"]]
               if lf.per_layer else params[name])
        change.update(weights.named(name, d(cur, key), lf.per_layer))
    return change


def _leaf_norms(grads, table, sq) -> dict:
    import jax.numpy as jnp

    out = {}

    def add(label, name, g):
        parts = table[name].parts
        if not parts:
            out[label] = float(sq(g))
            return
        for part, mask in parts.items():
            out[f"{label}[{part}]"] = float(sq(jnp.where(mask, g, 0)))

    for l, layer in enumerate(grads["layers"]):
        for k, g in layer.items():
            add(f"L{l}.{k}", k, g)
    for k, lf in table.items():
        if not lf.per_layer:
            add(k, k, grads[k])
    return out


@dataclasses.dataclass
class Readings:
    """What a per-layer metric's reader gets."""

    cell: spec.Cell
    peak: dict
    chips: int
    steps: int              # whole steps in the traced window
    window_s: float         # host clock over those steps
    compiles_in_window: int
    trace: dict             # tracefile.load form
    hlo: dict               # tracefile.hlo_ops of the step
    scopes: dict            # scopes.hlo_scopes of the step: its op_names


def read_metric(name: str, r: Readings):
    mod = importlib.import_module(f"{__package__}.metrics.{name}")
    return mod.read(r)


def hbm_peak(stats: dict) -> int:
    """A chip's peak of HBM held, from its allocator's counters: buffers
    (``peak_bytes_in_use``) plus the region reserved for the programs'
    temporaries (``peak_bytes_reserved``).  On a v5e the two are disjoint,
    and the step's activations and gradients live only in the second:
    with the sppo-gpt-7b step loaded, 2.51e9 bytes in use beside 12.32e9
    reserved."""
    return (stats.get("peak_bytes_in_use", 0)
            + stats.get("peak_bytes_reserved", 0))


def peak_of(kind: str) -> dict:
    with open(os.path.join(spec.HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if kind not in peaks:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return peaks[kind]


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float, require_tpu: bool = True, wrap_step=None,
        keep_trace: str | None = None, log=print,
        detail: dict | None = None) -> dict:
    """One run; returns the result object (the line run.py prints).
    ``keep_trace`` is a directory to leave the profiler's trace in;
    ``detail``, where given, receives both sides' readings in full."""
    import jax

    devices = find_chips(cell.chips, require_tpu=require_tpu)
    use_compile_cache()
    kind = devices[0].device_kind
    peak = peak_of(kind) if require_tpu else None
    counter = CompileCounter()
    feed = TokenFeed(cell.traffic, cell.model.vocab, seed)
    prog = Program(cell, seed, devices)
    log(f"compiled: {counter.programs} programs, {counter.cache_hits} from "
        f"the cache; plan {prog.cell.plan}; chunks {prog.cell.sched.lengths} "
        f"alphas {prog.cell.alphas}")
    call = prog.step if wrap_step is None else wrap_step(prog.step)

    def one_step(i):
        with jax.profiler.StepTraceAnnotation("bench.step", step_num=i):
            with jax.profiler.TraceAnnotation("bench.stage_batch"):
                batch = prog.stage(*feed.batch(i))
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                prog.params, prog.opt_state, met = call(
                    prog.params, prog.opt_state, batch)
            with jax.profiler.TraceAnnotation("bench.block"):
                jax.block_until_ready((prog.params, prog.opt_state, met))
        return float(met["loss"])

    # the first steps, through the window's own call and feed: warm-up, and
    # what the check compares
    losses, t_check = [], 0.0
    for i in range(cell.checked_steps):
        t = time.perf_counter()
        losses.append(one_step(i))
        log(f"step {i}: loss {losses[-1]!r} {time.perf_counter() - t:.3f} s")
        if i == 0:
            t = time.perf_counter()
            grad = prog.moment_norms(cell.optimizer["b1"])
            t_check += time.perf_counter() - t
    t = time.perf_counter()
    change = prog.change_norms()
    t_check += time.perf_counter() - t
    setup_s = time.perf_counter() - t_start - t_check
    log(f"set-up {setup_s:.3f} s (check readings {t_check:.3f} s apart)")

    tmp = (keep_trace or tempfile.mkdtemp(prefix="bench_trace_")) \
        if trace else None
    if trace:
        jax.profiler.start_trace(tmp)
    n0 = counter.programs
    step_i = cell.checked_steps
    t0 = time.perf_counter()
    while True:
        losses.append(one_step(step_i))
        step_i += 1
        t1 = time.perf_counter()
        if t1 - t0 >= seconds:
            break
    window_s = t1 - t0
    compiles = counter.programs - n0
    if trace:
        jax.profiler.stop_trace()
    steps = step_i - cell.checked_steps
    log(f"window: {steps} steps in {window_s:.3f} s, {compiles} compiles")
    peak_bytes = max(hbm_peak(d.memory_stats() or {}) for d in devices)
    prog_hlo = prog.hlo_text
    hlo = tracefile.hlo_ops(prog_hlo)
    prog.free()

    chips = len(devices)
    device = {"platform": devices[0].platform, "kind": kind, "count": chips,
              "memory_peak_bytes": peak_bytes}
    units = spec.units()
    out = {}
    if trace:
        files = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        tr = tracefile.load(files[0])
        if keep_trace is None:
            shutil.rmtree(tmp)
        else:
            with gzip.open(os.path.join(tmp, "step.hlo.txt.gz"), "wt") as f:
                f.write(prog_hlo)
        r = Readings(cell=cell, peak=peak, chips=chips, steps=steps,
                     window_s=window_s, compiles_in_window=compiles,
                     trace=tr, hlo=hlo, scopes=scopes.hlo_scopes(prog_hlo))
        metrics = {}
        for name in cell.per_layer:
            v = read_metric(name, r)
            if v is None:
                log(f"{name}: not measured, nothing to read in the trace")
            else:
                metrics[name] = {"value": v, "unit": units[name]}
        if tr["devices"] and tr["window"]:
            t0_ns, t1_ns = tr["window"]
            busy = [tracefile.busy_ns(ev, t0_ns, t1_ns)
                    for ev in tr["devices"].values()]
            device["busy_s"] = sum(busy) / len(busy) / 1e9
            device["window_s"] = (t1_ns - t0_ns) / 1e9
        out["breakdown"] = {"device_ops": tracefile.top_ops(tr),
                            "idle_gaps": tracefile.idle_gaps(tr)}
    else:
        e2e = {"tokens_per_s_per_chip":
               steps * feed.tokens_per_step / window_s / chips,
               "peak_hbm_gib": peak_bytes / 2**30, "setup_s": setup_s}
        metrics = {k: {"value": e2e[k], "unit": units[k]}
                   for k in cell.end_to_end}

    # the reference runs last, on a chip the program has left
    t = time.perf_counter()
    ref = reference_readings(cell, seed, cell.checked_steps)
    log(f"reference: {time.perf_counter() - t:.3f} s, losses {ref['loss']}")
    prog_read = {"loss": losses[:cell.checked_steps], "grad": grad,
                 "change": change}
    checks, correct = check.compare(prog_read, ref, cell.limits)
    if detail is not None:
        detail.update(prog=prog_read, ref=ref)
    out = {"correct": correct, "attempted": step_i,
           "failed": sum(not math.isfinite(x) for x in losses), **out}
    out.update(metrics=metrics, device=device, checks=checks)
    return out


