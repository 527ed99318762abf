"""CI memory-gate: measured-vs-predicted peak memory honesty check.

  PYTHONPATH=src python -m benchmarks.memgate \
      --budgets benchmarks/budgets.json --out memledger/ [--update]

For every gate in budgets.json this builds the cell (offload on, pp>1
emulated mesh), executes one real train-grad step through
runtime/memledger.measure, and enforces two contracts:

  1. honesty gate — measured peak bytes may not exceed the simulator's
     prediction (costmodel.chunk_act_bytes -> simulate.spmd_tick_peak over
     the runner's feed events) by more than ``max_ratio`` (1.10: the §5.2
     recurrence must describe reality);
  2. budget diff — the measured peak must stay within ``band`` of the
     value recorded in budgets.json, so any intentional change to the
     memory behavior shows up as a reviewed diff to that file
     (regenerate with --update).

Gates with ``"offload_moments": true`` additionally measure the executed
optimizer-state offload (DESIGN.md §11): one real AdamW update over the
measured grads, the ledger's moments channel (opt_m@/opt_v@ jaxpr walk +
update-phase probes + the one-H2D-per-leaf copy count), the *combined*
activations+moments device peak against ``predicted_combined_peak``, and a
strict-reduction check — moment offload must measurably lower the combined
device peak vs the same cell with ``offload_moments=False``.

Plain gates run the prefetch ablation (DESIGN.md §12): the same cell is
re-measured with ``prefetch="sync"`` and the gate fails unless
``prefetch="ahead"`` leaves the measured §5.2 peak unraised AND strictly
reduces the priced exposed-H2D (``MemLedger.price_h2d`` over the measured
bytes and backward windows).

Gates with ``"offload_dtype"`` (fp8/int8) run the compression ablation
instead (DESIGN.md §14): the same cell — same alphas, so the row split is
held fixed — is re-measured with ``offload_dtype="none"`` and the gate
fails unless the codec strictly cuts the measured host/wire off-bytes AND
the priced sync-mode exposed-H2D, while leaving the raw device drain
identical, and the one-step loss/grad drift of the compressed step against
the raw step stays within the gate's pinned tolerances.

The per-tick ledger CSVs (including the moments and h2d_stall_s columns,
plus the sync-mode ablation ledgers) land in --out and are uploaded as a
CI artifact.
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import json
import sys

import jax.numpy as jnp

from repro.configs.base import ShapeConfig, get_config
from repro.models.model_zoo import build_model
from repro.parallel import runner
from repro.runtime import memledger as ml

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


def run_gate(gate: dict):
    """Returns (measured_peak, predicted_peak, ledger, cell).

    Plain gates compare the §5.2 activation peak; opt-state gates
    (``offload_moments``) compare the combined activations+moments device
    peak and measure the moments channel from a real AdamW update."""
    import dataclasses

    cfg = get_config(gate["arch"])
    if gate.get("reduced", True):
        cfg = cfg.reduced()
    mdef = build_model(cfg)
    opt_gate = bool(gate.get("offload_moments", False))
    shape = ShapeConfig(gate["name"], gate["seq"], gate["batch"], "train")
    doc_lens = None
    if gate.get("doc_lens"):
        # packed variable-length gate cell (DESIGN.md §13): the seeded
        # skewed histogram resolves to document lengths, the measured step
        # runs the packed batch generated from them
        from repro.data import pipeline as dpipe

        doc_lens = [int(x) for x in
                    dpipe.sample_doc_lengths(**gate["doc_lens"])]
    cell = runner.resolve_cell(
        mdef, shape, data_size=gate["data_size"],
        model_size=gate["model_size"],
        overrides=dict(pp=gate["pp"], dp=gate["data_size"] // gate["pp"],
                       n_chunks=gate["n_chunks"], grad_accum=1,
                       partition="length", offload=True,
                       msp=gate.get("msp", False),
                       offload_moments=opt_gate,
                       opt_dtype=gate.get("opt_dtype", "float32"),
                       offload_dtype=gate.get("offload_dtype", "none"),
                       moments_dtype=gate.get("moments_dtype", "none")),
        doc_lens=doc_lens)
    cell = dataclasses.replace(cell, dtype=DTYPES[gate.get("dtype",
                                                           "bfloat16")])
    led = ml.measure(cell, data_size=gate["data_size"],
                     model_size=gate["model_size"], opt=opt_gate)
    if opt_gate:
        measured = led.combined_peak_bytes
        predicted = ml.predicted_combined_peak(
            cell, data_size=gate["data_size"])
    else:
        measured, predicted = led.peak_bytes, ml.predicted_spmd_peak(cell)
    return measured, predicted, led, cell


def prefetch_ablation_check(gate: dict, cell, led, out_dir: str) -> list:
    """The prefetch='ahead' seam must *pay off* against the autodiff
    placement (DESIGN.md §12): on the same cell with prefetch='sync' the
    measured §5.2 peak may not be lower (ahead never raises the peak — the
    one-slot staging buffer keeps the residual bytes identical), and the
    priced exposed-H2D over the measured bytes/windows must be strictly
    smaller under 'ahead'.  The sync-mode per-tick ledger (with the
    h2d_stall_s column) lands next to the main CSV in the artifact."""
    import dataclasses

    failures = []
    cell_sync = dataclasses.replace(
        cell, plan=dataclasses.replace(cell.plan, prefetch="sync"))
    led_sync = ml.measure(cell_sync, data_size=gate["data_size"],
                          model_size=gate["model_size"], baseline=False)
    led_sync.to_csv(os.path.join(out_dir,
                                 f"memledger-{gate['name']}-syncpf.csv"))
    if led.peak_bytes > led_sync.peak_bytes:
        failures.append(
            f"{gate['name']}: prefetch='ahead' raised the measured peak "
            f"({led.peak_bytes} B vs {led_sync.peak_bytes} B sync) — the "
            "one-slot staging invariant is broken")
    ahead_exp = led.h2d_exposed_s or 0.0
    sync_exp = led_sync.h2d_exposed_s or 0.0
    if sync_exp > 0.0:
        if not ahead_exp < sync_exp:
            failures.append(
                f"{gate['name']}: prefetch='ahead' exposed H2D "
                f"({ahead_exp:.3e}s) is not strictly below 'sync' "
                f"({sync_exp:.3e}s) — the one-chunk-ahead reload is not "
                "hiding under the next backward")
    elif any(r.off_bytes for r in led_sync.ticks):
        failures.append(
            f"{gate['name']}: sync-mode exposure priced 0 despite "
            "deployed off-rows — the h2d channel is broken")
    else:
        # a gate cell whose alphas quantize to zero rows has nothing to
        # ablate; the strict comparison would be vacuously unsatisfiable
        print(f"{gate['name']:32s} prefetch: no off-rows deployed — "
              "ablation vacuous (check the cell's alphas)")
    print(f"{gate['name']:32s} prefetch: exposed h2d "
          f"{ahead_exp:.3e}s ahead vs {sync_exp:.3e}s sync, peak "
          f"{led.peak_bytes} B vs {led_sync.peak_bytes} B")
    return failures


def moment_reduction_check(gate: dict, cell, led) -> list:
    """The executed path must *pay off*: the same cell with
    offload_moments=False has to show a strictly larger measured combined
    device peak, and the offloaded update must honor the
    one-H2D-per-moment-leaf contract."""
    import dataclasses

    failures = []
    cell_off = dataclasses.replace(
        cell, plan=dataclasses.replace(cell.plan, offload_moments=False))
    led_off = ml.measure(cell_off, data_size=gate["data_size"],
                         model_size=gate["model_size"], opt=True,
                         baseline=False)
    if not led.combined_peak_bytes < led_off.combined_peak_bytes:
        failures.append(
            f"{gate['name']}: moment offload did not reduce the measured "
            f"combined device peak ({led.combined_peak_bytes} B offloaded "
            f"vs {led_off.combined_peak_bytes} B resident)")
    mom = led.moments
    if mom is None:
        failures.append(f"{gate['name']}: no moments channel was measured")
    elif mom.h2d_count != 2 * mom.n_leaves:
        failures.append(
            f"{gate['name']}: explicit update staged {mom.h2d_count} H2D "
            f"copies for {mom.n_leaves} moment-tree leaves — the "
            "one-H2D-per-moment-leaf contract is broken")
    print(f"{gate['name']:32s} moments: offloaded "
          f"{led.moments.host_bytes if led.moments else 0:>12d} B host, "
          f"combined {led.combined_peak_bytes} B vs resident "
          f"{led_off.combined_peak_bytes} B")
    return failures


def quant_reduction_check(gate: dict, cell, led, out_dir: str) -> list:
    """The compressed channel must *pay off* honestly (DESIGN.md §14): the
    same cell with ``offload_dtype="none"`` — the plan replace preserves
    ``cell.alphas``, so both runs deploy the *identical* row split and the
    comparison isolates the codec's byte effect — has to show strictly
    larger measured host/wire off-bytes and strictly larger priced
    sync-mode exposed-H2D (sync prices every reload in full, making the
    comparison independent of the wall-clock backward windows), while the
    raw device bytes the §5.2 recurrence drains stay identical.  On top of
    the byte contract, the compressed step must still train: one real step
    of each cell from the same init/batch, with the loss drift and the
    relative grad-L2 drift within the gate's pinned tolerances."""
    import dataclasses

    import jax
    import numpy as np

    failures = []
    name, codec = gate["name"], cell.plan.offload_dtype
    cell_raw = dataclasses.replace(
        cell, plan=dataclasses.replace(cell.plan, offload_dtype="none"))
    led_raw = ml.measure(cell_raw, data_size=gate["data_size"],
                         model_size=gate["model_size"], baseline=False)
    led_raw.to_csv(os.path.join(out_dir, f"memledger-{name}-rawoff.csv"))
    comp_wire = led.off_wire_bytes_total
    raw_wire = led_raw.off_wire_bytes_total
    if not comp_wire < raw_wire:
        failures.append(
            f"{name}: codec {codec} did not cut the measured host off-bytes"
            f" ({comp_wire} B compressed vs {raw_wire} B raw)")
    if led.off_bytes_total != led_raw.off_bytes_total:
        failures.append(
            f"{name}: raw device drain diverged under compression "
            f"({led.off_bytes_total} B vs {led_raw.off_bytes_total} B) — "
            "the recurrence subject must be codec-independent")
    if comp_wire and not led.scale_bytes_total > 0:
        failures.append(
            f"{name}: compressed rows deployed but no act_scale bytes were "
            "traced — the per-row scales are not riding the keep set")
    from repro.core import costmodel as _cm

    bw = _cm.V5E.d2h_bw
    comp_exp = led.price_h2d(bw=bw, prefetch="sync")
    raw_exp = led_raw.price_h2d(bw=bw, prefetch="sync")
    if raw_exp > 0.0 and not comp_exp < raw_exp:
        failures.append(
            f"{name}: codec {codec} did not cut the priced sync exposed-H2D"
            f" ({comp_exp:.3e}s vs {raw_exp:.3e}s raw)")
    # one-step numerics drift against the raw-residency step
    mk = dict(data_size=gate["data_size"], model_size=gate["model_size"])
    fn_c, args_c = ml.build_step(cell, with_grad=True, **mk)
    fn_r, args_r = ml.build_step(cell_raw, with_grad=True, **mk)
    loss_c, grads_c = jax.jit(fn_c)(*args_c)
    loss_r, grads_r = jax.jit(fn_r)(*args_r)
    loss_drift = abs(float(loss_c) - float(loss_r)) / max(
        abs(float(loss_r)), 1e-9)
    flat_c = np.concatenate([np.asarray(l, np.float64).ravel()
                             for l in jax.tree_util.tree_leaves(grads_c)])
    flat_r = np.concatenate([np.asarray(l, np.float64).ravel()
                             for l in jax.tree_util.tree_leaves(grads_r)])
    gnorm = float(np.linalg.norm(flat_r))
    grad_drift = float(np.linalg.norm(flat_c - flat_r)) / max(gnorm, 1e-12)
    loss_tol = gate.get("loss_drift_tol", 0.02)
    grad_tol = gate.get("grad_drift_tol", 0.15)
    if loss_drift > loss_tol:
        failures.append(
            f"{name}: codec {codec} loss drift {loss_drift:.3e} exceeds "
            f"the pinned tolerance {loss_tol:.0e}")
    if grad_drift > grad_tol:
        failures.append(
            f"{name}: codec {codec} grad drift {grad_drift:.3e} exceeds "
            f"the pinned tolerance {grad_tol:.0e}")
    print(f"{name:32s} quant: wire {comp_wire} B vs {raw_wire} B raw, "
          f"scales {led.scale_bytes_total} B, sync h2d {comp_exp:.3e}s vs "
          f"{raw_exp:.3e}s, drift loss {loss_drift:.2e} grad "
          f"{grad_drift:.2e}")
    return failures


def run_serve_gate(gate: dict, out_dir: str, update: bool) -> list:
    """Type-0 honesty gate (DESIGN.md §16): serve a seeded trace through
    the continuous-batching engine, measure the paged KV pool's real
    per-rank device bytes, and hold them to the cost model's closed form
    (``costmodel.kv_pool_bytes``) within ``max_ratio`` — plus the budget
    band against the value pinned in budgets.json.  The pool ledger CSV
    (kv_pool_* summary rows) lands in the artifact next to the train
    ledgers."""
    import numpy as np

    from repro.launch import serve as serve_mod
    from repro.launch.mesh import make_test_mesh

    name = gate["name"]
    mesh = make_test_mesh(gate["data_size"], gate["model_size"])
    eng = serve_mod.ServeEngine(
        gate["arch"], mesh, s_bucket=gate["s_bucket"],
        slots=gate["slots"], max_new=gate["max_new"],
        block_tokens=gate["block_tokens"],
        reduced=gate.get("reduced", True))
    rng = np.random.default_rng(gate.get("seed", 0))
    reqs = []
    for i in range(gate.get("n_requests", 5)):
        plen = int(rng.integers(4, gate["s_bucket"] + 1))
        reqs.append(serve_mod.Request(
            rid=i, prompt=rng.integers(
                2, eng.cfg.vocab_size, size=plen).astype(np.int32),
            max_new=int(rng.integers(1, gate["max_new"] + 1)),
            arrival=int(rng.integers(0, 4))))
    _, stats = eng.run(reqs, mode="continuous")

    measured = stats.pool_bytes
    predicted = eng.predicted_pool_bytes()
    led = ml.MemLedger(pool=ml.PoolChannel(
        n_blocks=eng.geo.n_blocks, block_tokens=eng.geo.block_tokens,
        n_layers=eng.mdef.slots_per_stage(1), measured_bytes=measured,
        predicted_bytes=predicted, peak_blocks=max(stats.peak_blocks),
        total_blocks=sum(stats.total_blocks)))
    led.to_csv(os.path.join(out_dir, f"memledger-{name}.csv"))
    ratio = measured / max(predicted, 1)
    print(f"{name:32s} pool     {measured:>12d} B  "
          f"predicted {predicted:>14.0f} B  ratio {ratio:.4f}  "
          f"{stats.steps} steps / {stats.waves} waves, blocks peak "
          f"{max(stats.peak_blocks)} of {eng.geo.n_blocks}")
    failures = []
    if ratio > gate["max_ratio"]:
        failures.append(
            f"{name}: measured pool {measured} B exceeds "
            f"{gate['max_ratio']:.2f}x the cost model's predicted "
            f"{predicted:.0f} B (ratio {ratio:.4f}) — kv_pool_bytes no "
            "longer describes the device arrays")
    if update:
        gate["measured_pool_bytes"] = int(measured)
        gate["predicted_pool_bytes"] = int(predicted)
    else:
        want = gate.get("measured_pool_bytes")
        band = gate.get("band", 0.02)
        if want and abs(measured - want) > band * want:
            failures.append(
                f"{name}: measured pool {measured} B deviates more than "
                f"{band:.0%} from the budgeted {want} B — if intentional, "
                "regenerate with `python -m benchmarks.memgate --update`")
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--budgets", default="benchmarks/budgets.json")
    ap.add_argument("--out", default="memledger")
    ap.add_argument("--update", action="store_true",
                    help="rewrite budgets.json with the measured numbers")
    args = ap.parse_args(argv)

    with open(args.budgets) as f:
        budgets = json.load(f)
    os.makedirs(args.out, exist_ok=True)

    failures = []
    for gate in budgets["gates"]:
        name = gate["name"]
        if gate.get("kind") == "serve":
            failures.extend(run_serve_gate(gate, args.out, args.update))
            continue
        measured, predicted, led, cell = run_gate(gate)
        led.to_csv(os.path.join(args.out, f"memledger-{name}.csv"))
        ratio = measured / max(predicted, 1)
        exposed = led.exposed_transfer_s
        print(f"{name:32s} measured {measured:>12d} B  "
              f"predicted {predicted:>14.0f} B  ratio {ratio:.4f}  "
              f"step {led.step_time_s:.3f}s  exposed "
              f"{0.0 if exposed is None else exposed:.3f}s")
        if not led.runtime_coverage_ok():
            failures.append(f"{name}: runtime probes missed ticks or the "
                            "update phase (the step did not fully execute)")
        if gate.get("offload_moments"):
            failures.extend(moment_reduction_check(gate, cell, led))
        elif gate.get("offload_dtype", "none") != "none":
            # compression ablation on the compressed-residency cells (§14)
            failures.extend(quant_reduction_check(gate, cell, led,
                                                  args.out))
        else:
            # prefetch ablation on the plain activation cells (§12)
            failures.extend(prefetch_ablation_check(gate, cell, led,
                                                    args.out))
        if ratio > gate["max_ratio"]:
            failures.append(
                f"{name}: measured peak {measured} B exceeds "
                f"{gate['max_ratio']:.2f}x the simulator's predicted "
                f"{predicted:.0f} B (ratio {ratio:.4f}) — the §5.2 "
                "recurrence no longer describes the executed program")
        if args.update:
            gate["measured_peak_bytes"] = int(measured)
            gate["predicted_peak_bytes"] = int(predicted)
        else:
            want = gate.get("measured_peak_bytes")
            band = gate.get("band", 0.02)
            if want and abs(measured - want) > band * want:
                failures.append(
                    f"{name}: measured peak {measured} B deviates more "
                    f"than {band:.0%} from the budgeted {want} B — if "
                    "intentional, regenerate with "
                    "`python -m benchmarks.memgate --update`")

    if args.update:
        with open(args.budgets, "w") as f:
            json.dump(budgets, f, indent=2)
            f.write("\n")
        print(f"updated {args.budgets}")
    if failures:
        print("\nMEMORY GATE FAILED:", file=sys.stderr)
        for msg in failures:
            print(f"  - {msg}", file=sys.stderr)
        return 1
    print("memory gate OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
