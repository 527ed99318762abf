"""Flash-attention tile sweep on a TPU: per-kernel time against block shape.

Times the three Pallas kernels (forward, dq, dkv) of
``kernels/flash_attention.py`` at the two chunk shapes of the benchmark
cell ``gpt7b-16k-1chip`` (sppo-gpt-7b, 32 heads of 128, one sequence of
16384 tokens split 9728 + 6656): chunk 0 attends Tq 9728 against S 9728,
chunk 1 Tq 6656 against S 16384.  Each candidate ``(bq, bk)`` is passed
explicitly; ``auto`` is the shape-chosen default (``_tiles``).  Times are
host-clock medians over ``--reps`` calls that each end in
``block_until_ready``, so they include the fold transposes and dispatch
around each kernel: on a v5e the per-step line read 3% above the traced
kernel time at 128 x 128 and 19% above it at 512 x 512 / 512 x 1024.
Use it to rank tiles; take kernel time from a trace.

The per-step line weighs each kernel as the train step calls it on the
cell: 4 layers x (forward twice, forward and recompute; dq and dkv once)
per chunk.  Needs a TPU: exits 1 elsewhere.

  python benchmarks/bench_attn_tiles.py [--tiles 128x128,512x512,auto]
      [--reps 5] [--json attn_tiles.json]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as fa

H, HD, LAYERS = 32, 128, 4
CHUNKS = ((9728, 9728, 0), (6656, 16384, 9728))   # Tq, S, first q position
DEFAULT_TILES = ("128x128,256x256,256x512,512x256,512x512,256x1024,"
                 "512x1024,1024x512,auto")


def _inputs(Tq, S, q_off, key):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (1, Tq, H, HD), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, S, H, HD), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, S, H, HD), jnp.bfloat16)
    do = jax.random.normal(ks[3], (1, Tq, H, HD), jnp.float32)
    dl = jax.random.normal(ks[4], (1, Tq, H), jnp.float32)
    q_pos = (jnp.arange(Tq, dtype=jnp.int32) + q_off)[None]
    kv_pos = jnp.arange(S, dtype=jnp.int32)
    q_start = jnp.zeros((1, Tq), jnp.int32)
    return q, k, v, q_pos, kv_pos, q_start, do, dl


def _kernels(bq, bk):
    scale = HD ** -0.5

    def fwd(q, k, v, q_pos, kv_pos, q_start):
        return fa._fwd_impl(q, k, v, q_pos, kv_pos, q_start, True, scale,
                            bq, bk, False)

    def bwd(q, k, v, q_pos, kv_pos, q_start, do, m, dl):
        return fa._bwd_impl(q, k, v, q_pos, kv_pos, q_start, do, m, dl, True,
                            scale, bq, bk, False)

    return (jax.jit(fwd),
            jax.jit(lambda *a: bwd(*a)[0]),      # dq alone: dkv is dead code
            jax.jit(lambda *a: bwd(*a)[1:]))     # dkv alone


def _ms(fn, args, reps):
    jax.block_until_ready(fn(*args))            # compile and warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiles", default=DEFAULT_TILES)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"bench_attn_tiles: needs a TPU, found {dev.platform}",
              file=sys.stderr)
        sys.exit(1)
    print(f"device: {dev.device_kind} x{jax.device_count()}", flush=True)
    rows = []
    for tile in args.tiles.split(","):
        bq, bk = ((None, None) if tile == "auto"
                  else tuple(int(t) for t in tile.split("x")))
        per_step = 0.0
        for Tq, S, q_off in CHUNKS:
            q, k, v, q_pos, kv_pos, q_start, do, dl = _inputs(
                Tq, S, q_off, jax.random.PRNGKey(Tq))
            chosen = fa._tiles(1, Tq, S, HD, HD) if tile == "auto" else (bq, bk)
            fwd, dq, dkv = _kernels(bq, bk)
            _, m, _ = fwd(q, k, v, q_pos, kv_pos, q_start)
            pos = (q, k, v, q_pos, kv_pos, q_start)
            ms = {"fwd": _ms(fwd, pos, args.reps),
                  "dq": _ms(dq, pos + (do, m, dl), args.reps),
                  "dkv": _ms(dkv, pos + (do, m, dl), args.reps)}
            per_step += LAYERS * (2 * ms["fwd"] + ms["dq"] + ms["dkv"])
            row = dict(tile=tile, bq=chosen[0], bk=chosen[1], Tq=Tq, S=S,
                       **{f"{n}_ms": round(t, 3) for n, t in ms.items()})
            rows.append(row)
            print(json.dumps(row), flush=True)
        print(f"{tile}: kernels per step {per_step:.1f} ms", flush=True)
        rows.append(dict(tile=tile, kernel_ms_per_step=round(per_step, 1)))
    if args.json:
        os.makedirs(os.path.dirname(args.json) or ".", exist_ok=True)
        with open(args.json, "w") as f:
            json.dump(dict(device=dev.device_kind, rows=rows), f, indent=1)


if __name__ == "__main__":
    main()
