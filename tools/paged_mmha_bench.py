#!/usr/bin/env python
"""Time the paged decode-attention kernel on the chip at the serving cells'
shapes, one `pages_per_block` after another: what `PAGES_PER_BLOCK` in
`paddle_tpu/ops/kernels/mmha_pallas.py` was chosen from.

    chiprun -- python tools/paged_mmha_bench.py [--blocks 4,8,16,32]

Mistral-7B widths as `perfbench/configs/mistral-7b-v0.3.json` serves them
(batch 32, 32 heads / 8 KV x 128, page 16, 16 layers, pool 2049 pages, tables
256 wide, bf16) under two batches: `steady` (12 live rows, some 8 000 live
positions, as `chat_steady` holds) and `saturated` (31 rows, some 17 000).
Each line is one JSON object: milliseconds of 16 layers of attention alone,
of 16 layers of KV write plus attention (pools donated), the bytes the live
context holds over that time as a share of the HBM peak, and the largest gap
to the composite over `gather_layer` on one layer. Fails without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LAYERS, PAGES, HKV, PS, D, HEADS, BATCH, MAX_PAGES = 16, 2049, 8, 16, 128, 32, 32, 256


def batch(kind, rng):
    """(tables [B, max_pages], pos [B]) of a decode step of `kind`."""
    live, total = (12, 8150) if kind == "steady" else (31, 17000)
    ctx = rng.lognormal(np.log(total / live), 0.7, live)
    ctx = np.clip(ctx * total / ctx.sum(), 50, 2500).astype(np.int64)
    tables = np.zeros((BATCH, MAX_PAGES), np.int32)
    pos = np.zeros(BATCH, np.int32)
    pages = rng.permutation(np.arange(1, PAGES))
    used = 0
    for r, n in zip(rng.permutation(BATCH)[:live], ctx):
        k = -(-int(n) // PS) + 1            # a headroom page, as the engine
        tables[r, :k] = pages[used:used + k]
        used += k
        pos[r] = n - 1
    return tables, pos


def timed(fn, *args, n):
    import jax
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="4,8,16,32")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--iters", type=int, default=50)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        sys.exit("paged_mmha_bench: no TPU; its numbers are device times")
    from paddle_tpu.ops.kernels import mmha_pallas as mp
    from paddle_tpu.serving import kv_cache as kc

    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        hbm_peak = json.load(f)[jax.devices()[0].device_kind][
            "hbm_bytes_per_s"]
    rng = np.random.default_rng(args.seed)
    key = jax.random.PRNGKey(args.seed)
    shape = (LAYERS, PAGES, HKV, PS, D)
    kp = jax.random.normal(key, shape, jnp.bfloat16)
    vp = jax.random.normal(jax.random.fold_in(key, 1), shape, jnp.bfloat16)
    q = jax.random.normal(jax.random.fold_in(key, 2),
                          (BATCH, 1, HEADS, D), jnp.bfloat16)
    new = jax.random.normal(jax.random.fold_in(key, 3),
                            (BATCH, HKV, D), jnp.bfloat16)

    for kind in ("steady", "saturated"):
        tables, pos = batch(kind, rng)
        tab, p = jnp.asarray(tables), jnp.asarray(pos)
        live = int((pos + 1)[tables[:, 0] != 0].sum())
        need = live * HKV * D * 2 * 2 * LAYERS          # K and V, bf16
        rows = tables[:, 0] != 0
        p = jnp.where(jnp.asarray(rows), p, -1)         # as paged_attention
        for ppb in [int(x) for x in args.blocks.split(",")]:

            @jax.jit
            def attend(q, kp, vp, tab, p):
                out = q
                for i in range(LAYERS):
                    out = out + mp.paged_mmha_decode(
                        q, kp, vp, jnp.int32(i), tab, p, pages_per_block=ppb)
                return out

            @jax.jit
            def one(q, kp, vp, tab, p):
                return mp.paged_mmha_decode(q, kp, vp, jnp.int32(3), tab, p,
                                            pages_per_block=ppb)

            def step(q, kp, vp, tab, p, new):
                at = jnp.maximum(p, 0)
                page = jnp.take_along_axis(tab, (at // PS)[:, None], 1)[:, 0]
                out = q
                for i in range(LAYERS):
                    kp = kc.write_token_rows(kp, i, page, at % PS, new)
                    vp = kc.write_token_rows(vp, i, page, at % PS, new)
                    out = out + mp.paged_mmha_decode(
                        q, kp, vp, jnp.int32(i), tab, p, pages_per_block=ppb)
                return out, kp, vp

            ms, _ = timed(attend, q, kp, vp, tab, p, n=args.iters)
            ref = kc.paged_attention(q, kp, vp, 3, tab, p, interpret=False)
            gap = float(jnp.max(jnp.abs(
                one(q, kp, vp, tab, p).astype(jnp.float32)
                - ref.astype(jnp.float32))[rows]))
            stepj = jax.jit(step, donate_argnums=(1, 2))
            out, kp, vp = stepj(q, kp, vp, tab, p, new)
            jax.block_until_ready(out)
            t0 = time.perf_counter()
            for _ in range(args.iters):
                out, kp, vp = stepj(q, kp, vp, tab, p, new)
            jax.block_until_ready(out)
            step_ms = (time.perf_counter() - t0) / args.iters * 1e3
            print(json.dumps({
                "batch": kind, "pages_per_block": ppb, "live_positions": live,
                "attention_16_layers_ms": ms,
                "write_and_attention_16_layers_ms": step_ms,
                "live_bytes_share_of_hbm_peak":
                    need / hbm_peak / (ms * 1e-3),
                "max_gap_to_composite": gap,
                "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
