#!/usr/bin/env python
"""Time causal flash attention, forward and forward plus backward, on the
chip at the shapes the benchmark's cells run, for three implementations:
this package's Pallas kernels (`paddle_tpu/ops/kernels/flash_attention_pallas.py`)
at each block shape tried, the XLA composite (`_reference_attention` and its
vjp), and the Pallas kernel that ships with jax
(`jax.experimental.pallas.ops.tpu.flash_attention`) at the same block shapes.

    python tools/flash_attention_bench.py [--blocks 128x128,256x256]

Shapes, bf16: `gpt2-medium` is the training cell's [4, 1024, 16, 64];
`mistral-prefill` a Mistral-7B prefill of 2048 tokens, 32 heads / 8 KV x
128 (jax's kernel takes no GQA, so it is given the KV heads repeated, and
each implementation starts from and ends in the [B, S, H, D] layout). Each
line is one JSON object: milliseconds of one call, forward alone and forward
plus backward; the roofline share of the latter, counted as
`perfbench/work/flash_attention_train.py` counts it (six products, halved by
the causal mask, against the bf16 peak of `perfbench/peaks.json`); and the
largest gap of the output and of dQ to the float32 composite, over the
largest magnitude of each. Fails without a TPU.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SHAPES = {"gpt2-medium": (4, 1024, 16, 16, 64),
          "mistral-prefill": (1, 2048, 32, 8, 128)}


def timed(fn, *args, n):
    import jax
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e3


def implementations(blocks):
    """name -> (forward(q, k, v), forward_and_backward(q, k, v, g))."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu import flash_attention as lib
    from paddle_tpu.ops.kernels import flash_attention as fa
    from paddle_tpu.ops.kernels import flash_attention_pallas as fap

    impls = {}
    for bq, bk in blocks:
        def fwd(q, k, v, bq=bq, bk=bk):
            return fap.flash_attention_forward(q, k, v, causal=True,
                                               block_q=bq, block_k=bk)

        def fwd_bwd(q, k, v, g, bq=bq, bk=bk):
            out, lse = fap.flash_attention_forward_lse(
                q, k, v, causal=True, block_q=bq, block_k=bk)
            return (out, *fap.flash_attention_backward(
                q, k, v, out, lse, g, causal=True, block_q=bq, block_k=bk))

        impls[f"pallas_{bq}x{bk}"] = (fwd, fwd_bwd)

    def composite(q, k, v):
        return fa._reference_attention(q, k, v, True)

    def composite_bwd(q, k, v, g):
        out, vjp = jax.vjp(composite, q, k, v)
        return (out, *vjp(g))

    impls["xla_composite"] = (composite, composite_bwd)

    for bq, bk in blocks:
        sizes = lib.BlockSizes(
            block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
            block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
            block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk,
            block_q_dq=bq)

        def library(q, k, v, sizes=sizes):
            rep = q.shape[2] // k.shape[2]
            k, v = (jnp.repeat(t, rep, 2) for t in (k, v))
            q, k, v = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
            out = lib.flash_attention(q, k, v, causal=True,
                                      sm_scale=1.0 / math.sqrt(q.shape[-1]),
                                      block_sizes=sizes)
            return jnp.swapaxes(out, 1, 2)

        def library_bwd(q, k, v, g, library=library):
            out, vjp = jax.vjp(library, q, k, v)
            return (out, *vjp(g))

        impls[f"jax_pallas_{bq}x{bk}"] = (library, library_bwd)
    return impls


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--blocks", default="128x128,256x256,512x512,256x512,"
                                        "512x256,1024x1024")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    import jax
    if jax.devices()[0].platform != "tpu":
        sys.exit("flash_attention_bench: no TPU; its numbers are device times")
    from paddle_tpu.ops.kernels._common import x64_off

    kind = jax.devices()[0].device_kind
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peak = json.load(f)[kind]["bf16_flops_per_s"]
    blocks = [tuple(int(x) for x in b.split("x"))
              for b in args.blocks.split(",")]
    impls = implementations(blocks)
    with x64_off():             # the package turns x64 on; Mosaic wants 32
        measure(impls, peak, kind, args)


def measure(impls, peak, kind, args):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import flash_attention as fa

    key = jax.random.PRNGKey(args.seed)
    for shape, (b, s, h, h_kv, d) in SHAPES.items():
        ks = jax.random.split(key, 4)
        q = jax.random.normal(ks[0], (b, s, h, d), jnp.bfloat16)
        k = jax.random.normal(ks[1], (b, s, h_kv, d), jnp.bfloat16)
        v = jax.random.normal(ks[2], (b, s, h_kv, d), jnp.bfloat16)
        g = jax.random.normal(ks[3], (b, s, h, d), jnp.bfloat16)
        f32 = [t.astype(jnp.float32) for t in (q, k, v, g)]
        ref_out, ref_vjp = jax.vjp(
            lambda a, b_, c: fa._reference_attention(a, b_, c, True),
            *f32[:3])
        ref_dq = ref_vjp(f32[3])[0]
        flops = 6 * 2.0 * b * h * s * s * d / 2
        for name, (fwd, fwd_bwd) in impls.items():
            line = {"shape": shape, "impl": name, "device": kind}
            try:
                fj, fbj = jax.jit(fwd), jax.jit(fwd_bwd)
                line["fwd_ms"] = timed(fj, q, k, v, n=args.iters)
                line["fwd_bwd_ms"] = ms = timed(fbj, q, k, v, g,
                                                n=args.iters)
                line["fwd_bwd_roofline_pct"] = \
                    100 * flops / peak / (ms * 1e-3)
                out, dq = fbj(q, k, v, g)[:2]
                for tag, got, want in (("out", out, ref_out),
                                       ("dq", dq, ref_dq)):
                    line[f"{tag}_gap_rel_max"] = float(
                        jnp.max(jnp.abs(got.astype(jnp.float32) - want))
                        / jnp.max(jnp.abs(want)))
            except Exception as e:  # a block shape the chip refuses
                line["error"] = f"{type(e).__name__}: {str(e)[:300]}"
            print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
