#!/usr/bin/env python
"""Time the serving engine's sampler (`LLMEngine._sample`) alone on the chip,
at the serving cells' `[max_batch, vocab]`: what a decode step pays for
choosing its tokens when none, one or all of its rows sample.

    chiprun -- python tools/sampler_bench.py [--shapes 64x200192,32x32768]
        [--sampling-rows 0,1,all] [--iters 200]

Each line is one JSON object: the shape, the rows whose temperature is above
0, milliseconds a call of the jitted sampler alone (the step number moves
with every call, as the engine's does), and the logits' bytes over that time
as a share of the HBM peak (one read of the logits is all a greedy step
needs). `call_floor_ms` is the same loop over a jitted program of the same
arguments that touches no logits: what a call costs whatever it computes.
Fails without a TPU.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="64x200192,32x32768",
                    help="BxV of the logits, comma separated (Trinity's "
                         "and Mistral's decode steps)")
    ap.add_argument("--sampling-rows", default="0,1,all",
                    help="rows with temperature 0.8, the others greedy")
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--top-k", type=int, default=None)
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    import jax
    import jax.numpy as jnp
    import numpy as np
    if jax.devices()[0].platform != "tpu":
        sys.exit("sampler_bench: no TPU; its numbers are device times")
    from paddle_tpu.serving.engine import LLMEngine

    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        hbm_peak = json.load(f)[jax.devices()[0].device_kind][
            "hbm_bytes_per_s"]
    me = types.SimpleNamespace(
        config=types.SimpleNamespace(top_k=args.top_k))
    sample = jax.jit(lambda *a: LLMEngine._sample(me, *a))
    key = jnp.asarray(np.asarray(jax.random.PRNGKey(args.seed), np.uint32))
    steps = [jnp.int32(i) for i in range(args.iters + 1)]

    def timed(fn, logits, temps):
        out = fn(logits, temps, key, steps[-1])
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for step in steps[:-1]:
            out = fn(logits, temps, key, step)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.iters * 1e3, out

    idle = jax.jit(lambda logits, temps, key, step:
                   (temps > 0).astype(jnp.int32) + step)
    for shape in args.shapes.split(","):
        b, v = (int(x) for x in shape.split("x"))
        logits = jax.random.normal(jax.random.PRNGKey(args.seed + 1),
                                   (b, v), jnp.dtype(args.dtype))
        greedy = np.asarray(jnp.argmax(logits, axis=-1))
        for rows in args.sampling_rows.split(","):
            n = b if rows == "all" else int(rows)
            temps = jnp.asarray(np.where(np.arange(b) < n, 0.8, 0.0),
                                jnp.float32)
            floor, _ = timed(idle, logits, temps)
            ms, out = timed(sample, logits, temps)
            print(json.dumps({
                "shape": [b, v], "dtype": args.dtype, "top_k": args.top_k,
                "sampling_rows": n, "iters": args.iters, "sample_ms": ms,
                "call_floor_ms": floor,
                "logits_bytes_share_of_hbm_peak":
                    logits.nbytes / hbm_peak / (ms * 1e-3),
                "greedy_rows_are_the_argmax":
                    bool((np.asarray(out)[n:] == greedy[n:]).all()),
                "device": jax.devices()[0].device_kind}), flush=True)


if __name__ == "__main__":
    main()
