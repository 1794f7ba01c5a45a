#!/usr/bin/env python3
"""On the chip: the kernels the AFMoE serving path adds, each against its
composite at the published widths (Trinity-Mini), and how long each takes.

    python3 tools/afmoe_kernel_check.py [experts | banded]

Prints one JSON line a case: the widest gap to the composite and the
milliseconds of a call (median of 20, after a warm-up). A TPU only.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def timed(fn, *args, n=20):
    import jax
    jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(n):
        t = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t)
    return 1000.0 * sorted(ts)[len(ts) // 2]


def main():
    import jax
    import jax.numpy as jnp
    import numpy as np

    import paddle_tpu  # noqa: F401
    from paddle_tpu.incubate.distributed.models.moe import moe_layer as ml
    from paddle_tpu.ops.kernels import flash_attention_pallas as fap
    from paddle_tpu.serving import kv_cache
    assert jax.devices()[0].platform == "tpu", jax.devices()
    bf = jnp.bfloat16
    key = jax.random.key(0)
    nrm = lambda i, shape, std=1.0: (jax.random.normal(  # noqa: E731
        jax.random.fold_in(key, i), shape, jnp.float32) * std).astype(bf)

    if sys.argv[1:] != ["banded"]:
        # -- routed experts: 128 experts of 2048 x 1024, top 8
        gw = nrm(1, (128, 2048, 1024), 0.02)
        uw = nrm(2, (128, 2048, 1024), 0.02)
        dw = nrm(3, (128, 1024, 2048), 0.02)
        for n in (64, 2048, 8192):
            x = nrm(10 + n, (n, 2048))
            sel = jax.random.randint(jax.random.fold_in(key, 20 + n),
                                     (n, 8), 0, 128).astype(jnp.int32)
            w = jnp.full((n, 8), 0.125, jnp.float32)
            # the weights go in as arguments: closed over, each compile would
            # hold 1.6 GB of them as constants on the host
            kern = jax.jit(lambda x, s, w, *ws: ml.dropless_experts(
                x, s, w, *ws)[0])
            got = kern(x, sel, w, gw, uw, dw)
            rows = min(n, 256)      # the composite gathers weights by tile
            want = jax.jit(lambda x, s, w, *ws: ml.dropless_experts(
                x, s, w, *ws, interpret=False)[0])(
                    x[:rows], sel[:rows], w[:rows], gw, uw, dw) \
                if n <= 256 else None
            gap = None if want is None else float(jnp.abs(
                got[:rows].astype(jnp.float32)
                - want.astype(jnp.float32)).max())
            print(json.dumps({"case": f"dropless_experts n={n}", "gap": gap,
                              "rms": float(jnp.sqrt(jnp.mean(jnp.square(
                                  got.astype(jnp.float32))))),
                              "ms": timed(kern, x, sel, w, gw, uw, dw)}),
                  flush=True)

        # a long bucket whose later passes hold padding alone (no live tile)
        x = nrm(50, (8192, 2048))
        sel = jnp.where(
            jnp.arange(8192)[:, None] < 3000, jax.random.randint(
                jax.random.fold_in(key, 51), (8192, 8), 0, 128),
            128).astype(jnp.int32)
        out = jax.jit(lambda x, s, *ws: ml.dropless_experts(
            x, s, jnp.full((8192, 8), 0.125, jnp.float32), *ws)[0])(
                x, sel, gw, uw, dw)
        print(json.dumps({"case": "dropless_experts, 3000 live of 8192",
                          "rms_live": float(jnp.sqrt(jnp.mean(jnp.square(
                              out[:3000].astype(jnp.float32))))),
                          "max_padding": float(jnp.abs(out[3000:]).max())}),
              flush=True)
    if sys.argv[1:] == ["experts"]:
        return

    # -- banded flash forward: 32 / 4 heads x 128, against the composite a
    # block of 1024 query rows at a time (its [S, S] scores do not fit)
    def by_blocks(q, k, v, window):
        s, rows = q.shape[1], 1024
        kf = jnp.repeat(k[0].astype(jnp.float32), 8, axis=1)    # [S, 32, D]
        vf = jnp.repeat(v[0].astype(jnp.float32), 8, axis=1)
        kpos = jnp.arange(s)

        def block(i):
            q_i = jax.lax.dynamic_slice_in_dim(q[0], i * rows, rows) \
                .astype(jnp.float32)
            sc = jnp.einsum("qhd,khd->hqk", q_i, kf,
                            precision="highest") / 128 ** 0.5
            qpos = i * rows + jnp.arange(rows)
            mask = kpos[None, :] <= qpos[:, None]
            if window is not None:
                mask &= kpos[None, :] > qpos[:, None] - window
            p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", p, vf, precision="highest")

        return jax.lax.map(block, jnp.arange(s // rows)) \
            .reshape(1, s, 32, 128)

    for s, window in ((4096, 2048), (4096, None), (8192, 2048), (8192, None),
                      (16384, 2048), (16384, None)):
        q, k, v = nrm(30, (1, s, 32, 128)), nrm(31, (1, s, 4, 128)), \
            nrm(32, (1, s, 4, 128))
        f = jax.jit(lambda q, k, v: fap.flash_attention_forward_banded(
            q, k, v, window=window))
        want = jax.jit(lambda q, k, v: by_blocks(q, k, v, window))(q, k, v)
        gap = float(jnp.abs(f(q, k, v).astype(jnp.float32) - want).max())
        del want
        print(json.dumps({"case": f"banded s={s} window={window}",
                          "gap": gap, "ms": timed(f, q, k, v)}), flush=True)
    if sys.argv[1:] == ["banded"]:
        return

    # -- paged decode with the lower bound: 64 rows, contexts to 17k
    rng = np.random.default_rng(0)
    pool_k, pool_v = nrm(40, (4, 8257, 4, 16, 128)), \
        nrm(41, (4, 8257, 4, 16, 128))
    q = nrm(42, (64, 1, 32, 128))
    pos = rng.integers(300, 17000, 64).astype(np.int32)
    tables = np.zeros((64, 1088), np.int32)
    for r in range(64):     # 129 pages a row, those inside the window
        first = kv_cache.window_first_page(pos[r] + 1, 2048, 16)
        n = pos[r] // 16 + 1 - first
        tables[r, first:first + n] = 1 + r * 129 + np.arange(n)
    args = (q, pool_k, pool_v, 2, jnp.asarray(tables), jnp.asarray(pos))
    live = jnp.ones((64,), bool)
    f = jax.jit(lambda *a: kv_cache.paged_attention(*a, window=2048,
                                                    live=live))
    want = kv_cache.paged_attention(*args, interpret=False, window=2048,
                                    live=live)
    print(json.dumps({"case": "paged window 2048, 64 rows", "gap": float(
        jnp.abs(f(*args).astype(jnp.float32)
                - want.astype(jnp.float32)).max()),
        "ms": timed(f, *args)}), flush=True)


if __name__ == "__main__":
    main()
