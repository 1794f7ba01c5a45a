"""Pallas TPU A8W8 matmul: dynamic per-token int8 activation quant +
int8 x int8 MXU contraction + per-channel dequant, in one kernel.

Reference analog: the llm.int8 / A8W8 GEMM path behind
paddle.nn.quant.llm_int8_linear (python/paddle/nn/quant/
quantized_linear.py:186, cublasLt int8 GEMM with dequant epilogue). The
weight-only kernel (wo_matmul_pallas.py) covers the decode/GEMV regime,
where the matmul is weight-bandwidth-bound and the MXU idles either way;
this kernel covers the PREFILL regime, where the matmul is compute-bound
and int8 x int8 runs the MXU at twice the bf16 rate.

Per (row-block, col-block) grid step, entirely in VMEM:

    s   = rowmax(|x|) / 127                               (VPU reduction —
                                                           the block holds
                                                           the FULL K row)
    q   = clip(round(x / s), -127, 127)  as int8
    acc = q . w_blk                      as int32         (MXU)
    out = acc * s[:, None] * w_scale[None, :]             (dequant epilogue)

The quantized activation tile never exists outside VMEM and the dynamic
scales are never materialized at all, so the HBM cost is the bf16 x read,
the int8 weight read, and the output write — plus the MXU time halving.
(The rowmax is recomputed once per column block; a K-wide VPU reduction
per bf16 x read is noise next to the MXU contraction it feeds.)

Inference-path kernel (like the reference's): no custom_vjp; the
quantization PTQ/QAT flow owns training-time fake-quant gradients.

Public entry: `a8w8_matmul(x, w_q, w_scales)`; `nn.quant.llm_int8_linear`
dispatches its non-outlier GEMM here on TPU for prefill shapes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...cost_model.collective import chip_vmem_bytes
from ._common import pad_to_block, pick_row_block, x64_off, jit_x64_off

# 5/8 of the chip preset's VMEM (10 MiB on the 16 MiB presets): x + w +
# out + acc blocks, leaving headroom for the pipeline's double buffering
def _vmem_budget():
    return (chip_vmem_bytes() * 5) // 8


def _kernel(x_ref, w_ref, ws_ref, o_ref, *, nk_layout):
    x = x_ref[...].astype(jnp.float32)               # [bm, K]
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=1, keepdims=True),
                    1e-6) / 127.0                    # [bm, 1] per-token
    q = jnp.clip(jnp.round(x / s), -127.0, 127.0).astype(jnp.int8)
    # w block is [K, bn] ("kn") or [bn, K] ("nk" — the reference's
    # out-feature-major llm_int8 layout, contracted NT so the int8 weight
    # is never transposed in HBM)
    dims = (((1,), (1,)), ((), ())) if nk_layout else (((1,), (0,)), ((), ()))
    acc = jax.lax.dot_general(q, w_ref[...], dims,
                              preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * s * ws_ref[0].astype(jnp.float32)
    o_ref[...] = out.astype(o_ref.dtype)


def _pick_blocks(m, k, n, itemsize):
    bn = 256
    while k * bn > chip_vmem_bytes() // 4 and bn > 128:  # int8 weight block
        bn //= 2
    budget_x = max(_vmem_budget() - k * bn - bn * 4, k * itemsize * 8)
    bm = pick_row_block(m, k * itemsize, budget_x, key="a8w8")
    return bm, bn


@functools.partial(jit_x64_off, static_argnames=("layout", "interpret"))
def a8w8_matmul(x, w_q, w_scales, layout="kn", interpret=False):
    """[.., K] float @ int8 weight -> [.., N] in x.dtype, contracted in
    int8 on the MXU with per-token dynamic activation scales and [N]
    per-channel weight scales. `layout`: "kn" = w_q [K, N]; "nk" = w_q
    [N, K] (reference llm_int8 storage), contracted NT in-kernel."""
    if w_q.dtype != jnp.int8:
        raise ValueError(f"weight must be int8, got {w_q.dtype}")
    nk = layout == "nk"
    lead = x.shape[:-1]
    k, n = (w_q.shape[1], w_q.shape[0]) if nk else w_q.shape
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bm, bn = _pick_blocks(m, k, n, jnp.dtype(x.dtype).itemsize)
    x2p = pad_to_block(x2, bm, axis=0)
    w_p = pad_to_block(w_q, bn, axis=0 if nk else 1)
    ws_p = pad_to_block(w_scales.reshape(1, n).astype(jnp.float32), bn,
                        axis=1)
    mp = x2p.shape[0]
    np_ = w_p.shape[0] if nk else w_p.shape[1]
    w_spec = (pl.BlockSpec((bn, k), lambda mi, ni: (ni, 0)) if nk
              else pl.BlockSpec((k, bn), lambda mi, ni: (0, ni)))
    with x64_off():
        out = pl.pallas_call(
            functools.partial(_kernel, nk_layout=nk),
            grid=(mp // bm, np_ // bn),
            in_specs=[
                pl.BlockSpec((bm, k), lambda mi, ni: (mi, 0)),
                w_spec,
                pl.BlockSpec((1, bn), lambda mi, ni: (0, ni)),
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda mi, ni: (mi, ni)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
            interpret=interpret,
        )(x2p, w_p, ws_p)
    return out[:m, :n].reshape(*lead, n)


def use_kernel(m, k):
    """Prefill regime only: enough rows that the int8 MXU rate matters
    (decode/GEMV shapes stay on the weight-only kernel)."""
    return m >= 128 and k >= 256


def reference_a8w8(x, w_q, w_scales):
    """jnp composite with identical quantization semantics (int32
    contraction emulated in fp32 — exact for int8 operands)."""
    lead = x.shape[:-1]
    k, n = w_q.shape
    x2 = x.reshape(-1, k).astype(jnp.float32)
    s_row = jnp.maximum(jnp.max(jnp.abs(x2), axis=1, keepdims=True),
                        1e-6) / 127.0
    q = jnp.clip(jnp.round(x2 / s_row), -127.0, 127.0)
    acc = q @ w_q.astype(jnp.float32)
    out = acc * s_row * w_scales.reshape(1, n).astype(jnp.float32)
    return out.astype(x.dtype).reshape(*lead, n)


def pk_examples():
    """Representative invocations for the kernel analyzer (PK tier)."""
    s = jax.ShapeDtypeStruct
    return [
        ("a8w8_kn", a8w8_matmul,
         (s((512, 1024), jnp.bfloat16), s((1024, 2048), jnp.int8),
          s((2048,), jnp.float32)), {}),
        ("a8w8_nk", a8w8_matmul,
         (s((512, 1024), jnp.bfloat16), s((2048, 1024), jnp.int8),
          s((2048,), jnp.float32)), {"layout": "nk"}),
    ]
