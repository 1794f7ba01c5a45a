"""Pallas TPU weight-only int8 matmul: x @ dequant(w_int8) * scales.

Reference analog: the weight_only_linear int8 kernels
(paddle/phi/kernels/fusion/gpu/fused_weight_only_linear_pass +
weight_only_linear_kernel.cu) — weights stored int8 in HBM, dequantized
in-register inside the GEMM. The TPU win is HBM bandwidth: decode-time
matmuls are weight-bound, and reading int8 instead of bf16 halves the
traffic. The kernel streams an int8 [K, bn] weight block into VMEM,
converts to the activation dtype in-core (never materializing a bf16 copy
of the full weight in HBM, which the XLA composite risks), runs the MXU
contraction with f32 accumulation, and applies the per-output-channel
scale on the way out.

Layout: x [M, K] (activation dtype), w_q [K, N] int8, scales [N] f32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...cost_model.collective import chip_vmem_bytes
from ._common import pad_to_block, pick_row_block, x64_off, jit_x64_off

# x + w + out + acc blocks: 5/8 of the shared chip VMEM budget (10 MiB
# on the 16 MiB presets), same source of truth as the kernel analyzer
def _vmem_budget():
    return (chip_vmem_bytes() * 5) // 8


def _wo_kernel(x_ref, w_ref, s_ref, o_ref):
    x = x_ref[...]                                   # [bm, K] activation
    w = w_ref[...].astype(x.dtype)                   # int8 -> act dtype
    acc = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[...] = (acc * s_ref[0].astype(jnp.float32)).astype(o_ref.dtype)


def _wo_g_kernel(x_ref, w_ref, s_ref, o_ref, *, gsize):
    """Grouped scales: w [K, bn] int8, s [K/gsize, bn] — the per-K-group
    rescale applies to the WEIGHT before the contraction (a post-matmul
    rescale cannot express it), via a sublane-split reshape in VMEM."""
    x = x_ref[...]
    w = w_ref[...].astype(jnp.float32)               # [K, bn]
    s = s_ref[...].astype(jnp.float32)               # [K/gsize, bn]
    k, bn = w.shape
    wd = (w.reshape(k // gsize, gsize, bn) * s[:, None, :]) \
        .reshape(k, bn).astype(x.dtype)
    acc = jax.lax.dot_general(x, wd, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    o_ref[...] = acc.astype(o_ref.dtype)


def _pick_blocks(m, k, n, itemsize):
    """(bm, bn) blocks under the VMEM budget with full-K streaming. The row
    block goes through the shared pick_row_block so it is capped at the
    REAL row count (a decode GEMV of 8 rows must not pad to a 256-row
    block) and honors measured autotuner overrides."""
    bn = 256
    while k * bn > chip_vmem_bytes() // 4 and bn > 128:  # int8 weight block
        bn //= 2
    budget_x = max(_vmem_budget() - k * bn - bn * 4, k * itemsize * 8)
    bm = pick_row_block(m, k * itemsize, budget_x, key="wo_int8")
    return bm, bn


@functools.partial(jit_x64_off, static_argnames=("interpret",))
def wo_int8_matmul(x, w_q, scales, interpret=False):
    """[.., K] @ int8 [K, N] * scales -> [.., N] in x.dtype.

    `scales` is [N] (per output channel) or [K/G, N] (grouped — the
    per-K-group rescale happens in VMEM before the MXU contraction, so
    the dequantized weight never touches HBM)."""
    if w_q.dtype != jnp.int8:
        raise ValueError(f"weight must be int8, got {w_q.dtype}")
    lead = x.shape[:-1]
    k, n = w_q.shape
    grouped = scales.ndim == 2
    if grouped and k % scales.shape[0]:
        raise ValueError(f"grouped scales rows {scales.shape[0]} must "
                         f"divide K={k}")
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    bm, bn = _pick_blocks(m, k, n, jnp.dtype(x.dtype).itemsize)
    x2 = pad_to_block(x2, bm, axis=0)
    w_p = pad_to_block(w_q, bn, axis=1)
    mp, np_ = x2.shape[0], w_p.shape[1]

    if grouped:
        # the grouped kernel holds the int8 block PLUS an f32 dequant copy
        # plus its x-dtype cast in VMEM: budget for the expansion, and fall
        # back to the composite (trace-time ValueError, caught by the
        # dispatch) when even bn=128 cannot fit
        per_byte = 5 + jnp.dtype(x.dtype).itemsize
        if k * bn * per_byte > 6 * 1024 * 1024:
            bn = 128
        if k * bn * per_byte > 6 * 1024 * 1024:
            raise ValueError(
                f"grouped int8 kernel weight block cannot fit VMEM at "
                f"K={k}; use the composite path")
        w_p = pad_to_block(w_q, bn, axis=1)
        np_ = w_p.shape[1]
        gsize = k // scales.shape[0]
        s_p = pad_to_block(scales, bn, axis=1)
        kern = functools.partial(_wo_g_kernel, gsize=gsize)
        s_spec = pl.BlockSpec((k // gsize, bn), lambda mi, ni: (0, ni))
    else:
        kern = _wo_kernel
        s_p = pad_to_block(scales.reshape(1, n), bn, axis=1)
        s_spec = pl.BlockSpec((1, bn), lambda mi, ni: (0, ni))

    with x64_off():
        out = pl.pallas_call(
            kern,
            grid=(mp // bm, np_ // bn),
            in_specs=[
                pl.BlockSpec((bm, k), lambda mi, ni: (mi, 0)),
                pl.BlockSpec((k, bn), lambda mi, ni: (0, ni)),
                s_spec,
            ],
            out_specs=pl.BlockSpec((bm, bn), lambda mi, ni: (mi, ni)),
            out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
            interpret=interpret,
        )(x2, w_p, s_p)
    return out[:m, :n].reshape(*lead, n)


def dequant_grouped(w_q, scales):
    """Canonical grouped dequant: [K, N] int8 x [K/G, N] scales -> f32
    (the single definition the composites, VJP, and layers share)."""
    k, n = w_q.shape
    g = k // scales.shape[0]
    return (w_q.reshape(k // g, g, n).astype(jnp.float32)
            * scales[:, None, :].astype(jnp.float32)).reshape(k, n)


def reference_wo_int8_matmul(x, w_q, scales):
    """XLA composite (quantization.functional.dequant_matmul_int8);
    handles per-channel [N] and grouped [K/G, N] scales."""
    if scales.ndim == 2:
        return jnp.matmul(x, dequant_grouped(w_q, scales).astype(x.dtype))
    y = jnp.matmul(x, w_q.astype(x.dtype))
    return y * scales.astype(x.dtype)


# -- int4: two 4-bit values per byte, HALF-SPLIT layout --------------------
#
# Packing nibbles from INTERLEAVED columns (even=lo, odd=hi — the natural
# byte packing) would need a stride-2 lane scatter inside the kernel, a
# Mosaic relayout. Packing column halves instead — byte j holds column j
# (lo nibble) and column j + N/2 (hi nibble) — lets the kernel emit two
# CONTIGUOUS output slabs per packed block with plain shifts/masks.

def pack_int4_halves(q):
    """[K, N] int8 values in [-7, 7], N even -> [K, N/2] bytes."""
    if q.shape[1] % 2:
        raise ValueError("pack_int4_halves needs an even column count")
    half = q.shape[1] // 2
    lo = q[:, :half].astype(jnp.int32) & 0xF
    hi = q[:, half:].astype(jnp.int32) & 0xF
    return (lo | (hi << 4)).astype(jnp.int8)


def unpack_int4_halves(packed):
    """Inverse of pack_int4_halves: [K, N/2] bytes -> [K, N] int8."""
    b = packed.astype(jnp.int32)
    lo = (b & 0xF)
    hi = ((b >> 4) & 0xF)
    lo = jnp.where(lo > 7, lo - 16, lo)
    hi = jnp.where(hi > 7, hi - 16, hi)
    return jnp.concatenate([lo, hi], axis=1).astype(jnp.int8)


def _wo4_kernel(x_ref, w_ref, slo_ref, shi_ref, olo_ref, ohi_ref):
    x = x_ref[...]
    # [K, bn] packed bytes, widened: the chip's Mosaic has no int8 vector
    # shifts (`arith.shli` on vector<..xi8> fails to legalize). In int32
    # the ARITHMETIC shifts sign-extend the nibbles with no select:
    # hi = b >> 4; lo = (b << 28) >> 28
    b = w_ref[...].astype(jnp.int32)
    lo = ((b << 28) >> 28).astype(x.dtype)
    hi = (b >> 4).astype(x.dtype)
    acc_lo = jax.lax.dot_general(x, lo, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    acc_hi = jax.lax.dot_general(x, hi, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    olo_ref[...] = (acc_lo * slo_ref[0].astype(jnp.float32)).astype(
        olo_ref.dtype)
    ohi_ref[...] = (acc_hi * shi_ref[0].astype(jnp.float32)).astype(
        ohi_ref.dtype)


def _pick_blocks_int4(m, k, itemsize):
    """Like _pick_blocks but budgeted for the int4 kernel's in-VMEM
    expansion: per packed byte the kernel holds the byte, its int32
    widening, two sign-extended int32 planes and their activation-dtype
    casts (~13 + 2*itemsize bytes). Returns (bm, bn) or None when even the
    smallest block cannot fit (caller falls back to the composite —
    better a loud trace-time decision than a Mosaic OOM at compile)."""
    per_byte = 13 + 2 * itemsize
    bn = 256
    while k * bn * per_byte > 6 * 1024 * 1024 and bn > 128:
        bn //= 2
    if k * bn * per_byte > 6 * 1024 * 1024:
        return None
    budget_x = max(_vmem_budget() - k * bn * per_byte - 2 * bn * 4,
                   k * itemsize * 8)
    bm = pick_row_block(m, k * itemsize, budget_x, key="wo_int4")
    return bm, bn


@functools.partial(jit_x64_off, static_argnames=("interpret",))
def wo_int4_matmul(x, w_packed, scales, interpret=False):
    """[.., K] @ int4-packed [K, N/2] * scales [N] -> [.., N] in x.dtype.

    The packed bytes stay packed in HBM (half the int8 footprint AND half
    the weight read traffic); nibbles unpack in VMEM right before the MXU
    contraction. `scales` covers all N output columns (halves layout:
    column j of the packed byte -> outputs j and j + N/2)."""
    if w_packed.dtype != jnp.int8:
        raise ValueError(f"packed weight must be int8 bytes, "
                         f"got {w_packed.dtype}")
    lead = x.shape[:-1]
    k, half = w_packed.shape
    n = 2 * half
    if scales.shape[0] != n:
        raise ValueError(f"scales must cover {n} columns, "
                         f"got {scales.shape[0]}")
    x2 = x.reshape(-1, k)
    m = x2.shape[0]
    picked = _pick_blocks_int4(m, k, jnp.dtype(x.dtype).itemsize)
    if picked is None:
        raise ValueError(
            f"int4 kernel weight block cannot fit VMEM at K={k} (needs "
            f"K-blocking); use the composite path")
    bm, bn = picked
    x2 = pad_to_block(x2, bm, axis=0)
    w_p = pad_to_block(w_packed, bn, axis=1)
    s_lo = pad_to_block(scales[:half].reshape(1, half), bn, axis=1)
    s_hi = pad_to_block(scales[half:].reshape(1, half), bn, axis=1)
    mp, hp = x2.shape[0], w_p.shape[1]

    with x64_off():
        out_lo, out_hi = pl.pallas_call(
            _wo4_kernel,
            grid=(mp // bm, hp // bn),
            in_specs=[
                pl.BlockSpec((bm, k), lambda mi, ni: (mi, 0)),
                pl.BlockSpec((k, bn), lambda mi, ni: (0, ni)),
                pl.BlockSpec((1, bn), lambda mi, ni: (0, ni)),
                pl.BlockSpec((1, bn), lambda mi, ni: (0, ni)),
            ],
            out_specs=[
                pl.BlockSpec((bm, bn), lambda mi, ni: (mi, ni)),
                pl.BlockSpec((bm, bn), lambda mi, ni: (mi, ni)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((mp, hp), x.dtype),
                jax.ShapeDtypeStruct((mp, hp), x.dtype),
            ],
            interpret=interpret,
        )(x2, w_p, s_lo, s_hi)
    out = jnp.concatenate([out_lo[:m, :half], out_hi[:m, :half]], axis=1)
    return out.reshape(*lead, n)


def reference_wo_int4_matmul(x, w_packed, scales):
    w = unpack_int4_halves(w_packed)
    return jnp.matmul(x, w.astype(x.dtype)) * scales.astype(x.dtype)


def pk_examples():
    """Representative invocations for the kernel analyzer (PK tier)."""
    s = jax.ShapeDtypeStruct
    bf16 = jnp.bfloat16
    x = s((8, 1024), bf16)
    return [
        ("wo_int8", wo_int8_matmul,
         (x, s((1024, 4096), jnp.int8), s((4096,), jnp.float32)), {}),
        ("wo_int8_grouped", wo_int8_matmul,
         (x, s((1024, 4096), jnp.int8), s((8, 4096), jnp.float32)), {}),
        ("wo_int4", wo_int4_matmul,
         (x, s((1024, 2048), jnp.int8), s((4096,), jnp.float32)), {}),
    ]
