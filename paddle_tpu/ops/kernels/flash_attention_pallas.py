"""Pallas TPU flash-attention forward kernel.

Blocked online-softmax attention (FlashAttention-2 style): grid over
(batch*heads, q-blocks); the kernel scans k/v blocks keeping running max and
sum. Every MXU product takes its operands in the dtype the inputs are stored
in (bf16 in, bf16 operands; f32 in, f32 operands) and accumulates in f32; the
scores, the softmax statistics and the accumulators are f32, and P and dS are
cast to the value dtype for the products that take them. The softmax scale
rides on the operand that stays in VMEM across the k (or q) loop, scaled once
in f32: on the [block_q, block_k] scores it would cost the VPU a pass per
block.

Layout: [batch, seq, heads, head_dim] (reference flash_attn layout,
paddle/phi/kernels/gpu/flash_attn_kernel.cu).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import x64_off, jit_x64_off

NEG_INF = -1e30  # wrapped in jnp.float32 at use sites (x64 safety)
LSE_LANES = 128  # lse/delta stored [.., S, 128]: Mosaic wants full-lane layouts
#: q and k block of the whole-sequence kernels where it divides the sequence:
#: on one v5e chip forward plus backward ran 29 % (head width 64) and 36 %
#: (head width 128) faster at 512 x 512 than at 256 x 256
#: (tools/flash_attention_bench.py)
BLOCK = 512


def _block(block, s):
    """`block` where given, else BLOCK where it divides s, else 256; at most
    s."""
    if block is None:
        block = BLOCK if s % BLOCK == 0 else 256
    return min(block, s)


def _scaled(x, scale):
    """x * scale, computed in f32 and returned in x's own dtype."""
    return (x.astype(jnp.float32) * jnp.float32(scale)).astype(x.dtype)


def _attn_kernel(q_ref, k_ref, v_ref, *rest, causal, block_k,
                 seq_len, scale, block_q, has_seg=False, with_lse=False):
    # q_ref: [1, block_q, d]; k_ref/v_ref: [1, seq, d]
    # rest (in order): [qseg_ref [1, block_q, LSE_LANES], kseg_ref [1, 8, seq]
    # when has_seg], o_ref [1, block_q, d], [lse_ref [1, block_q, LSE_LANES]
    # when with_lse]. Segment masking follows the public TPU flash-attention
    # layout trick: q segments lane-broadcast, kv segments sublane-broadcast,
    # so the [block_q, block_k] compare needs no relayout.
    it = iter(rest)
    qseg_ref = next(it) if has_seg else None
    kseg_ref = next(it) if has_seg else None
    o_ref = next(it)
    lse_ref = next(it) if with_lse else None
    d = q_ref.shape[-1]
    q = _scaled(q_ref[0], scale)
    q_blk = pl.program_id(1)
    qs = qseg_ref[0][:, :1] if has_seg else None   # [block_q, 1]

    m = jnp.full((block_q, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((block_q, 1), jnp.float32)
    acc = jnp.zeros((block_q, d), jnp.float32)

    n_k = seq_len // block_k

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(i * block_k, block_k), :]
        v = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        valid = None
        if causal:
            q_pos = q_blk * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            valid = q_pos >= k_pos
        if has_seg:
            ks = kseg_ref[0, :1, pl.ds(i * block_k, block_k)]  # [1, block_k]
            same = qs == ks
            valid = same if valid is None else (valid & same)
        if valid is not None:
            s = jnp.where(valid, s, jnp.float32(NEG_INF))
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        if has_seg:
            # a fully-masked row keeps m == NEG_INF, where exp(s - m) == 1
            # for every masked entry — zero those explicitly so padding
            # rows produce 0 output instead of mean(v)
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc_new = alpha * acc + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    if causal:
        # only scan k blocks up to (and including) the diagonal block
        last = ((q_blk + 1) * block_q + block_k - 1) // jnp.int32(block_k)
        n_used = jnp.minimum(last, n_k)
        m, l, acc = jax.lax.fori_loop(jnp.int32(0), n_used.astype(jnp.int32), body,
                                      (m, l, acc))
    else:
        m, l, acc = jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_k), body,
                                      (m, l, acc))

    l_safe = jnp.maximum(l, jnp.float32(1e-30))
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    if with_lse:
        lse_ref[0] = jnp.broadcast_to(m + jnp.log(l_safe),
                                      (block_q, LSE_LANES))


def _kv_index_map(h, h_kv):
    """Grid row bi (over b*h q-heads) -> the k/v row it reads. GQA
    (h_kv < h): each group of h//h_kv q heads shares one kv head — the
    kernel fetches that kv block directly, with NO materialized repeat in
    HBM (the bandwidth win over repeat_kv; reference GQA glue expands)."""
    n_rep = h // h_kv

    def imap(bi, qi):
        return ((bi // h) * h_kv + (bi % h) // n_rep, 0, 0)

    return imap


SEG_SUBLANES = 8  # kv segments sublane-broadcast [B, 8, S] (Mosaic tiling)


def _seg_operands(segment_ids, b, s, h):
    """(lane-broadcast q segs [B,S,LSE_LANES], sublane-broadcast kv segs
    [B,8,S], extra in_specs) — index maps select the grid row's batch."""
    seg = segment_ids.astype(jnp.int32)
    seg_q = jnp.broadcast_to(seg[:, :, None], (b, s, LSE_LANES))
    seg_kv = jnp.broadcast_to(seg[:, None, :], (b, SEG_SUBLANES, s))
    return seg_q, seg_kv


def _fwd_common(q, k, v, segment_ids, causal, block_q, block_k, interpret,
                with_lse):
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    block_q, block_k = _block(block_q, s), _block(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq {s} must divide block sizes {block_q}/{block_k}")
    scale = 1.0 / math.sqrt(d)
    has_seg = segment_ids is not None

    # [B,S,H,D] -> [B*H, S, D] for blocking along seq
    qt = jnp.swapaxes(q, 1, 2).reshape(b * h, s, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * h_kv, s, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * h_kv, s, d)
    kv_map = _kv_index_map(h, h_kv)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda bi, qi: (bi, qi, 0)),
        pl.BlockSpec((1, s, d), kv_map),
        pl.BlockSpec((1, s, d), kv_map),
    ]
    operands = [qt, kt, vt]
    if has_seg:
        seg_q, seg_kv = _seg_operands(segment_ids, b, s, h)
        in_specs += [
            pl.BlockSpec((1, block_q, LSE_LANES),
                         lambda bi, qi: (bi // h, qi, 0)),
            pl.BlockSpec((1, SEG_SUBLANES, s), lambda bi, qi: (bi // h, 0, 0)),
        ]
        operands += [seg_q, seg_kv]

    blk_o = pl.BlockSpec((1, block_q, d), lambda bi, qi: (bi, qi, 0))
    if with_lse:
        out_specs = [blk_o, pl.BlockSpec((1, block_q, LSE_LANES),
                                         lambda bi, qi: (bi, qi, 0))]
        out_shape = [jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
                     jax.ShapeDtypeStruct((b * h, s, LSE_LANES), jnp.float32)]
    else:
        out_specs = blk_o
        out_shape = jax.ShapeDtypeStruct((b * h, s, d), q.dtype)

    with x64_off():
        res = pl.pallas_call(
            functools.partial(_attn_kernel, causal=causal, block_k=block_k,
                              seq_len=s, scale=scale, block_q=block_q,
                              has_seg=has_seg, with_lse=with_lse),
            grid=(b * h, s // block_q),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            interpret=interpret,
        )(*operands)
    if with_lse:
        out, lse = res
        return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2), lse[:, :, 0]
    return jnp.swapaxes(res.reshape(b, h, s, d), 1, 2)


@functools.partial(jit_x64_off, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_forward_lse(q, k, v, causal=False, block_q=None,
                                block_k=None, interpret=False,
                                segment_ids=None):
    """Returns (out [B,S,H,D], lse [B*H, S] float32). k/v may carry fewer
    heads than q (GQA): heads must divide evenly. `segment_ids` [B, S]
    restricts attention to equal segments (packed varlen batches,
    reference flash_attn_unpadded)."""
    return _fwd_common(q, k, v, segment_ids, causal, block_q, block_k,
                       interpret, with_lse=True)


@functools.partial(jit_x64_off, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_forward(q, k, v, causal=False, block_q=None, block_k=None,
                            interpret=False, segment_ids=None):
    """Primal-only forward: no logsumexp output (inference path). GQA and
    segment masking as in flash_attention_forward_lse."""
    return _fwd_common(q, k, v, segment_ids, causal, block_q, block_k,
                       interpret, with_lse=False)


# -- banded forward: a lower bound on the keys a query block reads ----------

def _banded_kernel(q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s, *, window,
                   block_q, block_k, scale):
    # grid (B*H, q blocks, k steps); q_ref/o_ref [1, block_q, d], k/v_ref
    # [1, block_k, d]: step j of q block qi holds k block first(qi) + j (the
    # index map clamps past the diagonal, so a step beyond it brings nothing
    # in and is skipped here). m_s/l_s [block_q, 128] lane-broadcast, acc_s
    # [block_q, d], all f32, carried over the k steps.
    qi, j = pl.program_id(1), pl.program_id(2)
    first = _first_k_block(qi, window, block_q, block_k)
    last = (qi * block_q + block_q - 1) // block_k
    kb = first + j

    @pl.when(j == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    @pl.when(kb <= last)
    def _score():
        k, v = k_ref[0], v_ref[0]
        # operands as they are stored (bf16 on the MXU), scores in f32
        s = jax.lax.dot_general(
            q_ref[0], k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * jnp.float32(scale)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        k_pos = kb * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        valid = q_pos >= k_pos
        if window is not None:
            valid &= k_pos > q_pos - window
        s = jnp.where(valid, s, jnp.float32(NEG_INF))
        m_prev = m_s[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row with no key yet in this block keeps m at NEG_INF, where
        # exp(s - m) would read 1 for every masked entry
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_s[...] = jnp.broadcast_to(
            alpha * l_s[:, :1] + jnp.sum(p, axis=1, keepdims=True), l_s.shape)
        acc_s[...] = alpha * acc_s[...] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)

    @pl.when(j == pl.num_programs(2) - 1)
    def _done():
        o_ref[0] = (acc_s[...] / jnp.maximum(
            l_s[:, :1], jnp.float32(1e-30))).astype(o_ref.dtype)


def _first_k_block(qi, window, block_q, block_k):
    """The first k block that holds a key some query of q block `qi` sees:
    0 without a window, else the block of position qi*block_q - window + 1."""
    if window is None:
        return qi * 0
    return jnp.maximum(qi * block_q - (window - 1), 0) // block_k


def banded_k_steps(s, window, block_q, block_k) -> int:
    """k blocks a q block can need: all of them without a window, else those
    a span of window - 1 + block_q positions can touch."""
    n_k = s // block_k
    if window is None:
        return n_k
    return min(n_k, (window - 1 + block_q - 1) // block_k + 2)


@functools.partial(jit_x64_off, static_argnames=("window", "block_q",
                                                 "block_k", "interpret"))
def flash_attention_forward_banded(q, k, v, window=None, block_q=1024,
                                   block_k=512, interpret=False):
    """Causal primal-only forward in which query i sees key j iff
    ``i - window < j <= i`` (``window=None``: plain causal). Unlike
    :func:`flash_attention_forward` it brings k and v in block by block, so a
    sequence need not fit VMEM whole, and a q block reads only the k blocks
    from the first one inside its window to its diagonal: those wholly before
    the window are neither fetched nor scored. [B, S, H, D] layout, GQA as
    in the other forwards."""
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    block_q, block_k = min(block_q, s), min(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq {s} must divide block sizes {block_q}/{block_k}")
    steps = banded_k_steps(s, window, block_q, block_k)
    qt = jnp.swapaxes(q, 1, 2).reshape(b * h, s, d)
    kt = jnp.swapaxes(k, 1, 2).reshape(b * h_kv, s, d)
    vt = jnp.swapaxes(v, 1, 2).reshape(b * h_kv, s, d)
    kv_row = _kv_index_map(h, h_kv)

    def kv_map(bi, qi, j):
        last = (qi * block_q + block_q - 1) // block_k
        kb = _first_k_block(qi, window, block_q, block_k) + j
        return (kv_row(bi, qi)[0], jnp.minimum(kb, last), 0)

    blk_q = pl.BlockSpec((1, block_q, d), lambda bi, qi, j: (bi, qi, 0))
    blk_k = pl.BlockSpec((1, block_k, d), kv_map)
    with x64_off():
        out = pl.pallas_call(
            functools.partial(_banded_kernel, window=window, block_q=block_q,
                              block_k=block_k, scale=1.0 / math.sqrt(d)),
            grid=(b * h, s // block_q, steps),
            in_specs=[blk_q, blk_k, blk_k],
            out_specs=blk_q,
            out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            scratch_shapes=[pltpu.VMEM((block_q, LSE_LANES), jnp.float32),
                            pltpu.VMEM((block_q, LSE_LANES), jnp.float32),
                            pltpu.VMEM((block_q, d), jnp.float32)],
            interpret=interpret,
        )(qt, kt, vt)
    return jnp.swapaxes(out.reshape(b, h, s, d), 1, 2)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
               causal, block_q, block_k, seq_len, scale, has_seg=False):
    """Grid (B*H, n_q): dQ for one q block, scanning k/v blocks.

    dS = P * (dO V^T - delta);  dQ = scale * dS K   with P = exp(S - lse).
    rest = [qseg_ref [1,bq,LSE_LANES], kseg_ref [1,8,S] when has_seg], dq_ref.
    """
    it = iter(rest)
    qseg_ref = next(it) if has_seg else None
    kseg_ref = next(it) if has_seg else None
    dq_ref = next(it)
    d = q_ref.shape[-1]
    q_blk = pl.program_id(1)
    q = _scaled(q_ref[0], scale)                      # [bq, d]
    do = do_ref[0]                                    # [bq, d]
    lse = lse_ref[0][:, :1]                           # [bq, 1]
    delta = delta_ref[0][:, :1]                       # [bq, 1]
    qs = qseg_ref[0][:, :1] if has_seg else None      # [bq, 1]

    n_k = seq_len // block_k
    acc = jnp.zeros((block_q, d), jnp.float32)

    def body(i, acc):
        k = k_ref[0, pl.ds(i * block_k, block_k), :]
        v = v_ref[0, pl.ds(i * block_k, block_k), :]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        valid = None
        if causal:
            q_pos = q_blk * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            valid = q_pos >= k_pos
        if has_seg:
            ks = kseg_ref[0, :1, pl.ds(i * block_k, block_k)]
            same = qs == ks
            valid = same if valid is None else (valid & same)
        if valid is not None:
            s = jnp.where(valid, s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse)                           # [bq, bk]
        if has_seg:
            # fully-masked rows have lse at the guard floor; exp(s - lse)
            # there is garbage — zero masked entries explicitly
            p = jnp.where(valid, p, 0.0)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return acc + jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        last = ((q_blk + 1) * block_q + block_k - 1) // jnp.int32(block_k)
        acc = jax.lax.fori_loop(jnp.int32(0),
                                jnp.minimum(last, n_k).astype(jnp.int32),
                                body, acc)
    else:
        acc = jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_k), body, acc)
    dq_ref[0] = (acc * jnp.float32(scale)).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                causal, block_q, block_k, seq_len, scale, has_seg=False):
    """Grid (B*H, n_k): dK/dV for one k/v block, scanning q blocks.

    dV = P^T dO;  dK = scale * dS^T Q.
    rest = [qseg_ref [1,S,LSE_LANES], kseg_ref [1,8,bk] when has_seg],
    dk_ref, dv_ref.
    """
    it = iter(rest)
    qseg_ref = next(it) if has_seg else None
    kseg_ref = next(it) if has_seg else None
    dk_ref = next(it)
    dv_ref = next(it)
    d = k_ref.shape[-1]
    k_blk = pl.program_id(1)
    k = _scaled(k_ref[0], scale)                      # [bk, d]
    v = v_ref[0]                                      # [bk, d]
    ks = kseg_ref[0, :1, :] if has_seg else None      # [1, bk]

    n_q = seq_len // block_q
    dk = jnp.zeros((block_k, d), jnp.float32)
    dv = jnp.zeros((block_k, d), jnp.float32)

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :]
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :][:, :1]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), :][:, :1]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        valid = None
        if causal:
            q_pos = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_blk * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            valid = q_pos >= k_pos
        if has_seg:
            qs = qseg_ref[0, pl.ds(i * block_q, block_q), :][:, :1]
            same = qs == ks
            valid = same if valid is None else (valid & same)
        if valid is not None:
            s = jnp.where(valid, s, jnp.float32(NEG_INF))
        p = jnp.exp(s - lse)                           # [bq, bk]
        if has_seg:
            p = jnp.where(valid, p, 0.0)
        dv = dv + jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta)                          # [bq, bk]
        dk = dk + jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return dk, dv

    if causal:
        start = (k_blk * block_k) // jnp.int32(block_q)
        dk, dv = jax.lax.fori_loop(start.astype(jnp.int32), jnp.int32(n_q),
                                  body, (dk, dv))
    else:
        dk, dv = jax.lax.fori_loop(jnp.int32(0), jnp.int32(n_q), body, (dk, dv))
    # dK = scale * dS^T Q: the scale on k served S alone
    dk_ref[0] = (dk * jnp.float32(scale)).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


@functools.partial(jit_x64_off, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_backward(q, k, v, out, lse, g, causal=False, block_q=None,
                             block_k=None, interpret=False, segment_ids=None):
    """Fused FA2-style backward: (dq, dk, dv) — dq [B,S,H,D], dk/dv with the
    kv head count (GQA: gradients of shared kv heads are summed over their
    query group).

    `lse` is the [B*H, S] logsumexp from flash_attention_forward_lse; `g` the
    output cotangent. delta = rowsum(dO * O) is computed outside the kernels
    (one fused XLA elementwise pass).
    """
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(f"q heads {h} not a multiple of kv heads {h_kv}")
    n_rep = h // h_kv
    block_q, block_k = _block(block_q, s), _block(block_k, s)
    if s % block_q or s % block_k:
        raise ValueError(f"seq {s} must divide block sizes {block_q}/{block_k}")
    scale = 1.0 / math.sqrt(d)

    def to_bh(t):
        hh = t.shape[2]
        return jnp.swapaxes(t, 1, 2).reshape(b * hh, s, d)

    qt, kt, vt, dot = to_bh(q), to_bh(k), to_bh(v), to_bh(g)
    ot = to_bh(out)
    kv_map = _kv_index_map(h, h_kv)
    delta1 = jnp.sum(dot.astype(jnp.float32) * ot.astype(jnp.float32),
                     axis=-1, keepdims=True)
    delta = jnp.broadcast_to(delta1, (b * h, s, LSE_LANES))
    lse3 = jnp.broadcast_to(lse[:, :, None], (b * h, s, LSE_LANES))

    full = lambda bi, qi: (bi, 0, 0)
    blk_q3 = pl.BlockSpec((1, block_q, d), lambda bi, qi: (bi, qi, 0))
    blk_q1 = pl.BlockSpec((1, block_q, LSE_LANES), lambda bi, qi: (bi, qi, 0))
    blk_k3 = pl.BlockSpec((1, block_k, d), lambda bi, ki: (bi, ki, 0))

    has_seg = segment_ids is not None
    dq_extra, dkv_extra = [], []
    dq_specs, dkv_specs = [], []
    if has_seg:
        seg_q, seg_kv = _seg_operands(segment_ids, b, s, h)
        dq_extra = [seg_q, seg_kv]
        dq_specs = [
            pl.BlockSpec((1, block_q, LSE_LANES),
                         lambda bi, qi: (bi // h, qi, 0)),
            pl.BlockSpec((1, SEG_SUBLANES, s), lambda bi, qi: (bi // h, 0, 0)),
        ]
        dkv_extra = [seg_q, seg_kv]
        dkv_specs = [
            pl.BlockSpec((1, s, LSE_LANES), lambda bi, ki: (bi // h, 0, 0)),
            pl.BlockSpec((1, SEG_SUBLANES, block_k),
                         lambda bi, ki: (bi // h, 0, ki)),
        ]

    with x64_off():
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, causal=causal, block_q=block_q,
                              block_k=block_k, seq_len=s, scale=scale,
                              has_seg=has_seg),
            grid=(b * h, s // block_q),
            in_specs=[
                blk_q3,                                    # q
                pl.BlockSpec((1, s, d), kv_map),           # k
                pl.BlockSpec((1, s, d), kv_map),           # v
                blk_q3,                                    # do
                blk_q1,                                    # lse
                blk_q1,                                    # delta
            ] + dq_specs,
            out_specs=blk_q3,
            out_shape=jax.ShapeDtypeStruct((b * h, s, d), q.dtype),
            interpret=interpret,
        )(qt, kt, vt, dot, lse3, delta, *dq_extra)

    # dk/dv: per-q-head partials (kv blocks fetched through kv_map — no
    # materialized repeat), summed over each kv head's query group after
    with x64_off():
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, causal=causal, block_q=block_q,
                              block_k=block_k, seq_len=s, scale=scale,
                              has_seg=has_seg),
            grid=(b * h, s // block_k),
            in_specs=[
                pl.BlockSpec((1, s, d), full),             # q
                pl.BlockSpec((1, block_k, d),
                             lambda bi, ki: (kv_map(bi, ki)[0], ki, 0)),
                pl.BlockSpec((1, block_k, d),
                             lambda bi, ki: (kv_map(bi, ki)[0], ki, 0)),
                pl.BlockSpec((1, s, d), full),             # do
                pl.BlockSpec((1, s, LSE_LANES), full),     # lse
                pl.BlockSpec((1, s, LSE_LANES), full),     # delta
            ] + dkv_specs,
            out_specs=[blk_k3, blk_k3],
            # GQA partials stay f32 until after the group sum — casting each
            # partial to bf16 first would add rounding the h_kv==h path
            # doesn't have
            out_shape=[
                jax.ShapeDtypeStruct(
                    (b * h, s, d), jnp.float32 if n_rep > 1 else k.dtype),
                jax.ShapeDtypeStruct(
                    (b * h, s, d), jnp.float32 if n_rep > 1 else v.dtype),
            ],
            interpret=interpret,
        )(qt, kt, vt, dot, lse3, delta, *dkv_extra)

    dq_out = jnp.swapaxes(dq.reshape(b, h, s, d), 1, 2)
    # n_rep==1 reduces over a size-1 axis — same result, no special case
    dk_out = jnp.swapaxes(
        dk.reshape(b, h_kv, n_rep, s, d).sum(2).astype(k.dtype), 1, 2)
    dv_out = jnp.swapaxes(
        dv.reshape(b, h_kv, n_rep, s, d).sum(2).astype(v.dtype), 1, 2)
    return dq_out, dk_out, dv_out


def pk_examples():
    """Representative invocations for the kernel analyzer (PK tier)."""
    s = jax.ShapeDtypeStruct
    bf16 = jnp.bfloat16
    b, sq, h, h_kv, d = 2, 1024, 8, 2, 128
    q = s((b, sq, h, d), bf16)
    kv = s((b, sq, h_kv, d), bf16)
    full = s((b, sq, h, d), bf16)
    lse = s((b * h, sq), jnp.float32)
    return [
        ("fwd_causal", flash_attention_forward, (q, kv, kv),
         dict(causal=True)),
        ("fwd_lse", flash_attention_forward_lse, (q, kv, kv), {}),
        ("fwd_banded", flash_attention_forward_banded,
         (s((1, 4096, 8, 128), bf16), s((1, 4096, 2, 128), bf16),
          s((1, 4096, 2, 128), bf16)), dict(window=2048)),
        ("bwd_causal", flash_attention_backward,
         (q, kv, kv, full, lse, full), dict(causal=True)),
    ]
