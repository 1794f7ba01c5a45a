"""Masked multi-head attention (decode) Pallas TPU kernel.

Reference analog: the fused decode-attention kernel family
(paddle/phi/kernels/fusion/gpu/masked_multihead_attention_kernel.cu) — one
query token per sequence attending over the KV cache, the inner loop of
autoregressive serving.

TPU design: grid over (batch, kv-head); each program loads the query group
(the `rep = H/Hkv` query heads sharing one kv head — GQA native, no cache
expansion) and scans the cache in `block_t` chunks with online softmax in
f32. The CURRENT length rides in as a scalar-prefetch arg, so one compiled
kernel serves every step of the decode loop: chunks wholly past `pos` are
never visited (the trip count is position-bounded, like the causal flash
kernel's diagonal cutoff), and the tail chunk is masked per element.

Cache layout is [B, Hkv, T, D] — time-contiguous per head, so each chunk is
one stride-free VMEM tile. T must be a multiple of the chunk size; the
decode path rounds its cache allocation up (masking hides the tail), see
models/llama.py _init_kv_cache.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...cost_model.collective import chip_vmem_bytes
from ._common import round_up, jit_x64_off


from ._common import x64_off as _x64_off  # shared shim (kept as the
#                                           historical name callers import)


NEG_INF = -1e30

# cache-scan chunk length; _init_kv_cache rounds cache allocations to this
# so t % BLOCK_T == 0 always holds on the decode path
BLOCK_T = 256



def _mmha_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, *, block_t, scale):
    # q_ref [1, 1, rep_p, D]; k/v_ref [1, 1, T, D]; o_ref [1, 1, rep_p, D]
    # pos_ref [B]: last valid position (inclusive) PER SEQUENCE — the
    # serving runtime's continuous batch decodes rows at different
    # lengths in one launch; uniform decode passes a broadcast scalar
    pos = pos_ref[pl.program_id(0)]
    d = q_ref.shape[-1]
    rep_p = q_ref.shape[-2]
    q = q_ref[0, 0].astype(jnp.float32) * jnp.float32(scale)   # [rep_p, D]

    m = jnp.full((rep_p, 1), NEG_INF, jnp.float32)
    l = jnp.zeros((rep_p, 1), jnp.float32)
    acc = jnp.zeros((rep_p, d), jnp.float32)

    def body(i, carry):
        m, l, acc = carry
        k = k_ref[0, 0, pl.ds(i * block_t, block_t), :].astype(jnp.float32)
        v = v_ref[0, 0, pl.ds(i * block_t, block_t), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        t_idx = i * block_t + jax.lax.broadcasted_iota(
            jnp.int32, (rep_p, block_t), 1)
        s = jnp.where(t_idx <= pos, s, jnp.float32(NEG_INF))
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        acc_new = alpha * acc + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, l_new, acc_new

    # position-bounded trip count: chunks past `pos` contribute nothing
    n_used = (pos + jnp.int32(block_t)) // jnp.int32(block_t)
    m, l, acc = jax.lax.fori_loop(jnp.int32(0), n_used, body, (m, l, acc))
    o_ref[0, 0] = (acc / jnp.maximum(l, jnp.float32(1e-30))).astype(
        o_ref.dtype)


def use_kernel(q_shape, cache_shape, cache_dtype, block_t=BLOCK_T) -> bool:
    """Gate: single new token, chunk-divisible cache, VMEM-resident k+v."""
    from . import _common as kern
    if not kern.available():
        return False
    if len(q_shape) != 4 or q_shape[1] != 1:
        return False                       # decode kernel: one token only
    b, h_kv, t, d = cache_shape
    if q_shape[3] != d or q_shape[2] % h_kv:
        return False
    if t % min(block_t, t) or t < 8:
        return False
    itemsize = jnp.dtype(cache_dtype).itemsize
    # k + v blocks stay VMEM-resident per (batch, kv-head) program: half the
    # chip budget, the rest for accumulators and double buffering
    return 2 * t * d * itemsize <= chip_vmem_bytes() // 2


@functools.partial(jit_x64_off, static_argnames=("block_t", "interpret"))
def mmha_decode(q, k_buf, v_buf, pos, block_t=BLOCK_T, interpret=False):
    """q [B, 1, H, D]; k_buf/v_buf [B, Hkv, T, D] (current token already
    written at `pos`); pos: traced scalar (uniform decode) or [B] vector
    (per-row lengths — the paged serving batch), last valid cache index.
    Returns [B, 1, H, D]."""
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(f"mmha_decode takes exactly one new token, got {s}")
    _, h_kv, t, _ = k_buf.shape
    rep = h // h_kv
    rep_p = max(8, round_up(rep, 8))
    block_t = min(block_t, t)
    scale = 1.0 / math.sqrt(d)

    # [B, 1, H, D] -> [B, Hkv, rep_p, D] (pad the query group to the Mosaic
    # sublane rule; padded rows compute garbage that is sliced away)
    qg = q[:, 0].reshape(b, h_kv, rep, d)
    if rep_p != rep:
        qg = jnp.concatenate(
            [qg, jnp.zeros((b, h_kv, rep_p - rep, d), qg.dtype)], axis=2)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h_kv),
        in_specs=[
            pl.BlockSpec((1, 1, rep_p, d), lambda bi, hi, p_: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, t, d), lambda bi, hi, p_: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, t, d), lambda bi, hi, p_: (bi, hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep_p, d),
                               lambda bi, hi, p_: (bi, hi, 0, 0)),
    )
    with _x64_off():
        out = pl.pallas_call(
            functools.partial(_mmha_kernel, block_t=block_t, scale=scale),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h_kv, rep_p, d), q.dtype),
            interpret=interpret,
        )(jnp.broadcast_to(jnp.reshape(pos, (-1,)).astype(jnp.int32), (b,)),
          qg, k_buf, v_buf)
    return out[:, :, :rep, :].reshape(b, 1, h, d)


def reference_mmha(q, k_buf, v_buf, pos):
    """Composite decode attention (what XLA runs without the kernel):
    grouped einsum over the [B, Hkv, T, D] cache with a <=pos mask.
    `pos` is a scalar (uniform decode) or [B] vector (the serving
    runtime's per-row lengths) — ONE composite for both, so the training
    and serving decode paths can never diverge."""
    b, s, h, d = q.shape
    h_kv, t = k_buf.shape[1], k_buf.shape[2]
    rep = h // h_kv
    qg = q.reshape(b, s, h_kv, rep, d).astype(jnp.float32)
    logits = jnp.einsum("bsgrd,bgtd->bgrst", qg,
                        k_buf.astype(jnp.float32)) / math.sqrt(d)
    # scalar pos -> [1,1,1,1,1], vector [B] -> [B,1,1,1,1]: same mask rule
    pos_b = jnp.reshape(jnp.asarray(pos), (-1, 1, 1, 1, 1))
    mask = jnp.arange(t)[None, None, None, None, :] <= pos_b
    logits = jnp.where(mask, logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bgrst,bgtd->bsgrd", probs, v_buf.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


def pk_examples():
    """Representative invocations for the kernel analyzer (PK tier)."""
    s = jax.ShapeDtypeStruct
    bf16 = jnp.bfloat16
    return [
        ("mmha_decode", mmha_decode,
         (s((8, 1, 32, 128), bf16), s((8, 8, 2048, 128), bf16),
          s((8, 8, 2048, 128), bf16), s((8,), jnp.int32)), {}),
    ]
