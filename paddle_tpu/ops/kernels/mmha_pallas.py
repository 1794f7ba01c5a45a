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

Two kernels live here, one for each way a KV cache is kept:

* :func:`mmha_decode` — the contiguous cache above. What
  ``models/generation.py:cached_attention`` (``model.generate``) and
  ``incubate/nn/functional/fused_transformer_serving.py`` call: one buffer a
  request, no page table. Its BlockSpec brings all T positions of a
  (row, KV head) into VMEM whatever ``pos`` is; only the loop is bounded.
* :func:`paged_mmha_decode` — the serving engine's decode program
  (``serving/kv_cache.py:paged_attention``). It takes the WHOLE paged pool
  ``[L, P, Hkv, ps, D]`` left in HBM, the layer, the page tables and the
  per-row positions as scalar prefetch, and fetches each row's live pages
  itself: grid over rows, inside a loop over the row's
  ``ceil((pos+1) / (pages_per_block*ps))`` blocks only, each block's pages
  brought in by double-buffered DMAs (one 32 KB page serves all KV heads;
  the next block, or the next row's first, is in flight under the current
  block's arithmetic). No gathered ``[B, Hkv, T, D]`` view exists, so a
  decode step reads what is live and not ``max_batch x max_seq_len``. It
  feeds the MXU operands in the pool's dtype (bf16 as it is stored; the
  f32 casts of the contiguous kernel made the MXU run multi-pass) and
  keeps scores, softmax and accumulators in f32.
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


# -- paged decode attention (the serving decode program's kernel) -----------

#: KV pages one block of the paged kernel fetches and scores at a time
#: (x page_size positions). Chosen on the chip at the serving cells' shapes
#: (B 32, Hkv 8, rep 4, D 128, ps 16, contexts 50-2500): see PERF.md.
PAGES_PER_BLOCK = 8


def _paged_mmha_kernel(layer_ref, tables_ref, pos_ref, lo_ref, q_ref, k_hbm,
                       v_hbm, o_ref, k_buf, v_buf, sems, m_s, l_s, acc_s,
                       slot_s, *, ppb, max_pages, scale):
    # grid (B,), one row a step, run in order. q_ref/o_ref
    # [1, Hkv, rep_p, D]; k_hbm/v_hbm the WHOLE pool [L, P, Hkv, ps, D],
    # left in HBM; k_buf/v_buf [2, ppb, Hkv, ps, D] VMEM (two slots);
    # sems [2 (k, v), 2 (slot)]; m_s/l_s [Hkv, rep_p, 128] lane-broadcast,
    # acc_s [Hkv, rep_p, D] f32; slot_s [1] SMEM: the slot the row's first
    # block lands in (the previous row started that fetch).
    b = pl.program_id(0)
    n_rows = pl.num_programs(0)
    layer = layer_ref[0]
    pos = pos_ref[b]
    h_kv, ps, d = k_buf.shape[2:]
    rep_p = q_ref.shape[2]
    bt = ppb * ps
    # position-bounded at both ends: only the blocks that hold a position
    # in lo .. pos (lo: the first key the row's query sees, 0 without a
    # window). A row with nothing live (pos < 0) still takes the one block
    # the row before it started fetching, and scores nothing

    def first_block(row):
        return jnp.minimum(lo_ref[row], jnp.maximum(pos_ref[row], 0)) \
            // jnp.int32(bt)

    lo = lo_ref[b]
    first = first_block(b)
    n_blocks = jnp.maximum(pos, 0) // jnp.int32(bt) + jnp.int32(1) - first

    def fetch(row, blk, slot):
        for j in range(ppb):
            # a table narrower than a whole number of blocks: its last
            # block re-reads the last page (past `pos`, masked below)
            idx = jnp.minimum(blk * ppb + j, max_pages - 1)
            page = tables_ref[row * max_pages + idx]
            pltpu.make_async_copy(k_hbm.at[layer, page], k_buf.at[slot, j],
                                  sems.at[0, slot]).start()
            pltpu.make_async_copy(v_hbm.at[layer, page], v_buf.at[slot, j],
                                  sems.at[1, slot]).start()

    def wait(slot):
        for j in range(ppb):    # a wait needs the copy's shape, not its page
            pltpu.make_async_copy(k_hbm.at[layer, 0], k_buf.at[slot, j],
                                  sems.at[0, slot]).wait()
            pltpu.make_async_copy(v_hbm.at[layer, 0], v_buf.at[slot, j],
                                  sems.at[1, slot]).wait()

    @pl.when(b == 0)
    def _first():
        slot_s[0] = jnp.int32(0)
        fetch(b, first, jnp.int32(0))

    slot0 = slot_s[0]
    m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
    l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
    acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    def score_block(i, slot):
        t_idx = i * bt + jax.lax.broadcasted_iota(jnp.int32, (rep_p, bt), 1)
        live = (t_idx <= pos) & (t_idx >= lo)
        for g in range(h_kv):   # one page DMA serves every KV head
            k = k_buf[slot, :, g].reshape(bt, d)
            v = v_buf[slot, :, g].reshape(bt, d)
            # operands in the pool's dtype (bf16 on the MXU as it is),
            # scores, softmax and accumulators in f32
            s = jax.lax.dot_general(
                q_ref[0, g].astype(k.dtype), k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * jnp.float32(scale)
            s = jnp.where(live, s, jnp.float32(NEG_INF))
            m_prev = m_s[g][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_new = alpha * l_s[g][:, :1] + jnp.sum(p, axis=1, keepdims=True)
            acc_s[g] = alpha * acc_s[g] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_s[g] = jnp.broadcast_to(m_new, m_s.shape[1:])
            l_s[g] = jnp.broadcast_to(l_new, l_s.shape[1:])

    def body(i, carry):
        slot = (slot0 + i) % 2
        # the next block of this row or, behind its last, the first block
        # of the next row: its fetch runs under this block's arithmetic
        last = i + 1 >= n_blocks
        nrow = jnp.where(last, b + 1, b)

        @pl.when(nrow < n_rows)
        def _prefetch():
            fetch(nrow, jnp.where(last, first_block(nrow), first + i + 1),
                  1 - slot)

        wait(slot)

        @pl.when(pos >= 0)
        def _score():
            score_block(first + i, slot)
        return carry

    jax.lax.fori_loop(jnp.int32(0), n_blocks, body, jnp.int32(0))
    slot_s[0] = (slot0 + n_blocks) % 2
    for g in range(h_kv):
        o_ref[0, g] = (acc_s[g] / jnp.maximum(
            l_s[g][:, :1], jnp.float32(1e-30))).astype(o_ref.dtype)


def use_paged_kernel(q_shape, pool_shape, pool_dtype) -> bool:
    """Gate of :func:`paged_mmha_decode`: kernels dispatching, one new
    token, and a page the kernel's tiles admit (head width a multiple of
    the 128 lanes, page size a multiple of the pool dtype's sublane tile,
    so a block's pages stack into one [T, D] operand without a relayout)."""
    from . import _common as kern
    if not kern.available():
        return False
    if len(q_shape) != 4 or q_shape[1] != 1 or len(pool_shape) != 5:
        return False
    _, _, h_kv, ps, d = pool_shape
    if q_shape[3] != d or q_shape[2] % h_kv or d % 128:
        return False
    itemsize = jnp.dtype(pool_dtype).itemsize
    if itemsize not in (2, 4) or ps % (32 // itemsize):
        return False
    # two slots of K and V blocks stay well inside VMEM
    return 4 * PAGES_PER_BLOCK * h_kv * ps * d * itemsize \
        <= chip_vmem_bytes() // 4


def paged_block_positions(page_size, max_pages,
                          pages_per_block=PAGES_PER_BLOCK) -> int:
    """Positions in one block of :func:`paged_mmha_decode`: a row holding
    n live positions has ceil(n / this) blocks of pages read."""
    return min(pages_per_block, max_pages) * page_size


@functools.partial(jit_x64_off,
                   static_argnames=("pages_per_block", "interpret"))
def paged_mmha_decode(q, k_pool, v_pool, layer, tables, pos, lo=None,
                      pages_per_block=PAGES_PER_BLOCK, interpret=False):
    """Decode attention straight from the paged pool.

    q [B, 1, H, D]; k_pool/v_pool [L, P, Hkv, ps, D], whole and left where
    they are (the kernel fetches pages itself, so no slice or gathered view
    of them is ever built); layer: traced int32 scalar; tables
    [B, max_pages] int32 (physical page of each logical page); pos [B]
    int32, last valid position per row (its token already written), or -1
    for a row with nothing live; lo [B] int32 or None: the first position
    each row's query sees (a window layer: pos - window + 1, not below 0;
    None: 0, every position up to pos). A row reads the blocks of
    pages_per_block*ps positions from the one that holds lo to the one that
    holds pos, both masked per element: what lies behind the window is
    neither fetched nor scored. A row with nothing live reads one block,
    scores nothing and returns zeros. Returns [B, 1, H, D]."""
    b, s, h, d = q.shape
    if s != 1:
        raise ValueError(
            f"paged_mmha_decode takes exactly one new token, got {s}")
    _, _, h_kv, ps, _ = k_pool.shape
    max_pages = tables.shape[1]
    rep = h // h_kv
    rep_p = max(8, round_up(rep, 8))
    ppb = paged_block_positions(ps, max_pages, pages_per_block) // ps
    scale = 1.0 / math.sqrt(d)

    qg = q[:, 0].reshape(b, h_kv, rep, d)
    if rep_p != rep:
        qg = jnp.concatenate(
            [qg, jnp.zeros((b, h_kv, rep_p - rep, d), qg.dtype)], axis=2)

    row = lambda bi, *_: (bi, 0, 0, 0)  # noqa: E731
    lo = jnp.zeros((b,), jnp.int32) if lo is None else \
        jnp.maximum(jnp.reshape(lo, (-1,)).astype(jnp.int32), 0)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, h_kv, rep_p, d), row),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, h_kv, rep_p, d), row),
        scratch_shapes=[
            pltpu.VMEM((2, ppb, h_kv, ps, d), k_pool.dtype),
            pltpu.VMEM((2, ppb, h_kv, ps, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((h_kv, rep_p, 128), jnp.float32),
            pltpu.VMEM((h_kv, rep_p, 128), jnp.float32),
            pltpu.VMEM((h_kv, rep_p, d), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    with _x64_off():
        out = pl.pallas_call(
            functools.partial(_paged_mmha_kernel, ppb=ppb,
                              max_pages=max_pages, scale=scale),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, h_kv, rep_p, d), q.dtype),
            # rows in order: each starts the next one's first fetch
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(jnp.reshape(layer, (1,)).astype(jnp.int32),
          tables.reshape(-1).astype(jnp.int32),
          jnp.reshape(pos, (-1,)).astype(jnp.int32), lo, qg, k_pool, v_pool)
    return out[:, :, :rep, :].reshape(b, 1, h, d)


def reference_mmha(q, k_buf, v_buf, pos, lo=None):
    """Composite decode attention (what XLA runs without the kernel):
    grouped einsum over the [B, Hkv, T, D] cache with a <=pos mask.
    `pos` is a scalar (uniform decode) or [B] vector (the serving
    runtime's per-row lengths) — ONE composite for both, so the training
    and serving decode paths can never diverge. `lo` (scalar or [B]): the
    first position seen, a window layer's lower bound."""
    b, s, h, d = q.shape
    h_kv, t = k_buf.shape[1], k_buf.shape[2]
    rep = h // h_kv
    qg = q.reshape(b, s, h_kv, rep, d).astype(jnp.float32)
    logits = jnp.einsum("bsgrd,bgtd->bgrst", qg,
                        k_buf.astype(jnp.float32)) / math.sqrt(d)
    # scalar pos -> [1,1,1,1,1], vector [B] -> [B,1,1,1,1]: same mask rule
    pos_b = jnp.reshape(jnp.asarray(pos), (-1, 1, 1, 1, 1))
    t_idx = jnp.arange(t)[None, None, None, None, :]
    mask = t_idx <= pos_b
    if lo is not None:
        mask &= t_idx >= jnp.reshape(jnp.asarray(lo), (-1, 1, 1, 1, 1))
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
        ("paged_mmha_decode", paged_mmha_decode,
         (s((8, 1, 32, 128), bf16), s((2, 257, 8, 16, 128), bf16),
          s((2, 257, 8, 16, 128), bf16), s((), jnp.int32),
          s((8, 128), jnp.int32), s((8,), jnp.int32)), {}),
    ]
