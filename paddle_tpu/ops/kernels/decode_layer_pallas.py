"""Persistent decode-LAYER mega-kernel: page-table gather -> mmha ->
o_proj -> attn junction -> MLP -> mlp junction in ONE ``pallas_call``.

After PR 9's epilogue mega-kernels, the remaining decode-path seams the
``fusion_targets`` table ranks are exactly the HBM round trips BETWEEN
the fused pieces: the page-table gather materializing the contiguous
``[B, Hkv, T, D]`` view, the mmha output crossing HBM into o_proj, and
the projection outputs crossing again into each epilogue. This kernel
(MPK's thesis applied to one decode layer) keeps the whole per-layer
tail VMEM-resident:

    grid (batch, page): the per-request page table rides in as a
    SCALAR-PREFETCH input and steers the k/v BlockSpec index maps —
    page ``pi`` of row ``bi`` DMAs pool page ``table[bi, pi]`` straight
    into VMEM. The gather IS the block steering; the ``[B, Hkv, T, D]``
    intermediate never exists.

    pages sweep innermost: online-softmax accumulators (m, l, acc) live
    in VMEM scratch across the page sweep (initialized at ``pi == 0``,
    pages wholly past the row's position skipped — the position-bounded
    trip the composite's mask implies). At the LAST page the layer tail
    runs in-register: o_proj, residual add + rmsnorm (the attention
    junction), gate/up -> swiglu -> down (the MLP), and the second
    junction folding the NEXT layer's input norm (or the final model
    norm) — the two outputs are the next layer's normed input and the
    residual stream, exactly the ``(y, h)`` contract of the composite
    ``block_decode_epilogue`` path in ``serving/model.py``.

QKV projections, RoPE and the KV-cache scatter stay OUTSIDE (a scatter
into the paged pool cannot ride a read-steered kernel); everything from
the gather down is one dispatch per layer instead of ~10.

The MLP intermediate dim is processed in static ``block_i`` column
chunks — the ONE measured tuning knob (``ops/kernels/autotune.py``
searches it via ``run_timed_trial`` and installs the winner through the
``_common`` override registry under :data:`BLOCK_I_KEY`).

Weights are VMEM-resident constant-index blocks, so :func:`use_kernel`
gates on the WHOLE layer (weights + page blocks + accumulators) fitting
half the chip preset's VMEM — serving-scale models fall back to the
composite path, which remains the parity oracle (token-exact greedy,
``tests/test_decode_layer_fused.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...cost_model.collective import chip_vmem_bytes
from ._common import (get_block_override, jit_x64_off, round_up,
                      x64_off as _x64_off)

NEG_INF = -1e30

#: override-registry key of the MLP intermediate column chunk (the
#: autotuner's search dimension for this kernel family)
BLOCK_I_KEY = "decode_layer_i"


def _named(fn, name):
    """Bind a real ``__name__`` so the traced ``pallas_call`` carries it —
    the graph analyzer's mega-kernel marker recognizes the prefix."""
    def kernel(*refs):
        return fn(*refs)
    kernel.__name__ = kernel.__qualname__ = name
    return kernel


def _decode_layer_kernel(tab_ref, pos_ref, q_ref, k_ref, v_ref, hres_ref,
                         wo_ref, wpost_ref, wg_ref, wu_ref, wd_ref,
                         wnext_ref, y_ref, h_ref, m_s, l_s, acc_s, *,
                         h_kv, rep, rep_p, page_size, scale, eps_post,
                         eps_next, block_i):
    """One (batch row, page) grid step.

    q_ref ``[1, Hkv, rep_p, D]`` (query groups, Mosaic-padded);
    k/v_ref ``[1, Hkv, ps, D]`` — THE page the table steered here;
    hres ``[1, Hd]``; weights constant blocks; outputs ``[1, Hd]``;
    scratch ``[Hkv * rep_p, D]`` f32 (m/l broadcast across lanes, so
    every read/write is a full-block vector op).
    """
    bi = pl.program_id(0)
    pi = pl.program_id(1)
    n_pages = pl.num_programs(1)
    d = q_ref.shape[-1]
    pos = pos_ref[bi]

    @pl.when(pi == 0)
    def _init():
        m_s[...] = jnp.full(m_s.shape, NEG_INF, jnp.float32)
        l_s[...] = jnp.zeros(l_s.shape, jnp.float32)
        acc_s[...] = jnp.zeros(acc_s.shape, jnp.float32)

    # pages wholly past the row's position hold nothing it attends to
    # (their table slots point at the trash page) — skip, like the
    # composite mask / mmha's position-bounded trip count
    @pl.when(pi * page_size <= pos)
    def _accumulate():
        # lanes of m_s / l_s all carry the same per-row scalar; max
        # recovers it as a full-block vector op (no 1-lane slicing)
        m = jnp.max(m_s[...], axis=1, keepdims=True)          # [R, 1]
        l = jnp.max(l_s[...], axis=1, keepdims=True)
        acc = acc_s[...]                                      # [R, D]

        s_heads = []
        for h in range(h_kv):
            qh = q_ref[0, h].astype(jnp.float32) * jnp.float32(scale)
            kh = k_ref[0, h].astype(jnp.float32)              # [ps, D]
            s_heads.append(jax.lax.dot_general(
                qh, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32))          # [rep_p, ps]
        s = jnp.concatenate(s_heads, axis=0)                  # [R, ps]
        t_idx = pi * page_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1)
        s = jnp.where(t_idx <= pos, s, jnp.float32(NEG_INF))

        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        pv = []
        for h in range(h_kv):
            ph = p[h * rep_p:(h + 1) * rep_p]                 # [rep_p, ps]
            vh = v_ref[0, h].astype(jnp.float32)              # [ps, D]
            pv.append(jax.lax.dot_general(
                ph, vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32))
        acc_s[...] = alpha * acc + jnp.concatenate(pv, axis=0)
        m_s[...] = jnp.broadcast_to(m_new, m_s.shape)
        l_s[...] = jnp.broadcast_to(l_new, l_s.shape)

    @pl.when(pi == n_pages - 1)
    def _finalize():
        l = jnp.max(l_s[...], axis=1, keepdims=True)
        o = acc_s[...] / jnp.maximum(l, jnp.float32(1e-30))   # [R, D]

        # o_proj without reshapes: one [1, D] x [D, Hd] dot per real
        # query head (padded rep rows are garbage and simply skipped)
        attn = None
        for h in range(h_kv):
            for r in range(rep):
                row = o[h * rep_p + r:h * rep_p + r + 1]      # [1, D]
                j = h * rep + r
                wrow = wo_ref[j * d:(j + 1) * d].astype(jnp.float32)
                part = jax.lax.dot_general(
                    row, wrow, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)       # [1, Hd]
                attn = part if attn is None else attn + part

        hres = hres_ref[...].astype(jnp.float32)              # [1, Hd]
        h1 = attn + hres
        rstd = jax.lax.rsqrt(jnp.mean(h1 * h1, axis=-1, keepdims=True)
                             + jnp.float32(eps_post))
        y1 = h1 * rstd * wpost_ref[...].astype(jnp.float32)

        # MLP in static block_i column chunks (the autotuned knob)
        i_size = wg_ref.shape[1]
        mlp = None
        for c0 in range(0, i_size, block_i):
            wg_c = wg_ref[:, c0:c0 + block_i].astype(jnp.float32)
            wu_c = wu_ref[:, c0:c0 + block_i].astype(jnp.float32)
            g = jax.lax.dot_general(y1, wg_c, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            u = jax.lax.dot_general(y1, wu_c, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            z = g * jax.nn.sigmoid(g) * u                     # swiglu
            wd_c = wd_ref[c0:c0 + block_i].astype(jnp.float32)
            part = jax.lax.dot_general(z, wd_c, (((1,), (0,)), ((), ())),
                                       preferred_element_type=jnp.float32)
            mlp = part if mlp is None else mlp + part

        h2 = h1 + mlp
        rstd2 = jax.lax.rsqrt(jnp.mean(h2 * h2, axis=-1, keepdims=True)
                              + jnp.float32(eps_next))
        y2 = h2 * rstd2 * wnext_ref[...].astype(jnp.float32)
        y_ref[...] = y2.astype(y_ref.dtype)
        h_ref[...] = h2.astype(h_ref.dtype)


def _pick_block_i(i_size):
    """MLP column chunk: the measured override when the autotuner
    installed one (clamped to a divisor), else the full width."""
    o = get_block_override(BLOCK_I_KEY)
    if o is None:
        return i_size
    o = min(int(o), i_size)
    while i_size % o:
        o -= 8
    return max(o, 8) if i_size % 8 == 0 else i_size


@functools.partial(jit_x64_off,
                   static_argnames=("scale", "eps_post", "eps_next",
                                    "block_i", "interpret"))
def _fwd(qg, k_layer, v_layer, tab, pos, hres, wo, wpost, wg, wu, wd,
         wnext, scale, eps_post, eps_next, block_i, interpret):
    b, h_kv, rep_p, d = qg.shape
    n_pages = tab.shape[1]
    page_size = k_layer.shape[2]
    hd = hres.shape[1]
    i_size = wg.shape[1]
    rep = wo.shape[0] // d // h_kv
    rep_total = h_kv * rep_p

    row_spec = pl.BlockSpec((1, hd), lambda bi, pi, tab_, pos_: (bi, 0))
    const2 = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda bi, pi, tab_, pos_: (0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages),
        in_specs=[
            pl.BlockSpec((1, h_kv, rep_p, d),
                         lambda bi, pi, tab_, pos_: (bi, 0, 0, 0)),
            # the page-table gather AS block-index steering: page `pi` of
            # row `bi` is pool page table[bi, pi] — no gathered [B,Hkv,T,D]
            # intermediate ever exists in HBM
            pl.BlockSpec((1, h_kv, page_size, d),
                         lambda bi, pi, tab_, pos_: (tab_[bi, pi], 0, 0, 0)),
            pl.BlockSpec((1, h_kv, page_size, d),
                         lambda bi, pi, tab_, pos_: (tab_[bi, pi], 0, 0, 0)),
            row_spec,                      # hres
            const2((h_kv * rep * d, hd)),  # wo
            const2((1, hd)),               # wpost
            const2((hd, i_size)),          # wg
            const2((hd, i_size)),          # wu
            const2((i_size, hd)),          # wd
            const2((1, hd)),               # wnext
        ],
        out_specs=[row_spec, row_spec],
        scratch_shapes=[
            pltpu.VMEM((rep_total, d), jnp.float32),   # m (lane-broadcast)
            pltpu.VMEM((rep_total, d), jnp.float32),   # l (lane-broadcast)
            pltpu.VMEM((rep_total, d), jnp.float32),   # acc
        ],
    )
    kern = _named(functools.partial(
        _decode_layer_kernel, h_kv=h_kv, rep=rep, rep_p=rep_p,
        page_size=page_size, scale=scale, eps_post=eps_post,
        eps_next=eps_next, block_i=block_i), "block_decode_layer")
    with _x64_off():
        y, h = pl.pallas_call(
            kern,
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((b, hd), hres.dtype),
                       jax.ShapeDtypeStruct((b, hd), hres.dtype)],
            interpret=interpret,
        )(tab.astype(jnp.int32), pos.astype(jnp.int32), qg, k_layer,
          v_layer, hres, wo, wpost.reshape(1, hd), wg, wu, wd,
          wnext.reshape(1, hd))
    return y, h


def decode_layer(q, k_layer, v_layer, tables, pos, hres, wo, w_post, wg,
                 wu, wd, w_next, eps_post=1e-6, eps_next=1e-6,
                 block_i=None, interpret=False):
    """One whole decode layer from the paged pool, fused.

    q ``[B, H, D]`` (post-RoPE, the layer's current token); k/v_layer
    ``[P, Hkv, ps, D]`` (ONE layer's pool slice, current token already
    written); tables ``[B, max_pages]`` int32; pos ``[B]`` int32 (last
    valid cache index per row); hres ``[B, Hd]`` the residual stream
    entering the layer; wo ``[H*D, Hd]``; w_post/w_next ``[Hd]`` rmsnorm
    weights of the attention junction and the NEXT layer's input norm
    (or the final model norm); wg/wu ``[Hd, I]``; wd ``[I, Hd]``.

    Returns ``(y_next, h_next)`` both ``[B, Hd]`` — the next layer's
    normed input and the residual stream, the composite path's
    ``_junction`` contract.
    """
    b, h, d = q.shape
    h_kv = k_layer.shape[1]
    rep = h // h_kv
    rep_p = max(8, round_up(rep, 8))
    i_size = wg.shape[1]
    if block_i is None:
        block_i = _pick_block_i(i_size)
    scale = 1.0 / math.sqrt(d)

    qg = q.reshape(b, h_kv, rep, d)
    if rep_p != rep:
        qg = jnp.concatenate(
            [qg, jnp.zeros((b, h_kv, rep_p - rep, d), qg.dtype)], axis=2)
    return _fwd(qg, k_layer, v_layer, tables, pos, hres, wo, w_post, wg,
                wu, wd, w_next, scale, float(eps_post), float(eps_next),
                int(block_i), bool(interpret))


def use_kernel(q_shape, pool_shape, n_pages, hd, i_size,
               dtype="float32") -> bool:
    """Dispatch gate: whole layer VMEM-resident.

    The weights, one page of k+v per kv head, the query group, and the
    f32 accumulators must fit HALF the chip preset's VMEM (room for
    Pallas double buffering) — serving-scale layers fall back to the
    composite path. ``pool_shape`` is the layer slice ``[P, Hkv, ps,
    D]``; ``n_pages`` the page-table width.
    """
    from . import _common as kern
    if not kern.available():
        return False
    if not (kern.interpret_mode() or kern._FORCE_DISPATCH):
        raise NotImplementedError(
            "block_decode_layer is withdrawn on the chip: the v5e compiler "
            "refuses its (1, hidden) row blocks (not (8, 128)-divisible nor "
            "the full array), and its VMEM gate admits no published width. "
            "Drop ServingConfig(fused_decode_layer=True); the default "
            "decode path (mmha + block_decode_epilogue) serves.")
    if len(q_shape) != 3 or len(pool_shape) != 4:
        return False
    b, h, d = q_shape
    _, h_kv, ps, d2 = pool_shape
    if d != d2 or h % h_kv or h * d != hd:
        return False
    if ps % 8 or ps < 8 or n_pages < 1:
        return False
    itemsize = jnp.dtype(dtype).itemsize
    rep_p = max(8, round_up(h // h_kv, 8))
    weights = (h * d * hd + 2 * hd * i_size + i_size * hd
               + 2 * hd) * itemsize
    blocks = (2 * h_kv * ps * d + h_kv * rep_p * d + 3 * hd) * itemsize
    scratch = 3 * h_kv * rep_p * d * 4
    return weights + blocks + scratch <= chip_vmem_bytes() // 2


def reference_decode_layer(q, k_layer, v_layer, tables, pos, hres, wo,
                           w_post, wg, wu, wd, w_next, eps_post=1e-6,
                           eps_next=1e-6):
    """Composite with identical semantics (the parity oracle / A-B
    baseline): page-table gather -> per-row-position attention ->
    o_proj -> junction -> swiglu MLP -> junction, plain jnp."""
    from ...serving import kv_cache
    b, h, d = q.shape
    hd = hres.shape[1]
    kc = kv_cache.gather_layer(k_layer[None], 0, tables)
    vc = kv_cache.gather_layer(v_layer[None], 0, tables)
    out = kv_cache.reference_paged_attention(q[:, None], kc, vc, pos)
    attn = out.reshape(b, h * d).astype(jnp.float32) @ wo.astype(
        jnp.float32)
    h1 = attn + hres.astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(h1 * h1, axis=-1, keepdims=True)
                         + jnp.float32(eps_post))
    y1 = h1 * rstd * w_post.astype(jnp.float32)[None]
    g = y1 @ wg.astype(jnp.float32)
    u = y1 @ wu.astype(jnp.float32)
    mlp = (g * jax.nn.sigmoid(g) * u) @ wd.astype(jnp.float32)
    h2 = h1 + mlp
    rstd2 = jax.lax.rsqrt(jnp.mean(h2 * h2, axis=-1, keepdims=True)
                          + jnp.float32(eps_next))
    y2 = h2 * rstd2 * w_next.astype(jnp.float32)[None]
    return y2.astype(hres.dtype), h2.astype(hres.dtype)


def pk_examples():
    """Representative invocations for the kernel analyzer (PK tier).

    Dims sized so the whole-layer VMEM residency (weights + page blocks
    + accumulators) fits every ``CHIP_PRESETS`` budget — the PK200 bound
    ``tests/test_decode_layer_fused.py`` asserts per chip."""
    s = jax.ShapeDtypeStruct
    f32 = jnp.float32
    b, h, h_kv, d, ps, pages, n_tab = 4, 8, 4, 64, 16, 16, 4
    hd, i_size = h * d, 1024
    return [
        ("decode_layer", decode_layer,
         (s((b, h, d), f32),                       # q
          s((pages, h_kv, ps, d), f32),            # k pool slice
          s((pages, h_kv, ps, d), f32),            # v pool slice
          s((b, n_tab), jnp.int32),                # page tables
          s((b,), jnp.int32),                      # positions
          s((b, hd), f32),                         # residual stream
          s((h * d, hd), f32),                     # wo
          s((hd,), f32),                           # w_post
          s((hd, i_size), f32),                    # wg
          s((hd, i_size), f32),                    # wu
          s((i_size, hd), f32),                    # wd
          s((hd,), f32)),                          # w_next
         {}),
    ]
