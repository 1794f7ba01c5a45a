"""Grouped expert matmul (MoE grouped-GEMM) Pallas TPU kernel.

Reference analog: the grouped/segmented GEMM the reference's fused MoE path
dispatches per expert group (paddle/phi/kernels/fusion/ moe kernels; CUDA
grouped GEMM). On TPU the capacity-bucketed layout [E, C, H] already gives
static shapes, so a dense einsum is MXU-friendly — but it multiplies every
padded capacity slot. This kernel takes the per-expert fill count and SKIPS
whole [block_c, block_f] output tiles that lie entirely beyond an expert's
fill level: with capacity_factor 1.25 and imbalanced routing, a large slice
of the einsum's FLOPs are zeros the compiler cannot know about.

Rows past counts[e] inside a live tile are masked to zero in the kernel
itself, so the zeroed-output contract holds for ANY padding content (the
live MoE path feeds zero padding rows anyway, but callers need not).

Public entry: `grouped_matmul(x, w, counts)` with custom_vjp — dx reuses the
kernel with w transposed (skipping the same tiles); dw is a dense einsum
over the count-masked cotangent (padding rows contribute nothing).
"""

from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._common import x64_off, jit_x64_off


def _kernel(c_ref, x_ref, w_ref, o_ref, *, block_c):
    # c_ref is the scalar-prefetch arg: counts[e] lives in SMEM (a (1,1)
    # VMEM block would violate Mosaic's 8x128-divisible block rule, caught
    # by tests/test_tpu_lowering.py)
    count = c_ref[pl.program_id(0)]
    c_start = pl.program_id(1) * block_c

    @pl.when(count > c_start)
    def _compute():
        x = x_ref[0]                                  # [bc, H]
        w = w_ref[0]                                  # [H, bf]
        out = jnp.dot(x, w, preferred_element_type=jnp.float32)
        # mask rows past the fill level inside a partially-live tile, so the
        # output matches the zeroed contract even for nonzero padding rows
        rows = c_start + jax.lax.broadcasted_iota(jnp.int32, out.shape, 0)
        o_ref[0] = jnp.where(rows < count, out, 0.0).astype(o_ref.dtype)

    @pl.when(count <= c_start)
    def _skip():
        o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)


def _pick_bc(c, target=128):
    """Capacity block: multiple of 8 (Mosaic sublane rule); indivisible
    capacities are padded rather than met with a degraded block."""
    from ._common import round_up
    return max(8, min(target, round_up(c, 8)))


def _pick_bf(f):
    """Output-feature block: the lane dim must be a multiple of 128 OR the
    full array dim, and — unlike the padded capacity axis — must DIVIDE f
    exactly (nothing pads f, so a floored grid would leave trailing output
    columns unwritten)."""
    if f % 128:
        return f  # full-dim lane block, always legal
    return 256 if f % 256 == 0 else 128


@functools.partial(jit_x64_off, static_argnames=("interpret",))
def _grouped_call(x, w, counts, interpret):
    from ._common import pad_to_block
    e, c, h = x.shape
    f = w.shape[-1]
    bc = _pick_bc(c)
    bf = _pick_bf(f)
    xp = pad_to_block(x, bc, axis=1)  # kernel masks rows >= counts[e] anyway
    cp = xp.shape[1]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(e, cp // bc, f // bf),
        in_specs=[pl.BlockSpec((1, bc, h), lambda e_, i, j, c_: (e_, i, 0)),
                  pl.BlockSpec((1, h, bf), lambda e_, i, j, c_: (e_, 0, j))],
        out_specs=pl.BlockSpec((1, bc, bf), lambda e_, i, j, c_: (e_, i, j)),
    )
    with x64_off():
        out = pl.pallas_call(
            functools.partial(_kernel, block_c=bc),
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((e, cp, f), x.dtype),
            interpret=interpret,
        )(counts.reshape(e).astype(jnp.int32), xp, w)
    return out[:, :c] if cp != c else out


def _primal(x, w, counts, interpret=False):
    return _grouped_call(x, w, counts, interpret)


grouped_matmul = jax.custom_vjp(_primal, nondiff_argnums=(3,))


def _vjp_fwd(x, w, counts, interpret):
    return _primal(x, w, counts, interpret), (x, w, counts)


def _vjp_bwd(interpret, saved, g):
    x, w, counts = saved
    dx = _grouped_call(g, jnp.swapaxes(w, 1, 2), counts, interpret)
    # mask cotangent rows past the fill level so dw matches the masked
    # forward even when x carries nonzero padding rows
    live = jnp.arange(x.shape[1])[None, :, None] < counts.reshape(-1, 1, 1)
    g_live = jnp.where(live, g.astype(jnp.float32), 0)
    dw = jnp.einsum("ech,ecf->ehf", x.astype(jnp.float32),
                    g_live).astype(w.dtype)
    dcounts = np.zeros(counts.shape, jax.dtypes.float0) \
        if jnp.issubdtype(counts.dtype, jnp.integer) else jnp.zeros_like(counts)
    return dx, dw, dcounts


grouped_matmul.defvjp(_vjp_fwd, _vjp_bwd)


def reference_grouped_matmul(x, w, counts):
    """Dense einsum reference (what XLA runs without the kernel), with the
    beyond-count slots zeroed to match the kernel's contract."""
    out = jnp.einsum("ech,ehf->ecf", x.astype(jnp.float32),
                     w.astype(jnp.float32)).astype(x.dtype)
    c = x.shape[1]
    mask = jnp.arange(c)[None, :, None] < counts.reshape(-1, 1, 1)
    return jnp.where(mask, out, 0)


# -- ragged groups: no capacity, nothing dropped ----------------------------
#
# The rows of every expert lie together, each group padded to a whole number
# of row tiles (`incubate/.../moe_layer.py:sort_by_expert` builds the layout),
# so a tile belongs to one expert: `tile_expert[t]` (scalar prefetch) picks
# the weight block, and a tile past `used` is skipped. The grid walks the
# tiles innermost: consecutive tiles of one expert keep the weight block's
# index, so it is fetched once per expert and column block, and a skipped
# tile keeps every index of the last live one, so it moves nothing.

#: widest block of an expert's output columns one step multiplies
RAGGED_BLOCK_N = 512


def _ragged_swiglu_kernel(te_ref, used_ref, x_ref, wg_ref, wu_ref, o_ref):
    @pl.when(pl.program_id(1) < used_ref[0])
    def _live():
        x = x_ref[...]
        g = jnp.dot(x, wg_ref[0], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[0], preferred_element_type=jnp.float32)
        o_ref[...] = (g * jax.nn.sigmoid(g) * u).astype(o_ref.dtype)


def _ragged_matmul_kernel(te_ref, used_ref, x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(1) < used_ref[0])
    def _live():
        o_ref[...] = jnp.dot(x_ref[...], w_ref[0],
                             preferred_element_type=jnp.float32
                             ).astype(o_ref.dtype)


def use_ragged_kernel(hidden: int, width: int, dtype) -> bool:
    """Gate of the two ragged kernels: kernels dispatching, both widths a
    whole number of 128-lane column blocks, a 2- or 4-byte dtype."""
    from . import _common as kern
    from ...core.flags import flag
    return (kern.available() and flag("use_pallas_kernels")
            and hidden % 128 == 0 and width % 128 == 0
            and jnp.dtype(dtype).itemsize in (2, 4))


def _ragged_call(kernel, x, ws, tile_expert, used, tile, interpret):
    rows, k = x.shape
    n = ws[0].shape[-1]
    bn = RAGGED_BLOCK_N if n % RAGGED_BLOCK_N == 0 else 128 \
        if n % 128 == 0 else n
    tiles = rows // tile
    # a skipped tile keeps the last live tile's blocks (tile 0's where no
    # tile is live: a pass that holds bucket padding alone)
    live = lambda t, u: jnp.maximum(jnp.minimum(t, u[0] - 1), 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n // bn, tiles),
        in_specs=[pl.BlockSpec((tile, k), lambda j, t, te, u: (live(t, u), 0))]
        + [pl.BlockSpec((1, k, bn), lambda j, t, te, u: (te[t], 0, j))
           for _ in ws],
        out_specs=pl.BlockSpec((tile, bn),
                               lambda j, t, te, u: (live(t, u), j)),
    )
    with x64_off():
        return pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary")),
            interpret=interpret,
        )(tile_expert.astype(jnp.int32),
          jnp.reshape(used, (1,)).astype(jnp.int32), x, *ws)


@functools.partial(jit_x64_off, static_argnames=("tile", "interpret"))
def moe_grouped_swiglu(x, gate_w, up_w, tile_expert, used, tile,
                       interpret=False):
    """silu(x Wg[e]) * (x Wu[e]) for every row tile, gate and up in one
    pass over x. x [R, H], R a multiple of `tile`; gate_w/up_w [E, H, I];
    tile_expert [R/tile] int32, the expert of each tile (a tile past
    `used` repeats the last live tile's); used: the number of live tiles.
    Returns [R, I]; rows of skipped tiles are not written."""
    return _ragged_call(_ragged_swiglu_kernel, x, (gate_w, up_w),
                        tile_expert, used, tile, interpret)


@functools.partial(jit_x64_off, static_argnames=("tile", "interpret"))
def moe_grouped_matmul(x, w, tile_expert, used, tile, interpret=False):
    """x W[e] for every row tile: x [R, K], w [E, K, N]; the rest as
    :func:`moe_grouped_swiglu`. Returns [R, N]."""
    return _ragged_call(_ragged_matmul_kernel, x, (w,), tile_expert, used,
                        tile, interpret)


def _int_zero(a):
    return np.zeros(a.shape, jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def ragged_swiglu(x, gate_w, up_w, tile_expert, used, tile, interpret):
    """:func:`moe_grouped_swiglu` with a backward: the composite's (each
    tile against its expert's gathered weights; serving never takes it)."""
    return moe_grouped_swiglu(x, gate_w, up_w, tile_expert, used, tile=tile,
                              interpret=interpret)


def _ragged_swiglu_fwd(x, gate_w, up_w, tile_expert, used, tile, interpret):
    return ragged_swiglu(x, gate_w, up_w, tile_expert, used, tile,
                         interpret), (x, gate_w, up_w, tile_expert, used)


def _ragged_swiglu_bwd(tile, interpret, saved, g):
    x, gate_w, up_w, tile_expert, used = saved
    _, vjp = jax.vjp(lambda a, b, c: reference_grouped_swiglu(
        a, b, c, tile_expert, tile), x, gate_w, up_w)
    return (*vjp(g), _int_zero(tile_expert), _int_zero(used))


ragged_swiglu.defvjp(_ragged_swiglu_fwd, _ragged_swiglu_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def ragged_matmul(x, w, tile_expert, used, tile, interpret):
    """:func:`moe_grouped_matmul` with the composite's backward."""
    return moe_grouped_matmul(x, w, tile_expert, used, tile=tile,
                              interpret=interpret)


def _ragged_matmul_fwd(x, w, tile_expert, used, tile, interpret):
    return ragged_matmul(x, w, tile_expert, used, tile, interpret), \
        (x, w, tile_expert, used)


def _ragged_matmul_bwd(tile, interpret, saved, g):
    x, w, tile_expert, used = saved
    _, vjp = jax.vjp(lambda a, b: reference_grouped_matmul_ragged(
        a, b, tile_expert, tile), x, w)
    return (*vjp(g), _int_zero(tile_expert), _int_zero(used))


ragged_matmul.defvjp(_ragged_matmul_fwd, _ragged_matmul_bwd)


def reference_grouped_swiglu(x, gate_w, up_w, tile_expert, tile):
    """What runs without the kernel (the CPU tests' sizes): each tile
    against its expert's gathered weights, f32 accumulation."""
    xt = x.reshape(-1, tile, x.shape[-1])
    g = jnp.einsum("tmh,thi->tmi", xt, gate_w[tile_expert],
                   preferred_element_type=jnp.float32)
    u = jnp.einsum("tmh,thi->tmi", xt, up_w[tile_expert],
                   preferred_element_type=jnp.float32)
    return (g * jax.nn.sigmoid(g) * u).astype(x.dtype).reshape(
        x.shape[0], -1)


def reference_grouped_matmul_ragged(x, w, tile_expert, tile):
    xt = x.reshape(-1, tile, x.shape[-1])
    return jnp.einsum("tmk,tkn->tmn", xt, w[tile_expert],
                      preferred_element_type=jnp.float32
                      ).astype(x.dtype).reshape(x.shape[0], -1)


def pk_examples():
    """Representative invocations for the kernel analyzer (PK tier)."""
    s = jax.ShapeDtypeStruct
    return [
        ("grouped_gemm", _grouped_call,
         (s((8, 256, 1024), jnp.bfloat16), s((8, 1024, 4096), jnp.bfloat16),
          s((8,), jnp.int32)), dict(interpret=False)),
        ("moe_grouped_swiglu", moe_grouped_swiglu,
         (s((2560, 2048), jnp.bfloat16), s((128, 2048, 1024), jnp.bfloat16),
          s((128, 2048, 1024), jnp.bfloat16), s((160,), jnp.int32),
          s((), jnp.int32)), dict(tile=16)),
        ("moe_grouped_matmul", moe_grouped_matmul,
         (s((2560, 1024), jnp.bfloat16), s((128, 1024, 2048), jnp.bfloat16),
          s((160,), jnp.int32), s((), jnp.int32)), dict(tile=16)),
    ]
