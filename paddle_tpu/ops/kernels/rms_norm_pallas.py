"""Fused RMSNorm(+residual-add) Pallas TPU kernel, forward and backward.

Reference analog: paddle/phi/kernels/fusion/gpu/fused_rms_norm* (the fused
rmsnorm+bias+residual CUDA kernels). TPU design: one VMEM pass per row block
computes the optional residual add and the normalised output — no
intermediate HBM round trip. Backward recomputes the f32 rstd from the saved
pre-norm activations (cheaper than storing a per-row vector, which would
force an awkward 1-D layout) and fuses the row-local dx with per-block
partial dw accumulation; partials are summed by one XLA reduce.

All pallas_call sites trace under jax.enable_x64(False): the framework
enables x64 globally, which turns index-map/loop literals into i64/f64 —
types Mosaic cannot legalize.

Public entry: `rms_norm_fused(x, weight, residual=None, eps)` with a
custom_vjp; non-TPU callers use the XLA composite (nn.functional.rms_norm
handles the dispatch). Tests run these kernels in interpret mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import x64_off, jit_x64_off


def _fwd_plain_kernel(x_ref, w_ref, o_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                    # [rows, H]
    rstd = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                         + jnp.float32(eps))
    o_ref[...] = (x * rstd * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _fwd_res_kernel(x_ref, res_ref, w_ref, o_ref, h_ref, *, eps):
    h = x_ref[...].astype(jnp.float32) + res_ref[...].astype(jnp.float32)
    rstd = jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True)
                         + jnp.float32(eps))
    o_ref[...] = (h * rstd * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
    h_ref[...] = h.astype(h_ref.dtype)


def _bwd_kernel(h_ref, w_ref, g_ref, dx_ref, dwp_ref, *, hidden, eps):
    """dx (row-local) + this block's partial dw; rstd recomputed from h.

    u = g*w; dx = rstd*u - h * rstd^3/H * rowsum(h*u);
    dw_partial = sum_rows g * h * rstd.
    """
    h = h_ref[...].astype(jnp.float32)                    # [rows, H]
    g = g_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)                    # [1, H]
    rstd = jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True)
                         + jnp.float32(eps))
    u = g * w
    dot = jnp.sum(h * u, axis=-1, keepdims=True)
    dx = rstd * u - h * (rstd * rstd * rstd) * (dot * jnp.float32(1.0 / hidden))
    dx_ref[...] = dx.astype(dx_ref.dtype)
    # [n_blocks, 8, H] output: sublane-dim 8 keeps the layout legal; the
    # wrapper reads row 0 of each block's 8 identical rows.
    dwp_ref[0] = jnp.broadcast_to(
        jnp.sum(g * h * rstd, axis=0, keepdims=True), (8, hidden))


def _pick_rows(n_rows, hidden):
    """Row block under the compiler's 16 MiB scoped-VMEM limit. The
    residual forward holds 4 row blocks (x, res in; y, h out), each double
    buffered, beside ~3 f32 row temporaries: ~11 f32 rows per row of block,
    budgeted against 12 MiB (v5e's compiler refused the 22 MiB the old
    one-buffer count asked for at hidden 4096). Zero pad rows normalise to
    finite values under +eps and contribute nothing to dw. Tunable: the
    auto_tuner's "rms_norm" block override wins when installed."""
    from ._common import pick_row_block
    return pick_row_block(n_rows, hidden * 4 * 11, 12 * 1024 * 1024,
                          key="rms_norm")


def _pad_rows(a, rows):
    from ._common import pad_to_block
    return pad_to_block(a, rows, axis=0)


@functools.partial(jit_x64_off, static_argnames=("eps", "interpret", "rows"))
def _fused_fwd(x2, res2, w, eps, interpret, rows):
    n, h = x2.shape
    x2p = _pad_rows(x2, rows)
    np_ = x2p.shape[0]
    grid = (np_ // rows,)
    row_spec = pl.BlockSpec((rows, h), lambda i: (i, 0))
    w_spec = pl.BlockSpec((1, h), lambda i: (0, 0))
    if res2 is None:
        with x64_off():
            out = pl.pallas_call(
                functools.partial(_fwd_plain_kernel, eps=eps),
                grid=grid,
                in_specs=[row_spec, w_spec],
                out_specs=row_spec,
                out_shape=jax.ShapeDtypeStruct((np_, h), x2.dtype),
                interpret=interpret,
            )(x2p, w.reshape(1, h))
        return out[:n], x2
    with x64_off():
        out, hsum = pl.pallas_call(
            functools.partial(_fwd_res_kernel, eps=eps),
            grid=grid,
            in_specs=[row_spec, row_spec, w_spec],
            out_specs=[row_spec, row_spec],
            out_shape=[jax.ShapeDtypeStruct((np_, h), x2.dtype),
                       jax.ShapeDtypeStruct((np_, h), x2.dtype)],
            interpret=interpret,
        )(x2p, _pad_rows(res2, rows), w.reshape(1, h))
    return out[:n], hsum[:n]


@functools.partial(jit_x64_off, static_argnames=("eps", "interpret", "rows"))
def _fused_bwd(h2, w, g2, eps, interpret, rows):
    n, h = h2.shape
    h2p = _pad_rows(h2, rows)
    np_ = h2p.shape[0]
    grid = (np_ // rows,)
    row_spec = pl.BlockSpec((rows, h), lambda i: (i, 0))
    with x64_off():
        dx, dw_part = pl.pallas_call(
            functools.partial(_bwd_kernel, hidden=h, eps=eps),
            grid=grid,
            in_specs=[row_spec,
                      pl.BlockSpec((1, h), lambda i: (0, 0)),
                      row_spec],
            out_specs=[row_spec, pl.BlockSpec((1, 8, h), lambda i: (i, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct((np_, h), h2.dtype),
                       jax.ShapeDtypeStruct((np_ // rows, 8, h), jnp.float32)],
            interpret=interpret,
        )(h2p, w.reshape(1, h), _pad_rows(g2, rows))
    return dx[:n], jnp.sum(dw_part[:, 0, :], axis=0)


def _run_fwd(x, weight, residual, eps, interpret):
    """((y, summed_residual_or_None), (hsum2d, shape)) — single forward body
    shared by the primal and vjp paths."""
    shp = x.shape
    h = shp[-1]
    x2 = x.reshape(-1, h)
    has_res = residual is not None
    res2 = residual.reshape(-1, h) if has_res else None
    out, hsum = _fused_fwd(x2, res2, weight, eps, interpret,
                           rows=_pick_rows(x2.shape[0], h))
    outs = (out.reshape(shp), hsum.reshape(shp) if has_res else None)
    return outs, (hsum, has_res)


def _primal(x, weight, residual, eps, interpret=False):
    """(y, summed_residual_or_None)."""
    return _run_fwd(x, weight, residual, eps, interpret)[0]


rms_norm_fused = jax.custom_vjp(_primal, nondiff_argnums=(3, 4))


def _vjp_fwd(x, weight, residual, eps, interpret):
    outs, (hsum, has_res) = _run_fwd(x, weight, residual, eps, interpret)
    return outs, (hsum, weight, x.shape, has_res)


def _vjp_bwd(eps, interpret, saved, grads):
    hsum, weight, shp, has_res = saved
    g_out, g_h = grads
    h = shp[-1]
    g2 = g_out.reshape(-1, h)
    dx, dw = _fused_bwd(hsum, weight, g2, eps, interpret,
                        rows=_pick_rows(hsum.shape[0], h))
    dx = dx.reshape(shp)
    if g_h is not None:
        dx = dx + g_h.reshape(shp)  # residual-stream cotangent joins dx
    # d(residual) == d(x): both feed the same pre-norm sum
    return dx, dw.astype(weight.dtype), (dx if has_res else None)


rms_norm_fused.defvjp(_vjp_fwd, _vjp_bwd)


def pk_examples():
    """Representative invocations for the kernel analyzer (PK tier)."""
    s = jax.ShapeDtypeStruct
    bf16 = jnp.bfloat16
    x = s((512, 1024), bf16)
    vec = s((1024,), bf16)
    kw = dict(eps=1e-6, interpret=False, rows=128)
    return [
        ("rms_fwd_plain", _fused_fwd, (x, None, vec), kw),
        ("rms_fwd_res", _fused_fwd, (x, x, vec), kw),
        ("rms_bwd", _fused_bwd, (x, vec, x), kw),
    ]
