"""Fused Adam/AdamW parameter-update Pallas TPU kernel.

Reference analog: paddle/phi/kernels/fusion/gpu/fused_adam_kernel.cu and the
distributed_fused_lamb family — one kernel per step that reads (w32, g, m, v)
and writes (w32', m', v', p_out) in a single pass. Under jit XLA already
fuses the jnp update chain reasonably, but it keeps the f32 master weights,
two moments and the model-dtype copy as separate fusions with their own HBM
round trips; this kernel does the whole decoupled-decay update — moments,
bias correction, decay, write-back, low-precision cast — in one VMEM pass
per block, which on an HBM-bound optimizer step is the difference that
matters.

Scalars (lr, 1/bias_corr1, 1/bias_corr2) arrive as a tiny (1, 4) f32 operand
so a jitted train step with an LR schedule never recompiles; betas/eps/decay
are Python-static per parameter group. Tests run interpret mode on CPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._common import pad_tail, padded_rows as _padded_rows, x64_off

_LANES = 128


def _adamw_kernel(s_ref, w_ref, g_ref, m_ref, v_ref,
                  wo_ref, mo_ref, vo_ref, po_ref,
                  *, beta1, beta2, eps, wd):
    lr = s_ref[0, 0]
    inv_bc1 = s_ref[0, 1]
    inv_bc2 = s_ref[0, 2]
    w = w_ref[...]                                   # f32 master weights
    g = g_ref[...].astype(jnp.float32)
    m = jnp.float32(beta1) * m_ref[...] + jnp.float32(1 - beta1) * g
    v = jnp.float32(beta2) * v_ref[...] + jnp.float32(1 - beta2) * (g * g)
    mhat = m * inv_bc1
    vhat = v * inv_bc2
    # every multiply keeps a VECTOR operand (a ref-loaded scalar is a 0-d
    # vector to Mosaic)
    w = w - (w * lr) * jnp.float32(wd)
    w = w - (mhat / (jnp.sqrt(vhat) + jnp.float32(eps))) * lr
    wo_ref[...] = w
    mo_ref[...] = m
    vo_ref[...] = v
    po_ref[...] = w.astype(po_ref.dtype)




@functools.partial(
    jax.jit,
    static_argnames=("beta1", "beta2", "eps", "wd", "out_dtype", "interpret"))
def _adamw_call(w32, g, m, v, scalars, *, beta1, beta2, eps, wd, out_dtype,
                interpret):
    n = w32.size
    rows, br = _padded_rows(-(-n // _LANES))
    pad = rows * _LANES - n

    def to2d(a, dt):
        flat = a.reshape(-1).astype(dt)
        if pad:
            flat = pad_tail(flat, pad)
        return flat.reshape(rows, _LANES)

    w2 = to2d(w32, jnp.float32)
    g2 = to2d(g, jnp.float32)
    m2 = to2d(m, jnp.float32)
    v2 = to2d(v, jnp.float32)

    grid = (rows // br,)
    blk = pl.BlockSpec((br, _LANES), lambda i: (i, 0))
    s_spec = pl.BlockSpec((1, 4), lambda i: (0, 0))
    f32 = jnp.float32
    with x64_off():
        wo, mo, vo, po = pl.pallas_call(
            functools.partial(_adamw_kernel, beta1=beta1, beta2=beta2,
                              eps=eps, wd=wd),
            grid=grid,
            in_specs=[s_spec, blk, blk, blk, blk],
            out_specs=[blk, blk, blk, blk],
            out_shape=[jax.ShapeDtypeStruct((rows, _LANES), f32),
                       jax.ShapeDtypeStruct((rows, _LANES), f32),
                       jax.ShapeDtypeStruct((rows, _LANES), f32),
                       jax.ShapeDtypeStruct((rows, _LANES), out_dtype)],
            interpret=interpret,
        )(scalars, w2, g2, m2, v2)

    def back(a2, shape):
        return a2.reshape(-1)[:n].reshape(shape)

    shp = w32.shape
    return (back(wo, shp), back(mo, shp), back(vo, shp), back(po, shp))


def adamw_update(w32, g, m, v, lr, step, *, beta1, beta2, eps, wd,
                 out_dtype, interpret=False):
    """One fused decoupled-decay Adam step.

    Returns (w32', m', v', p_out) where p_out is w32' cast to `out_dtype`.
    `lr`/`step` are traced device scalars (no recompile when a scheduler
    moves them); beta/eps/wd are static per parameter group.
    """
    t = jnp.asarray(step, jnp.float32)
    inv_bc1 = 1.0 / (1.0 - jnp.float32(beta1) ** t)
    inv_bc2 = 1.0 / (1.0 - jnp.float32(beta2) ** t)
    scalars = jnp.stack(
        [jnp.asarray(lr, jnp.float32), inv_bc1, inv_bc2,
         jnp.float32(0.0)]).reshape(1, 4)
    return _adamw_call(w32, g, m, v, scalars, beta1=float(beta1),
                       beta2=float(beta2), eps=float(eps), wd=float(wd),
                       out_dtype=jnp.dtype(out_dtype), interpret=interpret)


def pk_examples():
    """Representative invocations for the kernel analyzer (PK tier)."""
    s = jax.ShapeDtypeStruct
    f32 = jnp.float32
    arrs = (s((4096, 1024), f32),) * 4
    return [
        ("adamw_update", adamw_update,
         arrs + (s((), f32), s((), f32)),
         dict(beta1=0.9, beta2=0.999, eps=1e-8, wd=0.01,
              out_dtype=jnp.bfloat16)),
    ]
