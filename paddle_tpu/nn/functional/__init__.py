"""`paddle.nn.functional` equivalent (reference: python/paddle/nn/functional/).

Re-exports activation primitives from the op library and adds the layer-level
functionals: linear/embedding/norms/conv/pool/dropout/losses/attention.
"""

from __future__ import annotations

import math

import numpy as np
import jax
import jax.numpy as jnp

from ...core import dtype as dtypes
from ...core import generator as gen_mod
from ...core.tensor import Tensor, as_tensor
from ...autograd.function import apply
from ...ops.activation import *  # noqa: F401,F403
from ...ops.activation import __all__ as _act_all
from ...ops.creation import one_hot  # noqa: F401
from ...ops.manipulation import pad  # noqa: F401
from .loss import *  # noqa: F401,F403
from .loss import __all__ as _loss_all
from .conv import *  # noqa: F401,F403
from .conv import __all__ as _conv_all
from .pooling import *  # noqa: F401,F403
from .pooling import __all__ as _pool_all
from .vision import *  # noqa: F401,F403
from .vision import __all__ as _vision_all

__all__ = list(_act_all) + list(_loss_all) + list(_conv_all) + list(_pool_all) + list(_vision_all) + [
    "linear", "embedding", "layer_norm", "rms_norm", "fused_rms_norm_add",
    "fused_dropout_add_norm", "batch_norm", "group_norm",
    "instance_norm", "normalize", "dropout", "dropout2d", "dropout3d",
    "alpha_dropout", "cosine_similarity", "pairwise_distance", "one_hot", "pad",
    "scaled_dot_product_attention", "sparse_attention", "interpolate",
    "upsample", "pixel_shuffle",
    "unfold", "label_smooth", "sequence_mask", "gumbel_softmax", "rope",
    "gather_tree", "elu_", "hardtanh_", "leaky_relu_", "softmax_",
    "thresholded_relu_",
]


def linear(x, weight, bias=None, name=None) -> Tensor:
    """y = x @ W (+ b); W stored [in_features, out_features] like the reference
    (paddle/phi/kernels/impl/matmul_kernel_impl.h dispatch via matmul)."""
    if bias is None:
        return apply(lambda a, w: a @ w, x, weight, name="linear")
    return apply(lambda a, w, b: a @ w + b, x, weight, bias, name="linear")


def embedding(x, weight, padding_idx=None, sparse=False, name=None) -> Tensor:
    idx = as_tensor(x)._data

    def f(w):
        out = jnp.take(w, idx, axis=0)
        if padding_idx is not None:
            out = jnp.where((idx == padding_idx)[..., None],
                            jnp.zeros((), out.dtype), out)
        return out
    return apply(f, weight, name="embedding")


def layer_norm(x, normalized_shape=None, weight=None, bias=None, epsilon=1e-5,
               name=None) -> Tensor:
    if isinstance(normalized_shape, int):
        normalized_shape = (normalized_shape,)
    n_axes = len(normalized_shape) if normalized_shape is not None else 1

    def f(a, *wb):
        axes = tuple(range(a.ndim - n_axes, a.ndim))
        # compute statistics in float32 for bf16 stability (XLA fuses the cast)
        af = a.astype(jnp.float32) if a.dtype in (jnp.bfloat16, jnp.float16) else a
        mean = jnp.mean(af, axis=axes, keepdims=True)
        var = jnp.var(af, axis=axes, keepdims=True)
        out = (af - mean) * jax.lax.rsqrt(var + epsilon)
        out = out.astype(a.dtype)
        it = iter(wb)
        if weight is not None:
            out = out * next(it)
        if bias is not None:
            out = out + next(it)
        return out
    args = [x] + ([weight] if weight is not None else []) + \
        ([bias] if bias is not None else [])
    return apply(f, *args, name="layer_norm")


def rms_norm(x, weight=None, epsilon=1e-6, name=None) -> Tensor:
    """RMSNorm (reference: fused rms_norm kernel,
    paddle/phi/kernels/fusion/gpu/fused_rms_norm*). On TPU with a weight this
    dispatches to the fused Pallas forward+backward kernel."""
    from ...core.flags import flag
    from ...ops.kernels import _common as kern

    if weight is not None and kern.available() and flag("use_pallas_kernels"):
        from ...ops.kernels.rms_norm_pallas import rms_norm_fused
        return apply(
            lambda a, w: rms_norm_fused(a, w, None, epsilon,
                                        kern.interpret_mode())[0],
            x, weight, name="rms_norm")

    def f(a, *w):
        af = a.astype(jnp.float32) if a.dtype in (jnp.bfloat16, jnp.float16) else a
        ms = jnp.mean(jnp.square(af), axis=-1, keepdims=True)
        out = (af * jax.lax.rsqrt(ms + epsilon)).astype(a.dtype)
        if w:
            out = out * w[0]
        return out
    args = [x] + ([weight] if weight is not None else [])
    return apply(f, *args, name="rms_norm")


def fused_rms_norm_add(x, residual, weight, epsilon=1e-6, name=None):
    """(rmsnorm(x + residual) * weight, x + residual) — the pre-norm residual
    block primitive (reference: fused_rms_norm residual variants). One fused
    VMEM pass on TPU; XLA composite elsewhere."""
    from ...core.flags import flag
    from ...ops.kernels import _common as kern

    from ...autograd.function import apply_multi

    if kern.available() and flag("use_pallas_kernels"):
        from ...ops.kernels.rms_norm_pallas import rms_norm_fused
        return apply_multi(
            lambda a, r, w: rms_norm_fused(a, w, r, epsilon,
                                           kern.interpret_mode()),
            x, residual, weight, name="fused_rms_norm_add")

    def f(a, r, w):
        h = a + r
        hf = h.astype(jnp.float32) if h.dtype in (jnp.bfloat16, jnp.float16) else h
        ms = jnp.mean(jnp.square(hf), axis=-1, keepdims=True)
        return (hf * jax.lax.rsqrt(ms + epsilon)).astype(h.dtype) * w, h
    return apply_multi(f, x, residual, weight, name="fused_rms_norm_add")


def fused_dropout_add_norm(x, residual, weight, bias=None, p=0.0,
                           epsilon=1e-6, norm="rms", activation=None,
                           seed=None, training=True, name=None):
    """Transformer-block mega-kernel epilogue: ``activation(x)`` ->
    dropout -> ``+ residual`` -> rms/layer norm as ONE VMEM-resident
    Pallas pass on TPU (ops/kernels/block_fused_pallas.py, with a fused
    custom_vjp backward); identical-semantics XLA composite elsewhere.
    Returns ``(y, h)`` — the normalized output and the pre-norm residual
    sum (the next junction's residual stream).

    ``norm``: "rms" (no bias) | "layer". ``activation``: None (a
    projection output feeds the junction directly — the in-model case) |
    "gelu" (tanh form) | "swiglu" (x packed ``[.., 2I]``, residual
    ``[.., I]``). The dropout mask is a counter-hash of (seed, element
    index) — pass ``seed`` for a deterministic/per-step stream; without
    one a seed is drawn from the framework RNG at trace time (constant
    across steps inside ``to_static``, like ``fused_dropout_add``)."""
    from ...core.flags import flag
    from ...ops.kernels import _common as kern
    from ...ops.kernels import block_fused_pallas as bfp
    from ...autograd.function import apply_multi

    xt, rt = as_tensor(x), as_tensor(residual)
    p_eff = float(p) if training else 0.0
    if seed is None:
        if 0.0 < p_eff < 1.0:
            key = gen_mod.default_generator.split()
            seed = jax.random.randint(key, (), 0, 2147483647,
                                      dtype=jnp.int32)
        else:
            seed = 0
    seed_t = as_tensor(jnp.asarray(as_tensor(seed)._data, jnp.int32))

    use_kern = (kern.available() and flag("use_pallas_kernels")
                and bfp.use_kernel(tuple(xt.shape), tuple(rt.shape),
                                   activation))
    args = [xt, rt, weight] + ([bias] if bias is not None else [])
    has_bias = bias is not None

    if use_kern:
        def f(a, r, w, *rest):
            b = rest[0] if has_bias else None
            return bfp.fused_epilogue(a, r, w, b, seed_t._data, p_eff,
                                      epsilon, activation, norm, None,
                                      kern.interpret_mode())
    else:
        def f(a, r, w, *rest):
            b = rest[0] if has_bias else None
            return bfp.reference_fused_epilogue(a, r, w, b, seed_t._data,
                                                p_eff, epsilon, activation,
                                                norm)
    return apply_multi(f, *args, name="fused_dropout_add_norm")


def batch_norm(x, running_mean, running_var, weight=None, bias=None,
               training=False, momentum=0.9, epsilon=1e-5, data_format="NCHW",
               use_global_stats=None, name=None) -> Tensor:
    ch_axis = 1 if data_format.startswith("NC") else -1
    rm, rv = as_tensor(running_mean), as_tensor(running_var)
    use_batch = training and not use_global_stats

    def f(a, *wb):
        axes = tuple(i for i in range(a.ndim) if i != ch_axis % a.ndim)
        if use_batch:
            mean = jnp.mean(a, axis=axes)
            var = jnp.var(a, axis=axes)
        else:
            mean, var = rm._data, rv._data
        shape = [1] * a.ndim
        shape[ch_axis % a.ndim] = a.shape[ch_axis % a.ndim]
        out = (a - mean.reshape(shape)) * jax.lax.rsqrt(var.reshape(shape) + epsilon)
        it = iter(wb)
        if weight is not None:
            out = out * next(it).reshape(shape)
        if bias is not None:
            out = out + next(it).reshape(shape)
        return out

    if use_batch:
        # update running stats eagerly (matches reference kernel semantics)
        a = as_tensor(x)._data
        axes = tuple(i for i in range(a.ndim) if i != ch_axis % a.ndim)
        bm = jnp.mean(a, axis=axes)
        bv = jnp.var(a, axis=axes)
        rm._data = momentum * rm._data + (1 - momentum) * bm.astype(rm._data.dtype)
        rv._data = momentum * rv._data + (1 - momentum) * bv.astype(rv._data.dtype)

    args = [x] + ([weight] if weight is not None else []) + \
        ([bias] if bias is not None else [])
    return apply(f, *args, name="batch_norm")


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5,
               data_format="NCHW", name=None) -> Tensor:
    def f(a, *wb):
        if data_format.startswith("NC"):
            n, c = a.shape[0], a.shape[1]
            rest = a.shape[2:]
            g = a.reshape((n, num_groups, c // num_groups) + rest)
            axes = tuple(range(2, g.ndim))
            mean = jnp.mean(g, axis=axes, keepdims=True)
            var = jnp.var(g, axis=axes, keepdims=True)
            out = ((g - mean) * jax.lax.rsqrt(var + epsilon)).reshape(a.shape)
            shape = [1] * a.ndim
            shape[1] = c
        else:
            n, c = a.shape[0], a.shape[-1]
            rest = a.shape[1:-1]
            g = a.reshape((n,) + rest + (num_groups, c // num_groups))
            axes = tuple(range(1, g.ndim - 2)) + (g.ndim - 1,)
            mean = jnp.mean(g, axis=axes, keepdims=True)
            var = jnp.var(g, axis=axes, keepdims=True)
            out = ((g - mean) * jax.lax.rsqrt(var + epsilon)).reshape(a.shape)
            shape = [1] * a.ndim
            shape[-1] = c
        it = iter(wb)
        if weight is not None:
            out = out * next(it).reshape(shape)
        if bias is not None:
            out = out + next(it).reshape(shape)
        return out
    args = [x] + ([weight] if weight is not None else []) + \
        ([bias] if bias is not None else [])
    return apply(f, *args, name="group_norm")


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5,
                  data_format="NCHW", name=None) -> Tensor:
    def f(a, *wb):
        axes = tuple(range(2, a.ndim)) if data_format.startswith("NC") \
            else tuple(range(1, a.ndim - 1))
        mean = jnp.mean(a, axis=axes, keepdims=True)
        var = jnp.var(a, axis=axes, keepdims=True)
        out = (a - mean) * jax.lax.rsqrt(var + eps)
        c_axis = 1 if data_format.startswith("NC") else a.ndim - 1
        shape = [1] * a.ndim
        shape[c_axis] = a.shape[c_axis]
        it = iter(wb)
        if weight is not None:
            out = out * next(it).reshape(shape)
        if bias is not None:
            out = out + next(it).reshape(shape)
        return out
    args = [x] + ([weight] if weight is not None else []) + \
        ([bias] if bias is not None else [])
    return apply(f, *args, name="instance_norm")


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None) -> Tensor:
    def f(a):
        n = jnp.linalg.norm(a, ord=p, axis=axis, keepdims=True)
        return a / jnp.maximum(n, epsilon)
    return apply(f, x, name="normalize")


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None, rng_key=None) -> Tensor:
    """Dropout. Inside jitted code pass `rng_key` for per-step randomness;
    eagerly a fresh key is drawn from the global generator (reference RNG
    isolation semantics: fleet/layers/mpu/random.py)."""
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return apply(lambda a: a * (1.0 - p), x, name="dropout_infer")
        return as_tensor(x) if not isinstance(x, Tensor) else x
    key = rng_key if rng_key is not None else gen_mod.default_generator.split()

    def f(a):
        shape = a.shape if axis is None else tuple(
            a.shape[i] if i in np.atleast_1d(axis) else 1 for i in range(a.ndim))
        keep = jax.random.bernoulli(key, 1.0 - p, shape)
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), jnp.zeros((), a.dtype))
        return jnp.where(keep, a, jnp.zeros((), a.dtype))
    return apply(f, x, name="dropout")


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None) -> Tensor:
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None) -> Tensor:
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None) -> Tensor:
    if not training or p == 0.0:
        return as_tensor(x) if not isinstance(x, Tensor) else x
    key = gen_mod.default_generator.split()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    def f(a):
        keep = jax.random.bernoulli(key, 1.0 - p, a.shape)
        q = 1.0 - p
        coef_a = (q + alpha_p ** 2 * q * p) ** -0.5
        coef_b = -coef_a * alpha_p * p
        return coef_a * jnp.where(keep, a, jnp.asarray(alpha_p, a.dtype)) + coef_b
    return apply(f, x, name="alpha_dropout")


def cosine_similarity(x1, x2, axis=1, eps=1e-8, name=None) -> Tensor:
    def f(a, b):
        d = jnp.sum(a * b, axis=axis)
        na = jnp.linalg.norm(a, axis=axis)
        nb = jnp.linalg.norm(b, axis=axis)
        return d / jnp.maximum(na * nb, eps)
    return apply(f, x1, x2, name="cosine_similarity")


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False, name=None) -> Tensor:
    return apply(lambda a, b: jnp.linalg.norm(a - b + epsilon, ord=p, axis=-1,
                                              keepdims=keepdim), x, y,
                 name="pairwise_distance")


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None) -> Tensor:
    def f(l, *pd):
        k = l.shape[-1]
        if pd:
            return (1 - epsilon) * l + epsilon * pd[0]
        return (1 - epsilon) * l + epsilon / k
    args = [label] + ([prior_dist] if prior_dist is not None else [])
    return apply(f, *args, name="label_smooth")


def sequence_mask(lengths, maxlen=None, dtype="int64", name=None) -> Tensor:
    l = as_tensor(lengths)._data
    m = int(maxlen) if maxlen is not None else int(jnp.max(l))
    mask = jnp.arange(m) < l[..., None]
    return Tensor(mask.astype(dtypes.dtype_from_any(dtype).np_dtype))


def gumbel_softmax(x, temperature=1.0, hard=False, axis=-1, name=None) -> Tensor:
    key = gen_mod.default_generator.split()

    def f(a):
        g = jax.random.gumbel(key, a.shape, a.dtype)
        y = jax.nn.softmax((a + g) / temperature, axis=axis)
        if hard:
            idx = jnp.argmax(y, axis=axis, keepdims=True)
            one = (jnp.arange(y.shape[axis]) ==
                   jnp.moveaxis(idx, axis, -1)).astype(y.dtype)
            y_hard = jnp.moveaxis(one, -1, axis)
            return y_hard + y - jax.lax.stop_gradient(y)
        return y
    return apply(f, x, name="gumbel_softmax")


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False, training=True,
                                 name=None, window=None) -> Tensor:
    """SDPA with [batch, seq, heads, head_dim] layout (reference:
    paddle/phi/kernels/gpu/flash_attn_kernel.cu API). Uses the Pallas flash
    kernel on TPU when enabled, else an XLA-fused reference path. `window`
    (with `is_causal`): query i sees key j iff ``i - window < j <= i``."""
    from ...core.flags import flag
    from ...ops.kernels import flash_attention as fa
    mask_arr = as_tensor(attn_mask)._data if attn_mask is not None else None

    if fa.available() and flag("use_pallas_kernels") and dropout_p == 0.0 \
            and mask_arr is None:
        return apply(lambda q, k, v: fa.flash_attention(
            q, k, v, causal=is_causal, window=window),
            query, key, value, name="flash_attention")
    if window is not None and not is_causal:
        raise ValueError("window needs is_causal=True")

    drop_key = gen_mod.default_generator.split() if dropout_p > 0.0 and training \
        else None

    def f(q, k, v):
        k, v = fa.expand_kv_heads(q, k, v)  # GQA composite fallback
        # [B, S, H, D] -> [B, H, S, D]
        qh, kh, vh = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
        scale = 1.0 / math.sqrt(q.shape[-1])
        logits = jnp.einsum("bhsd,bhtd->bhst", qh, kh) * scale
        logits = logits.astype(jnp.float32)
        if is_causal:
            s, t = logits.shape[-2], logits.shape[-1]
            causal = jnp.tril(jnp.ones((s, t), bool), t - s)
            if window is not None:
                causal &= ~jnp.tril(jnp.ones((s, t), bool), t - s - window)
            logits = jnp.where(causal, logits, -jnp.inf)
        if mask_arr is not None:
            if jnp.issubdtype(mask_arr.dtype, jnp.bool_):
                logits = jnp.where(mask_arr, logits, -jnp.inf)
            else:
                logits = logits + mask_arr.astype(logits.dtype)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
        if drop_key is not None:
            keep = jax.random.bernoulli(drop_key, 1.0 - dropout_p, probs.shape)
            probs = jnp.where(keep, probs / (1.0 - dropout_p),
                              jnp.zeros((), probs.dtype))
        out = jnp.einsum("bhst,bhtd->bhsd", probs, vh)
        return jnp.swapaxes(out, 1, 2)
    return apply(f, query, key, value, name="scaled_dot_product_attention")


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None) -> Tensor:
    """Block-sparse attention over a CSR pattern (reference:
    python/paddle/nn/functional/sparse_attention.py over
    sparse_attention kernels). query/key/value: [B, H, S, D]; offset
    [B, H, S+1], columns [B, H, nnz] give each query row's attended keys.

    TPU design: the ragged CSR is expanded host-side to flat (row, col) edge
    lists (the pattern is static data, exactly how the reference feeds its
    kernel), then the edge-wise scores are computed densely on the VPU and
    reduced with segment softmax — no S×S materialization.
    """
    import numpy as np

    q, k, v = as_tensor(query), as_tensor(key), as_tensor(value)
    off = np.asarray(as_tensor(sparse_csr_offset).numpy(), np.int64)
    cols = np.asarray(as_tensor(sparse_csr_columns).numpy(), np.int64)
    b, h, s, d = q.shape
    counts = off[..., 1:] - off[..., :-1]          # [B, H, S]
    # cols has one fixed nnz per (b,h); expand each CSR offset row to a flat
    # row-index list of that same length
    rows = np.stack([np.repeat(np.arange(s), counts[bi, hi])
                     for bi in range(b) for hi in range(h)]).reshape(b, h, -1)
    rows_j = jnp.asarray(rows)
    cols_j = jnp.asarray(cols)

    # key_padding_mask: [B, S] (0/False = padded key); attn_mask: additive
    # [B, H, S, S] or broadcastable — both gathered down to per-edge values
    kpm = (as_tensor(key_padding_mask)._data
           if key_padding_mask is not None else None)
    am = as_tensor(attn_mask)._data if attn_mask is not None else None

    def f(qa, ka, va):
        scale = 1.0 / math.sqrt(d)
        nnz = rows_j.shape[-1]
        bh_b = jnp.repeat(jnp.arange(b), h)  # batch id per (b*h) slice
        bh_h = jnp.tile(jnp.arange(h), b)

        def one(qbh, kbh, vbh, r, c, bi, hi):
            e = jnp.sum(jnp.take(qbh, r, axis=0) * jnp.take(kbh, c, axis=0),
                        -1) * scale                      # [nnz]
            e = e.astype(jnp.float32)
            if am is not None:
                amb = jnp.broadcast_to(am, (b, h, s, s)).astype(jnp.float32)
                e = e + amb[bi, hi][r, c]
            if kpm is not None:
                keep = jnp.broadcast_to(kpm, (b, s))[bi]
                if jnp.issubdtype(keep.dtype, jnp.bool_):
                    dead = ~jnp.take(keep, c)
                else:
                    dead = jnp.take(keep, c) == 0
                e = jnp.where(dead, -jnp.inf, e)
            m = jax.ops.segment_max(e, r, num_segments=s)
            m = jnp.where(jnp.isfinite(m), m, 0.0)  # fully-masked rows
            p = jnp.exp(e - jnp.take(m, r))
            z = jax.ops.segment_sum(p, r, num_segments=s)
            w = p / jnp.take(jnp.maximum(z, 1e-30), r)
            return jax.ops.segment_sum(
                w[:, None].astype(vbh.dtype) * jnp.take(vbh, c, axis=0), r,
                num_segments=s)

        flat = jax.vmap(one)(qa.reshape(b * h, s, d), ka.reshape(b * h, s, d),
                             va.reshape(b * h, s, d),
                             rows_j.reshape(b * h, -1),
                             cols_j.reshape(b * h, -1), bh_b, bh_h)
        return flat.reshape(b, h, s, d)

    return apply(f, q, k, v, name="sparse_attention")


def rope(q, k, sin, cos, name=None):
    """Rotary position embedding applied to q and k
    (reference: fused_rope kernel, paddle/phi/kernels/fusion/gpu/fused_rope*).

    On TPU this dispatches to the fused Pallas kernel (one VMEM pass per
    tensor; the adjoint reuses the same kernel with -sin), falling back to
    the XLA composite elsewhere."""
    from ...core.flags import flag
    from ...ops.kernels import _common as kern
    sin_a, cos_a = as_tensor(sin)._data, as_tensor(cos)._data

    qt, kt = as_tensor(q), as_tensor(k)

    def _kernel_ok(t):
        return (t.ndim == 4 and t.shape[-1] % 2 == 0
                and cos_a.size == t.shape[1] * t.shape[-1])

    # both tensors ride the same kernel path, so BOTH layouts must fit it
    # (a 3-D or different-seq-len k the composite accepts must not crash
    # inside rope_apply's [b, s, h, d] unpack)
    use_kernel = (kern.available() and flag("use_pallas_kernels")
                  and _kernel_ok(qt) and _kernel_ok(kt))
    if use_kernel:
        from ...ops.kernels import rope_pallas as rp

        def fq(a):
            return rp.rope_apply(a, cos_a, sin_a, kern.interpret_mode())
    else:
        def rot(a):
            a1, a2 = jnp.split(a, 2, axis=-1)
            return jnp.concatenate([-a2, a1], axis=-1)

        def fq(a):
            return a * cos_a.astype(a.dtype) + rot(a) * sin_a.astype(a.dtype)
    q_out = apply(fq, q, name="rope_q")
    k_out = apply(fq, k, name="rope_k")
    return q_out, k_out


def interpolate(x, size=None, scale_factor=None, mode="nearest",
                align_corners=False, align_mode=0, data_format="NCHW",
                name=None) -> Tensor:
    x_t = as_tensor(x) if not isinstance(x, Tensor) else x
    nd = x_t.ndim
    spatial = nd - 2
    if data_format.startswith("NC"):
        sp_axes = list(range(2, nd))
    else:
        sp_axes = list(range(1, nd - 1))
    if size is not None:
        if isinstance(size, Tensor):
            size = size.tolist()
        tgt = [int(s) for s in np.atleast_1d(size)]
    else:
        sf = np.atleast_1d(scale_factor).astype(float)
        if sf.size == 1:
            sf = np.repeat(sf, spatial)
        tgt = [int(x_t.shape[a] * s) for a, s in zip(sp_axes, sf)]
    jmode = {"nearest": "nearest", "bilinear": "linear", "linear": "linear",
             "trilinear": "linear", "bicubic": "cubic", "area": "linear"}[mode]

    def f(a):
        shape = list(a.shape)
        for ax, t in zip(sp_axes, tgt):
            shape[ax] = t
        return jax.image.resize(a, shape, method=jmode)
    return apply(f, x_t, name="interpolate")


upsample = interpolate


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None) -> Tensor:
    r = upscale_factor

    def f(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
            a = a.reshape(n, c // (r * r), r, r, h, w)
            a = a.transpose(0, 1, 4, 2, 5, 3)
            return a.reshape(n, c // (r * r), h * r, w * r)
        n, h, w, c = a.shape
        a = a.reshape(n, h, w, r, r, c // (r * r))
        a = a.transpose(0, 1, 3, 2, 4, 5)
        return a.reshape(n, h * r, w * r, c // (r * r))
    return apply(f, x, name="pixel_shuffle")


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None) -> Tensor:
    ks = np.broadcast_to(np.atleast_1d(kernel_sizes), (2,))
    st = np.broadcast_to(np.atleast_1d(strides), (2,))
    pd = np.broadcast_to(np.atleast_1d(paddings), (2,))
    dl = np.broadcast_to(np.atleast_1d(dilations), (2,))

    def f(a):
        n, c, h, w = a.shape
        a = jnp.pad(a, ((0, 0), (0, 0), (pd[0], pd[0]), (pd[1], pd[1])))
        oh = (a.shape[2] - (dl[0] * (ks[0] - 1) + 1)) // st[0] + 1
        ow = (a.shape[3] - (dl[1] * (ks[1] - 1) + 1)) // st[1] + 1
        patches = []
        for i in range(ks[0]):
            for j in range(ks[1]):
                sl = a[:, :, i * dl[0]: i * dl[0] + oh * st[0]: st[0],
                       j * dl[1]: j * dl[1] + ow * st[1]: st[1]]
                patches.append(sl)
        out = jnp.stack(patches, axis=2)  # [N, C, k*k, OH, OW]
        return out.reshape(n, c * ks[0] * ks[1], oh * ow)
    return apply(f, x, name="unfold")


def gather_tree(ids, parents, name=None) -> Tensor:
    """Backtrace beam-search ancestry to full sequences (reference:
    nn/functional/extension.py:135, gather_tree CUDA kernel). ids/parents:
    [max_time, batch, beam]. Implemented as one reverse lax.scan — the
    TPU-native form of the reference's per-timestep backtrack loop."""
    def f(ids_a, par_a):
        ids_i = ids_a.astype(jnp.int64)
        par_i = par_a.astype(jnp.int64)
        t, b, k = ids_i.shape
        b_rows = jnp.arange(b)[:, None]

        def back(beams, xs):
            # beams: [B, K] beam index selecting step t's entries for each
            # final beam; out[t] = ids[t][beams], next = parents[t][beams]
            ids_t, par_t = xs
            out_t = ids_t[b_rows, beams]
            prev = par_t[b_rows, beams]
            return prev, out_t

        init = jnp.broadcast_to(jnp.arange(k, dtype=jnp.int64)[None], (b, k))
        _, outs = jax.lax.scan(back, init, (ids_i, par_i), reverse=True)
        return outs

    return apply(f, ids, parents, name="gather_tree")


# flash attention module surface (reference functional/__init__.py:83
# imports from .flash_attention; flash_attention/flash_attn_unpadded are
# used via the module path paddle.nn.functional.flash_attention.*)
from . import flash_attention  # noqa: F401,E402


# -- in-place activation variants (reference *_ surface; rebind contract) ---

def elu_(x, alpha=1.0, name=None) -> Tensor:
    from ...ops.math import _rebind
    return _rebind(x, elu(x, alpha))


def hardtanh_(x, min=-1.0, max=1.0, name=None) -> Tensor:
    from ...ops.math import _rebind
    return _rebind(x, hardtanh(x, min, max))


def leaky_relu_(x, negative_slope=0.01, name=None) -> Tensor:
    from ...ops.math import _rebind
    return _rebind(x, leaky_relu(x, negative_slope))


def softmax_(x, axis=-1, dtype=None, name=None) -> Tensor:
    from ...ops.math import _rebind
    return _rebind(x, softmax(x, axis=axis, dtype=dtype))


def thresholded_relu_(x, threshold=1.0, value=0.0, name=None) -> Tensor:
    from ...ops.math import _rebind
    return _rebind(x, thresholded_relu(x, threshold, value))
