"""Flash attention for TPU.

Reference analog: paddle/phi/kernels/gpu/flash_attn_kernel.cu (FA2 glue).
Here: Pallas TPU kernels for BOTH forward and backward (FlashAttention-2
blocked online-softmax forward saving logsumexp; fused dq / dkv backward
kernels — no O(S^2) materialisation in either direction). Layout matches the
reference flash_attn API: [batch, seq, heads, head_dim].

The primal-only path (inference / no-grad) uses a forward kernel that skips
the logsumexp output entirely; the vjp path saves lse for the fused backward.

On non-TPU backends `available()` is False and callers fall back to the XLA
composite in nn.functional.scaled_dot_product_attention. Tests exercise the
kernels on CPU via `force_interpret(True)` (Pallas interpret mode).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ._common import available, force_interpret, interpret_mode  # noqa: F401


def expand_kv_heads(q, k, v):
    """GQA fallback for composite paths: expand shared kv heads to match q
    (the Pallas kernels instead read shared heads via their index map)."""
    if k.shape[2] != q.shape[2]:
        if q.shape[2] % k.shape[2]:
            raise ValueError(
                f"q heads {q.shape[2]} not a multiple of kv heads "
                f"{k.shape[2]}")
        n_rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, n_rep, axis=2)
        v = jnp.repeat(v, n_rep, axis=2)
    return k, v


def _reference_attention(q, k, v, causal, segment_ids=None, window=None):
    k, v = expand_kv_heads(q, k, v)
    qh, kh, vh = (jnp.swapaxes(t, 1, 2) for t in (q, k, v))
    scale = 1.0 / math.sqrt(q.shape[-1])
    logits = jnp.einsum("bhsd,bhtd->bhst", qh, kh) * scale
    logits = logits.astype(jnp.float32)
    mask = None
    if causal:
        s, t = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), bool), t - s)
        if window is not None:      # query i sees key j iff i - window < j
            mask &= ~jnp.tril(jnp.ones((s, t), bool), t - s - window)
        mask = mask[None, None]
    if segment_ids is not None:
        same = (segment_ids[:, :, None] == segment_ids[:, None, :])[:, None]
        mask = same if mask is None else (mask & same)
    if segment_ids is not None:
        # finite mask value + explicit row zeroing: -inf would make softmax
        # (and its grad) NaN on fully-masked padding rows
        logits = jnp.where(mask, logits, jnp.float32(-1e30))
        probs = jax.nn.softmax(logits, axis=-1)
        probs = jnp.where(mask.any(-1, keepdims=True), probs, 0.0)
        probs = probs.astype(q.dtype)
    else:
        if mask is not None:
            logits = jnp.where(mask, logits, -jnp.inf)
        probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


def _pallas_ok(q) -> bool:
    """Kernel constraints: seq divisible by the block size it will pick,
    and that block a multiple of the 8-row sublane tile (the chip's
    compiler refuses a 7-row k block: "cannot statically prove that index
    in dimension 1 is a multiple of 8")."""
    if not available():
        return False
    s = q.shape[1]
    blk = min(256, s)
    return s % blk == 0 and blk % 8 == 0


#: longest sequence whose k and v the whole-sequence forward keeps in VMEM;
#: a longer causal one takes the banded forward, which brings them in by block
WHOLE_KV_MAX_SEQ = 4096


def _banded_ok(q) -> bool:
    s = q.shape[1]
    return available() and s % min(1024, s) == 0 and min(512, s) % 8 == 0 \
        and s % min(512, s) == 0


@jax.custom_vjp
def _flash_causal(q, k, v):
    return _flash_impl(q, k, v, True)


@jax.custom_vjp
def _flash_full(q, k, v):
    return _flash_impl(q, k, v, False)


def _flash_impl(q, k, v, causal):
    if causal and q.shape[1] > WHOLE_KV_MAX_SEQ and _banded_ok(q):
        # primal only (inference): k and v too long to keep in VMEM whole
        from .flash_attention_pallas import flash_attention_forward_banded
        return flash_attention_forward_banded(q, k, v,
                                              interpret=interpret_mode())
    if _pallas_ok(q):
        from .flash_attention_pallas import flash_attention_forward
        return flash_attention_forward(q, k, v, causal=causal,
                                       interpret=interpret_mode())
    return _reference_attention(q, k, v, causal)


def _fwd_impl(q, k, v, causal):
    if _pallas_ok(q):
        from .flash_attention_pallas import flash_attention_forward_lse
        out, lse = flash_attention_forward_lse(q, k, v, causal=causal,
                                               interpret=interpret_mode())
        return out, (q, k, v, out, lse)
    out = _reference_attention(q, k, v, causal)
    return out, (q, k, v, None, None)


def _bwd_impl(causal, res, g):
    q, k, v, out, lse = res
    if lse is not None:
        from .flash_attention_pallas import flash_attention_backward
        return flash_attention_backward(q, k, v, out, lse, g,
                                        causal=causal,
                                        interpret=interpret_mode())
    _, vjp = jax.vjp(lambda a, b, c: _reference_attention(a, b, c, causal),
                     q, k, v)
    return vjp(g)


_flash_causal.defvjp(lambda q, k, v: _fwd_impl(q, k, v, True),
                     lambda res, g: _bwd_impl(True, res, g))
_flash_full.defvjp(lambda q, k, v: _fwd_impl(q, k, v, False),
                   lambda res, g: _bwd_impl(False, res, g))


def _seg_float0(seg):
    import numpy as np
    return np.zeros(seg.shape, jax.dtypes.float0)


_WARNED_FALLBACK: set = set()


def _warn_fallback(where, exc):
    """The composite fallback is O(S^2) memory — never take it silently
    (review finding: a varlen batch quietly falling off the kernel path is
    exactly the blowup packing exists to avoid)."""
    if where not in _WARNED_FALLBACK:
        _WARNED_FALLBACK.add(where)
        import warnings
        warnings.warn(
            f"flash attention {where}: Pallas kernel unavailable "
            f"({type(exc).__name__}: {exc}); falling back to the XLA "
            f"composite, which materializes the [S, S] matrix",
            RuntimeWarning, stacklevel=3)


@jax.custom_vjp
def _flash_seg_causal(q, k, v, seg):
    return _flash_seg_impl(q, k, v, seg, True)


@jax.custom_vjp
def _flash_seg_full(q, k, v, seg):
    return _flash_seg_impl(q, k, v, seg, False)


def _flash_seg_impl(q, k, v, seg, causal):
    if _pallas_ok(q):
        try:
            from .flash_attention_pallas import flash_attention_forward
            return flash_attention_forward(q, k, v, causal=causal,
                                           interpret=interpret_mode(),
                                           segment_ids=seg)
        except Exception as e:
            _warn_fallback("segment forward", e)
    return _reference_attention(q, k, v, causal, seg)


def _seg_fwd_impl(q, k, v, seg, causal):
    if _pallas_ok(q):
        try:
            from .flash_attention_pallas import flash_attention_forward_lse
            out, lse = flash_attention_forward_lse(
                q, k, v, causal=causal, interpret=interpret_mode(),
                segment_ids=seg)
            return out, (q, k, v, seg, out, lse)
        except Exception as e:
            _warn_fallback("segment forward (vjp)", e)
    out = _reference_attention(q, k, v, causal, seg)
    return out, (q, k, v, seg, None, None)


def _seg_bwd_impl(causal, res, g):
    q, k, v, seg, out, lse = res
    if lse is not None:
        try:
            from .flash_attention_pallas import flash_attention_backward
            dq, dk, dv = flash_attention_backward(
                q, k, v, out, lse, g, causal=causal,
                interpret=interpret_mode(), segment_ids=seg)
            return dq, dk, dv, _seg_float0(seg)
        except Exception as e:
            _warn_fallback("segment backward", e)
    _, vjp = jax.vjp(
        lambda a, b, c: _reference_attention(a, b, c, causal, seg), q, k, v)
    return (*vjp(g), _seg_float0(seg))


_flash_seg_causal.defvjp(lambda q, k, v, s: _seg_fwd_impl(q, k, v, s, True),
                         lambda res, g: _seg_bwd_impl(True, res, g))
_flash_seg_full.defvjp(lambda q, k, v, s: _seg_fwd_impl(q, k, v, s, False),
                       lambda res, g: _seg_bwd_impl(False, res, g))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash_banded(q, k, v, window):
    if _banded_ok(q):
        from .flash_attention_pallas import flash_attention_forward_banded
        return flash_attention_forward_banded(q, k, v, window=window,
                                              interpret=interpret_mode())
    return _reference_attention(q, k, v, True, window=window)


def _banded_fwd(q, k, v, window):
    return _flash_banded(q, k, v, window), (q, k, v)


def _banded_bwd(window, res, g):
    # serving's forward: the backward is the composite's, [S, S] and all
    _, vjp = jax.vjp(lambda a, b, c: _reference_attention(
        a, b, c, True, window=window), *res)
    return vjp(g)


_flash_banded.defvjp(_banded_fwd, _banded_bwd)


def flash_attention(q, k, v, causal: bool = False, segment_ids=None,
                    window=None):
    """[B, S, H, D] attention; fused Pallas forward+backward on TPU.

    `window` (causal only): query i sees key j iff ``i - window < j <= i``.
    With a window the forward is the banded kernel (k blocks wholly before
    the window are never fetched) and the backward the composite's; without
    one, a causal primal-only call over more than WHOLE_KV_MAX_SEQ positions
    takes the banded kernel too (k and v by block, not whole in VMEM).

    k/v may carry fewer heads than q (GQA/MQA): the kernels read each shared
    kv head directly via the block index map instead of materializing the
    repeat (reference GQA glue expands kv in HBM first).

    `segment_ids` [B, S] int: tokens attend only within equal segment ids —
    the packed-varlen masking of the reference's flash_attn_unpadded
    (paddle/phi/kernels/gpu/flash_attn_kernel.cu varlen path), with causal
    applied inside each segment when both are set."""
    if window is not None and (not causal or segment_ids is not None):
        raise ValueError("a window needs causal=True and no segment_ids")
    if segment_ids is not None:
        seg = jnp.asarray(segment_ids, jnp.int32)
        return (_flash_seg_causal(q, k, v, seg) if causal
                else _flash_seg_full(q, k, v, seg))
    if window is not None:
        return _flash_banded(q, k, v, int(window))
    return _flash_causal(q, k, v) if causal else _flash_full(q, k, v)
