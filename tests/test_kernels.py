"""Pallas kernel correctness: flash attention fwd/bwd vs the XLA composite.

Runs the REAL Pallas kernels in interpret mode on CPU (same jaxpr path the
TPU Mosaic lowering consumes), checking both primal outputs and gradients
against the dense reference attention.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.kernels import flash_attention as fa
from paddle_tpu.ops.kernels.flash_attention_pallas import (
    flash_attention_backward,
    flash_attention_forward_lse,
)


def _ref(q, k, v, causal):
    return fa._reference_attention(q, k, v, causal)


def _rand_qkv(b=2, s=128, h=2, d=64, kv_h=None, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    shp = lambda heads: (b, s, heads, d)
    q = jnp.asarray(rng.standard_normal(shp(h)), dtype)
    k = jnp.asarray(rng.standard_normal(shp(kv_h or h)), dtype)
    v = jnp.asarray(rng.standard_normal(shp(kv_h or h)), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal):
    q, k, v = _rand_qkv()
    out, lse = flash_attention_forward_lse(q, k, v, causal=causal,
                                           block_q=64, block_k=64,
                                           interpret=True)
    ref = _ref(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # lse parity: logsumexp of the scaled (masked) logits
    b, s, h, d = q.shape
    qh, kh = jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2)
    logits = jnp.einsum("bhsd,bhtd->bhst", qh, kh) / math.sqrt(d)
    if causal:
        mask = jnp.tril(jnp.ones((s, s), bool))
        logits = jnp.where(mask, logits, -jnp.inf)
    ref_lse = jax.nn.logsumexp(logits, axis=-1).reshape(b * h, s)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_reference(causal):
    q, k, v = _rand_qkv(s=128)
    g = jnp.asarray(np.random.default_rng(1).standard_normal(q.shape),
                    q.dtype)
    out, lse = flash_attention_forward_lse(q, k, v, causal=causal,
                                           block_q=64, block_k=64,
                                           interpret=True)
    dq, dk, dv = flash_attention_backward(q, k, v, out, lse, g, causal=causal,
                                          block_q=64, block_k=64,
                                          interpret=True)
    _, vjp = jax.vjp(lambda a, b2, c: _ref(a, b2, c, causal), q, k, v)
    rdq, rdk, rdv = vjp(g)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv),
                               rtol=2e-4, atol=2e-4)


def test_custom_vjp_uses_pallas_backward():
    """End-to-end: flash_attention grad == reference grad (interpret mode)."""
    fa.force_interpret(True)
    try:
        q, k, v = _rand_qkv(s=64)
        g = jnp.ones_like(q)

        def loss(q, k, v):
            return jnp.sum(fa.flash_attention(q, k, v, causal=True) * g)

        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        ref_dq, ref_dk, ref_dv = jax.grad(
            lambda a, b2, c: jnp.sum(_ref(a, b2, c, True) * g),
            argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(dq), np.asarray(ref_dq),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(ref_dk),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(ref_dv),
                                   rtol=2e-4, atol=2e-4)
    finally:
        fa.force_interpret(False)


def test_primal_only_forward_kernel():
    """No-grad path uses the lse-free kernel and matches the reference."""
    fa.force_interpret(True)
    try:
        q, k, v = _rand_qkv(s=64)
        out = fa.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_ref(q, k, v, True)),
                                   rtol=2e-5, atol=2e-5)
    finally:
        fa.force_interpret(False)


def test_uneven_seq_falls_back():
    """seq not divisible by the block size -> XLA composite, still correct.

    s=300 > 256 and 300 % 256 != 0, so _pallas_ok is False and the XLA
    fallback branch actually runs (s<=256 always picks block=s and stays on
    the kernel path)."""
    assert not fa._pallas_ok(jnp.zeros((1, 300, 1, 64)))
    fa.force_interpret(True)
    try:
        q, k, v = _rand_qkv(s=300)
        out = fa.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(_ref(q, k, v, True)),
                                   rtol=2e-5, atol=2e-5)
    finally:
        fa.force_interpret(False)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("segmented", [False, True], ids=["dense", "seg"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("d", [64, 128])
def test_kernels_in_stored_dtype_match_f32_reference(d, causal, segmented,
                                                     dtype):
    """Forward and the three gradients of the kernels against the float32
    composite. bf16 inputs go to the MXU as bf16 (P and dS cast to the
    value dtype), so they are held to a bf16 tolerance; f32 inputs keep f32
    operands and the tolerances the f32 tests above hold them to. The
    blocks are the kernels' own choice for 1024 positions (two of 512)."""
    rng = np.random.default_rng(d + 2 * causal + segmented)
    b, s, h = 2, 1024, 1
    q, k, v, g = (jnp.asarray(rng.standard_normal((b, s, h, d)), dtype)
                  for _ in range(4))
    seg = jnp.asarray([[0] * 400 + [1] * 624, [0] * 1024], jnp.int32) \
        if segmented else None
    out, lse = flash_attention_forward_lse(q, k, v, causal=causal,
                                           interpret=True, segment_ids=seg)
    grads = flash_attention_backward(q, k, v, out, lse, g, causal=causal,
                                     interpret=True, segment_ids=seg)
    f32 = [t.astype(jnp.float32) for t in (q, k, v, g)]
    ref, vjp = jax.vjp(
        lambda a, b_, c: fa._reference_attention(a, b_, c, causal, seg),
        *f32[:3])
    for name, got, want in zip(("out", "dq", "dk", "dv"),
                               (out, *grads), (ref, *vjp(f32[3]))):
        assert got.dtype == q.dtype, name
        got, want = np.asarray(got.astype(jnp.float32)), np.asarray(want)
        if dtype == "float32":
            tol = 2e-5 if name == "out" else 2e-4
            np.testing.assert_allclose(got, want, rtol=tol, atol=tol,
                                       err_msg=name)
        else:
            np.testing.assert_allclose(
                got, want, rtol=2e-2, atol=1e-2 * np.abs(want).max(),
                err_msg=name)
            assert np.linalg.norm(got - want) < 1e-2 * np.linalg.norm(want)


# ---------------------------------------------------------------------------
# fused rmsnorm(+residual)
# ---------------------------------------------------------------------------

from paddle_tpu.ops.kernels.rms_norm_pallas import rms_norm_fused  # noqa: E402


def _rms_ref(x, w, res, eps=1e-6):
    h = x + (res if res is not None else 0.0)
    y = h * jax.lax.rsqrt(jnp.mean(h * h, -1, keepdims=True) + eps) * w
    return y, h


@pytest.mark.parametrize("with_res", [False, True])
def test_rms_norm_fused_forward(with_res):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 32, 256)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((4, 32, 256)), jnp.float32) \
        if with_res else None
    w = jnp.asarray(rng.standard_normal(256), jnp.float32)
    out, hsum = rms_norm_fused(x, w, res, 1e-6, True)
    ry, rh = _rms_ref(x, w, res)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ry),
                               rtol=1e-5, atol=1e-5)
    if with_res:
        np.testing.assert_allclose(np.asarray(hsum), np.asarray(rh),
                                   rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("with_res", [False, True])
def test_rms_norm_fused_grads(with_res):
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 16, 128)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((2, 16, 128)), jnp.float32) \
        if with_res else None
    w = jnp.asarray(rng.standard_normal(128), jnp.float32)

    def loss(x, w, *maybe_res):
        r = maybe_res[0] if maybe_res else None
        y, h = rms_norm_fused(x, w, r, 1e-6, True)
        extra = 0.5 * jnp.sum(h * h) if h is not None else 0.0
        return jnp.sum(y * y) + extra

    def loss_ref(x, w, *maybe_res):
        r = maybe_res[0] if maybe_res else None
        y, h = _rms_ref(x, w, r)
        extra = 0.5 * jnp.sum(h * h) if r is not None else 0.0
        return jnp.sum(y * y) + extra

    args = (x, w) + ((res,) if with_res else ())
    nums = tuple(range(len(args)))
    g1 = jax.grad(loss, argnums=nums)(*args)
    g2 = jax.grad(loss_ref, argnums=nums)(*args)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


def test_functional_fused_rms_norm_add():
    """nn.functional surface: XLA path on CPU, grads flow through Tensors."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    paddle.seed(0)
    x = paddle.randn([2, 8, 64])
    r = paddle.randn([2, 8, 64])
    w = paddle.create_parameter([64], "float32",
                                default_initializer=paddle.nn.initializer.Constant(1.0))
    x.stop_gradient = False
    r.stop_gradient = False
    y, h = F.fused_rms_norm_add(x, r, w)
    (y.sum() + h.sum()).backward()
    assert x.grad is not None and r.grad is not None and w.grad is not None
    ry, rh = _rms_ref(x._data, w._data, r._data)
    np.testing.assert_allclose(np.asarray(y._data), np.asarray(ry),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_forward_matches_reference(causal):
    """GQA: kv heads < q heads, fetched via the kernel's kv index map."""
    q, k, v = _rand_qkv(h=4, kv_h=2, seed=3)
    out, lse = flash_attention_forward_lse(q, k, v, causal=causal,
                                           block_q=64, block_k=64,
                                           interpret=True)
    ref = _ref(q, k, v, causal)  # reference expands the shared heads
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gqa_backward_matches_reference(causal):
    q, k, v = _rand_qkv(h=4, kv_h=2, seed=4)
    out, lse = flash_attention_forward_lse(q, k, v, causal=causal,
                                           block_q=64, block_k=64,
                                           interpret=True)
    g = jnp.ones_like(out)
    dq, dk, dv = flash_attention_backward(q, k, v, out, lse, g, causal=causal,
                                          block_q=64, block_k=64,
                                          interpret=True)
    assert dk.shape == k.shape and dv.shape == v.shape  # kv head count kept
    ref_f = lambda a, b_, c: jnp.sum(_ref(a, b_, c, causal))
    rdq, rdk, rdv = jax.grad(ref_f, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rdq),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv),
                               rtol=2e-4, atol=2e-4)


def test_gqa_flash_attention_end_to_end():
    """flash_attention() public custom-vjp entry with GQA under interpret
    mode (kernel path incl. kv-head-shaped cotangents) + the SDPA composite
    path both match the expanded reference."""
    from paddle_tpu.ops.kernels._common import force_interpret
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    q, k, v = _rand_qkv(h=4, kv_h=1, s=64, seed=5)  # MQA extreme
    ref = _ref(q, k, v, True)

    # kernel path through the public custom_vjp wrapper (interpret mode)
    force_interpret(True)
    try:
        out_k = fa.flash_attention(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
        loss = lambda a, b_, c: jnp.sum(fa.flash_attention(a, b_, c,
                                                           causal=True))
        dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        assert dk.shape == k.shape and dv.shape == v.shape
        ref_loss = lambda a, b_, c: jnp.sum(_ref(a, b_, c, True))
        rdq, rdk, rdv = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
        np.testing.assert_allclose(np.asarray(dk), np.asarray(rdk),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(dv), np.asarray(rdv),
                                   rtol=2e-4, atol=2e-4)
    finally:
        force_interpret(False)

    # composite path (no pallas): SDPA expands kv internally now
    qt, kt, vt = (paddle.to_tensor(np.asarray(t)) for t in (q, k, v))
    out = F.scaled_dot_product_attention(qt, kt, vt, is_causal=True)
    np.testing.assert_allclose(out.numpy(), np.asarray(ref), rtol=2e-4,
                               atol=2e-4)

    # non-divisible head counts fail loudly on both paths
    qbad = jnp.ones((1, 64, 6, 8))
    kbad = jnp.ones((1, 64, 4, 8))
    with pytest.raises(ValueError, match="not a multiple"):
        fa.expand_kv_heads(qbad, kbad, kbad)
