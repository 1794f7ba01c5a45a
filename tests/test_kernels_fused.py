"""Interpret-mode parity tests for the round-3 Pallas kernel families:
fused RoPE and the MoE grouped-GEMM (VERDICT r2 #3); and the AdamW update,
which is the compiler's own code since PR 32, against the NumPy formula on
the shapes the optimizer feeds it.

Each kernel's real jaxpr runs through the Pallas interpreter on CPU and is
compared against the XLA composite it replaces on TPU.
"""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.kernels import _common as kern
from paddle_tpu.ops.kernels import moe_gemm_pallas, rope_pallas


def _rope_tables(s, d, dtype=np.float32):
    ang = np.outer(np.arange(s), 1.0 / (10000 ** (np.arange(0, d, 2) / d)))
    cos = np.concatenate([np.cos(ang), np.cos(ang)], -1)
    sin = np.concatenate([np.sin(ang), np.sin(ang)], -1)
    return (cos.reshape(1, s, 1, d).astype(dtype),
            sin.reshape(1, s, 1, d).astype(dtype))


@pytest.mark.parametrize("shape", [(2, 16, 4, 64), (1, 24, 3, 32)])
def test_rope_kernel_matches_composite(shape):
    b, s, h, d = shape
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    cos, sin = _rope_tables(s, d)

    out = rope_pallas.rope_apply(x, cos, sin, True)
    ref = rope_pallas.rope_reference(x, cos, sin)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-6)

    d1 = jax.grad(lambda a: jnp.sum(rope_pallas.rope_apply(a, cos, sin, True)
                                    * g))(x)
    d2 = jax.grad(lambda a: jnp.sum(rope_pallas.rope_reference(a, cos, sin)
                                    * g))(x)
    np.testing.assert_allclose(np.asarray(d1), np.asarray(d2), atol=1e-6)


def test_f_rope_dispatches_to_kernel_under_interpret():
    """F.rope uses the Pallas kernel when kernels are 'available' and still
    matches the composite path bit-for-bit at f32."""
    import paddle_tpu.nn.functional as F

    b, s, h, d = 2, 16, 4, 64
    rng = np.random.default_rng(1)
    q = paddle.to_tensor(rng.standard_normal((b, s, h, d)).astype(np.float32),
                         stop_gradient=False)
    k = paddle.to_tensor(rng.standard_normal((b, s, 2, d)).astype(np.float32))
    cos, sin = _rope_tables(s, d)
    qo_ref, ko_ref = F.rope(paddle.to_tensor(q.numpy()),
                            paddle.to_tensor(k.numpy()),
                            paddle.to_tensor(sin), paddle.to_tensor(cos))
    kern.force_interpret(True)
    try:
        qo, ko = F.rope(q, k, paddle.to_tensor(sin), paddle.to_tensor(cos))
        qo.sum().backward()
    finally:
        kern.force_interpret(False)
    np.testing.assert_allclose(qo.numpy(), qo_ref.numpy(), atol=1e-6)
    np.testing.assert_allclose(ko.numpy(), ko_ref.numpy(), atol=1e-6)
    assert q.grad is not None


def _numpy_adamw(w, g, m, v, lr, t, b1, b2, eps, wd):
    """Decoupled-decay Adam, step `t`, in float64 on the host."""
    w, g, m, v = (np.asarray(a, np.float64) for a in (w, g, m, v))
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g ** 2
    w = w * (1 - lr * wd) - lr * (m / (1 - b1 ** t)) / (
        np.sqrt(v / (1 - b2 ** t)) + eps)
    return w, m, v


#: what the optimizer really feeds the update: GPT-2 medium's matrices as
#: they are stored, rows of its embedding, a bias, a last dimension that is
#: no multiple of 128 lanes, a weight of three dimensions
ADAMW_SHAPES = [(1024, 4096), (4096, 1024), (1024, 3072), (304, 1024),
                (4096,), (64, 1000), (4, 16, 128)]


@pytest.mark.parametrize("shape", ADAMW_SHAPES, ids=str)
def test_adamw_step_matches_numpy_in_the_parameters_shape(shape):
    """One `AdamW.step()` over a bfloat16 parameter with a float32 master,
    from a state in mid-run: master, moments and the bfloat16 copy against
    the NumPy formula, each in the parameter's own shape."""
    from paddle_tpu.core.tensor import Parameter, Tensor
    rng = np.random.default_rng(2)
    w = rng.standard_normal(shape).astype(np.float32)
    g = np.asarray(jnp.asarray(rng.standard_normal(shape), jnp.bfloat16),
                   np.float32)
    m = (rng.standard_normal(shape) * 0.1).astype(np.float32)
    v = (np.abs(rng.standard_normal(shape)) * 0.01).astype(np.float32)
    b1, b2, eps, wd, lr, t = 0.9, 0.95, 1e-8, 0.1, 3e-4, 7.0

    p = Parameter(jnp.asarray(w, jnp.bfloat16))
    opt = paddle.optimizer.AdamW(lr, beta1=b1, beta2=b2, epsilon=eps,
                                 parameters=[p], weight_decay=wd,
                                 multi_precision=True, fuse=False)
    opt._get_master(p)._data = jnp.asarray(w)
    opt._add_accumulator("moment1", p, dtype=jnp.float32)._data = \
        jnp.asarray(m)
    opt._add_accumulator("moment2", p, dtype=jnp.float32)._data = \
        jnp.asarray(v)
    opt._step_tensor._data = jnp.asarray(t - 1, jnp.float32)
    p._grad = Tensor(jnp.asarray(g, jnp.bfloat16))
    opt.step()

    we, me, ve = _numpy_adamw(w, g, m, v, lr, t, b1, b2, eps, wd)
    got = {"master": opt._master_weights[id(p)],
           "moment1": opt._accumulators["moment1"][id(p)],
           "moment2": opt._accumulators["moment2"][id(p)]}
    for (name, have), want in zip(got.items(), (we, me, ve)):
        assert tuple(have.shape) == shape and have._data.dtype == jnp.float32
        np.testing.assert_allclose(np.asarray(have._data), want, rtol=2e-6,
                                   atol=1e-7, err_msg=name)
    assert tuple(p.shape) == shape and p._data.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(p._data.astype(jnp.float32)),
        np.asarray(got["master"]._data.astype(jnp.bfloat16)
                   .astype(jnp.float32)))


def _gpt_and_batches(steps=3):
    from paddle_tpu.models import gpt2_tiny
    paddle.seed(0)
    model = gpt2_tiny()
    rng = np.random.default_rng(3)
    rows = rng.integers(0, 1024, (steps, 4, 33))
    return model, [(paddle.to_tensor(r[:, :-1]), paddle.to_tensor(r[:, 1:]))
                   for r in rows]


@pytest.mark.parametrize("how", ["eager", "fused", "to_static"])
def test_adamw_three_steps_on_a_gpt_match_the_plain_loop(how):
    """Three AdamW steps of a two-layer GPT (op by op, through the fused
    multi-tensor step, and inside a compiled train step) against a plain
    loop over the parameters in NumPy, fed the gradients of each step."""
    b1, b2, eps, wd, lr = 0.9, 0.95, 1e-8, 0.1, 1e-3
    model, batches = _gpt_and_batches()
    params = dict(model.named_parameters())
    opt = paddle.optimizer.AdamW(lr, beta1=b1, beta2=b2, epsilon=eps,
                                 parameters=list(params.values()),
                                 weight_decay=wd, fuse=how == "fused")
    ref = {n: (p.numpy().astype(np.float64), 0.0, 0.0)
           for n, p in params.items()}

    def step(x, y):
        _, loss = model(x, labels=y)
        loss.backward()
        grads = [p.grad for p in params.values()]
        opt.step()
        opt.clear_grad()
        return loss, grads

    run = paddle.jit.to_static(step) if how == "to_static" else step
    for t, (x, y) in enumerate(batches, 1):
        _, grads = run(x, y)
        for (n, (w, m, v)), g in zip(ref.items(), grads):
            ref[n] = _numpy_adamw(w, g.numpy(), m, v, lr, float(t), b1, b2,
                                  eps, wd)
    for n, p in params.items():
        np.testing.assert_allclose(p.numpy(), ref[n][0], rtol=2e-5,
                                   atol=2e-6, err_msg=n)
        assert tuple(opt._accumulators["moment1"][id(p)].shape) == \
            tuple(p.shape)


def test_adamw_state_dict_round_trip_keeps_the_parameters_shapes():
    """Moments and masters are saved as float32 tensors of the parameter's
    shape, one per parameter, and a run resumed from them goes on as the
    uninterrupted one."""
    from paddle_tpu.core.tensor import Tensor

    def build():
        model, batches = _gpt_and_batches(steps=3)
        opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters(),
                                     weight_decay=0.1, multi_precision=True,
                                     fuse=False)
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16")
        return model, opt, batches

    def step(model, opt, x, y):
        _, loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()

    model, opt, batches = build()
    for x, y in batches[:2]:
        step(model, opt, x, y)
    saved = opt.state_dict()
    low = {p.name for p in model.parameters()
           if p._data.dtype == jnp.bfloat16}
    assert low and set(saved["master_weights"]) == low
    for p in model.parameters():
        held = [saved[f"{p.name}_moment1"], saved[f"{p.name}_moment2"]]
        if p.name in low:
            held.append(saved["master_weights"][p.name])
        for t in held:
            assert tuple(t.shape) == tuple(p.shape), p.name
            assert t._data.dtype == jnp.float32, p.name
    weights = {n: p._data for n, p in model.named_parameters()}
    saved = {k: ({n: Tensor(w._data) for n, w in v.items()}
                 if k == "master_weights" else
                 Tensor(v._data) if isinstance(v, Tensor) else v)
             for k, v in saved.items()}

    step(model, opt, *batches[2])
    went_on = [p._data for p in model.parameters()]
    for n, p in model.named_parameters():
        p._data = weights[n]
    resumed = paddle.optimizer.AdamW(
        1e-3, parameters=model.parameters(), weight_decay=0.1,
        multi_precision=True, fuse=False)
    resumed.set_state_dict(saved)
    step(model, resumed, *batches[2])
    for p, want in zip(model.parameters(), went_on):
        np.testing.assert_array_equal(
            np.asarray(p._data.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)), err_msg=p.name)


def test_grouped_matmul_matches_einsum():
    rng = np.random.default_rng(4)
    e_, c, h, f = 4, 16, 32, 64
    counts = jnp.asarray([16, 5, 0, 9], jnp.int32)
    mask = jnp.arange(c)[None, :, None] < counts.reshape(-1, 1, 1)
    x = jnp.where(mask, jnp.asarray(rng.standard_normal((e_, c, h)),
                                    jnp.float32), 0)
    w = jnp.asarray(rng.standard_normal((e_, h, f)), jnp.float32)
    g = jnp.where(jnp.arange(c)[None, :, None] < counts.reshape(-1, 1, 1),
                  jnp.asarray(rng.standard_normal((e_, c, f)), jnp.float32), 0)

    out = moe_gemm_pallas.grouped_matmul(x, w, counts, True)
    ref = moe_gemm_pallas.reference_grouped_matmul(x, w, counts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    d1 = jax.grad(lambda a, b: jnp.sum(
        moe_gemm_pallas.grouped_matmul(a, b, counts, True) * g),
        argnums=(0, 1))(x, w)
    d2 = jax.grad(lambda a, b: jnp.sum(
        moe_gemm_pallas.reference_grouped_matmul(a, b, counts) * g),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(d1[0]), np.asarray(d2[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(d1[1]), np.asarray(d2[1]),
                               atol=1e-5)


def test_padded_row_paths_numeric_parity():
    """Non-block-divisible shapes take the zero-pad-and-slice path in the
    rms/rope/moe kernels; verify fwd+bwd numerics (not just lowering) so a
    wrong pad axis or slice can't hide behind all-zero lowering tests."""
    rng = np.random.default_rng(21)
    from paddle_tpu.ops.kernels import rms_norm_pallas as rn
    from paddle_tpu.ops.kernels import rope_pallas as rp

    # rmsnorm at n=13 rows (pads to 16)
    x = jnp.asarray(rng.standard_normal((1, 13, 64)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((64,)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((1, 13, 64)), jnp.float32)

    def comp(x, w, r):
        h = x + r
        return h * jax.lax.rsqrt(
            jnp.mean(h * h, -1, keepdims=True) + 1e-6) * w

    y, _ = rn.rms_norm_fused(x, w, res, 1e-6, True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(comp(x, w, res)),
                               atol=2e-5)
    g1 = jax.grad(lambda *t: jnp.sum(rn.rms_norm_fused(*t, 1e-6, True)[0]),
                  argnums=(0, 1, 2))(x, w, res)
    g2 = jax.grad(lambda *t: jnp.sum(comp(*t)), argnums=(0, 1, 2))(x, w, res)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)

    # rope at s=13 (pads to 16), half-duplicated table layout
    xq = jnp.asarray(rng.standard_normal((2, 13, 2, 32)), jnp.float32)
    pos = np.arange(13)
    inv = 1.0 / (10000 ** (np.arange(0, 16) / 16))
    ang = np.concatenate([pos[:, None] * inv[None]] * 2, -1)
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)
    got = rp.rope_apply(xq, cos, sin, True)
    want = rp.rope_reference(xq, cos, sin)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    gk = jax.grad(lambda t: jnp.sum(rp.rope_apply(t, cos, sin, True)))(xq)
    gc = jax.grad(lambda t: jnp.sum(rp.rope_reference(t, cos, sin)))(xq)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(gc), atol=2e-5)

    # moe grouped matmul at c=10 (pads to 16), f=384 (128-divisible but NOT
    # 256-divisible — the block must divide f or trailing columns go
    # unwritten; regression for the floored-grid NaN bug)
    xm = jnp.asarray(rng.standard_normal((2, 10, 32)), jnp.float32)
    wm = jnp.asarray(rng.standard_normal((2, 32, 384)), jnp.float32)
    counts = jnp.asarray([7, 3], jnp.int32)
    got = moe_gemm_pallas.grouped_matmul(xm, wm, counts, True)
    want = moe_gemm_pallas.reference_grouped_matmul(xm, wm, counts)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    d1 = jax.grad(lambda a, b: jnp.sum(
        moe_gemm_pallas.grouped_matmul(a, b, counts, True)),
        argnums=(0, 1))(xm, wm)
    d2 = jax.grad(lambda a, b: jnp.sum(
        moe_gemm_pallas.reference_grouped_matmul(a, b, counts)),
        argnums=(0, 1))(xm, wm)
    np.testing.assert_allclose(np.asarray(d1[0]), np.asarray(d2[0]),
                               atol=1e-4)  # f32 accumulation-order noise
    np.testing.assert_allclose(np.asarray(d1[1]), np.asarray(d2[1]), atol=1e-4)


def test_grouped_matmul_nonzero_padding_is_masked():
    """Rows past counts[e] are masked INSIDE live tiles: garbage padding
    content must not leak into the output (kernel contract is unconditional,
    not dependent on the dispatch one-hot zeroing the padding)."""
    rng = np.random.default_rng(11)
    e_, c, h, f = 2, 8, 16, 32
    counts = jnp.asarray([5, 0], jnp.int32)
    x = jnp.asarray(rng.standard_normal((e_, c, h)), jnp.float32)  # no zeroing
    w = jnp.asarray(rng.standard_normal((e_, h, f)), jnp.float32)
    out = moe_gemm_pallas.grouped_matmul(x, w, counts, True)
    ref = moe_gemm_pallas.reference_grouped_matmul(x, w, counts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)

    # grads must honor the mask too: dw from garbage padding rows is zero
    d1 = jax.grad(lambda a, b: jnp.sum(
        moe_gemm_pallas.grouped_matmul(a, b, counts, True)),
        argnums=(0, 1))(x, w)
    d2 = jax.grad(lambda a, b: jnp.sum(
        moe_gemm_pallas.reference_grouped_matmul(a, b, counts)),
        argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(d1[0]), np.asarray(d2[0]), atol=1e-5)
    np.testing.assert_allclose(np.asarray(d1[1]), np.asarray(d2[1]), atol=1e-5)


def test_moe_layer_grouped_path_matches_vmap():
    """MoELayer forward+backward parity: grouped-GEMM kernel vs the generic
    vmapped expert path, same weights and routing."""
    from paddle_tpu.models import qwen2_moe_tiny

    def run(fast):
        paddle.seed(0)
        model = qwen2_moe_tiny()
        if fast:
            kern.force_interpret(True)
        try:
            x = paddle.to_tensor(
                np.arange(2 * 16).reshape(2, 16).astype(np.int64) % 100)
            y = paddle.to_tensor(
                np.arange(2 * 16).reshape(2, 16).astype(np.int64) % 100)
            _, loss = model(x, labels=y)
            loss.backward()
            grads = [p.grad.numpy().copy() for p in model.parameters()
                     if p.grad is not None][:6]
            return float(loss), grads
        finally:
            if fast:
                kern.force_interpret(False)

    loss_fast, g_fast = run(True)
    loss_ref, g_ref = run(False)
    assert abs(loss_fast - loss_ref) < 1e-4, (loss_fast, loss_ref)
    for a, b in zip(g_fast, g_ref):
        np.testing.assert_allclose(a, b, rtol=1e-3, atol=1e-5)


def test_bias_dropout_ln_matches_composite():
    """Fused bias+dropout+residual+layernorm kernel vs the XLA composite:
    forward AND all six gradients (x, bias, residual, gamma, beta; the
    mask is non-differentiable), including a non-divisible row count."""
    from paddle_tpu.ops.kernels import bias_dropout_ln_pallas as bd
    rng = np.random.default_rng(31)
    for shape in [(2, 16, 64), (1, 13, 32)]:
        x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        res = jnp.asarray(rng.standard_normal(shape), jnp.float32)
        b = jnp.asarray(rng.standard_normal(shape[-1]), jnp.float32)
        g = jnp.asarray(rng.standard_normal(shape[-1]), jnp.float32)
        be = jnp.asarray(rng.standard_normal(shape[-1]), jnp.float32)
        keep = rng.random(shape) > 0.3
        mask = jnp.asarray(keep / 0.7, jnp.float32)

        y, h = bd.bias_dropout_ln(x, b, res, mask, g, be, 1e-5, True)
        yr, hr = bd.reference_bias_dropout_ln(x, b, res, mask, g, be, 1e-5)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-5)
        np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=2e-5)

        def loss_k(x, b, res, g, be):
            y, h = bd.bias_dropout_ln(x, b, res, mask, g, be, 1e-5, True)
            return jnp.sum(y * y) + jnp.sum(h)

        def loss_r(x, b, res, g, be):
            y, h = bd.reference_bias_dropout_ln(x, b, res, mask, g, be, 1e-5)
            return jnp.sum(y * y) + jnp.sum(h)

        gk = jax.grad(loss_k, argnums=(0, 1, 2, 3, 4))(x, b, res, g, be)
        gr = jax.grad(loss_r, argnums=(0, 1, 2, 3, 4))(x, b, res, g, be)
        for a_, b_ in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a_), np.asarray(b_),
                                       atol=5e-4)


def test_fused_bias_dropout_residual_ln_public_api():
    """The incubate functional dispatches to the kernel under interpret
    mode and matches eval-mode composite numerics; training mode masks."""
    import paddle_tpu.incubate.nn.functional as IF
    from paddle_tpu.ops.kernels import _common as kern
    rng = np.random.default_rng(32)
    x = paddle.to_tensor(rng.standard_normal((2, 8, 32)).astype(np.float32))
    res = paddle.to_tensor(rng.standard_normal((2, 8, 32)).astype(np.float32))
    g = paddle.to_tensor(rng.standard_normal(32).astype(np.float32))
    b = paddle.to_tensor(rng.standard_normal(32).astype(np.float32))

    kern.force_interpret(True)
    try:
        out_k = IF.fused_bias_dropout_residual_layer_norm(
            x, res, ln_scale=g, ln_bias=b, dropout_rate=0.5, training=False)
    finally:
        kern.force_interpret(False)
    out_c = IF.fused_bias_dropout_residual_layer_norm(
        x, res, ln_scale=g, ln_bias=b, dropout_rate=0.5, training=False)
    np.testing.assert_allclose(out_k.numpy(), out_c.numpy(), atol=2e-5)

    # training path produces a masked (different) result but valid grads
    x2 = paddle.to_tensor(rng.standard_normal((2, 8, 32)).astype(np.float32))
    x2.stop_gradient = False
    kern.force_interpret(True)
    try:
        out_t = IF.fused_bias_dropout_residual_layer_norm(
            x2, res, ln_scale=g, ln_bias=b, dropout_rate=0.5, training=True)
        out_t.sum().backward()
    finally:
        kern.force_interpret(False)
    assert x2.grad is not None
    assert np.isfinite(x2.grad.numpy()).all()


def test_bias_dropout_ln_maskless_variant_and_p1():
    """mask=None selects the maskless kernel (inference path: no ones
    tensor streamed) and matches the mask-of-ones numerics incl. grads;
    dropout_rate=1.0 in the public API yields finite zeros-path output."""
    from paddle_tpu.ops.kernels import bias_dropout_ln_pallas as bd
    rng = np.random.default_rng(36)
    shape = (2, 13, 32)
    x = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    res = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    b = jnp.asarray(rng.standard_normal(shape[-1]), jnp.float32)
    g = jnp.asarray(rng.standard_normal(shape[-1]), jnp.float32)
    be = jnp.asarray(rng.standard_normal(shape[-1]), jnp.float32)
    ones = jnp.ones(shape, jnp.float32)

    y0, h0 = bd.bias_dropout_ln(x, b, res, None, g, be, 1e-5, True)
    y1, h1 = bd.bias_dropout_ln(x, b, res, ones, g, be, 1e-5, True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=1e-6)
    np.testing.assert_allclose(np.asarray(h0), np.asarray(h1), atol=1e-6)

    gk = jax.grad(lambda *t: jnp.sum(
        bd.bias_dropout_ln(t[0], t[1], t[2], None, t[3], t[4],
                           1e-5, True)[0] ** 2),
        argnums=(0, 1, 2, 3, 4))(x, b, res, g, be)
    gr = jax.grad(lambda *t: jnp.sum(
        bd.bias_dropout_ln(t[0], t[1], t[2], ones, t[3], t[4],
                           1e-5, True)[0] ** 2),
        argnums=(0, 1, 2, 3, 4))(x, b, res, g, be)
    for a_, b_ in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a_), np.asarray(b_), atol=1e-5)

    import paddle_tpu.incubate.nn.functional as IF
    kern.force_interpret(True)
    try:
        out = IF.fused_bias_dropout_residual_layer_norm(
            paddle.to_tensor(np.asarray(x)), paddle.to_tensor(np.asarray(res)),
            dropout_rate=1.0, training=True)
    finally:
        kern.force_interpret(False)
    assert np.isfinite(out.numpy()).all()  # not NaN: mask is exact zeros


def test_ce_kernel_ignore_index():
    """Rows at ignore_index contribute 0 loss and exactly zero gradients
    (the reference cross_entropy padding contract)."""
    from paddle_tpu.ops.kernels import ce_pallas as cp
    rng = np.random.default_rng(37)
    n, v = 6, 40
    lg = jnp.asarray(rng.standard_normal((n, v)), jnp.float32)
    lb = jnp.asarray([3, -100, 7, -100, 0, 39], jnp.int32)
    loss = cp.c_softmax_with_cross_entropy(lg, lb, 0, None, True, -100)
    valid = np.asarray(lb) != -100
    want = np.asarray(cp.reference_ce(lg, jnp.where(lb == -100, 0, lb)))
    np.testing.assert_allclose(np.asarray(loss)[valid], want[valid],
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(loss)[~valid], 0.0)
    grads = jax.grad(lambda a: jnp.sum(
        cp.c_softmax_with_cross_entropy(a, lb, 0, None, True, -100)))(lg)
    np.testing.assert_allclose(np.asarray(grads)[~valid], 0.0)
    assert np.abs(np.asarray(grads)[valid]).max() > 0

    # the live layer honors its configured ignore_index via the kernel
    from paddle_tpu.distributed.meta_parallel import ParallelCrossEntropy
    x = paddle.to_tensor(np.asarray(lg))
    x.stop_gradient = False
    kern.force_interpret(True)
    try:
        out = ParallelCrossEntropy()(x, paddle.to_tensor(
            np.asarray(lb, np.int64)))
        out.sum().backward()
    finally:
        kern.force_interpret(False)
    np.testing.assert_allclose(out.numpy()[~valid], 0.0)
    np.testing.assert_allclose(x.grad.numpy()[~valid], 0.0)


def test_ce_kernel_matches_reference_and_grads():
    """Fused softmax-CE kernel (single shard): loss + dlogits parity with
    the XLA composite, odd row/vocab sizes included."""
    from paddle_tpu.ops.kernels import ce_pallas as cp
    rng = np.random.default_rng(33)
    for n, v in [(16, 256), (13, 200)]:
        lg = jnp.asarray(rng.standard_normal((n, v)), jnp.float32)
        lb = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)
        loss = cp.c_softmax_with_cross_entropy(lg, lb, 0, None, True)
        want = cp.reference_ce(lg, lb)
        np.testing.assert_allclose(np.asarray(loss), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        gk = jax.grad(lambda a: jnp.sum(
            cp.c_softmax_with_cross_entropy(a, lb, 0, None, True)))(lg)
        gr = jax.grad(lambda a: jnp.sum(cp.reference_ce(a, lb)))(lg)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-5)


def test_ce_kernel_sharded_combine_shard_map():
    """Vocab-sharded CE inside shard_map over an 8-device mesh equals the
    dense CE: per-shard one-pass stats + pmax/psum combine (the
    c_softmax_with_cross_entropy TP contract)."""
    from paddle_tpu.ops.kernels import ce_pallas as cp
    from jax.sharding import Mesh, PartitionSpec as P
    shard_map = jax.shard_map

    devs = jax.devices("cpu")[:8]
    mesh = Mesh(np.array(devs), ("mp",))
    rng = np.random.default_rng(34)
    n, v = 8, 64 * 8
    lg = jnp.asarray(rng.standard_normal((n, v)), jnp.float32)
    lb = jnp.asarray(rng.integers(0, v, (n,)), jnp.int32)

    def local(lg_shard, lb_full):
        # the stats kernel wants a STATIC vocab_start; shift the labels by
        # this shard's offset instead (global - start == local id)
        idx = jax.lax.axis_index("mp")
        shifted = lb_full - idx * (v // 8)
        return cp.c_softmax_with_cross_entropy(
            lg_shard, shifted, 0, "mp", True)

    sharded = shard_map(local, mesh=mesh,
                        in_specs=(P(None, "mp"), P(None)),
                        out_specs=P(None), check_vma=False)
    got = sharded(lg, lb)
    want = cp.reference_ce(lg, lb)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5, rtol=1e-5)


def test_parallel_cross_entropy_fused_single_device():
    """ParallelCrossEntropy off-mesh rides the fused CE kernel and matches
    F.cross_entropy, including backward."""
    from paddle_tpu.distributed.meta_parallel import ParallelCrossEntropy
    from paddle_tpu.ops.kernels import _common as kern
    import paddle_tpu.nn.functional as F

    rng = np.random.default_rng(35)
    logits_np = rng.standard_normal((6, 50)).astype(np.float32)
    labels_np = rng.integers(0, 50, (6,)).astype(np.int64)
    ce = ParallelCrossEntropy()

    x1 = paddle.to_tensor(logits_np)
    x1.stop_gradient = False
    kern.force_interpret(True)
    try:
        l1 = ce(x1, paddle.to_tensor(labels_np))
        l1.sum().backward()
    finally:
        kern.force_interpret(False)
    x2 = paddle.to_tensor(logits_np)
    x2.stop_gradient = False
    l2 = F.cross_entropy(x2, paddle.to_tensor(labels_np), reduction="none")
    l2.sum().backward()
    np.testing.assert_allclose(l1.numpy(), l2.numpy(), atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(x1.grad.numpy(), x2.grad.numpy(), atol=1e-5)


def test_pallas_block_autotune_mechanism():
    """tune_pallas_blocks measures every candidate with its override
    INSTALLED (a static jit arg, so each candidate compiles its own
    program), keeps the best, and restores state on failure (VERDICT r3
    component #24)."""
    from paddle_tpu.auto_tuner import tune_pallas_blocks
    from paddle_tpu.ops.kernels import _common as _kc
    from paddle_tpu.ops.kernels import rms_norm_pallas as rn

    rng = np.random.default_rng(41)
    x = jnp.asarray(rng.standard_normal((1, 64, 128)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((128,)), jnp.float32)

    seen = []

    def run():
        seen.append(_kc.get_block_override("rms_norm"))
        return rn.rms_norm_fused(x, w, None, 1e-6, True)[0]

    # rigged timer: pretend 32 is fastest — the tuner must install it
    fake = {8: 3.0, 16: 2.0, 32: 0.5, 64: 1.0}

    def timer(fn):
        fn()
        return fake[_kc.get_block_override("rms_norm")]

    try:
        best, timings = tune_pallas_blocks(
            "rms_norm", run, candidates=(8, 16, 32, 64), timer=timer)
        assert best == 32 and timings == fake
        assert _kc.get_block_override("rms_norm") == 32
        assert sorted(set(seen)) == [8, 16, 32, 64]  # each override ran

        # the override actually changes the executed program: parity at a
        # forced small block vs the heuristic
        _kc.set_block_override("rms_norm", 8)
        y8 = rn.rms_norm_fused(x, w, None, 1e-6, True)[0]
        _kc.set_block_override("rms_norm", None)
        yh = rn.rms_norm_fused(x, w, None, 1e-6, True)[0]
        np.testing.assert_allclose(np.asarray(y8), np.asarray(yh),
                                   atol=1e-6)

        # failure rolls the override back
        _kc.set_block_override("rms_norm", 16)

        def boom(fn):
            raise RuntimeError("measurement failed")

        with pytest.raises(RuntimeError):
            tune_pallas_blocks("rms_norm", run, candidates=(8,),
                               timer=boom)
        assert _kc.get_block_override("rms_norm") == 16
    finally:
        _kc.set_block_override("rms_norm", None)


# ---- masked multi-head (decode) attention kernel --------------------------

@pytest.mark.parametrize("cfg", [
    # (b, h, h_kv, d, t, pos)
    (2, 8, 2, 64, 256, 0),       # GQA, first decode step
    (2, 8, 2, 64, 256, 130),     # GQA, mid-cache (crosses a 128 boundary)
    (1, 4, 4, 32, 256, 255),     # MHA, cache full
    (1, 6, 3, 128, 512, 300),    # odd rep=2, two chunks used
])
def test_mmha_decode_matches_composite(cfg):
    from paddle_tpu.ops.kernels import mmha_pallas
    b, h, h_kv, d, t, pos = cfg
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    kb = jnp.asarray(rng.standard_normal((b, h_kv, t, d)), jnp.float32)
    vb = jnp.asarray(rng.standard_normal((b, h_kv, t, d)), jnp.float32)
    out = mmha_pallas.mmha_decode(q, kb, vb, jnp.int32(pos), interpret=True)
    ref = mmha_pallas.reference_mmha(q, kb, vb, jnp.int32(pos))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_mmha_use_kernel_gate():
    from paddle_tpu.ops.kernels import mmha_pallas
    kern.force_interpret(True)
    try:
        ok = mmha_pallas.use_kernel((2, 1, 8, 64), (2, 2, 256, 64),
                                    jnp.float32)
        assert ok
        # multi-token prefill, chunk-indivisible cache, oversized cache
        assert not mmha_pallas.use_kernel((2, 3, 8, 64), (2, 2, 256, 64),
                                          jnp.float32)
        assert not mmha_pallas.use_kernel((2, 1, 8, 64), (2, 2, 300, 64),
                                          jnp.float32)
        assert not mmha_pallas.use_kernel((2, 1, 8, 64),
                                          (2, 2, 65536, 64), jnp.float32)
    finally:
        kern.force_interpret(False)


def test_cached_attention_dispatches_mmha_kernel():
    """The generation-path cached_attention hits the decode kernel for the
    single-token steady state and matches its own composite path."""
    from paddle_tpu.models.generation import cached_attention
    rng = np.random.default_rng(3)
    b, h, h_kv, d, t = 2, 8, 2, 64, 256
    pos = 100
    kb = rng.standard_normal((b, h_kv, t, d)).astype(np.float32)
    vb = rng.standard_normal((b, h_kv, t, d)).astype(np.float32)
    q = paddle.to_tensor(rng.standard_normal((b, 1, h, d)).astype(np.float32))
    k = paddle.to_tensor(rng.standard_normal((b, 1, h_kv, d)).astype(np.float32))
    v = paddle.to_tensor(rng.standard_normal((b, 1, h_kv, d)).astype(np.float32))
    cache = (paddle.to_tensor(kb), paddle.to_tensor(vb))

    out_ref, (kb_ref, vb_ref) = cached_attention(q, k, v, cache, pos)
    kern.force_interpret(True)
    try:
        out_kern, (kb2, vb2) = cached_attention(q, k, v, cache, pos)
    finally:
        kern.force_interpret(False)
    np.testing.assert_allclose(np.asarray(out_kern.numpy()),
                               np.asarray(out_ref.numpy()),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(kb2.numpy()),
                                  np.asarray(kb_ref.numpy()))


class TestWeightOnlyInt8Matmul:
    """Fused weight-only int8 matmul (reference weight_only_linear int8,
    paddle/phi/kernels/fusion/gpu/weight_only_linear_kernel.cu)."""

    def _mk(self, m, k, n, seed=0):
        import numpy as np
        import jax.numpy as jnp
        from paddle_tpu.quantization.functional import quantize_weight_int8
        rng = np.random.default_rng(seed)
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        w_q, s = quantize_weight_int8(w, axis=1)
        return x, w_q, s

    def test_kernel_matches_composite(self):
        import numpy as np
        from paddle_tpu.ops.kernels import _common as kern
        from paddle_tpu.ops.kernels.wo_matmul_pallas import (
            reference_wo_int8_matmul, wo_int8_matmul)
        x, w_q, s = self._mk(24, 384, 200)   # deliberately unaligned m, n
        out = wo_int8_matmul(x, w_q, s, interpret=True)
        ref = reference_wo_int8_matmul(x, w_q, s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)

    def test_dispatch_and_grads(self):
        import numpy as np
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.kernels import _common as kern
        from paddle_tpu.quantization.functional import dequant_matmul_int8
        x, w_q, s = self._mk(16, 128, 96, seed=1)
        kern.force_interpret(True)
        try:
            out = dequant_matmul_int8(x, w_q, s)
        finally:
            kern.force_interpret(False)
        ref = jnp.matmul(x, w_q.astype(x.dtype)) * s
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)
        # grads wrt x and scales match the differentiated composite
        def f(fn, x, s):
            return jnp.sum(fn(x, w_q, s) ** 2)
        gx, gs = jax.grad(lambda x, s: f(dequant_matmul_int8, x, s),
                          argnums=(0, 1))(x, s)
        rx, rs = jax.grad(
            lambda x, s: jnp.sum((jnp.matmul(x, w_q.astype(x.dtype)) * s) ** 2),
            argnums=(0, 1))(x, s)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(gs), np.asarray(rs),
                                   atol=1e-2, rtol=1e-3)

    def test_tpu_lowering(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.kernels.wo_matmul_pallas import wo_int8_matmul
        x = jnp.zeros((64, 512), jnp.bfloat16)
        w = jnp.zeros((512, 1024), jnp.int8)
        s = jnp.zeros((1024,), jnp.float32)
        jax.jit(lambda a, b, c: wo_int8_matmul(a, b, c)).trace(
            x, w, s).lower(lowering_platforms=("tpu",))


class TestWeightOnlyLinearAPI:
    """paddle.nn.quant weight_quantize/weight_dequantize/weight_only_linear
    (reference python/paddle/nn/quant/quantized_linear.py:25,70,116)."""

    def test_int8_roundtrip_and_linear(self):
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu.nn import quant as Q
        rng = np.random.default_rng(0)
        w = paddle.to_tensor(rng.standard_normal((64, 48)).astype(np.float32))
        x = paddle.to_tensor(rng.standard_normal((4, 64)).astype(np.float32))
        b = paddle.to_tensor(rng.standard_normal((48,)).astype(np.float32))
        qw, s = Q.weight_quantize(w, algo="weight_only_int8")
        wd = Q.weight_dequantize(qw, s, algo="weight_only_int8")
        np.testing.assert_allclose(np.asarray(wd.numpy()),
                                   np.asarray(w.numpy()), atol=2e-2)
        y = Q.weight_only_linear(x, qw, bias=b, weight_scale=s,
                                 weight_dtype="int8")
        ref = np.asarray(x.numpy()) @ np.asarray(wd.numpy()) + \
            np.asarray(b.numpy())
        np.testing.assert_allclose(np.asarray(y.numpy()), ref, atol=1e-3,
                                   rtol=1e-3)

    def test_int4_pack_roundtrip_and_linear(self):
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu.nn import quant as Q
        rng = np.random.default_rng(1)
        w = paddle.to_tensor(rng.standard_normal((32, 17)).astype(np.float32))
        x = paddle.to_tensor(rng.standard_normal((3, 32)).astype(np.float32))
        qw, s = Q.weight_quantize(w, algo="weight_only_int4")
        assert qw.shape == [32, 9]  # two nibbles per byte, odd N padded
        wd = Q.weight_dequantize(qw, s, algo="weight_only_int4")
        assert wd.shape == [32, 17]
        np.testing.assert_allclose(np.asarray(wd.numpy()),
                                   np.asarray(w.numpy()), atol=0.25)
        y = Q.weight_only_linear(x, qw, weight_scale=s, weight_dtype="int4")
        ref = np.asarray(x.numpy()) @ np.asarray(wd.numpy())
        np.testing.assert_allclose(np.asarray(y.numpy()), ref, atol=1e-3,
                                   rtol=1e-3)

    def test_bad_algo_rejected(self):
        import numpy as np
        import pytest
        import paddle_tpu as paddle
        from paddle_tpu.nn import quant as Q
        w = paddle.to_tensor(np.ones((8, 8), np.float32))
        with pytest.raises(ValueError, match="algo"):
            Q.weight_quantize(w, algo="llm.int8")


class TestWeightOnlyInt4Kernel:
    """Fused int4 weight-only matmul: packed bytes stay packed in HBM,
    nibbles unpack in VMEM (halves layout, wo_matmul_pallas)."""

    def test_pack_roundtrip(self):
        import numpy as np
        import jax.numpy as jnp
        from paddle_tpu.ops.kernels.wo_matmul_pallas import (
            pack_int4_halves, unpack_int4_halves)
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.integers(-7, 8, (16, 24)), jnp.int8)
        np.testing.assert_array_equal(
            np.asarray(unpack_int4_halves(pack_int4_halves(q))),
            np.asarray(q))

    def test_kernel_matches_composite(self):
        import numpy as np
        import jax.numpy as jnp
        from paddle_tpu.ops.kernels.wo_matmul_pallas import (
            pack_int4_halves, reference_wo_int4_matmul, wo_int4_matmul)
        rng = np.random.default_rng(1)
        k, n = 256, 120
        q = jnp.asarray(rng.integers(-7, 8, (k, n)), jnp.int8)
        packed = pack_int4_halves(q)
        s = jnp.asarray(rng.random(n) * 0.05 + 0.01, jnp.float32)
        x = jnp.asarray(rng.standard_normal((10, k)), jnp.float32)
        out = wo_int4_matmul(x, packed, s, interpret=True)
        ref = reference_wo_int4_matmul(x, packed, s)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-4, rtol=1e-4)

    def test_weight_only_linear_int4_grads(self):
        import numpy as np
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.kernels import _common as kern
        from paddle_tpu.ops.kernels.wo_matmul_pallas import (
            pack_int4_halves, unpack_int4_halves)
        from paddle_tpu.quantization.functional import dequant_matmul_int4
        rng = np.random.default_rng(2)
        k, n = 64, 32
        q = jnp.asarray(rng.integers(-7, 8, (k, n)), jnp.int8)
        packed = pack_int4_halves(q)
        s = jnp.asarray(rng.random(n) * 0.05 + 0.01, jnp.float32)
        x = jnp.asarray(rng.standard_normal((6, k)), jnp.float32)
        kern.force_interpret(True)
        try:
            gx, gs = jax.grad(
                lambda x, s: jnp.sum(dequant_matmul_int4(x, packed, s) ** 2),
                argnums=(0, 1))(x, s)
        finally:
            kern.force_interpret(False)
        w = unpack_int4_halves(packed).astype(jnp.float32)
        rx, rs = jax.grad(
            lambda x, s: jnp.sum((jnp.matmul(x, w) * s) ** 2),
            argnums=(0, 1))(x, s)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   atol=1e-3, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(gs), np.asarray(rs),
                                   atol=1e-2, rtol=1e-3)

    def test_tpu_lowering(self):
        import jax
        import jax.numpy as jnp
        from paddle_tpu.ops.kernels.wo_matmul_pallas import wo_int4_matmul
        x = jnp.zeros((64, 512), jnp.bfloat16)
        w = jnp.zeros((512, 512), jnp.int8)   # 1024 output columns
        s = jnp.zeros((1024,), jnp.float32)
        jax.jit(lambda a, b, c: wo_int4_matmul(a, b, c)).trace(
            x, w, s).lower(lowering_platforms=("tpu",))


class TestGroupedWeightQuantize:
    """group_size scales (reference weight_quantize group modes): finer
    per-K-group scales recover accuracy on outlier-heavy weights."""

    def test_grouped_int8_accuracy_beats_per_channel(self):
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu.nn import quant as Q
        rng = np.random.default_rng(0)
        w = rng.standard_normal((128, 32)).astype(np.float32)
        w[:16] *= 50.0   # outlier K-rows wreck one shared channel scale
        wt = paddle.to_tensor(w)
        qw_pc, s_pc = Q.weight_quantize(wt, algo="weight_only_int8")
        qw_g, s_g = Q.weight_quantize(wt, algo="weight_only_int8",
                                      group_size=32)
        assert s_g.shape == [4, 32]
        err_pc = np.abs(np.asarray(Q.weight_dequantize(
            qw_pc, s_pc).numpy()) - w)[16:].mean()
        err_g = np.abs(np.asarray(Q.weight_dequantize(
            qw_g, s_g).numpy()) - w)[16:].mean()
        assert err_g < err_pc / 4, (err_g, err_pc)

    def test_grouped_linear_matches_dequant_matmul(self):
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu.nn import quant as Q
        rng = np.random.default_rng(1)
        w = paddle.to_tensor(rng.standard_normal((64, 24)).astype(np.float32))
        x = paddle.to_tensor(rng.standard_normal((5, 64)).astype(np.float32))
        for algo, dt in (("weight_only_int8", "int8"),
                         ("weight_only_int4", "int4")):
            qw, s = Q.weight_quantize(w, algo=algo, group_size=16)
            y = Q.weight_only_linear(x, qw, weight_scale=s, weight_dtype=dt)
            wd = Q.weight_dequantize(qw, s, algo=algo)
            ref = np.asarray(x.numpy()) @ np.asarray(wd.numpy())
            np.testing.assert_allclose(np.asarray(y.numpy()), ref,
                                       atol=1e-3, rtol=1e-3)

    def test_indivisible_group_raises(self):
        import numpy as np
        import pytest
        import paddle_tpu as paddle
        from paddle_tpu.nn import quant as Q
        w = paddle.to_tensor(np.ones((50, 8), np.float32))
        with pytest.raises(ValueError, match="divide"):
            Q.weight_quantize(w, group_size=16)


def test_grouped_int8_kernel_matches_composite():
    """The grouped-scale Pallas path (per-K-group rescale in VMEM) must
    match the dequantize-then-matmul composite, fwd and grads."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import _common as kern
    from paddle_tpu.ops.kernels.wo_matmul_pallas import (
        reference_wo_int8_matmul, wo_int8_matmul)
    from paddle_tpu.quantization.functional import dequant_matmul_int8
    rng = np.random.default_rng(0)
    k, n, G = 256, 96, 4
    wq = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    s = jnp.asarray(rng.random((G, n)) * 0.02 + 0.001, jnp.float32)
    x = jnp.asarray(rng.standard_normal((12, k)), jnp.float32)
    out = wo_int8_matmul(x, wq, s, interpret=True)
    ref = reference_wo_int8_matmul(x, wq, s)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-3, rtol=1e-3)
    # grads through the public dispatch (interpret kernel path)
    kern.force_interpret(True)
    try:
        gx, gsc = jax.grad(
            lambda x, s: jnp.sum(dequant_matmul_int8(x, wq, s) ** 2),
            argnums=(0, 1))(x, s)
    finally:
        kern.force_interpret(False)
    def comp(x, s):
        wd = (wq.reshape(G, k // G, n).astype(jnp.float32)
              * s[:, None]).reshape(k, n)
        return jnp.sum(jnp.matmul(x, wd) ** 2)
    rx, rs = jax.grad(comp, argnums=(0, 1))(x, s)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(rx), atol=1e-2,
                               rtol=1e-3)
    np.testing.assert_allclose(np.asarray(gsc), np.asarray(rs), atol=1e-1,
                               rtol=1e-3)


def test_grouped_int8_kernel_tpu_lowering():
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels.wo_matmul_pallas import wo_int8_matmul
    x = jnp.zeros((32, 512), jnp.bfloat16)
    w = jnp.zeros((512, 768), jnp.int8)
    s = jnp.zeros((8, 768), jnp.float32)
    jax.jit(lambda a, b, c: wo_int8_matmul(a, b, c)).trace(
        x, w, s).lower(lowering_platforms=("tpu",))


# ---- round-4b families: fused SwiGLU + fused masked softmax -------------


def test_swiglu_kernel_matches_composite():
    """Fused SwiGLU (two-arg and packed) vs the XLA composite: forward and
    both gradients, including a non-divisible row count."""
    from paddle_tpu.ops.kernels import swiglu_pallas as sg
    rng = np.random.default_rng(7)
    for rows in (32, 13):
        g = jnp.asarray(rng.standard_normal((rows, 256)), jnp.float32)
        u = jnp.asarray(rng.standard_normal((rows, 256)), jnp.float32)
        y = sg.swiglu_fused(g, u, True)
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(sg.reference_swiglu(g, u)),
                                   atol=1e-5)
        gk = jax.grad(lambda a, b: jnp.sum(sg.swiglu_fused(a, b, True) ** 2),
                      argnums=(0, 1))(g, u)
        gr = jax.grad(lambda a, b: jnp.sum(sg.reference_swiglu(a, b) ** 2),
                      argnums=(0, 1))(g, u)
        for a, b in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)
        # packed layout: same math, one input row holding [g | u]
        x = jnp.concatenate([g, u], axis=-1)
        yp = sg.swiglu_packed(x, True)
        np.testing.assert_allclose(np.asarray(yp), np.asarray(y), atol=1e-6)
        dxp = jax.grad(lambda a: jnp.sum(sg.swiglu_packed(a, True) ** 2))(x)
        np.testing.assert_allclose(
            np.asarray(dxp),
            np.concatenate([np.asarray(gk[0]), np.asarray(gk[1])], -1),
            atol=1e-4, rtol=1e-4)


def test_swiglu_public_dispatch_uses_kernel():
    """paddle.swiglu dispatches to the Pallas kernel for lane-aligned
    shapes and falls back to the composite otherwise; numerics match in
    both modes."""
    rng = np.random.default_rng(8)
    x_al = paddle.to_tensor(
        rng.standard_normal((4, 512)).astype(np.float32), stop_gradient=False)
    x_odd = paddle.to_tensor(
        rng.standard_normal((4, 70)).astype(np.float32), stop_gradient=False)
    ref_al = paddle.nn.functional.swiglu(x_al).numpy()
    ref_odd = paddle.nn.functional.swiglu(x_odd).numpy()
    kern.force_interpret(True)
    kern._LAST_PICK.pop("swiglu", None)
    try:
        y_al = paddle.nn.functional.swiglu(x_al)
        # pin the dispatch: the aligned call must have reached the kernel
        # (a broken guard would fall back silently and still match)
        assert kern.get_last_pick("swiglu") is not None
        y_odd = paddle.nn.functional.swiglu(x_odd)
        y_al.sum().backward()
        assert x_al.grad is not None
    finally:
        kern.force_interpret(False)
    np.testing.assert_allclose(y_al.numpy(), ref_al, atol=1e-5)
    np.testing.assert_allclose(y_odd.numpy(), ref_odd, atol=1e-6)


def test_softmax_mask_kernel_matches_composite():
    """Fused masked softmax (additive mask + causal tri) vs the composite:
    forward and dx, including a row count that does not divide the block."""
    from paddle_tpu.ops.kernels import softmax_mask_pallas as sm
    rng = np.random.default_rng(9)
    for sq in (16, 13):
        x = jnp.asarray(rng.standard_normal((2, 3, sq, 128)), jnp.float32)
        mask = jnp.asarray(
            np.where(rng.random((2, 1, sq, 128)) > 0.2, 0.0, -1e9),
            jnp.float32)
        y = sm.softmax_mask_fused(x, mask, True)
        yr = sm.reference_softmax_mask(x, mask)
        np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=2e-6)
        gk, gmk = jax.grad(
            lambda a, m: jnp.sum(sm.softmax_mask_fused(a, m, True) ** 2),
            argnums=(0, 1))(x, mask)
        gr, gmr = jax.grad(
            lambda a, m: jnp.sum(sm.reference_softmax_mask(a, m) ** 2),
            argnums=(0, 1))(x, mask)
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gr), atol=1e-5)
        # the mask gradient (a trainable additive bias) must flow on the
        # kernel path exactly as on the composite — incl. the head-axis
        # broadcast reduction back to [b, 1, sq, sk]
        np.testing.assert_allclose(np.asarray(gmk), np.asarray(gmr),
                                   atol=1e-5)

        yt = sm.softmax_mask_tri(x, True)
        ytr = sm.reference_softmax_mask(x)
        np.testing.assert_allclose(np.asarray(yt), np.asarray(ytr),
                                   atol=2e-6)
        gt = jax.grad(
            lambda a: jnp.sum(sm.softmax_mask_tri(a, True) ** 2))(x)
        gtr = jax.grad(
            lambda a: jnp.sum(sm.reference_softmax_mask(a) ** 2))(x)
        np.testing.assert_allclose(np.asarray(gt), np.asarray(gtr),
                                   atol=1e-5)


def test_softmax_mask_fuse_public_api():
    """paddle.incubate.softmax_mask_fuse(_upper_triangle) match the
    composite through the public Tensor path, kernel and fallback modes."""
    rng = np.random.default_rng(10)
    xn = rng.standard_normal((2, 2, 8, 128)).astype(np.float32)
    mn = np.where(rng.random((2, 1, 8, 128)) > 0.2, 0.0, -1e9).astype(
        np.float32)

    def run():
        x = paddle.to_tensor(xn, stop_gradient=False)
        m = paddle.to_tensor(mn)
        y = paddle.incubate.softmax_mask_fuse(x, m)
        yt = paddle.incubate.softmax_mask_fuse_upper_triangle(
            paddle.to_tensor(xn))
        y.sum().backward()
        return y.numpy(), yt.numpy(), x.grad.numpy()

    y0, yt0, g0 = run()
    kern.force_interpret(True)
    try:
        y1, yt1, g1 = run()
    finally:
        kern.force_interpret(False)
    np.testing.assert_allclose(y0, y1, atol=1e-5)
    np.testing.assert_allclose(yt0, yt1, atol=1e-5)
    np.testing.assert_allclose(g0, g1, atol=1e-5)


def test_lamb_kernel_matches_reference_update():
    """Fused LAMB (two-pass: moments+norm partials, trust apply) vs the
    composite, including a lane-indivisible size (padded tail must not
    perturb the trust ratio)."""
    from paddle_tpu.ops.kernels import lamb_pallas as lp
    rng = np.random.default_rng(21)
    for n in (1024, 1000 + 13):
        w = jnp.asarray(rng.standard_normal(n), jnp.float32)
        g = jnp.asarray(rng.standard_normal(n), jnp.float32)
        m = jnp.asarray(rng.standard_normal(n) * 0.1, jnp.float32)
        v = jnp.asarray(rng.random(n) * 0.01, jnp.float32)
        kw = dict(beta1=0.9, beta2=0.999, eps=1e-6, wd=0.01)
        w2, m2, v2, p_out, trust = lp.lamb_update(
            w, g, m, v, 1e-3, 3.0, out_dtype=jnp.bfloat16, interpret=True,
            **kw)
        wr, mr, vr, tr = lp.reference_lamb(w, g, m, v, 1e-3, 3.0, **kw)
        np.testing.assert_allclose(np.asarray(w2), np.asarray(wr),
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(m2), np.asarray(mr),
                                   rtol=1e-5, atol=1e-8)
        np.testing.assert_allclose(np.asarray(v2), np.asarray(vr),
                                   rtol=1e-5, atol=1e-8)
        np.testing.assert_allclose(float(trust), float(tr), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(p_out),
                                   np.asarray(wr.astype(jnp.bfloat16)))


def test_lamb_optimizer_fused_path_matches_unfused():
    """paddle.optimizer.Lamb steps identically through the fused kernel
    and the composite (two steps, trust ratio live both times)."""
    rng = np.random.default_rng(22)
    wn = rng.standard_normal((128, 80)).astype(np.float32)  # 10240 >= 8192
    gn = rng.standard_normal((2, 128, 80)).astype(np.float32)

    def run(fused):
        paddle.seed(0)
        w = paddle.to_tensor(wn.copy(), stop_gradient=False)
        w.name = "w"
        opt = paddle.optimizer.Lamb(learning_rate=0.01,
                                    lamb_weight_decay=0.02, parameters=[w])
        if fused:
            kern.force_interpret(True)
        try:
            for i in range(2):
                (w * paddle.to_tensor(gn[i])).sum().backward()
                opt.step()
                opt.clear_grad()
        finally:
            if fused:
                kern.force_interpret(False)
        return w.numpy()

    np.testing.assert_allclose(run(True), run(False), rtol=1e-4, atol=1e-6)


def test_lamb_multi_precision_master_weights():
    """multi_precision Lamb keeps f32 master weights through the fused
    kernel (emit_w32 path): repeated tiny updates on a bf16 param must
    accumulate in the master copy instead of vanishing in bf16 rounding."""
    rng = np.random.default_rng(23)
    wn = (rng.standard_normal((128, 80)) * 4).astype(np.float32)
    gn = np.full((128, 80), 1e-3, np.float32)

    def run(fused):
        paddle.seed(0)
        w = paddle.to_tensor(wn.astype(np.float32), stop_gradient=False)
        w._data = w._data.astype(jnp.bfloat16)
        w.name = "w"
        opt = paddle.optimizer.Lamb(learning_rate=1e-4,
                                    lamb_weight_decay=0.0, parameters=[w],
                                    multi_precision=True)
        if fused:
            kern.force_interpret(True)
        try:
            for _ in range(3):
                (w * paddle.to_tensor(gn.astype(np.float32))).sum().backward()
                opt.step()
                opt.clear_grad()
        finally:
            if fused:
                kern.force_interpret(False)
        master = opt._get_master(w)
        assert master is not None and master._data.dtype == jnp.float32
        return np.asarray(master._data)

    np.testing.assert_allclose(run(True), run(False), rtol=1e-4, atol=1e-6)


# ---------------------------------------------------------------------------
# fused dropout + residual add (in-kernel counter-hash mask)
# ---------------------------------------------------------------------------

def test_dropout_add_kernel_matches_hash_reference():
    """The Pallas kernel's mask is a pure function of (seed, index): the
    interpret-mode kernel must match the jnp reference BIT-EXACTLY."""
    from paddle_tpu.ops.kernels import dropout_add_pallas as dak
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((48, 256)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((48, 256)), jnp.float32)
    seed = jnp.int32(1234)
    y = dak.dropout_add(x, res, seed, 0.3, True)
    want = dak.reference_dropout_add(x, res, seed, 0.3)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(want))
    # keep rate ~ 1-p and first moment preserved (upscale_in_train)
    kept = np.asarray(y - res) != 0.0
    assert abs(kept.mean() - 0.7) < 0.03
    np.testing.assert_allclose(np.asarray(y - res).mean(),
                               np.asarray(x).mean(), atol=0.05)


def test_dropout_add_backward_regenerates_identical_mask():
    """No mask residual: the bwd kernel re-derives keep from the saved
    seed — dx must be nonzero exactly where the fwd kept x."""
    from paddle_tpu.ops.kernels import dropout_add_pallas as dak
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((40, 192)), jnp.float32)
    res = jnp.asarray(rng.standard_normal((40, 192)), jnp.float32)
    seed = jnp.int32(77)
    p = 0.4

    def f(a, b):
        return dak.dropout_add(a, b, seed, p, True)

    y, vjp = jax.vjp(f, x, res)
    dy = jnp.ones_like(y)
    dx, dres = vjp(dy)
    kept = np.asarray(y - res) != 0.0
    np.testing.assert_array_equal(np.asarray(dx) != 0.0, kept)
    np.testing.assert_allclose(np.asarray(dx)[kept], 1.0 / (1.0 - p),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(dres), 1.0)


def test_dropout_add_seed_sensitivity():
    from paddle_tpu.ops.kernels import dropout_add_pallas as dak
    x = jnp.ones((32, 128), jnp.float32)
    res = jnp.zeros((32, 128), jnp.float32)
    a = np.asarray(dak.dropout_add(x, res, jnp.int32(1), 0.5, True))
    b = np.asarray(dak.dropout_add(x, res, jnp.int32(1), 0.5, True))
    c = np.asarray(dak.dropout_add(x, res, jnp.int32(2), 0.5, True))
    np.testing.assert_array_equal(a, b)
    assert (a != c).any()
    # block-size independence: a different row-block must not change the
    # mask (the hash is over GLOBAL indices, not block-locals)
    kern.set_block_override("dropout_add", 8)
    try:
        d = np.asarray(dak.dropout_add(x, res, jnp.int32(1), 0.5, True))
    finally:
        kern.set_block_override("dropout_add", None)
    np.testing.assert_array_equal(a, d)


def test_fused_dropout_add_public_api_dispatches(monkeypatch):
    """The public API must actually reach the Pallas kernel: with the seed
    draw pinned, the output bit-matches the kernel's hash reference — the
    XLA-threefry fallback cannot produce this mask, so a silently broken
    dispatch gate fails here."""
    import paddle_tpu.incubate.nn.functional as IF
    from paddle_tpu.incubate.nn import FusedDropoutAdd
    from paddle_tpu.ops.kernels import dropout_add_pallas as dak

    monkeypatch.setattr(jax.random, "randint",
                        lambda key, shape, lo, hi, dtype=None:
                        jnp.asarray(4242, jnp.int32))
    paddle.seed(7)
    x = paddle.to_tensor(
        np.random.default_rng(3).standard_normal((16, 128)).astype("float32"))
    x.stop_gradient = False
    y = paddle.to_tensor(
        np.random.default_rng(4).standard_normal((16, 128)).astype("float32"))
    kern.force_interpret(True)
    try:
        out = IF.fused_dropout_add(x, y, p=0.25, training=True)
        loss = out.sum()
        loss.backward()
        layer_out = FusedDropoutAdd(p=0.25)(x, y)
    finally:
        kern.force_interpret(False)
    want = dak.reference_dropout_add(x._data, y._data, jnp.int32(4242), 0.25)
    np.testing.assert_array_equal(out.numpy(), np.asarray(want))
    np.testing.assert_array_equal(layer_out.numpy(), np.asarray(want))
    kept = (out.numpy() - y.numpy()) != 0.0
    assert abs(kept.mean() - 0.75) < 0.05
    g = x.grad.numpy()
    np.testing.assert_array_equal(g != 0.0, kept)
    # eval mode / p=0 fall back to identity
    out_eval = IF.fused_dropout_add(x, y, p=0.25, training=False)
    np.testing.assert_allclose(out_eval.numpy(), x.numpy() + y.numpy(),
                               rtol=1e-6)


# ---------------------------------------------------------------------------
# fused linear param-grad accumulate (x^T dy folded into the grad buffer)
# ---------------------------------------------------------------------------

def test_linear_grad_acc_kernel_matches_composite():
    from paddle_tpu.ops.kernels import linear_grad_add_pallas as lga
    rng = np.random.default_rng(0)
    for (m, k, n, dt, adt) in [(700, 300, 500, jnp.bfloat16, jnp.float32),
                               (512, 256, 256, jnp.float32, jnp.float32),
                               (1024, 384, 128, jnp.bfloat16, jnp.bfloat16)]:
        x = jnp.asarray(rng.standard_normal((m, k)), dt)
        dy = jnp.asarray(rng.standard_normal((m, n)), dt)
        acc = jnp.asarray(rng.standard_normal((k, n)), adt)
        got = lga.linear_grad_acc(x, dy, jnp.array(acc), interpret=True)
        want = lga.reference_grad_acc(x, dy, acc)
        err = float(jnp.max(jnp.abs(
            got.astype(jnp.float32) - want.astype(jnp.float32))))
        denom = float(jnp.max(jnp.abs(want.astype(jnp.float32)))) or 1.0
        assert err / denom < (2e-2 if adt == jnp.bfloat16 else 1e-5), \
            (m, k, n, err, denom)


def test_fused_linear_param_grad_add_public_api():
    """Reference call contract (mp_layers.py:251): returns the accumulated
    (dweight, dbias); multi_precision=True keeps a fresh accumulator fp32."""
    import paddle_tpu.incubate.nn.functional as IF
    rng = np.random.default_rng(5)
    x = paddle.to_tensor(rng.standard_normal((8, 4, 48)).astype("float32"))
    dy = paddle.to_tensor(rng.standard_normal((8, 4, 32)).astype("float32"))
    dw0 = paddle.to_tensor(rng.standard_normal((48, 32)).astype("float32"))
    db0 = paddle.to_tensor(rng.standard_normal((32,)).astype("float32"))

    kern.force_interpret(True)
    try:
        dw, db = IF.fused_linear_param_grad_add(x, dy, dw0, db0,
                                                multi_precision=True,
                                                has_bias=True)
    finally:
        kern.force_interpret(False)
    x2 = x.numpy().reshape(-1, 48)
    dy2 = dy.numpy().reshape(-1, 32)
    np.testing.assert_allclose(dw.numpy(), dw0.numpy() + x2.T @ dy2,
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(db.numpy(), db0.numpy() + dy2.sum(0),
                               rtol=2e-5, atol=2e-5)
    # no accumulator: fresh fp32 buffer (multi_precision) from bf16 grads
    xb = paddle.to_tensor(x.numpy().astype("float32")).astype("bfloat16")
    dyb = paddle.to_tensor(dy.numpy().astype("float32")).astype("bfloat16")
    kern.force_interpret(True)
    try:
        dw2, db2 = IF.fused_linear_param_grad_add(xb, dyb, None, None,
                                                  multi_precision=True,
                                                  has_bias=True)
    finally:
        kern.force_interpret(False)
    assert str(dw2.dtype) in ("paddle.float32", "float32"), dw2.dtype
    np.testing.assert_allclose(dw2.numpy(), x2.T @ dy2, rtol=2e-2, atol=2e-1)
    dw3, db3 = IF.fused_linear_param_grad_add(x, dy, dw0, None,
                                              has_bias=False)
    assert db3 is None


# ---------------------------------------------------------------------------
# A8W8 int8 matmul (dynamic per-token quant + int8 MXU + dequant epilogue)
# ---------------------------------------------------------------------------

def test_a8w8_matmul_matches_composite_both_layouts():
    """Bit-exact parity on a boundary-free construction: x = q * 2^-5 with
    integer q in [-127, 127] and a pinned rowmax makes s_row exactly 2^-5,
    so round(x/s) has no rounding ambiguity between the interpreter and
    XLA — any kernel/composite divergence is a real bug, not a ulp flip."""
    from paddle_tpu.ops.kernels import a8w8_matmul_pallas as a8
    rng = np.random.default_rng(0)
    m, k, n = 300, 384, 272
    q_np = rng.integers(-127, 128, (m, k)).astype(np.float32)
    q_np[:, 0] = 127.0  # pin the row max -> s_row = 2^-5 exactly
    x = jnp.asarray(q_np * 2.0 ** -5, jnp.bfloat16)  # exactly representable
    wkn = jnp.asarray(rng.integers(-127, 128, (k, n)), jnp.int8)
    ws = jnp.asarray(rng.random(n) * 0.02 + 0.01, jnp.float32)
    want = np.asarray(a8.reference_a8w8(x, wkn, ws), np.float32)
    # cross-check the reference itself against the plain float matmul
    dense = (q_np * 2.0 ** -5) @ np.asarray(wkn, np.float32) \
        * np.asarray(ws)[None, :]
    np.testing.assert_allclose(want, dense.astype(np.float32), rtol=1e-2,
                               atol=1e-2)
    for layout, w in (("kn", wkn), ("nk", jnp.asarray(wkn.T))):
        got = np.asarray(a8.a8w8_matmul(x, w, ws, layout=layout,
                                        interpret=True), np.float32)
        np.testing.assert_array_equal(got, want, err_msg=layout)


def test_llm_int8_linear_prefill_dispatches_to_a8w8():
    """Prefill-shaped llm_int8_linear must agree between the Pallas A8W8
    path (stop_gradient inputs, kernel available) and the XLA fallback."""
    from paddle_tpu.nn.quant import llm_int8_linear
    rng = np.random.default_rng(1)
    m, k, n = 256, 320, 160
    x_np = rng.standard_normal((m, k)).astype("float32")
    x_np[:, 7] *= 40.0  # force an outlier column through the fp path
    w_np = rng.integers(-127, 128, (n, k)).astype("int8")
    s_np = (rng.random(n) * 0.02 + 0.01).astype("float32")
    b_np = rng.standard_normal((n,)).astype("float32")

    x = paddle.to_tensor(x_np)
    w = paddle.to_tensor(w_np)
    s = paddle.to_tensor(s_np)
    b = paddle.to_tensor(b_np)
    # count kernel invocations so a silently-dead dispatch gate fails here
    from paddle_tpu.ops.kernels import a8w8_matmul_pallas as a8
    calls = []
    real = a8.a8w8_matmul
    a8.a8w8_matmul = lambda *a, **kw: (calls.append(1), real(*a, **kw))[1]
    kern.force_interpret(True)
    try:
        got = llm_int8_linear(x, w, bias=b, weight_scale=s)
    finally:
        kern.force_interpret(False)
        a8.a8w8_matmul = real
    assert calls, "prefill llm_int8_linear did not dispatch to the kernel"
    want = llm_int8_linear(x, w, bias=b, weight_scale=s)  # XLA fallback
    np.testing.assert_allclose(got.numpy(), want.numpy(), rtol=2e-3,
                               atol=2e-2)
    # grad-needing inputs must stay on the differentiable fallback
    xg = paddle.to_tensor(x_np)
    xg.stop_gradient = False
    kern.force_interpret(True)
    try:
        out = llm_int8_linear(xg, w, bias=b, weight_scale=s)
        out.sum().backward()   # must not hit the AD-rule-less pallas_call
    finally:
        kern.force_interpret(False)
    assert xg.grad is not None
    # ...but no_grad mode with the same grad-tracked input DOES dispatch
    calls.clear()
    a8.a8w8_matmul = lambda *a, **kw: (calls.append(1), real(*a, **kw))[1]
    kern.force_interpret(True)
    try:
        with paddle.no_grad():
            llm_int8_linear(xg, w, bias=b, weight_scale=s)
    finally:
        kern.force_interpret(False)
        a8.a8w8_matmul = real
    assert calls, "no_grad inference skipped the A8W8 kernel"
