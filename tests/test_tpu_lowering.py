"""Cross-platform TPU lowering proofs.

`jax.jit(fn).trace(...).lower(lowering_platforms=("tpu",))` runs the Mosaic
lowering pipeline on CPU — bad BlockSpecs, unsupported ops and dtype errors
surface here. What only the chip's compiler refuses (block alignment against
the real tiling, VMEM budgets) is covered by tests/test_chip_compile.py.

Covered: every Pallas kernel family (forward AND backward where one exists)
plus the flagship GPT train step traced with real-kernel dispatch forced on,
so the kernels are proven to lower in-context, not just in isolation.

Reference analog: paddle/phi/kernels/fusion/gpu/* compiling in the
reference's CUDA CI.
"""

from __future__ import annotations

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.kernels import _common as kern


def lower_tpu(fn, *args):
    """Lower `fn(*args)` for the TPU target from the CPU host; returns the
    StableHLO text (raises on any Mosaic lowering failure)."""
    lowered = jax.jit(fn).trace(*args).lower(lowering_platforms=("tpu",))
    return lowered.as_text()


def assert_mosaic(txt):
    assert "tpu_custom_call" in txt, "no Mosaic custom call in lowered HLO"


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("gqa", [1, 4], ids=["mha", "gqa4"])
def test_flash_attention_fwd_bwd_lowers(dtype, gqa):
    from paddle_tpu.ops.kernels import flash_attention_pallas as fap
    b, s, h, d = 2, 512, 8, 64
    q = jnp.zeros((b, s, h, d), dtype)
    k = jnp.zeros((b, s, h // gqa, d), dtype)
    v = jnp.zeros((b, s, h // gqa, d), dtype)

    fwd = functools.partial(fap.flash_attention_forward, causal=True)
    assert_mosaic(lower_tpu(fwd, q, k, v))

    def fwd_bwd(q, k, v):
        out, lse = fap.flash_attention_forward_lse(q, k, v, causal=True)
        return fap.flash_attention_backward(q, k, v, out, lse,
                                            jnp.ones_like(out), causal=True)

    assert_mosaic(lower_tpu(fwd_bwd, q, k, v))


def _kernel_dot_operands(closed):
    """{kernel function name: [(lhs dtype, rhs dtype) of every dot_general
    in its body, loops included]} over the pallas_calls of `closed`."""
    found = {}

    def dots(jaxpr, out):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                out.append(tuple(str(v.aval.dtype) for v in eqn.invars))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                dots(sub, out)

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                body = eqn.params["jaxpr"]
                dots(body, found.setdefault(body.debug_info.func_name, []))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(closed.jaxpr)
    return found


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_flash_attention_dots_take_stored_dtype(dtype):
    """Every MXU product of the forward, dQ and dKV kernels takes its
    operands in the dtype the inputs are stored in: a cast back to f32 in
    front of a dot (multi-pass f32 on the MXU) fails here."""
    from paddle_tpu.ops.kernels import flash_attention_pallas as fap
    q = jnp.zeros((1, 512, 2, 64), dtype)
    seg = jnp.zeros((1, 512), jnp.int32)

    def fwd_bwd(q, k, v):
        out, lse = fap.flash_attention_forward_lse(q, k, v, causal=True,
                                                   segment_ids=seg)
        return fap.flash_attention_backward(q, k, v, out, lse,
                                            jnp.ones_like(out), causal=True,
                                            segment_ids=seg)

    found = _kernel_dot_operands(jax.make_jaxpr(fwd_bwd)(q, q, q))
    assert set(found) == {"_attn_kernel", "_dq_kernel", "_dkv_kernel"}
    # Q.K^T, P.V forward; S, dP, dS.K in dQ; S, P^T.dO, dP, dS^T.Q in dKV
    assert {name: len(ops) for name, ops in found.items()} == {
        "_attn_kernel": 2, "_dq_kernel": 3, "_dkv_kernel": 4}
    for name, ops in found.items():
        assert set(ops) == {(dtype, dtype)}, (name, ops)


@pytest.mark.parametrize("shape", [(1, 509, 256), (3, 17, 384),
                                   (1, 509, 18432)])  # 18432: rows=56 budget
def test_rms_norm_prime_rows_lowers(shape):
    """Row counts that defeat the divisor search (prime / tiny) must be
    padded to a sublane-legal block, not degraded to rows=1 (which Mosaic
    rejects). Regression for the round-3 verdict's _pick_rows finding."""
    from paddle_tpu.ops.kernels import rms_norm_pallas as rnp_
    x = jnp.zeros(shape, jnp.float32)
    w = jnp.ones((shape[-1],), jnp.float32)
    assert_mosaic(lower_tpu(
        lambda a, b: rnp_.rms_norm_fused(a, b, None, 1e-6, False), x, w))


@pytest.mark.parametrize("shape", [(2, 127, 4, 64), (1, 509, 2, 128),
                                   (1, 509, 36, 128)])  # feat 4608: rows=56
def test_rope_prime_seq_lowers(shape):
    from paddle_tpu.ops.kernels import rope_pallas as rp
    x = jnp.zeros(shape, jnp.float32)
    cos = jnp.zeros((shape[1], shape[-1]), jnp.float32)
    assert_mosaic(lower_tpu(
        lambda a, c, s: rp.rope_apply(a, c, s, False), x, cos, cos))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rms_norm_fused_lowers(dtype):
    from paddle_tpu.ops.kernels import rms_norm_pallas as rnp_
    x = jnp.zeros((4, 128, 256), dtype)
    w = jnp.ones((256,), dtype)
    res = jnp.zeros((4, 128, 256), dtype)

    fn = functools.partial(rnp_.rms_norm_fused, eps=1e-6, interpret=False)
    assert_mosaic(lower_tpu(lambda a, b, r: fn(a, b, r), x, w, res))

    def grad_fn(a, b, r):
        return jax.grad(
            lambda *t: jnp.sum(fn(*t)[0].astype(jnp.float32)),
            argnums=(0, 1, 2))(a, b, r)

    assert_mosaic(lower_tpu(grad_fn, x, w, res))


@pytest.mark.parametrize("shape", [(2, 128, 8, 64), (1, 1024, 4, 128)])
def test_rope_fwd_bwd_lowers(shape):
    from paddle_tpu.ops.kernels import rope_pallas as rp
    b, s, h, d = shape
    x = jnp.zeros(shape, jnp.float32)
    cos = jnp.zeros((s, d), jnp.float32)
    sin = jnp.zeros((s, d), jnp.float32)

    fn = lambda a, c, si: rp.rope_apply(a, c, si, False)
    assert_mosaic(lower_tpu(fn, x, cos, sin))
    assert_mosaic(lower_tpu(
        lambda a, c, si: jax.grad(lambda t: jnp.sum(fn(t, c, si)))(a),
        x, cos, sin))


@pytest.mark.parametrize("c,f", [(154, 1024), (313, 1000), (128, 384)])
def test_moe_grouped_matmul_odd_capacity_lowers(c, f):
    """Capacity = ceil(capacity_factor*n*k/e) is rarely 8-divisible (154,
    313, ...) and intermediate sizes need not divide 128: the kernel must
    pad/full-block, not degrade bc/bf below the Mosaic rules."""
    from paddle_tpu.ops.kernels import moe_gemm_pallas as mg
    e, hd = 4, 512
    x = jnp.zeros((e, c, hd), jnp.float32)
    w = jnp.zeros((e, hd, f), jnp.float32)
    counts = jnp.zeros((e,), jnp.int32)
    assert_mosaic(lower_tpu(
        lambda a, b: mg.grouped_matmul(a, b, counts, False), x, w))


def test_moe_grouped_matmul_fwd_bwd_lowers():
    from paddle_tpu.ops.kernels import moe_gemm_pallas as mg
    e, c, hd, f = 8, 256, 512, 1024
    x = jnp.zeros((e, c, hd), jnp.bfloat16)
    w = jnp.zeros((e, hd, f), jnp.bfloat16)
    counts = jnp.zeros((e,), jnp.int32)

    assert_mosaic(lower_tpu(
        lambda a, b: mg.grouped_matmul(a, b, counts, False), x, w))

    def grad_fn(a, b):
        return jax.grad(lambda *t: jnp.sum(
            mg.grouped_matmul(*t, counts, False).astype(jnp.float32)),
            argnums=(0, 1))(a, b)

    assert_mosaic(lower_tpu(grad_fn, x, w))


@pytest.mark.parametrize("shape", [(4, 128, 512), (1, 509, 384)])
def test_bias_dropout_ln_lowers(shape):
    from paddle_tpu.ops.kernels import bias_dropout_ln_pallas as bd
    x = jnp.zeros(shape, jnp.float32)
    vec = jnp.zeros((shape[-1],), jnp.float32)

    def fwd(x, b, r, m, g, be):
        return bd.bias_dropout_ln(x, b, r, m, g, be, 1e-5, False)

    assert_mosaic(lower_tpu(fwd, x, vec, x, x, vec, vec))

    def grad_fn(x, b, r, m, g, be):
        return jax.grad(lambda *t: jnp.sum(
            bd.bias_dropout_ln(t[0], t[1], t[2], m, t[3], t[4],
                               1e-5, False)[0]),
            argnums=(0, 1, 2, 3, 4))(x, b, r, g, be)

    assert_mosaic(lower_tpu(grad_fn, x, vec, x, x, vec, vec))

    # maskless (inference) kernel variant lowers too
    assert_mosaic(lower_tpu(
        lambda x, b, r, g, be: bd.bias_dropout_ln(x, b, r, None, g, be,
                                                  1e-5, False),
        x, vec, x, vec, vec))


@pytest.mark.parametrize("nv", [(64, 32000), (13, 50257)])
def test_ce_kernel_lowers(nv):
    from paddle_tpu.ops.kernels import ce_pallas as cp
    n, v = nv
    lg = jnp.zeros((n, v), jnp.float32)
    lb = jnp.zeros((n,), jnp.int32)
    assert_mosaic(lower_tpu(
        lambda a: cp.c_softmax_with_cross_entropy(a, lb, 0, None, False),
        lg))
    assert_mosaic(lower_tpu(
        lambda a: jax.grad(lambda t: jnp.sum(
            cp.c_softmax_with_cross_entropy(t, lb, 0, None, False)))(a),
        lg))


@pytest.fixture
def forced_dispatch():
    """Trace live paths with real kernel dispatch on (lowering only — the
    traced program is never executed on the CPU host)."""
    kern.force_dispatch(True)
    try:
        yield
    finally:
        kern.force_dispatch(False)


def test_flagship_train_step_lowers_with_kernels(forced_dispatch):
    """The full GPT train step — forward, loss, backward, the optimizer's
    AdamW update of every parameter in its own shape — lowers for TPU with
    the Pallas kernels dispatched in-context.
    This is the program bench.py times on real hardware."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.nn.utils import bind_param_arrays

    paddle.seed(0)
    cfg = GPTConfig(vocab_size=1024, max_position_embeddings=256,
                    hidden_size=256, num_layers=2, num_heads=4)
    model = GPT(cfg)
    params = list(model.parameters())
    arrays = [p._d for p in params]

    def loss_fn(arrays, ids, labels):
        # jax differentiates here, so the framework's own tape stays off:
        # recording it too would differentiate the kernels' vjp rules a
        # second time, which pallas_call does not support
        with bind_param_arrays(params, arrays), paddle.no_grad():
            _, loss = model(Tensor(ids), labels=Tensor(labels))
        return loss._d

    from paddle_tpu.optimizer.optimizers import _adam_update

    def train_step(arrays, ids, labels):
        loss, grads = jax.value_and_grad(loss_fn)(arrays, ids, labels)
        new_arrays = []
        for a, g in zip(arrays, grads):
            w, *_ = _adam_update(
                a, g, jnp.zeros(a.shape, jnp.float32),
                jnp.zeros(a.shape, jnp.float32), None, jnp.float32(1e-3),
                jnp.float32(1), beta1=0.9, beta2=0.999, eps=1e-8,
                decay=0.01, out_dtype=None)
            new_arrays.append(w)
        return loss, new_arrays

    ids = jnp.zeros((2, 256), jnp.int32)
    labels = jnp.zeros((2, 256), jnp.int32)
    txt = lower_tpu(train_step, arrays, ids, labels)
    assert_mosaic(txt)


def test_train_step_optimizer_moves_no_parameter_and_holds_no_float64():
    """PR 32's finding: on the chip `[rows, cols] -> [n / 128, 128]` is no
    view of a tiled array but a copy, and eight of them round the AdamW
    update were 17 % of GPT-2 medium's step. The compiled train step (amp
    O2, float32 masters) reshapes nothing of a parameter's size under the
    scope `optimizer`, and computes nothing there in float64 though the
    process runs under x64."""
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt2_tiny
    from test_serving_programs import equations

    paddle.seed(0)
    model = gpt2_tiny(hidden_size=256, num_heads=4)
    opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters(),
                                 weight_decay=0.1, multi_precision=True)
    model, opt = paddle.amp.decorate(model, opt, level="O2",
                                     dtype="bfloat16")

    def train_step(x, y):
        _, loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step = paddle.jit.to_static(train_step)
    ids = paddle.to_tensor(np.zeros((2, 128), np.int32))
    for _ in range(2):              # discovery, then the compiled program
        step(ids, ids)
    (jitted, cell, _), = step._cache.values()
    # traced anew with the kernels dispatched as on the chip (a trace
    # alone: nothing of it runs here)
    kern.force_dispatch(True)
    try:
        jaxpr = jax.jit(lambda *a: jitted.__wrapped__(*a)).trace(
            *cell["avals"]).jaxpr.jaxpr
    finally:
        kern.force_dispatch(False)
    sizes = {int(np.prod(p.shape)) for p in model.parameters()}
    under, bad = 0, []
    for eqn, stack in equations(jaxpr):
        if "optimizer" not in stack.split("/"):
            continue
        under += 1
        if eqn.primitive.name in ("reshape", "transpose", "pad",
                                  "concatenate") and any(
                int(np.prod(v.aval.shape)) in sizes for v in eqn.invars
                if hasattr(v.aval, "shape") and v.aval.shape):
            bad.append((eqn.primitive.name, stack, str(eqn.invars[0].aval)))
        bad += [("float64", stack, eqn.primitive.name) for v in eqn.outvars
                if getattr(v.aval, "dtype", None) == jnp.float64]
    assert under > 10 * len(sizes), under
    assert not bad, bad[:8]


def test_cached_decode_loop_lowers(forced_dispatch):
    """The whole incremental-decode program — prefill + KV-cache
    while_loop with on-device sampling — lowers for TPU with kernels
    dispatched (rope rides its Pallas kernel inside the loop body)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import llama_tiny
    from paddle_tpu.models.generation import _cached_decode

    paddle.seed(1)
    model = llama_tiny()
    model.eval()
    buf = jnp.zeros((1, 24), jnp.int64)
    key = jnp.zeros((2,), jnp.uint32)

    def fn(buf, key, temp, eos):
        return _cached_decode(model, buf, 4, key, temp, eos, 24,
                              True, 5, True)

    assert_mosaic(lower_tpu(fn, buf, key, jnp.float32(0.8), jnp.int64(1)))


def test_llama_forward_lowers_with_kernels(forced_dispatch):
    """Llama (rmsnorm + rope + flash attention in one program) lowers for
    TPU — the three transformer-glue kernels compose in-context."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.autograd.grad_mode import no_grad
    from paddle_tpu.models import llama_tiny
    from paddle_tpu.nn.utils import bind_param_arrays

    paddle.seed(0)
    model = llama_tiny()
    model.eval()
    params = list(model.parameters())
    arrays = [p._d for p in params]

    def fwd(arrays, ids):
        with bind_param_arrays(params, arrays):
            with no_grad():
                out = model(Tensor(ids))
        out = out[0] if isinstance(out, tuple) else out
        return out._d

    ids = jnp.zeros((1, 256), jnp.int32)
    assert_mosaic(lower_tpu(fwd, arrays, ids))


@pytest.mark.parametrize("cfg", [(2, 8, 2, 64, 512), (1, 4, 4, 128, 256)],
                         ids=["gqa4", "mha"])
def test_mmha_decode_lowers(cfg):
    """The decode-attention kernel (one token over the [B, Hkv, T, D]
    cache, scalar-prefetch position) lowers for TPU."""
    from paddle_tpu.ops.kernels import mmha_pallas
    b, h, h_kv, d, t = cfg
    q = jnp.zeros((b, 1, h, d), jnp.bfloat16)
    kb = jnp.zeros((b, h_kv, t, d), jnp.bfloat16)
    vb = jnp.zeros((b, h_kv, t, d), jnp.bfloat16)
    assert_mosaic(lower_tpu(
        lambda a, kk, vv: mmha_pallas.mmha_decode(a, kk, vv, jnp.int32(37)),
        q, kb, vb))


def test_paged_mmha_decode_lowers():
    """The paged decode-attention kernel (the whole pool left in HBM, page
    tables, positions and the layer as scalar prefetch, page DMAs in the
    kernel) lowers for TPU."""
    from paddle_tpu.ops.kernels import mmha_pallas
    q = jnp.zeros((4, 1, 8, 128), jnp.bfloat16)
    pool = jnp.zeros((2, 33, 2, 16, 128), jnp.bfloat16)
    tables = jnp.zeros((4, 16), jnp.int32)
    pos = jnp.zeros((4,), jnp.int32)
    assert_mosaic(lower_tpu(
        lambda a, kk, vv, t, p: mmha_pallas.paged_mmha_decode(
            a, kk, vv, jnp.int32(1), t, p), q, pool, pool, tables, pos))


def test_swiglu_fwd_bwd_lowers():
    from paddle_tpu.ops.kernels import swiglu_pallas as sg
    g = jnp.zeros((256, 2048), jnp.bfloat16)
    u = jnp.zeros((256, 2048), jnp.bfloat16)

    def grad_fn(a, b):
        return jax.grad(lambda t: jnp.sum(
            sg.swiglu_fused(t[0], t[1], False)))((a, b))

    assert_mosaic(lower_tpu(lambda a, b: sg.swiglu_fused(a, b, False), g, u))
    assert_mosaic(lower_tpu(grad_fn, g, u))
    x = jnp.zeros((256, 4096), jnp.bfloat16)
    assert_mosaic(lower_tpu(lambda a: sg.swiglu_packed(a, False), x))
    assert_mosaic(lower_tpu(
        lambda a: jax.grad(lambda t: jnp.sum(sg.swiglu_packed(t, False)))(a),
        x))


@pytest.mark.parametrize("sq", [512, 509])
def test_softmax_mask_fwd_bwd_lowers(sq):
    from paddle_tpu.ops.kernels import softmax_mask_pallas as sm
    x = jnp.zeros((2, 4, sq, 512), jnp.bfloat16)
    m = jnp.zeros((2, 1, sq, 512), jnp.bfloat16)
    assert_mosaic(lower_tpu(lambda a, b: sm.softmax_mask_fused(a, b, False),
                            x, m))
    assert_mosaic(lower_tpu(lambda a: sm.softmax_mask_tri(a, False), x))
    assert_mosaic(lower_tpu(
        lambda a, b: jax.grad(
            lambda t: jnp.sum(sm.softmax_mask_fused(t, b, False)))(a), x, m))
    assert_mosaic(lower_tpu(
        lambda a: jax.grad(
            lambda t: jnp.sum(sm.softmax_mask_tri(t, False)))(a), x))


@pytest.mark.parametrize("n", [128 * 1024, 100003])
def test_lamb_update_lowers(n):
    from paddle_tpu.ops.kernels import lamb_pallas as lp
    w = jnp.zeros((n,), jnp.float32)
    txt = lower_tpu(
        lambda w_, g, m, v: lp.lamb_update(
            w_, g, m, v, 1e-3, 2.0, beta1=0.9, beta2=0.999, eps=1e-6,
            wd=0.01, out_dtype=jnp.bfloat16),
        w, w, w, w)
    assert_mosaic(txt)


def test_fused_multi_transformer_decode_lowers():
    """The serving fused_multi_transformer decode step lowers for TPU with
    the mmha Pallas kernel in-context (kernel-qualifying cache shape)."""
    import paddle_tpu as paddle
    from paddle_tpu.incubate.nn.functional import fused_multi_transformer

    rng = np.random.default_rng(0)
    L, b, nh, hd, dff, T = 1, 1, 2, 128, 64, 64
    d = nh * hd

    def mk(*shape):
        return paddle.to_tensor(
            (rng.standard_normal(shape) * 0.05).astype(np.float32))

    w = dict(
        ln_s=[paddle.to_tensor(np.ones(d, np.float32))], ln_b=[mk(d)],
        qkv_w=[mk(3, nh, hd, d)], qkv_b=[mk(3, nh, hd)],
        lin_w=[mk(nh * hd, d)], lin_b=[mk(d)],
        fln_s=[paddle.to_tensor(np.ones(d, np.float32))], fln_b=[mk(d)],
        f1_w=[mk(d, dff)], f1_b=[mk(dff)], f2_w=[mk(dff, d)], f2_b=[mk(d)])

    def step(x_arr, cache_arr, ts_arr):
        out, caches = fused_multi_transformer(
            paddle.Tensor(x_arr), w["ln_s"], w["ln_b"], w["qkv_w"],
            w["qkv_b"], w["lin_w"], w["lin_b"], w["fln_s"], w["fln_b"],
            w["f1_w"], w["f1_b"], w["f2_w"], w["f2_b"],
            cache_kvs=[paddle.Tensor(cache_arr)],
            time_step=paddle.Tensor(ts_arr))
        return out._data, caches[0]._data

    x = jnp.zeros((b, 1, d), jnp.float32)
    cache = jnp.zeros((2, b, nh, T, hd), jnp.float32)
    ts = jnp.asarray([3], jnp.int32)
    kern.force_dispatch(True)
    try:
        txt = lower_tpu(step, x, cache, ts)
    finally:
        kern.force_dispatch(False)
    assert_mosaic(txt)


def test_llm_int8_linear_lowers():
    """llm_int8_linear lowers for TPU (int8 dot riding the MXU)."""
    import paddle_tpu as paddle
    from paddle_tpu.nn.quant import llm_int8_linear

    w = jnp.ones((32, 64), jnp.int8)
    s = jnp.ones((32,), jnp.float32)

    def f(xa):
        return llm_int8_linear(paddle.Tensor(xa), paddle.Tensor(w),
                               weight_scale=paddle.Tensor(s))._data

    txt = lower_tpu(f, jnp.zeros((4, 64), jnp.float32))
    assert "stablehlo" in txt or "module" in txt


def test_dropout_add_fwd_bwd_lowers():
    """fused dropout+add: in-kernel counter-hash mask (uint32 iota, mul,
    xor-shift) must survive Mosaic lowering in both passes."""
    from paddle_tpu.ops.kernels import dropout_add_pallas as dak

    x = jnp.zeros((64, 512), jnp.bfloat16)
    res = jnp.zeros((64, 512), jnp.bfloat16)
    seed = jnp.int32(5)

    def fwd(a, b):
        return dak.dropout_add(a, b, seed, 0.1)

    assert_mosaic(lower_tpu(fwd, x, res))

    def fwd_bwd(a, b):
        y, vjp = jax.vjp(lambda u, v: dak.dropout_add(u, v, seed, 0.1), a, b)
        return vjp(jnp.ones_like(y))

    assert_mosaic(lower_tpu(fwd_bwd, x, res))


def test_linear_grad_acc_lowers():
    """fused linear param-grad accumulate: MXU dot_general + fp32 VMEM
    scratch + revisited output tile + input/output alias must all lower."""
    from paddle_tpu.ops.kernels import linear_grad_add_pallas as lga

    x = jnp.zeros((1024, 512), jnp.bfloat16)
    dy = jnp.zeros((1024, 768), jnp.bfloat16)
    acc = jnp.zeros((512, 768), jnp.float32)
    assert_mosaic(lower_tpu(lambda a, b, c: lga.linear_grad_acc(a, b, c),
                            x, dy, acc))


@pytest.mark.parametrize("act,norm,p,bias_on", [
    (None, "rms", 0.1, False),        # attention epilogue
    ("gelu", "layer", 0.1, True),     # MLP epilogue, gelu form
    ("swiglu", "rms", 0.0, False),    # MLP epilogue, swiglu form
])
def test_block_epilogue_fwd_bwd_lowers(act, norm, p, bias_on):
    """Transformer-block mega-kernel epilogues: (act ->) dropout ->
    residual-add -> norm and their single-kernel backwards must lower —
    incl. the in-kernel hash mask, the packed swiglu dx concat, and the
    8-row partial dw/db layout."""
    from paddle_tpu.ops.kernels import block_fused_pallas as bf
    hd = 256
    xw = hd * 2 if act == "swiglu" else hd
    x = jnp.zeros((2, 64, xw), jnp.bfloat16)
    res = jnp.zeros((2, 64, hd), jnp.bfloat16)
    w = jnp.ones((hd,), jnp.float32)
    b = jnp.zeros((hd,), jnp.float32) if bias_on else None
    seed = jnp.int32(3)

    fwd = lambda *a: bf.fused_epilogue(  # noqa: E731
        a[0], a[1], a[2], b, seed, p, 1e-5, act, norm, None, False)
    txt = lower_tpu(lambda *a: fwd(*a)[0], x, res, w)
    assert_mosaic(txt)
    assert "block_" in txt  # analyzer-visible kernel name embedded

    def fwd_bwd(x, res, w):
        def f(*t):
            y, h = fwd(*t)
            return jnp.sum(y.astype(jnp.float32)) + \
                jnp.sum(h.astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2))(x, res, w)

    assert_mosaic(lower_tpu(fwd_bwd, x, res, w))


def test_serving_decode_epilogue_lowers():
    """The decode-step epilogue at continuous-batch shape [B, 1, H]."""
    from paddle_tpu.ops.kernels import block_fused_pallas as bf
    x = jnp.zeros((8, 1, 256), jnp.float32)
    res = jnp.zeros((8, 1, 256), jnp.float32)
    w = jnp.ones((256,), jnp.float32)
    txt = lower_tpu(
        lambda a, r, ww: bf.decode_epilogue(a, r, ww, 1e-6, False)[0],
        x, res, w)
    assert_mosaic(txt)
    assert "block_decode_epilogue" in txt


def test_llama_fused_trunk_lowers(forced_dispatch):
    """The whole Llama fused trunk — rope + flash attention + swiglu +
    both block epilogues per layer, final norm folded — lowers as ONE
    program (the TPU bench/serving path)."""
    import paddle_tpu as paddle
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.autograd.grad_mode import no_grad
    from paddle_tpu.models import llama_tiny

    paddle.seed(0)
    model = llama_tiny()
    model.eval()
    assert model._use_fused_blocks()

    def fwd(ids):
        with no_grad():
            return model(Tensor(ids))._data

    txt = lower_tpu(fwd, jnp.zeros((1, 256), jnp.int32))
    assert_mosaic(txt)
    # both junctions take the projection output directly (act=None), so
    # every epilogue in the trunk traces under the attn-epilogue name
    assert "block_attn_epilogue" in txt


@pytest.mark.parametrize("layout", ["kn", "nk"])
def test_a8w8_matmul_lowers(layout):
    """A8W8: in-VMEM activation quantization + int8 x int8 MXU dot +
    dequant epilogue must lower for both weight layouts."""
    from paddle_tpu.ops.kernels import a8w8_matmul_pallas as a8

    x = jnp.zeros((512, 1024), jnp.bfloat16)
    w = jnp.zeros((1024, 768) if layout == "kn" else (768, 1024), jnp.int8)
    ws = jnp.ones((768,), jnp.float32)
    assert_mosaic(lower_tpu(
        lambda a, b, c: a8.a8w8_matmul(a, b, c, layout=layout), x, w, ws))
