"""Plain GPT-2: forward, loss, gradients and AdamW, in float32.

Follows "Language Models are Unsupervised Multitask Learners" (pre-LN blocks,
learned positions, tanh GELU, tied output embedding) and Loshchilov & Hutter's
decoupled weight decay. Departures from the paper, all from the configuration
as run: the vocabulary is padded to the file's `vocab_size` and the softmax
runs over the padded width; weight decay covers every leaf (the program gives
AdamW no filter).

`quant="fp8"` is the control: every matmul's two operands are rounded to
float8_e4m3 (per-tensor scale, straight-through gradient), the precision below
the bf16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..harness.compare import (grad_norms_from_moment2, slice_norms,
                               slice_sums)
from ..harness.weights import GPT2_LAYER_LEAVES as LAYER_LEAVES


def _fq(x):
    """Round to float8_e4m3 on a per-tensor scale; gradient passes through."""
    scale = jnp.max(jnp.abs(x)) / 448.0 + 1e-30
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def _mm(a, b, quant):
    if quant == "fp8":
        a, b = _fq(a), _fq(b)
    return jnp.matmul(a, b, precision="highest")


def _layer_norm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(h, lp, heads, eps, quant):
    b, t, d = h.shape
    a = _layer_norm(h, lp["ln1.weight"], lp["ln1.bias"], eps)
    qkv = _mm(a, lp["attn.qkv.weight"], quant) + lp["attn.qkv.bias"]
    qkv = qkv.reshape(b, t, 3, heads, d // heads)
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") \
        / math.sqrt(d // heads)
    mask = jnp.tril(jnp.ones((t, t), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")
    h = h + _mm(o.reshape(b, t, d), lp["attn.proj.weight"], quant) \
        + lp["attn.proj.bias"]
    m = _layer_norm(h, lp["ln2.weight"], lp["ln2.bias"], eps)
    m = _gelu_tanh(_mm(m, lp["mlp.fc.weight"], quant) + lp["mlp.fc.bias"])
    return h + _mm(m, lp["mlp.proj.weight"], quant) + lp["mlp.proj.bias"]


def loss_fn(params, x, y, *, heads, eps, quant=None):
    """Mean cross-entropy of rows x [b, t] against labels y [b, t]."""
    t = x.shape[1]
    h = params["wte.weight"][x] + params["wpe.weight"][:t]
    stack = {k: params[k] for k in LAYER_LEAVES}

    def body(h, lp):
        return _block(h, lp, heads, eps, quant), None

    h, _ = jax.lax.scan(jax.checkpoint(body), h, stack)
    h = _layer_norm(h, params["ln_f.weight"], params["ln_f.bias"], eps)
    logits = _mm(h, params["wte.weight"].T, quant)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], -1))


@functools.partial(jax.jit, static_argnames=("heads", "eps", "quant", "rows"))
def loss_and_grad(params, x, y, *, heads, eps, quant, rows):
    """Loss and gradient of the mean over the whole batch, taken in blocks
    of `rows` rows so that the activations fit beside the optimizer state."""
    n = x.shape[0] // rows
    xs = x.reshape(n, rows, -1)
    ys = y.reshape(n, rows, -1)
    g = jax.value_and_grad(functools.partial(loss_fn, heads=heads, eps=eps,
                                             quant=quant))

    def body(acc, xy):
        l, gr = g(params, xy[0], xy[1])
        return (acc[0] + l / n,
                jax.tree.map(lambda a, b: a + b / n, acc[1], gr)), None

    zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(body, zero, (xs, ys))
    return loss, grads


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps", "wd"),
                   donate_argnums=(0, 2, 3))
def adamw(params, grads, m, v, step, *, lr, b1, b2, eps, wd):
    def one(w, g, m_, v_):
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        mhat = m_ / (1 - b1 ** step)
        vhat = v_ / (1 - b2 ** step)
        w = w * (1 - lr * wd) - lr * mhat / (jnp.sqrt(vhat) + eps)
        return w, m_, v_
    out = jax.tree.map(one, params, grads, m, v)
    pick = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                  is_leaf=lambda t: isinstance(t, tuple))
    return pick(0), pick(1), pick(2)


@jax.jit
def leaf_norms(tree):
    """{leaf: norms}: [slices] for a leaf, [layers, slices] for the stacked
    leaves, sliced along the last axis as the comparison slices them."""
    def norm(name, a):
        if name in LAYER_LEAVES:
            return jax.vmap(slice_norms)(a)
        return slice_norms(a)
    return {k: norm(k, a) for k, a in tree.items()}


@jax.jit
def leaf_sums(tree):
    """{leaf: sums}, cut as `leaf_norms` cuts them."""
    def total(name, a):
        if name in LAYER_LEAVES:
            return jax.vmap(slice_sums)(a)
        return slice_sums(a)
    return {k: total(k, a) for k, a in tree.items()}


def train_steps(params0, batches, *, heads, eps, opt, rows, quant=None,
                fault=None):
    """Drive `len(batches)` AdamW steps from `params0` (float32 tree).
    Returns the losses, each step's gradient norms by leaf (as computed,
    and as worked out from the second moment's sums, the way the program's
    are read) and the norms of the parameters' change over all the steps.

    `quant` is the control; `fault` plants one in every step: "half_batch"
    leaves out half of the rows and takes the mean over the rest, "frozen"
    returns the state unchanged, "sign" flips the update."""
    params = jax.tree.map(lambda a: a + 0.0, params0)
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, grads, from_state, sums = [], [], [], None
    for i, (x, y) in enumerate(batches):
        if fault == "half_batch":
            x, y = x[:x.shape[0] // 2], y[:y.shape[0] // 2]
        loss, g = loss_and_grad(
            params, x, y, heads=heads, eps=eps, rows=min(rows, x.shape[0]),
            quant=quant)
        grads.append(jax.device_get(leaf_norms(g)))
        if fault != "frozen":
            o = dict(opt, lr=-opt["lr"]) if fault == "sign" else opt
            params, m, v = adamw(params, g, m, v, jnp.float32(i + 1), **o)
        now = jax.device_get(leaf_sums(v))
        from_state.append(grad_norms_from_moment2(sums, now, opt["b2"]))
        sums = now
        losses.append(float(loss))
    delta = leaf_norms(jax.tree.map(lambda a, b: a - b, params, params0))
    return losses, grads, from_state, delta
