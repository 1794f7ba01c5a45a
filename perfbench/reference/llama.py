"""Plain decoder of the Llama layout (Mistral-7B): float32, one sequence at
a time, one layer at a time, full causal attention, no cache.

Follows the Mistral-7B config: pre-RMSNorm, rotate-half RoPE at `rope_theta`,
grouped-query attention (each KV head shared by heads/kv_heads query heads),
SwiGLU MLP, untied output head. No sliding window (v0.3 has none).

Weights are regenerated from the seed one layer at a time by
`harness.weights`, so the model is never held whole in float32.

`quant="int8"` is the control: every linear layer's weights are rounded to
int8 per output channel and its input to int8 per token (symmetric,
round-to-nearest), the precision below the bf16 the configuration states.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..harness import weights


#: queries per block of the attention (a sequence is padded to a multiple)
QUERY_BLOCK = 512


def _q8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0 + 1e-30
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


def _linear(x, w, quant):
    w = w.astype(jnp.float32)
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision="highest")


def _rmsnorm(x, w, eps):
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, theta):
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=("heads", "kv_heads", "head_dim",
                                             "theta", "eps", "quant"),
                   donate_argnums=(0,))
def layer(x, w, *, heads, kv_heads, head_dim, theta, eps, quant):
    """One decoder layer over one sequence x [T, hidden] (float32)."""
    t = x.shape[0]
    h = _rmsnorm(x, w["input_layernorm.weight"], eps)
    q = _linear(h, w["self_attn.q_proj.weight"], quant) \
        .reshape(t, heads, head_dim)
    k = _linear(h, w["self_attn.k_proj.weight"], quant) \
        .reshape(t, kv_heads, head_dim)
    v = _linear(h, w["self_attn.v_proj.weight"], quant) \
        .reshape(t, kv_heads, head_dim)
    q, k = _rope(q, theta), _rope(k, theta)
    rep = heads // kv_heads
    nb = t // QUERY_BLOCK
    qb = q.reshape(nb, QUERY_BLOCK, kv_heads, rep, head_dim)
    kpos = jnp.arange(t)

    def block(args):        # one block of queries against every key
        i, q_i = args
        s = jnp.einsum("qgrd,kgd->grqk", q_i, k, precision="highest") \
            / math.sqrt(head_dim)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        mask = kpos[None, :] <= qpos[:, None]
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v, precision="highest")

    o = jax.lax.map(block, (jnp.arange(nb), qb)) \
        .reshape(t, heads * head_dim)
    x = x + _linear(o, w["self_attn.o_proj.weight"], quant)
    h = _rmsnorm(x, w["post_attention_layernorm.weight"], eps)
    g = _linear(h, w["mlp.gate_proj.weight"], quant)
    u = _linear(h, w["mlp.up_proj.weight"], quant)
    return x + _linear(jax.nn.silu(g) * u, w["mlp.down_proj.weight"], quant)


@functools.partial(jax.jit, static_argnames=("span", "eps", "quant"))
def head(x, start, norm_w, head_w, *, span, eps, quant):
    """Logits [span, vocab] of positions start .. start+span of x [T, h]."""
    rows = jax.lax.dynamic_slice_in_dim(x, start, span, axis=0)
    return _linear(_rmsnorm(rows, norm_w, eps), head_w, quant)


def bucket(n: int, step: int = QUERY_BLOCK) -> int:
    return -(-n // step) * step


def logits_of(cfg: dict, words, seqs, spans, span: int, quant=None):
    """For each token sequence (a list of ids) the float32 logits
    [span, vocab] from position spans[i] on: the model's prediction for
    positions spans[i]+1 ... Rows past the sequence's end are padding."""
    sizes = weights.llama_sizes(cfg)
    top = weights.llama_top(words, vocab=cfg["vocab_size"],
                            hidden=cfg["hidden_size"],
                            std=cfg["initializer_range"])
    xs = []
    for ids in seqs:
        t = bucket(max(len(ids), 1) + span)
        padded = jnp.zeros((t,), jnp.int32).at[:len(ids)].set(
            jnp.asarray(ids, jnp.int32))
        xs.append(top["embed_tokens.weight"][padded].astype(jnp.float32))
    for i in range(cfg["num_hidden_layers"]):
        w = weights.llama_layer(words, jnp.int32(i), **sizes)
        xs = [layer(x, w, heads=sizes["heads"], kv_heads=sizes["kv_heads"],
                    head_dim=sizes["head_dim"],
                    theta=float(cfg["rope_theta"]),
                    eps=float(cfg["rms_norm_eps"]), quant=quant) for x in xs]
        del w
    return [head(x, jnp.int32(s), top["norm.weight"], top["lm_head.weight"],
                 span=span, eps=float(cfg["rms_norm_eps"]), quant=quant)
            for x, s in zip(xs, spans)]


@jax.jit
def gaps(ref_logits, tokens):
    """By how much each token's reference logit lies below the best."""
    best = jnp.max(ref_logits, axis=-1)
    mine = jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]
    return best - mine
