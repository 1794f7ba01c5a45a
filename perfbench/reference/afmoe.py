"""Plain decoder of the AFMoE layout (Trinity): float32 at `highest`, one
sequence at a time, one layer at a time, a dense [S, S] mask, a loop over
experts, no cache, no kernels. Weights are regenerated from the seed by
`harness.weights_afmoe`, a layer at a time; nothing the program holds is read.

The equations (config.json of the source, and the `transformers`
implementation of `model_type: "afmoe"` where the keys do not say):

  h0 = E[ids] * sqrt(hidden)                                   (mup_enabled)
  a = rms(h; w_in); q, k, v, g = a Wq, a Wk, a Wv, a Wg
  q = rms(q; w_qn), k = rms(k; w_kn)  over the head's width
  window layer: rotate-half RoPE on q, k; query i sees key j iff
    i - window < j <= i.  global layer: no positional encoding, causal
  o = (softmax(q k^T / sqrt(d)) v * sigmoid(g)) Wo;  h = h + rms(o; w_pa)
  m = rms(h; w_pm)
  dense layer: f = (silu(m Wg1) * (m Wu1)) Wd1
  routed: s = sigmoid(m Wr); sel = top_k(s + b); w = s[sel] / (sum + 1e-20)
    * route_scale; f = shared(m) + sum_e w_e expert_e(m)
  h = h + rms(f; w_pf);  logits = rms(h; w_norm) W_head

Departures from the published description, each for the run's sake and none
for the result's: a sequence is padded to one of a few lengths, each a whole
number of query blocks (the padding lies after every real position and is
never seen by one);
attention is computed a block of query rows at a time against all keys
(the mask is the dense one, cut into those rows); every token is put through
every expert and the unchosen ones weighted 0 (a loop over experts, in place
of a gather per token); the experts' bf16 weights are widened one expert at
a time.

`quant="int8"` is the control: every linear layer and every expert has its
weights rounded to int8 per output channel and its input per token; the
router stays in float32 (a deployment that quantises keeps it so, and the
weaker control is the fairer limit). `fault` plants, in the reference put in
the program's place, one of the faults the comparison has to catch. What a
fault changes reaches the layer as traced scalars (`knobs`), so the sound
layer and every fault are one compiled program a length, and a sequence is
padded to one of a few lengths (`padded_len`): a run compiles a dozen
programs, not one for each sequence, layer kind and fault.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from ..harness import weights_afmoe as gen
from .llama import QUERY_BLOCK, _linear, _rmsnorm, _rope, bucket, gaps  # noqa: F401

FAULTS = ("no_window", "rope_on_global", "no_bias", "no_route_scale",
          "no_shared", "drop_over_2x")

#: a window no context reaches: a global layer's
NO_WINDOW = 2 ** 30


def knobs(cfg: dict, i: int, fault=None) -> dict:
    """What layer i's equations take beside its weights: the sound values,
    or those of one planted fault."""
    windowed = cfg["layer_types"][i] == "sliding_attention"
    return {
        "window": jnp.int32(cfg["sliding_window"] if windowed
                            and fault != "no_window" else NO_WINDOW),
        "rope": jnp.bool_(windowed or fault == "rope_on_global"),
        "bias": jnp.float32(fault != "no_bias"),
        "route_scale": jnp.float32(1.0 if fault == "no_route_scale"
                                   else cfg["route_scale"]),
        "shared": jnp.float32(fault != "no_shared"),
        # experts keep this many times their mean load, in token order
        # (0: every token, as the model has it)
        "capacity": jnp.int32(2 if fault == "drop_over_2x" else 0),
    }


def _swiglu(m, w, prefix, quant):
    g = _linear(m, w[prefix + "gate_proj.weight"], quant)
    u = _linear(m, w[prefix + "up_proj.weight"], quant)
    return _linear(jax.nn.silu(g) * u, w[prefix + "down_proj.weight"], quant)


def route(m, w, kn, *, top_k):
    """(sel [T, k], weights [T, k]) of the normed rows m [T, H]."""
    s = jax.nn.sigmoid(jnp.matmul(
        m, w["mlp.router.weight"].astype(jnp.float32), precision="highest"))
    b = w["mlp.expert_bias"].astype(jnp.float32)
    _, sel = jax.lax.top_k(s + b * kn["bias"], top_k)
    chosen = jnp.take_along_axis(s, sel, axis=-1)
    wts = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return sel, wts * kn["route_scale"]


def _routed(m, w, length, kn, *, top_k, quant):
    sel, wts = route(m, w, kn, top_k=top_k)
    e = w["mlp.gate_w"].shape[0]
    # a planted capacity, filled in token order by the sequence's `length`
    # real tokens (none: every token keeps its experts)
    onehot = jax.nn.one_hot(sel.reshape(-1), e, dtype=jnp.int32)
    order = (jnp.cumsum(onehot, 0) * onehot).sum(-1).reshape(sel.shape)
    cap = jnp.where(kn["capacity"] > 0,
                    kn["capacity"] * length * top_k // e, NO_WINDOW)
    wts = jnp.where(order <= cap, wts, 0.0)

    def one(i, acc):
        ew = {"gate_proj.weight": w["mlp.gate_w"][i],
              "up_proj.weight": w["mlp.up_w"][i],
              "down_proj.weight": w["mlp.down_w"][i]}
        coef = jnp.sum(jnp.where(sel == i, wts, 0.0), axis=-1)
        return acc + coef[:, None] * _swiglu(m, ew, "", quant)

    out = jax.lax.fori_loop(0, e, one, jnp.zeros_like(m))
    return out + kn["shared"] * _swiglu(m, w, "mlp.shared_experts.", quant)


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "head_dim", "theta", "eps", "top_k", "quant"),
    donate_argnums=(0,))
def layer(x, w, length, kn, *, heads, kv_heads, head_dim, theta, eps, top_k,
          quant):
    """One decoder layer over one sequence x [T, hidden] (float32), of which
    the first `length` rows are real (read by a planted capacity alone);
    `kn` as `knobs` gives it. A routed layer is one whose leaves hold
    `mlp.router.weight`."""
    t = x.shape[0]
    a = _rmsnorm(x, w["input_layernorm.weight"], eps)
    q = _linear(a, w["self_attn.q_proj.weight"], quant) \
        .reshape(t, heads, head_dim)
    k = _linear(a, w["self_attn.k_proj.weight"], quant) \
        .reshape(t, kv_heads, head_dim)
    v = _linear(a, w["self_attn.v_proj.weight"], quant) \
        .reshape(t, kv_heads, head_dim)
    g = _linear(a, w["self_attn.gate_proj.weight"], quant)
    q = _rmsnorm(q, w["self_attn.q_norm.weight"], eps)
    k = _rmsnorm(k, w["self_attn.k_norm.weight"], eps)
    q = jnp.where(kn["rope"], _rope(q, theta), q)
    k = jnp.where(kn["rope"], _rope(k, theta), k)
    rep = heads // kv_heads
    nb = t // QUERY_BLOCK
    qb = q.reshape(nb, QUERY_BLOCK, kv_heads, rep, head_dim)
    kpos = jnp.arange(t)

    def block(args):        # a block of the mask's rows against every key
        i, q_i = args
        s = jnp.einsum("qgrd,kgd->grqk", q_i, k, precision="highest") \
            / math.sqrt(head_dim)
        qpos = i * QUERY_BLOCK + jnp.arange(QUERY_BLOCK)
        mask = (kpos[None, :] <= qpos[:, None]) \
            & (kpos[None, :] > qpos[:, None] - kn["window"])
        p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v, precision="highest")

    o = jax.lax.map(block, (jnp.arange(nb), qb)) \
        .reshape(t, heads * head_dim)
    o = _linear(o * jax.nn.sigmoid(g), w["self_attn.o_proj.weight"], quant)
    x = x + _rmsnorm(o, w["post_attention_layernorm.weight"], eps)
    m = _rmsnorm(x, w["pre_mlp_layernorm.weight"], eps)
    if "mlp.router.weight" in w:
        f = _routed(m, w, length, kn, top_k=top_k, quant=quant)
    else:
        f = _swiglu(m, w, "mlp.", quant)
    return x + _rmsnorm(f, w["post_mlp_layernorm.weight"], eps)


@functools.partial(jax.jit, static_argnames=("span", "eps", "quant"))
def head(x, start, norm_w, head_w, *, span, eps, quant):
    """Logits [span, vocab] of positions start .. start+span of x [T, h]."""
    rows = jax.lax.dynamic_slice_in_dim(x, start, span, axis=0)
    return _linear(_rmsnorm(rows, norm_w, eps), head_w, quant)


def padded_len(n: int, longest: int) -> int:
    """The length a sequence of n positions is computed at: the next power
    of two from 4 query blocks on, or `longest` rounded up to a block where
    that is less (what the longest sequence of a run takes)."""
    t = 4 * QUERY_BLOCK
    while t < n:
        t *= 2
    return min(t, max(bucket(longest), bucket(n)))


def hidden_of(cfg: dict, words, seqs, span: int, quant=None, fault=None,
              longest: int = 0):
    """(top leaves, [x [T, hidden] after the last layer] for each token
    sequence); a sequence is padded past its end plus `span` as `padded_len`
    says, with `longest` the most positions any sequence of the run can
    have."""
    top = gen.top(words, vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
                  std=cfg["initializer_range"])
    scale = math.sqrt(cfg["hidden_size"]) if cfg.get("mup_enabled") else 1.0
    xs = []
    for ids in seqs:
        t = padded_len(max(len(ids), 1) + span, longest + span)
        padded = jnp.zeros((t,), jnp.int32).at[:len(ids)].set(
            jnp.asarray(ids, jnp.int32))
        xs.append(top["embed_tokens.weight"][padded].astype(jnp.float32)
                  * scale)
    for i in range(cfg["num_hidden_layers"]):
        w = dict(gen.layer_leaves(cfg, words, i))
        kn = knobs(cfg, i, fault)
        xs = [layer(x, w, jnp.int32(len(ids)), kn,
                    heads=cfg["num_attention_heads"],
                    kv_heads=cfg["num_key_value_heads"],
                    head_dim=cfg["head_dim"], theta=float(cfg["rope_theta"]),
                    eps=float(cfg["rms_norm_eps"]),
                    top_k=cfg["num_experts_per_tok"], quant=quant)
              for x, ids in zip(xs, seqs)]
        del w
    return top, xs


def logits_of(cfg: dict, words, seqs, spans, span: int, quant=None,
              fault=None):
    """For each token sequence the float32 logits [span, vocab] from
    position spans[i] on. Rows past the sequence's end are padding. All of
    them at once: for the tests' sizes; the driver takes `hidden_of` and the
    head a sequence at a time."""
    top, xs = hidden_of(cfg, words, seqs, span, quant, fault)
    return [head(x, jnp.int32(s), top["norm.weight"], top["lm_head.weight"],
                 span=span, eps=float(cfg["rms_norm_eps"]), quant=quant)
            for x, s in zip(xs, spans)]
