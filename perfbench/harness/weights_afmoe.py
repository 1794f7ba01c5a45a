"""Seeded weights of the AFMoE layout (`paddle_tpu/models/afmoe.py`), made as
`harness/weights.py` makes the others: on the device, in bf16, from the two
seed words as a traced argument, one leaf group at a time so that neither the
program nor the plain reference ever holds the model twice.

Norm weights are 1 + 0.1 N(0, 1) (a norm left out or put in the wrong place
then shows); the selection bias of a routed layer is 0.05 N(0, 1) rounded to
bf16, small against the spread of the sigmoid scores (some 0.2) and large
enough that a selection made without it differs.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .weights import _key, _normal

BF = jnp.bfloat16


def sizes(cfg: dict) -> dict:
    """The generators' arguments from a configuration under the source's
    keys."""
    return dict(hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
                dense=cfg["intermediate_size"],
                expert=cfg["moe_intermediate_size"],
                experts=cfg["num_experts"], shared=cfg["num_shared_experts"],
                layers=cfg["num_hidden_layers"],
                std=cfg["initializer_range"])


def _norm(key, idx, n):
    return (1.0 + 0.1 * jax.random.normal(jax.random.fold_in(key, idx), (n,),
                                          jnp.float32)).astype(BF)


def _lkey(words, layer):
    return jax.random.fold_in(_key(words), 1000 + layer)


@functools.partial(jax.jit, static_argnames=("hidden", "heads", "kv_heads",
                                             "head_dim", "layers", "std"))
def attention(words, layer, *, hidden, heads, kv_heads, head_dim, layers,
              std):
    """One layer's attention leaves and its four norms; `layer` is traced."""
    key = _lkey(words, layer)
    q, kv = heads * head_dim, kv_heads * head_dim
    pstd = std / math.sqrt(2 * layers)
    return {
        "input_layernorm.weight": _norm(key, 0, hidden),
        "post_attention_layernorm.weight": _norm(key, 1, hidden),
        "pre_mlp_layernorm.weight": _norm(key, 2, hidden),
        "post_mlp_layernorm.weight": _norm(key, 3, hidden),
        "self_attn.q_norm.weight": _norm(key, 4, head_dim),
        "self_attn.k_norm.weight": _norm(key, 5, head_dim),
        "self_attn.q_proj.weight": _normal(key, 6, (hidden, q), std, BF),
        "self_attn.k_proj.weight": _normal(key, 7, (hidden, kv), std, BF),
        "self_attn.v_proj.weight": _normal(key, 8, (hidden, kv), std, BF),
        "self_attn.gate_proj.weight": _normal(key, 9, (hidden, q), std, BF),
        "self_attn.o_proj.weight": _normal(key, 10, (q, hidden), pstd, BF),
    }


@functools.partial(jax.jit, static_argnames=("hidden", "width", "layers",
                                             "std", "base"))
def swiglu(words, layer, *, hidden, width, layers, std, base):
    """A SwiGLU's three matrices (a dense layer's MLP at base 20, a shared
    expert at base 30) under the names gate/up/down_proj.weight."""
    key = _lkey(words, layer)
    pstd = std / math.sqrt(2 * layers)
    return {
        "gate_proj.weight": _normal(key, base, (hidden, width), std, BF),
        "up_proj.weight": _normal(key, base + 1, (hidden, width), std, BF),
        "down_proj.weight": _normal(key, base + 2, (width, hidden), pstd, BF),
    }


@functools.partial(jax.jit, static_argnames=("hidden", "experts", "std"))
def router(words, layer, *, hidden, experts, std):
    key = _lkey(words, layer)
    bias = 0.05 * jax.random.normal(jax.random.fold_in(key, 41), (experts,),
                                    jnp.float32)
    return {"router.weight": _normal(key, 40, (hidden, experts), std, BF),
            "expert_bias": bias.astype(BF)}


@functools.partial(jax.jit, static_argnames=("which", "hidden", "expert",
                                             "experts", "layers", "std"))
def expert_stack(words, layer, *, which, hidden, expert, experts, layers,
                 std):
    """One of the three stacked expert leaves [E, ., .]: which = gate_w,
    up_w or down_w. A leaf a call: 0.5 GB in bf16 at the published sizes."""
    key = _lkey(words, layer)
    idx = {"gate_w": 50, "up_w": 51, "down_w": 52}[which]
    if which == "down_w":
        return _normal(key, idx, (experts, expert, hidden),
                       std / math.sqrt(2 * layers), BF)
    return _normal(key, idx, (experts, hidden, expert), std, BF)


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "std"))
def top(words, *, vocab, hidden, std):
    key = _key(words)
    return {"embed_tokens.weight": _normal(key, 0, (vocab, hidden), std, BF),
            "norm.weight": _norm(key, 2, hidden),
            "lm_head.weight": _normal(key, 1, (hidden, vocab), std, BF)}


def layer_leaves(cfg: dict, words, i: int):
    """(name, array) of layer i's leaves under the names of
    `Afmoe.named_parameters()` and, for `mlp.expert_bias`,
    `named_buffers()`, one group at a time."""
    sz = sizes(cfg)
    li = jnp.int32(i)
    common = dict(hidden=sz["hidden"], layers=sz["layers"], std=sz["std"])
    yield from attention(words, li, heads=sz["heads"],
                         kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
                         **common).items()
    if i < cfg["num_dense_layers"]:
        for n, a in swiglu(words, li, width=sz["dense"], base=20,
                           **common).items():
            yield "mlp." + n, a
        return
    for n, a in router(words, li, hidden=sz["hidden"], experts=sz["experts"],
                       std=sz["std"]).items():
        yield "mlp." + n, a
    for n, a in swiglu(words, li, width=sz["expert"] * sz["shared"], base=30,
                       **common).items():
        yield "mlp.shared_experts." + n, a
    for which in ("gate_w", "up_w", "down_w"):
        yield "mlp." + which, expert_stack(
            words, li, which=which, expert=sz["expert"],
            experts=sz["experts"], **common)


def leaves(cfg: dict, words):
    """Every leaf of the model, the top first and then layer by layer."""
    yield from top(words, vocab=cfg["vocab_size"], hidden=cfg["hidden_size"],
                   std=cfg["initializer_range"]).items()
    for i in range(cfg["num_hidden_layers"]):
        for name, arr in layer_leaves(cfg, words, i):
            yield f"layers.{i}.{name}", arr
