"""Weights from --seed, made on the device in the type they are used in.

One jitted generator per layout. The seed is a traced argument (two 32-bit
words), so the compiled generator is shared by every seed and found in the
compile cache after a cell's first run. The program's parameters are assigned
from these arrays; the plain references call the same generators again and
take nothing the program holds.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp


def _key(words):
    k = jax.random.key(0)
    return jax.random.fold_in(jax.random.fold_in(k, words[0]), words[1])


def _normal(key, idx, shape, std, dtype):
    k = jax.random.fold_in(key, idx)
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


# -- GPT-2 layout (stacked over layers; small enough to hold twice) ----------

def gpt2_sizes(cfg: dict) -> dict:
    """The generator's arguments from a configuration under GPT-2's keys."""
    return dict(vocab=cfg["vocab_size"], positions=cfg["n_positions"],
                hidden=cfg["n_embd"], layers=cfg["n_layer"],
                ffn=4 * cfg["n_embd"], std=cfg["initializer_range"])


GPT2_LAYER_LEAVES = ("ln1.weight", "ln1.bias", "attn.qkv.weight",
                     "attn.qkv.bias", "attn.proj.weight", "attn.proj.bias",
                     "ln2.weight", "ln2.bias", "mlp.fc.weight", "mlp.fc.bias",
                     "mlp.proj.weight", "mlp.proj.bias")


@functools.partial(jax.jit, static_argnames=("vocab", "positions", "hidden",
                                             "layers", "ffn", "std"))
def gpt2_stacked(words, *, vocab, positions, hidden, layers, ffn, std=0.02):
    """{leaf: array}: per-layer leaves stacked on axis 0. Matrices, biases
    and embeddings in bf16 (what amp O2 casts them to), LayerNorm in f32
    (what it leaves alone). GPT-2's own init: N(0, std), residual
    projections N(0, std / sqrt(2 layers)), biases 0, LayerNorm 1 and 0."""
    key = _key(words)
    bf, f32 = jnp.bfloat16, jnp.float32
    pstd = std / math.sqrt(2 * layers)
    L, h = layers, hidden
    return {
        "wte.weight": _normal(key, 0, (vocab, h), std, bf),
        "wpe.weight": _normal(key, 1, (positions, h), std, bf),
        "ln1.weight": jnp.ones((L, h), f32), "ln1.bias": jnp.zeros((L, h), f32),
        "attn.qkv.weight": _normal(key, 2, (L, h, 3 * h), std, bf),
        "attn.qkv.bias": jnp.zeros((L, 3 * h), bf),
        "attn.proj.weight": _normal(key, 3, (L, h, h), pstd, bf),
        "attn.proj.bias": jnp.zeros((L, h), bf),
        "ln2.weight": jnp.ones((L, h), f32), "ln2.bias": jnp.zeros((L, h), f32),
        "mlp.fc.weight": _normal(key, 4, (L, h, ffn), std, bf),
        "mlp.fc.bias": jnp.zeros((L, ffn), bf),
        "mlp.proj.weight": _normal(key, 5, (L, ffn, h), pstd, bf),
        "mlp.proj.bias": jnp.zeros((L, h), bf),
        "ln_f.weight": jnp.ones((h,), f32), "ln_f.bias": jnp.zeros((h,), f32),
    }


def gpt2_named(stacked, layers):
    """The stacked leaves under the names `GPT.named_parameters()` gives."""
    out = {}
    for name, arr in stacked.items():
        if name in GPT2_LAYER_LEAVES:
            for i in range(layers):
                out[f"blocks.{i}.{name}"] = arr[i]
        else:
            out[name] = arr
    return out


@functools.partial(jax.jit, static_argnames=("layers",))
def gpt2_unstack(stacked, layers):
    return gpt2_named(stacked, layers)


# -- Llama layout (Mistral): one layer at a time, never the model twice ------

def llama_sizes(cfg: dict) -> dict:
    """The layer generator's arguments from a configuration under the
    Mistral/Llama keys."""
    return dict(hidden=cfg["hidden_size"], heads=cfg["num_attention_heads"],
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
                ffn=cfg["intermediate_size"], layers=cfg["num_hidden_layers"],
                std=cfg["initializer_range"])


def llama_leaves(cfg: dict, words):
    """(name, array) of every leaf under `Llama.named_parameters()`'s names,
    the top first and then layer by layer, so that a caller who assigns as it
    goes never holds the model twice."""
    yield from llama_top(words, vocab=cfg["vocab_size"],
                         hidden=cfg["hidden_size"],
                         std=cfg["initializer_range"]).items()
    sizes = llama_sizes(cfg)
    for i in range(cfg["num_hidden_layers"]):
        for name, arr in llama_layer(words, jnp.int32(i), **sizes).items():
            yield f"layers.{i}.{name}", arr


LLAMA_LAYER_LEAVES = ("input_layernorm.weight", "self_attn.q_proj.weight",
                      "self_attn.k_proj.weight", "self_attn.v_proj.weight",
                      "self_attn.o_proj.weight",
                      "post_attention_layernorm.weight",
                      "mlp.gate_proj.weight", "mlp.up_proj.weight",
                      "mlp.down_proj.weight")


@functools.partial(jax.jit, static_argnames=("hidden", "heads", "kv_heads",
                                             "head_dim", "ffn", "layers",
                                             "std"))
def llama_layer(words, layer, *, hidden, heads, kv_heads, head_dim, ffn,
                layers, std=0.02):
    """One decoder layer's leaves in bf16; `layer` is traced."""
    key = jax.random.fold_in(_key(words), 1000 + layer)
    bf = jnp.bfloat16
    pstd = std / math.sqrt(2 * layers)
    q, kv = heads * head_dim, kv_heads * head_dim
    return {
        "input_layernorm.weight": jnp.ones((hidden,), bf),
        "self_attn.q_proj.weight": _normal(key, 0, (hidden, q), std, bf),
        "self_attn.k_proj.weight": _normal(key, 1, (hidden, kv), std, bf),
        "self_attn.v_proj.weight": _normal(key, 2, (hidden, kv), std, bf),
        "self_attn.o_proj.weight": _normal(key, 3, (q, hidden), pstd, bf),
        "post_attention_layernorm.weight": jnp.ones((hidden,), bf),
        "mlp.gate_proj.weight": _normal(key, 4, (hidden, ffn), std, bf),
        "mlp.up_proj.weight": _normal(key, 5, (hidden, ffn), std, bf),
        "mlp.down_proj.weight": _normal(key, 6, (ffn, hidden), pstd, bf),
    }


@functools.partial(jax.jit, static_argnames=("vocab", "hidden", "std"))
def llama_top(words, *, vocab, hidden, std=0.02):
    key = _key(words)
    bf = jnp.bfloat16
    return {
        "embed_tokens.weight": _normal(key, 0, (vocab, hidden), std, bf),
        "norm.weight": jnp.ones((hidden,), bf),
        "lm_head.weight": _normal(key, 1, (hidden, vocab), std, bf),
    }


def assign(named_params, leaves):
    """Give each (lazily built) program parameter its array, as the
    (name, array) pairs come. Every name and shape has to match: a layout
    the generator does not know is an error."""
    params = dict(named_params)
    seen = set()
    for name, arr in leaves:
        if name not in params:
            raise KeyError(f"the program has no parameter {name!r}")
        p = params[name]
        if tuple(p.shape) != tuple(arr.shape):
            raise ValueError(f"{name}: program {tuple(p.shape)} != "
                             f"generator {tuple(arr.shape)}")
        p._data = arr
        p._lazy_spec = None
        seen.add(name)
    if seen != set(params):
        raise KeyError("parameters the generator does not make: "
                       f"{sorted(set(params) - seen)[:8]}")
