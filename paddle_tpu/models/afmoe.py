"""AFMoE decoder-only LM (``model_type: "afmoe"``: Arcee Trinity).

What differs from Llama, layer by layer (the equations are those of the
published config and of the ``transformers`` implementation of the type):

* the embedding is scaled by ``sqrt(hidden_size)`` (``mup_enabled``);
* attention is grouped-query with an RMS norm over each head of q and k and
  an output gate, ``o = (attn * sigmoid(x Wg)) Wo``; a
  ``"sliding_attention"`` layer applies rotate-half RoPE and lets query i see
  key j iff ``i - window < j <= i``; a ``"full_attention"`` layer is causal
  and has no positional encoding at all;
* four norms a layer: ``h += norm(attn(norm(h)))``, ``h += norm(mlp(norm(h)))``;
* the first ``num_dense_layers`` feed-forwards are SwiGLU MLPs, the others
  routed: sigmoid scores over all experts, the top k of ``score + bias``
  chosen (the bias takes no part in the weights), the chosen scores
  normalised to sum 1 and scaled by ``route_scale``, a shared expert added.
  No token is dropped (``incubate/.../moe_layer.py:dropless_experts``).

Serving goes through ``serving.LLMEngine`` like Llama: the adapter asks each
layer for its kinds (``self_attn.window``, ``self_attn.use_rope``, a routed
``mlp``), see ``serving/model.py``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from .. import nn
from ..autograd.function import apply_multi
from ..core.tensor import Tensor
from ..nn import functional as F
from .llama import _rope_memo, _rope_tables

__all__ = ["AfmoeConfig", "Afmoe", "afmoe_tiny"]

WINDOW, GLOBAL = "sliding_attention", "full_attention"


@dataclass
class AfmoeConfig:
    vocab_size: int = 200192
    max_position_embeddings: int = 131072
    hidden_size: int = 2048
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 6144          # dense layers
    moe_intermediate_size: int = 1024      # one expert
    num_dense_layers: int = 2
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    sliding_window: int = 2048
    layer_types: list = field(default_factory=list)   # default: every
    #                                     4th layer global, the rest window
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    mup_enabled: bool = True
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False

    def __post_init__(self):
        if not self.layer_types:
            self.layer_types = [GLOBAL if (i + 1) % 4 == 0 else WINDOW
                                for i in range(self.num_layers)]
        if len(self.layer_types) != self.num_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_layers is {self.num_layers}")
        bad = set(self.layer_types) - {WINDOW, GLOBAL}
        if bad:
            raise ValueError(f"unknown layer types {sorted(bad)}")


def _attr(std):
    return paddle.framework.ParamAttr(initializer=nn.initializer.Normal(0.0, std))


class AfmoeMLP(nn.Layer):
    """SwiGLU of one width: a dense layer's MLP, or the shared expert."""

    def __init__(self, cfg: AfmoeConfig, width: int):
        super().__init__()
        std, h = cfg.initializer_range, cfg.hidden_size
        self.gate_proj = nn.Linear(h, width, weight_attr=_attr(std),
                                   bias_attr=False)
        self.up_proj = nn.Linear(h, width, weight_attr=_attr(std),
                                 bias_attr=False)
        self.down_proj = nn.Linear(
            width, h, bias_attr=False,
            weight_attr=_attr(std / math.sqrt(2 * cfg.num_layers)))

    def forward(self, x):
        return self.down_proj(paddle.swiglu(self.gate_proj(x),
                                            self.up_proj(x)))


class AfmoeAttention(nn.Layer):
    """Gated GQA attention with per-head q/k norms. ``window`` (None on a
    global layer) and ``use_rope`` are what the serving adapter reads."""

    def __init__(self, cfg: AfmoeConfig, layer_type: str):
        super().__init__()
        self.n_head, self.n_kv, self.head_dim = \
            cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        self.window = int(cfg.sliding_window) if layer_type == WINDOW \
            else None
        self.use_rope = layer_type == WINDOW
        attr = _attr(cfg.initializer_range)
        o_attr = _attr(cfg.initializer_range / math.sqrt(2 * cfg.num_layers))
        h, q_out = cfg.hidden_size, cfg.num_heads * cfg.head_dim
        kv_out = cfg.num_kv_heads * cfg.head_dim
        lin = lambda i, o, a: nn.Linear(i, o, weight_attr=a,  # noqa: E731
                                        bias_attr=False)
        self.q_proj, self.k_proj = lin(h, q_out, attr), lin(h, kv_out, attr)
        self.v_proj, self.gate_proj = lin(h, kv_out, attr), lin(h, q_out, attr)
        self.o_proj = lin(q_out, h, o_attr)
        self.q_norm = nn.RMSNorm(cfg.head_dim, cfg.rms_norm_eps)
        self.k_norm = nn.RMSNorm(cfg.head_dim, cfg.rms_norm_eps)

    def qkv(self, x):
        """(q [B, S, H, D], k, v [B, S, Hkv, D], gate [B, S, H D]) of the
        normed input: q and k normed per head, no RoPE yet."""
        b, s, _ = x.shape
        q = self.q_norm(self.q_proj(x).reshape(
            [b, s, self.n_head, self.head_dim]))
        k = self.k_norm(self.k_proj(x).reshape(
            [b, s, self.n_kv, self.head_dim]))
        v = self.v_proj(x).reshape([b, s, self.n_kv, self.head_dim])
        return q, k, v, self.gate_proj(x)

    def out(self, attn, gate):
        """``(attn * sigmoid(gate)) Wo``; attn [B, S, H, D]."""
        b, s = attn.shape[0], attn.shape[1]
        flat = attn.reshape([b, s, self.n_head * self.head_dim])
        return self.o_proj(flat * F.sigmoid(gate))

    def forward(self, x, cos, sin):
        q, k, v, gate = self.qkv(x)
        if self.use_rope:
            q, k = F.rope(q, k, sin, cos)
        attn = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                              window=self.window)
        return self.out(attn, gate)


class AfmoeMoE(nn.Layer):
    """Sigmoid-routed experts with a selection bias, plus a shared expert."""

    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        self.n_experts, self.top_k = cfg.num_experts, cfg.num_experts_per_tok
        self.route_norm, self.route_scale = cfg.route_norm, cfg.route_scale
        h, m, e = cfg.hidden_size, cfg.moe_intermediate_size, cfg.num_experts
        std = cfg.initializer_range
        dstd = std / math.sqrt(2 * cfg.num_layers)
        self.router = nn.Linear(h, e, weight_attr=_attr(std), bias_attr=False)
        # added to the scores for the choice of experts alone: a buffer
        # (cast with the model; `route` adds it in float32)
        self.register_buffer("expert_bias",
                             Tensor(jnp.zeros((e,), jnp.float32)))
        self.gate_w = self.create_parameter([e, h, m], attr=_attr(std))
        self.up_w = self.create_parameter([e, h, m], attr=_attr(std))
        self.down_w = self.create_parameter([e, m, h], attr=_attr(dstd))
        self.shared_experts = AfmoeMLP(cfg, m * cfg.num_shared_experts)
        self._routed_jit = None

    def route(self, tokens, router_w, bias):
        """(sel [n, k] int32, weights [n, k] f32) of tokens [n, H]: scores
        and their matmul in float32."""
        s = jax.nn.sigmoid(jnp.matmul(
            tokens.astype(jnp.float32), router_w.astype(jnp.float32),
            precision="highest"))
        _, sel = jax.lax.top_k(s + bias.astype(jnp.float32), self.top_k)
        w = jnp.take_along_axis(s, sel, axis=-1)
        if self.route_norm:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return sel.astype(jnp.int32), w * self.route_scale

    def routed(self, x, live=None):
        """(shared(x) + routed experts of x, experts_hit): x [..., H];
        experts_hit an int32 scalar, the experts that got a row. `live`
        (a bool array over x's rows, flattened): the rows that are tokens;
        the others (bucket padding, idle decode slots) are sent to no
        expert."""
        from ..incubate.distributed.models.moe.moe_layer import \
            dropless_experts
        shape = x.shape

        def fn(tok, rw, bias, gw, uw, dw, *live):
            with jax.named_scope("moe_route"):
                sel, w = self.route(tok, rw, bias)
                if live:
                    sel = jnp.where(live[0][:, None], sel, self.n_experts)
            with jax.named_scope("moe_experts"):
                out, sizes = dropless_experts(tok, sel, w, gw, uw, dw)
            return out, jnp.sum(sizes > 0).astype(jnp.int32)

        # one program where it runs eagerly (`to_static`'s discovery call
        # would else dispatch the routing's fifty small ops one by one);
        # kept, so that the layer's next eager call finds it compiled
        if self._routed_jit is None:
            self._routed_jit = jax.jit(fn)
        out, hit = apply_multi(
            self._routed_jit, x.reshape([-1, shape[-1]]), self.router.weight,
            self.expert_bias, self.gate_w, self.up_w, self.down_w,
            *([] if live is None else [live]), name="afmoe_experts")
        with jax.named_scope("moe_shared"):
            shared = self.shared_experts(x)
        return shared + out.reshape(shape), hit

    def forward(self, x):
        return self.routed(x)[0]


class AfmoeDecoderLayer(nn.Layer):
    def __init__(self, cfg: AfmoeConfig, index: int):
        super().__init__()
        h, eps = cfg.hidden_size, cfg.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(h, eps)
        self.self_attn = AfmoeAttention(cfg, cfg.layer_types[index])
        self.post_attention_layernorm = nn.RMSNorm(h, eps)
        self.pre_mlp_layernorm = nn.RMSNorm(h, eps)
        self.mlp = AfmoeMLP(cfg, cfg.intermediate_size) \
            if index < cfg.num_dense_layers else AfmoeMoE(cfg)
        self.post_mlp_layernorm = nn.RMSNorm(h, eps)

    def forward(self, x, cos, sin):
        x = x + self.post_attention_layernorm(
            self.self_attn(self.input_layernorm(x), cos, sin))
        return x + self.post_mlp_layernorm(
            self.mlp(self.pre_mlp_layernorm(x)))


class Afmoe(nn.Layer):
    def __init__(self, cfg: AfmoeConfig):
        super().__init__()
        self.cfg = cfg
        attr = _attr(cfg.initializer_range)
        self.embed_tokens = nn.Embedding(cfg.vocab_size, cfg.hidden_size,
                                         weight_attr=attr)
        self.layers = nn.LayerList(
            [AfmoeDecoderLayer(cfg, i) for i in range(cfg.num_layers)])
        self.norm = nn.RMSNorm(cfg.hidden_size, cfg.rms_norm_eps)
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Linear(cfg.hidden_size, cfg.vocab_size,
                                     weight_attr=attr, bias_attr=False)
        self._rope_cache: dict[int, tuple] = {}

    def _rope(self, s):
        return _rope_memo(self._rope_cache, s,
                          lambda: _rope_tables(self.cfg, s))

    def _embed(self, input_ids):
        x = self.embed_tokens(input_ids)
        if self.cfg.mup_enabled:
            x = x * math.sqrt(self.cfg.hidden_size)
        return x

    def _head(self, x):
        x = self.norm(x)
        if self.cfg.tie_word_embeddings:
            return paddle.matmul(x, self.embed_tokens.weight,
                                 transpose_y=True)
        return self.lm_head(x)

    def forward(self, input_ids, labels=None):
        cos, sin = self._rope(input_ids.shape[1])
        x = self._embed(input_ids)
        for layer in self.layers:
            x = layer(x, cos, sin)
        logits = self._head(x)
        if labels is not None:
            loss = F.cross_entropy(
                logits.reshape([-1, self.cfg.vocab_size]).cast("float32"),
                labels.reshape([-1]))
            return logits, loss
        return logits


def afmoe_tiny(**kw) -> Afmoe:
    """The tests' size: dense-window, window, window, window, global."""
    cfg = dict(vocab_size=256, max_position_embeddings=128, hidden_size=64,
               num_layers=5, num_heads=4, num_kv_heads=2, head_dim=16,
               intermediate_size=96, moe_intermediate_size=32,
               num_dense_layers=1, num_experts=8, num_experts_per_tok=2,
               sliding_window=8,
               layer_types=[WINDOW, WINDOW, WINDOW, WINDOW, GLOBAL])
    cfg.update(kw)
    return Afmoe(AfmoeConfig(**cfg))
