"""Serving adapter: Llama-family causal LMs over the paged KV cache.

The training model owns its modules (projections, norms, MLP, head); the
adapter owns the *serving dataflow*: how prompts prefill pages, how one
decode token flows through every layer against the paged pool, and how
weight-only quantized linears (``nn/quant``) substitute for the float
projections. Everything here runs both eagerly (the ``to_static``
discovery step) and under trace (the compiled prefill/decode programs) —
all shapes static, all per-request variation carried in values
(positions, page tables), never in shapes.

Supported model structure (the Llama family — ``models/llama.py`` and
anything matching its module layout): ``embed_tokens``, ``layers`` of
decoder blocks with ``input_layernorm`` / ``self_attn(q_proj, k_proj,
v_proj, o_proj)`` / ``post_attention_layernorm`` / ``mlp(gate_proj,
up_proj, down_proj)``, rotate-half RoPE, and a final ``_head`` (or
``norm`` + ``lm_head``/tied embeddings). A model missing the contract
raises at adapter construction with the missing pieces named.

What else a layer may be is asked of the layer, and the path follows the
answers (``models/afmoe.py`` gives all of them):

* attention: ``self_attn.window`` (an int: the query sees the last so many
  positions, and the layer's KV lives in the window group of the cache;
  None or absent: global), ``self_attn.use_rope`` (absent: True),
  ``self_attn.qkv(h)`` -> (q, k, v, gate) and ``self_attn.out(attn, gate)``
  (per-head norms, an output gate; absent: the four plain projections);
* feed-forward: ``mlp.routed(y)`` -> (out, experts_hit) for routed experts
  (absent: dense, ``mlp(y)``);
* norms: ``pre_mlp_layernorm`` and ``post_mlp_layernorm`` beside the usual
  two make the layer ``h += norm(attn(norm(h))); h += norm(mlp(norm(h)))``;
* the model's ``_embed(ids)`` where the embedding is scaled.

What a layer is gets read off its modules once, at construction
(:class:`_LayerKind`). The four programs (decode, speculative verify,
prefill, prefill chunk) share one loop over the layers,
:meth:`ServingModel._run_layers`; a program owns its positions, its
``cache_step`` (where a layer's new K/V go and what its query attends to)
and which positions the head reads. The verify and chunk programs bind one
pool and one page table, and the fused junctions fold a plain layer's two
norms, so those three take a model whose every layer is the plain Llama
layer alone (:attr:`ServingModel.plain`).
"""

from __future__ import annotations

import contextlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..nn import functional as F
from . import kv_cache

__all__ = ["ServingModel"]

_QUANT_ALGOS = {"weight_only_int8": "int8", "weight_only_int4": "int4",
                "int8": "int8", "int4": "int4"}

#: (tag, module path) per decoder layer — the linears the quant path swaps
_LAYER_LINEARS = (
    ("q", ("self_attn", "q_proj")), ("k", ("self_attn", "k_proj")),
    ("v", ("self_attn", "v_proj")), ("o", ("self_attn", "o_proj")),
    ("gate", ("mlp", "gate_proj")), ("up", ("mlp", "up_proj")),
    ("down", ("mlp", "down_proj")),
)


def _get_path(obj, path):
    for p in path:
        obj = getattr(obj, p, None)
        if obj is None:
            return None
    return obj


@dataclass(frozen=True)
class _LayerKind:
    """What one decoder layer is, read off its modules once."""
    window: int | None   # the query sees the last so many positions and the
    #                      layer's KV lives in the window group; None: global
    index: int           # of the layer inside its group's pool
    own_qkv: bool        # self_attn.qkv() / .out() (per-head norms, an
    #                      output gate), else the four plain projections
    rope: bool
    routed: bool         # mlp.routed(): routed experts, else dense mlp(y)
    four_norms: bool     # pre/post_mlp_layernorm beside the usual two

    @property
    def plain(self) -> bool:
        return self.rope and not (self.window or self.own_qkv
                                  or self.routed or self.four_norms)


@contextlib.contextmanager
def _attention_scope(kind: _LayerKind):
    """The scopes of a layer's attention op and output projection:
    ``attention`` and, inside it, the layer's group."""
    with jax.named_scope("attention"), jax.named_scope(
            "attention_window" if kind.window else "attention_global"):
        yield


class ServingModel:
    """Prefill/decode forward of a Llama-family LM over a :class:`PagePool`.

    ``quant`` (None | "weight_only_int8" | "weight_only_int4" | "int8" |
    "int4") pre-quantizes every decoder-layer linear once at construction
    and dispatches ``nn.quant.weight_only_linear`` in both forwards (the
    lm head and embeddings stay float for logit fidelity).
    """

    def __init__(self, model, quant: str | None = None,
                 quant_group_size: int = -1, fused_block: bool = True):
        self.model = model
        cfg = getattr(model, "cfg", None)
        missing = [n for n in ("embed_tokens", "layers") if
                   getattr(model, n, None) is None]
        if cfg is None:
            missing.append("cfg (num_heads/num_kv_heads/head_dim/"
                           "max_position_embeddings)")
        if not (callable(getattr(model, "_head", None))
                or (getattr(model, "norm", None) is not None
                    and (getattr(model, "lm_head", None) is not None
                         or getattr(cfg, "tie_word_embeddings", False)))):
            missing.append("_head (or norm + lm_head/tied embeddings)")
        layers = list(getattr(model, "layers", []) or [])
        for i, layer in enumerate(layers):
            for n in ("input_layernorm", "post_attention_layernorm",
                      "self_attn", "mlp"):
                if getattr(layer, n, None) is None:
                    missing.append(f"layers[{i}].{n}")
        if missing:
            raise TypeError(
                "ServingModel needs a Llama-family module layout; "
                f"{type(model).__name__} is missing: {', '.join(missing)}")
        # each layer's kinds, asked of the layer here and nowhere else
        windows = [getattr(layer.self_attn, "window", None)
                   for layer in layers]
        if len({w for w in windows if w}) > 1:
            raise TypeError("ServingModel keeps one window group: the "
                            f"layers' windows differ ({windows})")
        self.window = next((int(w) for w in windows if w), None)
        kinds, counts = [], {True: 0, False: 0}
        for layer, w in zip(layers, windows):
            kinds.append(_LayerKind(
                window=int(w) if w else None, index=counts[bool(w)],
                own_qkv=callable(getattr(layer.self_attn, "qkv", None)),
                rope=bool(getattr(layer.self_attn, "use_rope", True)),
                routed=callable(getattr(layer.mlp, "routed", None)),
                four_norms=getattr(layer, "pre_mlp_layernorm", None)
                is not None))
            counts[bool(w)] += 1
        self._kinds = tuple(kinds)
        self.n_window_layers, self.n_global_layers = counts[True], counts[False]
        self.routed_layers = sum(kind.routed for kind in kinds)
        self.n_experts = max((getattr(layer.mlp, "n_experts", 0)
                              for layer in layers), default=0)
        embed = getattr(model, "_embed", None)
        self._embed = embed if callable(embed) else model.embed_tokens
        #: every layer the plain Llama layer: what chunked prefill, the
        #: verify program, quantised linears and the fused junctions assume
        self.plain = all(kind.plain for kind in kinds) and not callable(embed)
        self.cfg = cfg
        self.n_head = cfg.num_heads
        self.n_kv = cfg.num_kv_heads
        self.head_dim = cfg.head_dim
        self.max_pos = cfg.max_position_embeddings
        self.pool: kv_cache.PagePool | None = None
        self.window_pool: kv_cache.PagePool | None = None
        # experts that got a row, one traced scalar a routed layer, of the
        # program being traced (`take_counts` empties it)
        self._hits: list = []
        self._live = None       # rows that are tokens, set by each forward
        # {program: {stage: kernel or "composite"}} — which path each
        # compiled program's stages took at trace time (the kernel gates
        # below decide per shape; nothing falls back unrecorded)
        self.paths: dict = {}
        # {program: (whole, block)}: the positions of the pool one layer
        # reads per call. A page-table gather reads `whole` (every slot of
        # every table row, from the shapes the forward gathers) whatever
        # is live and has block 0; the paged decode kernel reads each
        # row's live positions rounded up to `block`
        self.gathered: dict = {}
        self._prog = ""
        # fused decode epilogue (block_fused_pallas.decode_epilogue) needs
        # the final norm + head EXPOSED as attributes so the last junction
        # can fold the norm in and the head skip its own; a model carrying
        # only an opaque _head keeps the per-op tail
        self._fused_block = bool(fused_block) and self.plain and \
            getattr(model, "norm", None) is not None and \
            (getattr(model, "lm_head", None) is not None
             or getattr(cfg, "tie_word_embeddings", False))

        self._quant_dtype = None
        self._qweights: dict = {}
        if quant:
            if not self.plain:
                raise TypeError(
                    f"quant={quant!r} swaps the seven linears of a plain "
                    f"Llama layer; {type(model).__name__} has other layers")
            if quant not in _QUANT_ALGOS:
                raise ValueError(f"quant must be one of "
                                 f"{sorted(_QUANT_ALGOS)}, got {quant!r}")
            algo = quant if quant.startswith("weight_only_") else \
                "weight_only_" + quant
            self._quant_dtype = _QUANT_ALGOS[quant]
            from ..nn.quant import weight_quantize
            for i, layer in enumerate(layers):
                for tag, path in _LAYER_LINEARS:
                    mod = _get_path(layer, path)
                    if mod is None or getattr(mod, "weight", None) is None:
                        raise TypeError(
                            f"quant={quant!r}: layers[{i}]."
                            f"{'.'.join(path)} has no weight to quantize")
                    qw, scale = weight_quantize(
                        mod.weight, algo=algo, group_size=quant_group_size)
                    self._qweights[(tag, i)] = (qw.detach(), scale.detach())

    # -- wiring --------------------------------------------------------------

    def bind_pool(self, pool: kv_cache.PagePool,
                  window_pool: kv_cache.PagePool | None = None
                  ) -> "ServingModel":
        """`pool` holds the global layers' KV, `window_pool` (needed iff
        the model has window layers) the window layers'."""
        for grp, n, name in ((pool, self.n_global_layers, "global"),
                             (window_pool, self.n_window_layers, "window")):
            if grp is None:
                if n:
                    raise ValueError(f"the model has {n} {name} layer(s) "
                                     f"and was given no {name} pool")
                continue
            if (grp.num_layers, grp.num_kv_heads, grp.head_dim) != \
                    (n, self.n_kv, self.head_dim):
                raise ValueError(
                    f"{name} pool shape (layers={grp.num_layers}, "
                    f"kv={grp.num_kv_heads}, d={grp.head_dim}) does not "
                    f"match model (layers={n}, "
                    f"kv={self.n_kv}, d={self.head_dim})")
        self.pool, self.window_pool = pool, window_pool
        return self

    def take_counts(self):
        """int32 [1]: the experts that got a row in the forward just
        traced, summed over its routed layers; None for a model with no
        routed layer. The program hands it out beside the tokens."""
        hits, self._hits = self._hits, []
        if not hits:
            return None
        return jnp.sum(jnp.stack(hits)).astype(jnp.int32).reshape(1)

    @property
    def quantized(self) -> bool:
        return bool(self._qweights)

    def _note(self, stage: str, path: str) -> None:
        self.paths.setdefault(self._prog, {})[stage] = path

    def _note_gather(self, tables, block: int = 0) -> None:
        """What this program reads of the pool per layer: a gather reads
        every slot of every row of `tables` ([rows, max_pages]), whatever
        is live; the paged kernel (`block` > 0) each row's live positions
        rounded up to its block."""
        self.gathered[self._prog] = (
            int(tables.shape[0]) * int(tables.shape[1])
            * self.pool.page_size, int(block))

    # -- shared pieces -------------------------------------------------------

    def _rope_tables(self):
        """Full-length (cos, sin) ``[1, T, 1, D]`` tables, memoized on the
        model when it exposes ``_rope`` (Llama), else built/cached here."""
        rope = getattr(self.model, "_rope", None)
        if callable(rope):
            return rope(self.max_pos)
        cached = getattr(self, "_rope_cache", None)
        if cached is None:
            from ..models.llama import _rope_tables
            cached = self._rope_cache = _rope_tables(self.cfg, self.max_pos)
        return cached

    def _linear(self, tag, i, x, module):
        q = self._qweights.get((tag, i))
        if q is None:
            return module(x)
        from ..nn.quant import weight_only_linear
        qw, scale = q
        shp = x.shape
        y = weight_only_linear(x.reshape([-1, shp[-1]]), qw,
                               bias=getattr(module, "bias", None),
                               weight_scale=scale,
                               weight_dtype=self._quant_dtype)
        return y.reshape(list(shp[:-1]) + [y.shape[-1]])

    def _head(self, x):
        m = self.model
        if callable(getattr(m, "_head", None)):
            return m._head(x)
        x = m.norm(x)
        if getattr(self.cfg, "tie_word_embeddings", False):
            import paddle_tpu as paddle
            return paddle.matmul(x, m.embed_tokens.weight, transpose_y=True)
        return m.lm_head(x)

    def _head_normed(self, x):
        """lm head over an ALREADY-normalized hidden state (the fused
        path's last junction folded the final norm in)."""
        m = self.model
        if getattr(m, "lm_head", None) is not None:
            return m.lm_head(x)
        import paddle_tpu as paddle
        return paddle.matmul(x, m.embed_tokens.weight, transpose_y=True)

    def _logits(self, hidden, normed, valid=None):
        """Logits Tensor of `hidden` ``[b, s, H]`` (`normed`: the final
        norm is already in): at every position, or ``[b, 1, V]`` at the
        last of the first `valid` positions (a traced scalar) alone."""
        with jax.named_scope("head_sample"):
            if valid is not None:
                hidden = Tensor(jax.lax.dynamic_slice_in_dim(
                    hidden._data, valid - 1, 1, axis=1))   # [b, 1, H]
            return self._head_normed(hidden) if normed \
                else self._head(hidden)

    def _attn_in(self, i, kind, layer, h, b, s, sin, cos):
        """(q, k, v, gate) of layer `i` for the normed input `h`, RoPE
        applied where the layer has it; gate None without an output gate."""
        attn = layer.self_attn
        if kind.own_qkv:
            q, k, v, gate = attn.qkv(h)
        else:
            gate = None
            q = self._linear("q", i, h, attn.q_proj) \
                .reshape([b, s, self.n_head, self.head_dim])
            k = self._linear("k", i, h, attn.k_proj) \
                .reshape([b, s, self.n_kv, self.head_dim])
            v = self._linear("v", i, h, attn.v_proj) \
                .reshape([b, s, self.n_kv, self.head_dim])
        if kind.rope:
            q, k = F.rope(q, k, sin, cos)
        return q, k, v, gate

    def _attn_out(self, i, layer, out, gate, b, s):
        """The attention block's output from the heads' `out` ([B, S, H, D]
        array): through the layer's gate where it has one, then o_proj."""
        if gate is not None:
            return layer.self_attn.out(Tensor(out), gate)
        return self._linear(
            "o", i, Tensor(out.reshape(b, s, self.n_head * self.head_dim)),
            layer.self_attn.o_proj)

    def _ffn(self, i, kind, mlp, y):
        """The layer's feed-forward of the normed `y`: routed experts
        (their hit count kept for `take_counts`), quantised linears, or the
        module's own dense forward."""
        if kind.routed:
            from ..ops.kernels import moe_gemm_pallas as mg
            self._note("experts", "moe_grouped" if mg.use_ragged_kernel(
                int(y.shape[-1]), int(mlp.gate_w.shape[-1]), y._data.dtype)
                else "composite")
            # `_live`: which rows of this program's batch are tokens
            out, hit = mlp.routed(y, self._live)
            self._hits.append(hit._data)
            return out
        if not self._qweights:
            return mlp(y)
        import paddle_tpu as paddle
        g = self._linear("gate", i, y, mlp.gate_proj)
        u = self._linear("up", i, y, mlp.up_proj)
        return self._linear("down", i, paddle.swiglu(g, u), mlp.down_proj)

    def _pool_of(self, kind):
        """The pool of a layer's group; the layer is `kind.index` in it."""
        return self.window_pool if kind.window else self.pool

    def _layer_tail(self, i, kind, layers, fused, x, hres, attn_out):
        """(x, y, hres) after layer `i`'s post-attention half. Fused: both
        residual junctions are single block_decode_epilogue passes and the
        next layer's input norm (the final model norm after the LAST
        layer) folds into the MLP junction, so `y` is the next normed
        input and `hres` the residual stream. Else `x` is the stream: a
        fused residual-add + rmsnorm, then the MLP (the same primitive
        chain as ``LlamaDecoderLayer.forward``); with four norms a layer,
        each half's output is normed before it is added."""
        layer = layers[i]
        if fused:
            y, hres = self._junction(attn_out, hres,
                                     layer.post_attention_layernorm)
            m = self._ffn(i, kind, layer.mlp, y)
            nxt = layers[i + 1].input_layernorm if i + 1 < len(layers) \
                else self.model.norm
            y, hres = self._junction(m, hres, nxt)
            return x, y, hres
        if kind.four_norms:
            x = x + layer.post_attention_layernorm(attn_out)
            x = x + layer.post_mlp_layernorm(self._ffn(
                i, kind, layer.mlp, layer.pre_mlp_layernorm(x)))
        else:
            y, h = F.fused_rms_norm_add(
                attn_out, x, layer.post_attention_layernorm.weight,
                layer.post_attention_layernorm._epsilon)
            x = h + self._ffn(i, kind, layer.mlp, y)
        return x, None, hres

    # -- fused junctions (block_fused_pallas.decode_epilogue) ----------------

    def _fused_active(self) -> bool:
        """Fused-junction gate: ``ServingConfig(fused_block=)`` AND the
        Pallas kernels dispatching (TPU / interpret tests). Off, the
        per-op residual add and norm run."""
        from ..core.flags import flag
        from ..ops.kernels import _common as kern
        active = (self._fused_block and kern.available()
                  and flag("use_pallas_kernels") and flag("use_fused_blocks"))
        if not active:
            self._note("junction", "composite")
        return active

    def _junction(self, x, residual, norm_mod):
        """(normed, h): one residual junction as a single
        ``block_decode_epilogue`` Pallas pass (projection output ->
        residual add -> rmsnorm). Shape-static — per-request variation
        stays in values, so the compiled decode program never retraces."""
        from ..autograd.function import apply_multi
        from ..ops.kernels import _common as kern
        from ..ops.kernels import block_fused_pallas as bfp
        eps = norm_mod._epsilon
        if bfp.use_kernel(tuple(x.shape), tuple(residual.shape)):
            self._note("junction", "block_decode_epilogue")

            def fn(a, r, w):
                return bfp.decode_epilogue(a, r, w, eps,
                                           kern.interpret_mode())
        else:  # tiny batches below the kernel's amortization floor
            self._note("junction", "composite")

            def fn(a, r, w):
                return bfp.reference_fused_epilogue(
                    a, r, w, None, 0, 0.0, eps, None, "rms")
        return apply_multi(fn, x, residual, norm_mod.weight,
                           name="serving_decode_epilogue")

    # -- the layer loop ------------------------------------------------------

    def _run_layers(self, x, sin, cos, b, s, cache_step):
        """The decoder layers over the embedded tokens `x` ``[b, s, H]``:
        the one loop of the four programs. A layer is its input norm (or
        the fused junction's `y`), q/k/v with the RoPE rows `sin`/`cos`
        where it has RoPE, the program's ``cache_step(kind, q, k, v)``,
        the output projection and the post-attention half.

        ``cache_step`` is what a program does with the cache: it writes
        the layer's new K/V where they belong and returns the heads'
        output, a ``[b, s, n_head, head_dim]`` array, of attending to what
        the query may see; `kind` says which pool, which layer of it and
        which window.

        Returns (hidden ``[b, s, H]``, normed): with the fused junctions
        on, the last one folded the model's final norm in.
        """
        layers = list(self.model.layers)
        fused = self._fused_active()
        hres = x
        for i, (layer, kind) in enumerate(zip(layers, self._kinds)):
            with jax.named_scope("attention"):
                if i == 0 or not fused:     # else the last junction's `y`
                    y = layer.input_layernorm(x)
                q, k, v, gate = self._attn_in(i, kind, layer, y, b, s,
                                              sin, cos)
            out = cache_step(kind, q, k, v)
            with _attention_scope(kind):
                attn_out = self._attn_out(i, layer, out, gate, b, s)
            with jax.named_scope("mlp"):
                x, y, hres = self._layer_tail(i, kind, layers, fused, x,
                                              hres, attn_out)
        return (y, True) if fused else (x, False)

    # -- decode --------------------------------------------------------------

    def decode_forward(self, tokens, positions, tables, window_tables=None):
        """One continuous-batch decode token per row.

        tokens ``[B]`` int32 (last emitted token per slot), positions
        ``[B]`` int32 (absolute position that token occupies — its KV is
        written there), tables ``[B, max_pages]`` int32 (the global
        group's), window_tables the window group's (a model with window
        layers; slots behind a row's window hold the trash page). Inactive
        slots carry position 0 and an all-trash table. Returns logits
        Tensor ``[B, vocab]`` for the NEXT position.
        """
        self._prog = "decode"
        pool = self.pool
        ps = pool.page_size
        pos = positions._data.astype(jnp.int32)
        tab = tables._data.astype(jnp.int32)
        b = int(tokens.shape[0])
        path = kv_cache.paged_attention_path(
            (b, 1, self.n_head, self.head_dim), pool.k._data.shape,
            pool.k._data.dtype)

        def write_at(t):    # (page, slot) of each row's new token
            return jnp.take_along_axis(t, (pos // ps)[:, None],
                                       axis=1)[:, 0], pos % ps

        page_ids, slots = write_at(tab)
        wtab = w_page_ids = None
        if self.window_pool is not None:
            wtab = window_tables._data.astype(jnp.int32)
            w_page_ids, _ = write_at(wtab)
        # an inactive slot has an all-trash global table; a live row's
        # window table starts with the trash page once its window has moved
        live = self._live = tab[:, 0] != kv_cache.TRASH_PAGE

        cos_f, sin_f = self._rope_tables()
        cos = Tensor(cos_f._data[0, pos][:, None])      # [B, 1, 1, D]
        sin = Tensor(sin_f._data[0, pos][:, None])

        self._note("attention", path)
        self._note_gather(tab, kv_cache.paged_block_positions(
            path, ps, int(tab.shape[1])))
        write = kv_cache.write_token_rows if path == kv_cache.PAGED_PATH \
            else kv_cache.write_token

        def cache_step(kind, q, k, v):
            grp, j = self._pool_of(kind), kind.index
            t_i, pages_i = (wtab, w_page_ids) if kind.window \
                else (tab, page_ids)
            kp = write(grp.k._data, j, pages_i, slots, k._data[:, 0])
            vp = write(grp.v._data, j, pages_i, slots, v._data[:, 0])
            grp.k._data, grp.v._data = kp, vp
            with _attention_scope(kind):
                # the whole pools go in: the paged kernel fetches the
                # live pages itself (a layer slice here would be copied)
                return kv_cache.paged_attention(
                    q._data, kp, vp, j, t_i, pos, window=kind.window,
                    live=live if kind.window else None)

        x = self._embed(Tensor(tokens._data.reshape(b, 1)))
        logits = self._logits(
            *self._run_layers(x, sin, cos, b, 1, cache_step))
        return Tensor(logits._data[:, 0, :])

    # -- speculative verify --------------------------------------------------

    def verify_forward(self, tokens, positions, draft_len, tables):
        """One speculative-verify step: K+1 tokens per batch row — the
        last accepted token plus up to K drafts — scored in a SINGLE
        forward over the paged pool.

        tokens ``[B, S]`` int32 (``S = K+1`` static; lane 0 = last
        emitted token, lanes ``1..draft_len`` the drafts, the rest
        padding), positions ``[B]`` int32 (absolute position of lane 0 —
        the row's ``cur_len - 1``), draft_len ``[B]`` int32 (valid
        drafts per row; lanes past ``draft_len`` write to the trash
        page), tables ``[B, max_pages]`` int32. Draft KV is written
        speculatively THROUGH the page table (the scheduler has already
        grown the table and copy-on-written any shared page in the
        span); attention is :func:`~.kv_cache.chunk_attention` with
        per-row starts, so lane ``i`` sees everything resident through
        position ``base + i`` — the draft hypothesis scored causally
        against the real cache. Returns logits Tensor ``[B, S, vocab]``
        (lane ``i`` = the distribution at position ``base + i + 1``).
        All shapes static; per-request variation rides in values — the
        compiled verify program NEVER retraces.
        """
        self._prog = "verify"
        pool = self.pool
        ps = pool.page_size
        base = positions._data.astype(jnp.int32)              # [B]
        dlen = draft_len._data.astype(jnp.int32)              # [B]
        tab = tables._data.astype(jnp.int32)                  # [B, P]
        b, s = int(tokens.shape[0]), int(tokens.shape[1])
        max_pages = int(tab.shape[1])
        self._note_gather(tab)

        lane = jnp.arange(s, dtype=jnp.int32)[None]           # [1, S]
        pos = base[:, None] + lane                            # [B, S]
        valid = lane <= dlen[:, None]
        pos_c = jnp.clip(pos, 0, self.max_pos - 1)
        page_idx = jnp.minimum(pos_c // ps, max_pages - 1)
        w_page = jnp.where(valid, jnp.take_along_axis(tab, page_idx,
                                                      axis=1),
                           jnp.int32(kv_cache.TRASH_PAGE))    # [B, S]
        w_slot = pos_c % ps

        cos_f, sin_f = self._rope_tables()
        cos = Tensor(cos_f._data[0, pos_c])                   # [B, S, 1, D]
        sin = Tensor(sin_f._data[0, pos_c])

        def cache_step(kind, q, k, v):
            # write_token scatter over the flattened [B*S] lanes: one
            # (page, slot) per lane, invalid lanes steered to trash
            kp, vp = (kv_cache.write_token(
                p._data, kind.index, w_page.reshape(-1), w_slot.reshape(-1),
                t._data.reshape(b * s, self.n_kv, self.head_dim))
                for p, t in ((pool.k, k), (pool.v, v)))
            pool.k._data, pool.v._data = kp, vp
            kc = kv_cache.gather_layer(kp, kind.index, tab)
            vc = kv_cache.gather_layer(vp, kind.index, tab)
            with _attention_scope(kind):
                return kv_cache.chunk_attention(q._data, kc, vc, base)

        hidden, normed = self._run_layers(self._embed(tokens), sin, cos,
                                          b, s, cache_step)
        return self._logits(hidden, normed)                   # [B, S, V]

    # -- prefill -------------------------------------------------------------

    def prefill_forward(self, tokens, prompt_len, table_row, window_row=None):
        """Whole-prompt forward for one request, writing its KV pages.

        tokens ``[1, L_bucket]`` int32 (prompt padded to the compile
        bucket), prompt_len scalar int32 (traced — one compiled program
        per bucket serves every length), table_row ``[max_pages]`` int32
        (the global group's), window_row the window group's: the pages
        behind the prompt's last window are the trash page there, so a
        long prompt keeps only its last window in that group.
        Padding positions' KV writes land in the trash page; causal
        attention keeps them out of every real position's output.
        Returns logits Tensor ``[1, vocab]`` at position ``prompt_len-1``
        (the first generated token's distribution).
        """
        self._prog = "prefill"
        n = int(tokens.shape[1])
        plen = prompt_len._data.reshape(()).astype(jnp.int32)
        self._live = jnp.arange(n, dtype=jnp.int32) < plen
        rows = {False: table_row._data.astype(jnp.int32)}
        if self.window_pool is not None:
            rows[True] = window_row._data.astype(jnp.int32)

        cos_f, sin_f = self._rope_tables()
        cos = Tensor(cos_f._data[:, :n])
        sin = Tensor(sin_f._data[:, :n])

        def cache_step(kind, q, k, v):
            grp, row = self._pool_of(kind), rows[bool(kind.window)]
            grp.k._data = kv_cache.write_prefill(
                grp.k._data, kind.index, row, plen, k._data[0],
                grp.page_size)
            grp.v._data = kv_cache.write_prefill(
                grp.v._data, kind.index, row, plen, v._data[0],
                grp.page_size)
            with _attention_scope(kind):
                return F.scaled_dot_product_attention(
                    q, k, v, is_causal=True, window=kind.window)._data

        hidden, normed = self._run_layers(self._embed(tokens), sin, cos,
                                          1, n, cache_step)
        logits = self._logits(hidden, normed, valid=plen)
        return Tensor(logits._data[:, 0, :])

    # -- chunked prefill -----------------------------------------------------

    def prefill_chunk_forward(self, tokens, start, chunk_len, table_row):
        """One prefill CHUNK of a request's context against the paged
        pool: positions ``[start, start + chunk_len)`` of the sequence,
        attending to everything already resident (earlier chunks and
        cached prefix pages) through the page table.

        tokens ``[1, C_bucket]`` int32 (the chunk's tokens padded to the
        compile bucket), ``start``/``chunk_len`` traced scalars int32,
        table_row ``[max_pages]`` int32. KV writes land at absolute
        positions through the table (padding lanes -> trash page);
        attention is :func:`~.kv_cache.chunk_attention` over the gathered
        view (written-then-gathered, so the chunk sees itself causally).
        Returns logits Tensor ``[1, vocab]`` at the chunk's LAST valid
        position — meaningful on the final chunk, where it seeds the
        first generated token exactly like the monolithic program's
        ``logits[prompt_len - 1]``.
        """
        self._prog = "chunk"
        pool = self.pool
        ps = pool.page_size
        n = int(tokens.shape[1])
        s0 = start._data.reshape(()).astype(jnp.int32)
        clen = chunk_len._data.reshape(()).astype(jnp.int32)
        tab_row = table_row._data.astype(jnp.int32)
        max_pages = int(tab_row.shape[0])
        self._note_gather(tab_row[None])

        t_loc = jnp.arange(n, dtype=jnp.int32)
        pos = s0 + t_loc                      # absolute sequence positions
        valid = t_loc < clen
        pos_c = jnp.clip(pos, 0, self.max_pos - 1)

        cos_f, sin_f = self._rope_tables()
        cos = Tensor(cos_f._data[:, pos_c])           # [1, C, 1, D]
        sin = Tensor(sin_f._data[:, pos_c])

        page_idx = jnp.minimum(pos // ps, max_pages - 1)
        w_page = jnp.where(valid, tab_row[page_idx],
                           jnp.int32(kv_cache.TRASH_PAGE))
        w_slot = pos % ps

        def cache_step(kind, q, k, v):
            # write_token's scatter semantics fit a chunk exactly: one
            # (page, slot) per lane, padding lanes steered to trash
            kp, vp = (kv_cache.write_token(p._data, kind.index, w_page,
                                           w_slot, t._data[0])
                      for p, t in ((pool.k, k), (pool.v, v)))
            pool.k._data, pool.v._data = kp, vp
            kc = kv_cache.gather_layer(kp, kind.index, tab_row[None])
            vc = kv_cache.gather_layer(vp, kind.index, tab_row[None])
            with _attention_scope(kind):
                return kv_cache.chunk_attention(q._data, kc, vc, s0)

        hidden, normed = self._run_layers(self._embed(tokens), sin, cos,
                                          1, n, cache_step)
        logits = self._logits(hidden, normed, valid=clen)
        return Tensor(logits._data[:, 0, :])
