"""Expert-parallel MoE layer.

Reference: python/paddle/incubate/distributed/models/moe/moe_layer.py:263 —
`MoELayer` routes tokens through `MoEScatter`/`MoEGather` PyLayers (:99,:149)
backed by hand-written `global_scatter`/`global_gather` all-to-all ops.

TPU-native redesign (GShard style): routing is expressed as dispatch/combine
einsums over a [tokens, experts, capacity] one-hot; expert FFNs are stacked
[E, ...] parameters sharded over an expert mesh axis, and the XLA partitioner
lowers the token<->expert einsums into the all-to-all pair over ICI — the
exact comm pattern global_scatter/global_gather implement by hand, but fused
and overlapped by the compiler.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .....core.tensor import Tensor, Parameter
from .....autograd.function import apply
from .....autograd.grad_mode import no_grad
from .....nn.layer import Layer
from .gate import BaseGate, NaiveGate, GShardGate, SwitchGate

__all__ = ["MoELayer", "sort_by_expert", "dropless_experts", "row_tile"]


# -- routed experts that drop nothing ---------------------------------------
#
# No capacity and no [n, e, c] tensor: the (token, choice) rows are sorted by
# expert, each expert's rows padded to whole row tiles, and two grouped
# matmuls over the ragged groups (`ops/kernels/moe_gemm_pallas.py`) do the
# experts' SwiGLU; a weighted gather puts the rows back. Whatever the
# imbalance, every choice of every token is computed.

#: tokens of one pass through the experts: a longer input is cut into
#: passes of this many, so that the sorted copies of the tokens (k of each)
#: stay a fraction of a prefill's memory; the weights are read once a pass
DROPLESS_CHUNK = 4096


def row_tile(rows: int, n_experts: int) -> int:
    """Row tile of the grouped matmuls for `rows` (token, choice) rows over
    `n_experts`: the mean group rounded up to a power of two, between the
    16 rows of a bf16 tile and 256."""
    mean = max(1, -(-rows // n_experts))
    return int(min(256, max(16, 1 << (mean - 1).bit_length())))


def sort_by_expert(sel, n_experts: int, tile: int):
    """The padded sorted layout of routed rows. sel [n, k] int32: the
    experts each token chose, `n_experts` standing for none (a row of
    padding, an idle slot: sorted behind every expert's rows, in tiles no
    kernel runs). Returns (src [R] the token that feeds each row of the
    layout, dest [n, k] the row of each (token, choice), tile_expert
    [R / tile], used: live tiles, sizes [E] rows per expert). R is static:
    n k plus a tile short of one for every expert, rounded up to tiles."""
    n, k = sel.shape
    rows = n * k
    total = -(-(rows + n_experts * (tile - 1)) // tile) * tile
    flat = sel.reshape(-1).astype(jnp.int32)
    # a row's rank among its expert's rows, in token order: a running count
    # over one-hot columns (no sort: the groups' order is the experts')
    onehot = flat[:, None] == jnp.arange(n_experts + 1, dtype=jnp.int32)
    counts = jnp.cumsum(onehot.astype(jnp.int32), axis=0)
    rank = jnp.sum(jnp.where(onehot, counts, 0), axis=1) - 1
    sizes = counts[-1]
    # every expert's group padded to whole tiles; the rows of none as they are
    padded = jnp.where(jnp.arange(n_experts + 1) < n_experts,
                       -(-sizes // tile) * tile, sizes)
    ends = jnp.cumsum(padded)
    dest = (ends - padded)[flat] + rank
    src = jnp.zeros((total,), jnp.int32).at[dest].set(
        jnp.arange(rows, dtype=jnp.int32) // k)
    used = ends[n_experts - 1] // tile
    t = jnp.minimum(jnp.arange(total // tile, dtype=jnp.int32),
                    jnp.maximum(used - 1, 0))
    tile_expert = jnp.minimum(
        jnp.searchsorted(ends[:n_experts], t * tile, side="right"),
        n_experts - 1).astype(jnp.int32)
    return src, dest.reshape(n, k), tile_expert, used.astype(jnp.int32), \
        sizes[:n_experts]


def _dropless_pass(x, sel, weights, gate_w, up_w, down_w, interpret):
    from .....ops.kernels import moe_gemm_pallas as mg
    n, k = sel.shape
    e = gate_w.shape[0]
    tile = row_tile(n * k, e)
    src, dest, tile_expert, used, sizes = sort_by_expert(sel, e, tile)
    rows = x[src]                                   # [R, H]
    if interpret is True or (interpret is None and mg.use_ragged_kernel(
            x.shape[-1], gate_w.shape[-1], x.dtype)):
        from .....ops.kernels import _common as kern
        itp = interpret is True or kern.interpret_mode()
        h = mg.ragged_swiglu(rows, gate_w, up_w, tile_expert, used, tile,
                             itp)
        y = mg.ragged_matmul(h, down_w, tile_expert, used, tile, itp)
    else:
        h = mg.reference_grouped_swiglu(rows, gate_w, up_w, tile_expert,
                                        tile)
        y = mg.reference_grouped_matmul_ragged(h, down_w, tile_expert, tile)
    # a row that chose no expert lies in a tile nothing wrote
    picked = jnp.where((sel < e)[..., None], y[dest].astype(jnp.float32), 0.0)
    out = jnp.einsum("nk,nkh->nh", weights.astype(jnp.float32), picked)
    return out.astype(x.dtype), sizes


def dropless_experts(x, sel, weights, gate_w, up_w, down_w, interpret=None):
    """sum_j weights[t, j] * expert_{sel[t, j]}(x[t]) for every token, each
    expert a SwiGLU, nothing dropped. x [n, H]; sel [n, k] int32 (E: no
    expert, the row is worked on by none and adds nothing); weights
    [n, k]; gate_w/up_w [E, H, I]; down_w [E, I, H]. `interpret=True`
    forces the kernels in interpret mode, `False` the composite. Returns
    (out [n, H], sizes [E]: rows each expert got)."""
    n = x.shape[0]
    if n <= DROPLESS_CHUNK or n % DROPLESS_CHUNK:
        return _dropless_pass(x, sel, weights, gate_w, up_w, down_w,
                              interpret)
    c = n // DROPLESS_CHUNK
    cut = lambda a: a.reshape(c, DROPLESS_CHUNK, *a.shape[1:])  # noqa: E731
    out, sizes = jax.lax.map(
        lambda a: _dropless_pass(*a, gate_w, up_w, down_w, interpret),
        (cut(x), cut(sel), cut(weights)))
    return out.reshape(n, -1), jnp.sum(sizes, axis=0)


def _functionalize(template: Layer):
    names_params = list(template.named_parameters())
    params = [p for _, p in names_params]

    def expert_fn(param_arrays, x):
        saved = [(p._d, p._node) for p in params]
        for p, a in zip(params, param_arrays):
            p._d = a
            p._node = None
        try:
            with no_grad():
                out = template(Tensor(x))
            return out._d
        finally:
            for p, (d, n) in zip(params, saved):
                p._d = d
                p._node = n

    return [n for n, _ in names_params], params, expert_fn


class MoELayer(Layer):
    """moe_group maps to the expert mesh axis (default 'dp': experts live
    across data-parallel ranks, the reference's usual deployment)."""

    def __init__(self, d_model, experts, gate=None, moe_group=None,
                 mp_group=None, recompute_interval=0, top_k=2,
                 capacity_factor=1.25, expert_parallel_axis="dp",
                 shared_experts=None, name=None):
        super().__init__()
        self.d_model = d_model
        self.num_expert = len(experts)
        self.capacity_factor = capacity_factor
        self._axis = expert_parallel_axis
        # one shared decision for gate world_size AND stacked-param
        # sharding: the expert axis participates only when it divides the
        # global expert count
        from .....distributed.topology import get_mesh
        mesh = get_mesh()
        self._ep_size = 1
        if mesh is not None and expert_parallel_axis in mesh.axis_names and \
                self.num_expert % mesh.shape[expert_parallel_axis] == 0:
            self._ep_size = mesh.shape[expert_parallel_axis]
        if gate is None or isinstance(gate, dict):
            cfg = gate or {}
            gtype = cfg.get("type", "gshard")
            top_k = cfg.get("top_k", top_k)
            cls = {"naive": NaiveGate, "gshard": GShardGate,
                   "switch": SwitchGate}[gtype]
            # world_size = expert-axis size: `experts` is the GLOBAL list, so
            # per-rank num_expert * world_size = len(experts) (the reference's
            # tot_expert contract, moe_layer.py:263)
            gate = cls(d_model, self.num_expert // self._ep_size,
                       world_size=self._ep_size, top_k=top_k)
        self.gate = gate
        self.top_k = gate.top_k
        # always-on experts added to every token's output (DeepSeekMoE /
        # Qwen2-MoE shared experts; reference incubate moe shared variants)
        self.shared_experts = shared_experts

        # stack expert params: [E, ...] sharded over the expert axis
        self._param_names, self._template_params, self._expert_fn = \
            _functionalize(experts[0])
        # SwiGLU FFN experts (the Llama/Qwen2-MoE shape) get the grouped-GEMM
        # Pallas path: capacity tiles beyond each expert's fill count are
        # skipped instead of multiplied as zeros (reference: fused MoE
        # grouped-GEMM dispatch kernels)
        self._ffn_fast = self._param_names == [
            "gate_proj.weight", "up_proj.weight", "down_proj.weight"]
        self._stacked: list[Parameter] = []
        for j, pname in enumerate(self._param_names):
            per = [dict(e.named_parameters())[pname]._d for e in experts]
            stacked = Parameter(jnp.stack(per, axis=0),
                                name=f"moe_experts.{pname}")
            from .....distributed.sharding_utils import mark_sharding
            if self._ep_size > 1:
                mark_sharding(stacked,
                              P(self._axis, *([None] * (stacked.ndim - 1))))
            self.add_parameter(f"expert_{j}", stacked)
            self._stacked.append(stacked)
        self.l_aux = None

    def forward(self, x):
        b_shape = x.shape
        h = self.d_model
        tokens = x.reshape([-1, h])
        n = tokens.shape[0]
        e = self.num_expert
        k = self.top_k
        capacity = max(int(math.ceil(self.capacity_factor * n * k / e)), 1)

        logits = self.gate(tokens)  # [n, e]
        expert_fn = self._expert_fn
        n_params = len(self._stacked)
        from .....core.flags import flag
        from .....ops.kernels import _common as kern
        use_grouped = (self._ffn_fast and kern.available()
                       and flag("use_pallas_kernels"))
        interpret = kern.interpret_mode()

        def jfn(tok, lg, *stacked):
            probs = jax.nn.softmax(lg.astype(jnp.float32), axis=-1)
            # top-k routing
            topv, topi = jax.lax.top_k(probs, k)          # [n, k]
            topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
            route_oh = jax.nn.one_hot(topi, e, dtype=jnp.float32)  # [n, k, e]
            # position of each token within its expert queue
            pos = jnp.cumsum(route_oh.reshape(-1, e), axis=0).reshape(n, k, e) \
                - route_oh  # 0-based arrival order
            keep = pos < capacity
            onehot = route_oh * keep                      # post-capacity-drop
            pos_idx = jnp.einsum("nke->nk", pos * onehot).astype(jnp.int32)
            cap_oh = jax.nn.one_hot(jnp.where(jnp.sum(onehot, -1) > 0,
                                              pos_idx, capacity),
                                    capacity + 1, dtype=jnp.float32)[..., :capacity]
            # dispatch [n, e, c] / combine [n, e, c]
            dispatch = jnp.einsum("nke,nkc->nec", onehot, cap_oh)
            combine = jnp.einsum("nk,nke,nkc->nec", topv, onehot, cap_oh)
            expert_in = jnp.einsum("nec,nh->ech", dispatch,
                                   tok.astype(jnp.float32)).astype(tok.dtype)
            stacked_params = list(stacked)

            if use_grouped:
                from .....ops.kernels.moe_gemm_pallas import grouped_matmul
                counts = jnp.sum(dispatch, axis=(0, 2)).astype(jnp.int32)
                gate_w, up_w, down_w = stacked_params
                gh = grouped_matmul(expert_in, gate_w, counts, interpret)
                uh = grouped_matmul(expert_in, up_w, counts, interpret)
                act = (jax.nn.silu(gh.astype(jnp.float32))
                       * uh.astype(jnp.float32)).astype(expert_in.dtype)
                expert_out = grouped_matmul(act, down_w, counts, interpret)
            else:
                def run_one(param_arrays, xin):
                    return expert_fn(param_arrays, xin)
                expert_out = jax.vmap(run_one)(stacked_params, expert_in)
            out = jnp.einsum("nec,ech->nh", combine,
                             expert_out.astype(jnp.float32)).astype(tok.dtype)
            # aux load-balance loss (GShard eq.(4), generalised to top-k):
            # f_i = fraction of routing slots assigned to expert i BEFORE the
            # capacity drop (load balance must see intended routing, not the
            # post-drop truncation), m_i = mean gate prob; aux = E * f . m
            me = jnp.mean(probs, axis=0)
            ce = jnp.mean(jnp.sum(route_oh, axis=1) / k, axis=0)
            aux = jnp.sum(me * ce) * e
            return out, aux

        out, aux = _apply2(jfn, tokens, logits, self._stacked)
        self.l_aux = aux
        out = out.reshape(b_shape)
        if self.shared_experts is not None:
            out = out + self.shared_experts(x)
        return out


def _apply2(jfn, tokens, logits, stacked):
    from .....autograd.function import apply_multi
    out, aux = apply_multi(lambda *arrs: jfn(arrs[0], arrs[1], *arrs[2:]),
                           tokens, logits, *stacked, name="moe")
    return out, aux
