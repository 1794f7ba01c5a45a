"""Dataflow IR for the graph tier: a flat op graph built from a ClosedJaxpr.

The AST tier (:mod:`paddle_tpu.analysis.rules`) sees Python source; this
module sees what XLA sees — the traced jaxpr. :func:`build_graph` flattens
a ``ClosedJaxpr`` (inlining ``jit``/``custom_vjp``/``custom_jvp``/
``remat``/``shard_map`` sub-jaxprs, keeping ``pallas_call``/``scan``/
``while``/``cond`` opaque) into a list of :class:`OpNode` with:

* an **op kind** (elementwise / reduce / matmul / layout / collective /
  transfer / pallas / sharding / control / other) — the vocabulary the
  fusion model and the GA rules share;
* per-op **FLOPs and HBM-bytes estimates** (bytes = operands + results at
  aval sizes: what a non-fused execution would move through HBM);
* a **source span** mapped back through jaxpr ``source_info`` to the
  outermost non-framework frame, so findings land on the model line that
  created the op, not on ``nn/functional`` internals.

Estimates are roofline-style bounds, not measurements: they answer
"which boundary moves the most bytes", the question fusion targeting
needs, and are cross-validated against ``attribute_memory()`` measured
peaks by the bench (docs/static_analysis.md#graph-tier).
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field

__all__ = ["OpNode", "DataflowGraph", "build_graph", "aval_bytes",
           "KIND_ELEMENTWISE", "KIND_REDUCE", "KIND_MATMUL", "KIND_LAYOUT",
           "KIND_GATHER", "KIND_COLLECTIVE", "KIND_TRANSFER", "KIND_PALLAS",
           "KIND_SHARDING", "KIND_CONTROL", "KIND_RNG", "KIND_OTHER"]

KIND_ELEMENTWISE = "elementwise"
KIND_REDUCE = "reduce"
KIND_MATMUL = "matmul"
KIND_LAYOUT = "layout"
KIND_GATHER = "gather"
KIND_COLLECTIVE = "collective"
KIND_TRANSFER = "transfer"
KIND_PALLAS = "pallas"
KIND_SHARDING = "sharding"
KIND_CONTROL = "control"
KIND_RNG = "rng"
KIND_OTHER = "other"

# one-output-element-per-input-element ops: fusible producer AND consumer
_ELEMENTWISE = {
    "add", "add_any", "sub", "mul", "div", "rem", "pow", "integer_pow",
    "neg", "abs", "sign", "floor", "ceil", "round", "exp", "exp2", "expm1",
    "log", "log1p", "log2", "sqrt", "rsqrt", "cbrt", "square", "logistic",
    "tanh", "sin", "cos", "tan", "asin", "acos", "atan", "atan2", "sinh",
    "cosh", "asinh", "acosh", "atanh", "erf", "erfc", "erf_inv", "max",
    "min", "and", "or", "xor", "not", "shift_left",
    "shift_right_logical", "shift_right_arithmetic", "eq", "ne", "lt",
    "le", "gt", "ge", "select_n", "clamp", "nextafter", "is_finite",
    "convert_element_type", "bitcast_convert_type", "stop_gradient",
    "copy", "real", "imag", "conj", "population_count", "clz",
}
_REDUCE = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod", "reduce_and",
    "reduce_or", "reduce_xor", "argmax", "argmin", "reduce_precision",
    "cumsum", "cumprod", "cummax", "cummin", "cumlogsumexp",
}
_MATMUL = {"dot_general", "conv_general_dilated", "ragged_dot"}
# shape plumbing: fuses as a producer (free relayout inside a loop fusion)
_LAYOUT = {
    "reshape", "broadcast_in_dim", "transpose", "squeeze", "expand_dims",
    "slice", "concatenate", "pad", "rev", "iota", "split",
}
_GATHER = {"gather", "scatter", "scatter_add", "scatter_mul", "scatter_min",
           "scatter_max", "dynamic_slice", "dynamic_update_slice",
           "sort", "top_k", "take_along_axis"}
_COLLECTIVE = {"psum", "all_gather", "all_to_all", "ppermute",
               "psum_scatter", "pmax", "pmin", "reduce_scatter",
               "all_reduce"}
_TRANSFER = {"device_put", "copy_p"}
_RNG = {"threefry2x32", "random_bits", "random_seed", "random_wrap",
        "random_fold_in", "random_unwrap", "rng_bit_generator",
        "rng_uniform"}
_CONTROL = {"scan", "while", "cond", "fori_loop", "custom_root",
            "custom_linear_solve"}

# sub-jaxpr params inlined into the flat graph, by primitive name
_INLINE_PARAMS = {
    "jit": "jaxpr",
    "closed_call": "call_jaxpr",
    "core_call": "call_jaxpr",
    "custom_jvp_call": "call_jaxpr",
    "custom_vjp_call": "call_jaxpr",
    "remat2": "jaxpr",
    "shard_map": "jaxpr",
}

_FRAMEWORK_DIR = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))  # .../paddle_tpu


def classify(prim: str) -> str:
    if prim in _ELEMENTWISE:
        return KIND_ELEMENTWISE
    if prim in _REDUCE:
        return KIND_REDUCE
    if prim in _MATMUL:
        return KIND_MATMUL
    if prim in _LAYOUT:
        return KIND_LAYOUT
    if prim in _GATHER:
        return KIND_GATHER
    if prim in _COLLECTIVE:
        return KIND_COLLECTIVE
    if prim in _TRANSFER or prim.startswith("device_put"):
        return KIND_TRANSFER
    if prim == "pallas_call":
        return KIND_PALLAS
    if prim == "sharding_constraint":
        return KIND_SHARDING
    if prim in _CONTROL:
        return KIND_CONTROL
    if prim in _RNG:
        return KIND_RNG
    return KIND_OTHER


def aval_bytes(aval) -> int:
    """HBM footprint of one abstract value (0 for non-array avals)."""
    shape = getattr(aval, "shape", None)
    dtype = getattr(aval, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        try:
            n *= int(d)
        except (TypeError, ValueError):  # symbolic dim: count as 1
            pass
    return n * getattr(dtype, "itemsize", 4)


def _flops_of(prim: str, eqn, out_elems: int, in_elems: int) -> float:
    """Roofline FLOPs estimate per primitive (elementwise ~1 flop/elem;
    dot_general 2*M*N*K from the dimension numbers; reduce ~in_elems)."""
    if prim == "dot_general":
        try:
            (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
            lshape = eqn.invars[0].aval.shape
            k = 1
            for d in lc:
                k *= int(lshape[d])
            return 2.0 * out_elems * k
        except Exception:
            return 2.0 * out_elems
    if prim == "conv_general_dilated":
        try:
            rhs = eqn.invars[1].aval.shape
            k = 1
            for d in rhs:
                k *= int(d)
            return 2.0 * out_elems * k / max(int(rhs[0]), 1)
        except Exception:
            return 2.0 * out_elems
    if prim in _REDUCE:
        return float(in_elems)
    if prim in _ELEMENTWISE:
        return float(out_elems)
    return 0.0


class VarRef:
    """A jaxpr var at one inline instance.

    jax CACHES traced sub-jaxprs (two ``jnp.var`` calls share one jit
    jaxpr object), so raw var identity collides when the same sub-jaxpr
    is inlined at two call sites. A VarRef is interned per
    ``(inline-scope, var)``: ref identity == logical-value identity
    across the whole flattened graph.
    """

    __slots__ = ("var", "scope")

    def __init__(self, var, scope: int):
        self.var = var
        self.scope = scope

    @property
    def aval(self):
        return getattr(self.var, "aval", None)

    def __repr__(self):
        return f"VarRef({self.var}@{self.scope})"


@dataclass
class OpNode:
    index: int
    prim: str
    kind: str
    invars: list = field(default_factory=list)    # VarRefs (non-literal)
    outvars: list = field(default_factory=list)   # VarRefs
    bytes_in: int = 0
    bytes_out: int = 0
    flops: float = 0.0
    file: str = ""
    line: int = 0
    name: str = ""        # pallas kernel name / jit name, when present
    sharding_spec: object = None   # PartitionSpec on sharding_constraint
    effectful: bool = False
    path: str = ""        # inline path, e.g. "jit:_einsum"

    param_sig: str = ""   # stable digest of eqn.params (duplicate detection)

    @property
    def span(self) -> str:
        return f"{self.file}:{self.line}" if self.file else "<jaxpr>"


class DataflowGraph:
    """Flat def-use graph over a traced program.

    ``nodes`` are in topological (program) order. ``producer[var] -> node``
    and ``consumers[var] -> [node, ...]`` key by jaxpr var identity.
    """

    def __init__(self, name: str = "<jaxpr>"):
        self.name = name
        self.nodes: list[OpNode] = []
        self.producer: dict = {}
        self.consumers: dict = {}
        self.invars: list = []
        self.constvars: list = []
        self.outvars: list = []

    # -- derived quantities -------------------------------------------------
    def args_bytes(self) -> int:
        return sum(aval_bytes(v.aval) for v in self.invars) + \
            sum(aval_bytes(v.aval) for v in self.constvars)

    def total_flops(self) -> float:
        return sum(n.flops for n in self.nodes)

    def total_bytes(self) -> int:
        return sum(n.bytes_in + n.bytes_out for n in self.nodes)

    def producer_of(self, var):
        return self.producer.get(id(var))

    def consumers_of(self, var):
        return self.consumers.get(id(var), [])


def pallas_kernel_name(eqn, default: str) -> str:
    """A ``pallas_call`` eqn's name: its explicit ``name=`` when given,
    else the kernel body function's name."""
    body = eqn.params["jaxpr"]
    return str(eqn.params.get("name")
               or getattr(body, "jaxpr", body).debug_info.func_name
               or default)


def _user_frame(source_info, prefer_file: str | None = None,
                exclude_files: frozenset = frozenset()):
    """(file, line) for an eqn: the innermost frame outside jax AND outside
    paddle_tpu internals (the model author's line); framework frames only
    when nothing else exists. ``exclude_files`` drops harness frames (the
    bench's own trace_layer call site) so spans land on model code."""
    try:
        from jax._src import source_info_util as siu
        frames = list(siu.user_frames(source_info.traceback))
    except Exception:
        return "", 0
    fallback = ("", 0)
    for fr in frames:
        f, ln = fr.file_name, int(fr.start_line)
        if os.path.abspath(f) in exclude_files:
            continue
        if not fallback[0]:
            fallback = (f, ln)
        if prefer_file and os.path.abspath(f) == prefer_file:
            return f, ln
        if not f.startswith(_FRAMEWORK_DIR):
            return f, ln
    return fallback


def _is_jaxpr(obj) -> bool:
    return hasattr(obj, "eqns") or hasattr(obj, "jaxpr")


def _param_sig(eqn) -> str:
    """Order-stable digest of an eqn's params, cheap enough to compute for
    every node. Jaxpr-valued params collapse to an identity token (two
    eqns sharing the same sub-jaxpr object are the same computation; two
    distinct traces never are)."""
    parts = []
    try:
        for k in sorted(eqn.params):
            v = eqn.params[k]
            if _is_jaxpr(v):
                parts.append(f"{k}=<jaxpr#{id(v)}>")
            else:
                parts.append(f"{k}={repr(v)[:64]}")
    except Exception:
        return ""
    return ",".join(parts)


def _as_open(jaxpr_like):
    """(jaxpr, consts) for a ClosedJaxpr or plain Jaxpr."""
    inner = getattr(jaxpr_like, "jaxpr", None)
    if inner is not None and hasattr(inner, "eqns"):
        return inner, list(getattr(jaxpr_like, "consts", []))
    return jaxpr_like, []


def build_graph(closed_jaxpr, name: str = "<jaxpr>",
                prefer_file: str | None = None,
                max_depth: int = 8,
                exclude_files=()) -> DataflowGraph:
    """Flatten a ClosedJaxpr into a :class:`DataflowGraph`.

    Sub-jaxprs of call-like primitives (see ``_INLINE_PARAMS``) are inlined
    so an op chain split across ``jit`` boundaries is still one chain;
    opaque primitives (``pallas_call``, control flow) become single nodes
    carrying their whole-body byte counts.
    """
    import itertools

    import jax
    from jax.extend.core import Literal

    g = DataflowGraph(name=name)
    jaxpr, _consts = _as_open(closed_jaxpr)
    prefer = os.path.abspath(prefer_file) if prefer_file else None
    excludes = frozenset(os.path.abspath(f) for f in exclude_files)

    scope_ids = itertools.count()
    root_scope = next(scope_ids)
    interned: dict = {}

    def ref_of(v, scope: int) -> VarRef:
        key = (scope, id(v))
        r = interned.get(key)
        if r is None:
            r = interned[key] = VarRef(v, scope)
        return r

    def resolve(r: VarRef, sub_map: dict) -> VarRef:
        """Follow inline mappings transitively: an inner formal var may map
        to a mid-level var that is itself a formal var of a further-out
        inline. Bounded by inline depth."""
        hops = 0
        while r in sub_map and hops <= max_depth + 1:
            r = sub_map[r]
            hops += 1
        return r

    g.invars = [ref_of(v, root_scope) for v in jaxpr.invars]
    g.constvars = [ref_of(v, root_scope) for v in jaxpr.constvars]
    g.outvars = [ref_of(v, root_scope) for v in jaxpr.outvars
                 if not isinstance(v, Literal)]

    def visit(jx, path: str, depth: int, scope: int, sub_map: dict):
        """Walk eqns; sub_map maps inner VarRefs -> outer VarRefs at
        inline boundaries so def-use chains cross the call. Each inline
        instance gets a fresh scope so a CACHED sub-jaxpr inlined twice
        yields distinct refs (jax shares traced jaxpr objects)."""
        for eqn in jx.eqns:
            prim = str(eqn.primitive)
            inline_key = _INLINE_PARAMS.get(prim)
            sub = eqn.params.get(inline_key) if inline_key else None
            if sub is not None and _is_jaxpr(sub) and depth < max_depth:
                inner, _iconsts = _as_open(sub)
                inner_scope = next(scope_ids)
                nmap = dict(sub_map)
                # custom_vjp/jvp pass residual consts first; align tails
                # POSITIONALLY (literals kept so positions stay true, then
                # skipped: a literal operand's inner formal simply has no
                # producer, like a constant)
                outer_in = list(eqn.invars)
                inner_in = list(inner.invars)
                for iv, ov in zip(reversed(inner_in), reversed(outer_in)):
                    if isinstance(ov, Literal) or \
                            isinstance(iv, Literal):
                        continue
                    nmap[ref_of(iv, inner_scope)] = resolve(
                        ref_of(ov, scope), sub_map)
                inner_out = list(inner.outvars)
                for iv, ov in zip(inner_out, eqn.outvars):
                    # identity passthrough (outvar is a formal invar) keeps
                    # its invar mapping; the post-visit loop aliases it
                    if not isinstance(iv, Literal) and \
                            ref_of(iv, inner_scope) not in nmap:
                        nmap[ref_of(iv, inner_scope)] = ref_of(ov, scope)
                sub_name = str(eqn.params.get("name", "") or "")
                visit(inner, f"{path}{prim}:{sub_name}/" if sub_name
                      else f"{path}{prim}/", depth + 1, inner_scope, nmap)
                # inner outvar may itself be an inner invar (identity):
                # record a passthrough producer for the outer outvar
                for iv, ov in zip(inner_out, eqn.outvars):
                    if isinstance(iv, Literal):
                        continue
                    ovr = resolve(ref_of(ov, scope), sub_map)
                    if id(ovr) not in g.producer:
                        src = resolve(ref_of(iv, inner_scope), nmap)
                        if id(src) in g.producer:
                            g.producer[id(ovr)] = g.producer[id(src)]
                continue

            node = OpNode(index=len(g.nodes), prim=prim,
                          kind=classify(prim), path=path)
            ins = [resolve(ref_of(v, scope), sub_map) for v in eqn.invars
                   if not isinstance(v, Literal)]
            node.invars = ins
            # map formal sub-jaxpr outvars to their outer vars so the
            # producer registration below links inner producers to outer
            # consumers (and liveness sees one var, not two)
            node.outvars = [resolve(ref_of(v, scope), sub_map)
                            for v in eqn.outvars]
            node.bytes_in = sum(aval_bytes(v.aval) for v in ins)
            node.bytes_out = sum(aval_bytes(v.aval) for v in eqn.outvars)
            out_elems = sum(
                max(node_elems(v), 1) for v in eqn.outvars)
            in_elems = sum(max(node_elems(v), 1) for v in ins)
            node.flops = _flops_of(prim, eqn, out_elems, in_elems)
            node.effectful = bool(getattr(eqn, "effects", ()))
            node.file, node.line = _user_frame(eqn.source_info, prefer,
                                               excludes)
            if prim == "pallas_call":
                node.name = pallas_kernel_name(eqn, "pallas")
            elif prim == "sharding_constraint":
                sh = eqn.params.get("sharding")
                node.sharding_spec = getattr(sh, "spec", None)
            elif prim in _CONTROL:
                # opaque body: charge the body's bytes once so a scan does
                # not look free to the liveness/traffic estimators
                body = eqn.params.get("jaxpr") or \
                    eqn.params.get("cond_jaxpr")
                if body is not None and _is_jaxpr(body):
                    inner, _ = _as_open(body)
                    node.flops += sum(
                        _flops_of(str(e.primitive), e,
                                  sum(max(node_elems(v), 1)
                                      for v in e.outvars),
                                  sum(max(node_elems(v), 1)
                                      for v in e.invars
                                      if not isinstance(
                                          v, Literal)))
                    for e in inner.eqns)
            g.nodes.append(node)
            for v in ins:
                g.consumers.setdefault(id(v), []).append(node)
            for v in node.outvars:
                g.producer[id(v)] = node

    def node_elems(v) -> int:
        aval = getattr(v, "aval", None)
        shape = getattr(aval, "shape", None)
        if shape is None:
            return 0
        n = 1
        for d in shape:
            try:
                n *= int(d)
            except (TypeError, ValueError):
                pass
        return n

    visit(jaxpr, "", 0, root_scope, {})
    return g
