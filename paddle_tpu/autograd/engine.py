"""Backward engine: reverse traversal of the GradNode graph.

Reference analog: `egr::Backward` / `egr::Grad`
(paddle/fluid/eager/backward.cc:428 — in-degree BFS + ready queue with
`GradTensorHolder` accumulation). We do a depth-first topological sort from the
root tensors, then sweep in reverse, calling each node's vjp and accumulating
cotangents. Leaf tensors (no producing node, stop_gradient=False) receive
``.grad``; `grad()` instead collects cotangents for explicit inputs.
"""

from __future__ import annotations

import time as _time

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["run_backward", "backward", "grad"]


def _topo_order(roots):
    """Post-order DFS over GradNodes reachable from root tensors. Edges come
    from each node's RECORDED input_nodes (captured at op-record time), not
    the live `t._node`, which in-place ops may have rebound since."""
    order, seen = [], set()
    stack = [(n, False) for t in roots if (n := t._node) is not None]
    while stack:
        node, processed = stack.pop()
        if processed:
            order.append(node)
            continue
        if id(node) in seen:
            continue
        seen.add(id(node))
        stack.append((node, True))
        for n_in, _ in node.input_nodes:
            if n_in is not None and id(n_in) not in seen:
                stack.append((n_in, False))
    return order  # topological (inputs before consumers)


def run_backward(tensors, grad_tensors=None, retain_graph=False, create_graph=False,
                 inputs=None, accumulate_leaf=True, allow_unused=False):
    """Shared engine behind `Tensor.backward` and `paddle.grad`.

    Returns a dict {id(tensor): cotangent Tensor} for ``inputs`` when given.
    """
    from ..core.tensor import Tensor
    from .function import apply_multi
    from .grad_mode import set_grad_enabled

    if isinstance(tensors, Tensor):
        tensors = [tensors]
    if grad_tensors is None:
        grad_tensors = [None] * len(tensors)
    elif isinstance(grad_tensors, Tensor):
        grad_tensors = [grad_tensors]

    # graph-break replay (jit/sot.py): the prefix program already ran this
    # backward; the replayed loss carries no graph, so the re-executed
    # Python `backward()` is a no-op (grads were written back as state)
    from ..jit import sot
    sot.probe_note_backward()
    if sot._S.mode == "replay" and \
            all(t._node is None for t in tensors):
        return {}

    from ..profiler.profiler import host_self_span
    with host_self_span("backward_engine(host)"), \
            jax.named_scope("backward"):
        return _run_backward_impl(tensors, grad_tensors, retain_graph,
                                  create_graph, inputs, accumulate_leaf,
                                  allow_unused)


def _run_backward_impl(tensors, grad_tensors, retain_graph, create_graph,
                       inputs, accumulate_leaf, allow_unused):
    from ..core.tensor import Tensor
    from .function import apply_multi
    from .grad_mode import set_grad_enabled

    # node -> list of per-output cotangents (Tensor or None)
    cot: dict[int, list] = {}
    leaf_grads: dict[int, Tensor] = {}
    leaf_tensors: dict[int, Tensor] = {}
    # interior tensors whose cotangent the caller wants (paddle.grad on
    # non-leaf inputs): capture the slot value when the producing node fires.
    watched: dict[int, list] = {}
    if inputs is not None:
        for t in inputs:
            if t._node is not None:
                watched.setdefault(id(t._node), []).append(t)
    # seed the roots
    root_leaf = []
    for t, g in zip(tensors, grad_tensors):
        if g is None:
            if t._data.size != 1:
                raise RuntimeError(
                    "grad can be implicitly created only for scalar outputs; "
                    f"got shape {tuple(t._data.shape)}")
            g = Tensor(jnp.ones_like(t._data), stop_gradient=not create_graph)
        elif not isinstance(g, Tensor):
            g = Tensor(jnp.asarray(g), stop_gradient=True)
        if t._node is None:
            if not t.stop_gradient:
                root_leaf.append((t, g))
            continue
        slots = cot.setdefault(id(t._node), [None] * len(t._node.out_meta))
        slots[t._out_index] = _acc(slots[t._out_index], g)

    order = _topo_order(tensors)
    node_by_id = {id(n): n for n in order}

    with set_grad_enabled(bool(create_graph)):
        for node in reversed(order):
            slots = cot.pop(id(node), None)
            if slots is None:
                continue
            for t_w in watched.get(id(node), ()):
                g_w = slots[t_w._out_index]
                if g_w is not None:
                    leaf_grads[id(t_w)] = g_w
            if node.consumed and node.vjp_fn is None:
                raise RuntimeError(
                    "trying to backward through the graph a second time; "
                    "set retain_graph=True if you need to")
            from ..profiler.profiler import (op_timing_active,
                                             record_op_time)
            t0 = _time.perf_counter() if op_timing_active() else None
            # fill missing output cotangents with zeros; integer outputs take
            # float0 zeros as jax.vjp requires for non-differentiable outputs
            cts = []
            for s, (shape, dtype) in zip(slots, node.out_meta):
                if s is not None:
                    cts.append(s)
                elif jnp.issubdtype(dtype, jnp.inexact):
                    cts.append(Tensor(jnp.zeros(shape, dtype), stop_gradient=True))
                else:
                    # raw np float0 zeros; cannot be wrapped in a Tensor
                    cts.append(np.zeros(shape, jax.dtypes.float0))
            raw_cts = [c._data if isinstance(c, Tensor) else c for c in cts]
            if create_graph and node.jfn is not None:
                # re-derive the vjp symbolically so the cotangent graph stays
                # connected to the primal inputs (higher-order grad)
                jfn, multi = node.jfn, node.multi_out
                n_in = len(node.raw_inputs)
                primal_args = [t if t is not None else raw
                               for t, raw in zip(node.inputs, node.raw_inputs)]

                def regrad(*args, _jfn=jfn, _multi=multi, _n=n_in):
                    primals, c = args[:_n], args[_n:]
                    _, vjp = jax.vjp(_jfn, *primals)
                    return tuple(vjp(tuple(c) if _multi else c[0]))

                in_cots = apply_multi(regrad, *primal_args, *cts,
                                      name=f"{node.name}_grad")
                in_cots = in_cots[:n_in]
            elif create_graph:
                vjp_fn, multi = node.vjp_fn, node.multi_out
                in_cots = apply_multi(
                    lambda *c: tuple(vjp_fn(tuple(c) if multi else c[0])),
                    *cts, name=f"{node.name}_grad")
            else:
                raw = node.vjp_fn(tuple(raw_cts) if node.multi_out else raw_cts[0])
                if t0 is not None:
                    record_op_time(f"{node.name}_grad",
                                   [r for r in raw if r is not None], t0)
                in_cots = tuple(
                    None if r is None or
                    (hasattr(r, "dtype") and r.dtype == jax.dtypes.float0)
                    else Tensor(r, stop_gradient=True) for r in raw)
            if not retain_graph:
                node.vjp_fn = None
                node.consumed = True
            for t_in, (n_in, oi_in), c in zip(node.inputs, node.input_nodes,
                                              in_cots):
                if t_in is None or t_in.stop_gradient or c is None:
                    continue
                c = _run_hooks(t_in, c)
                if n_in is not None:
                    s = cot.setdefault(id(n_in), [None] * len(n_in.out_meta))
                    s[oi_in] = _acc(s[oi_in], c)
                else:
                    leaf_grads[id(t_in)] = _acc(leaf_grads.get(id(t_in)), c)
                    leaf_tensors[id(t_in)] = t_in
            if not retain_graph:
                # a consumed node frees what it saved: the activations it
                # holds would otherwise live as long as the loss tensor
                node.inputs, node.input_nodes = [], []
                node.raw_inputs, node.jfn = [], None

    for t, g in root_leaf:
        g = _run_hooks(t, g)
        leaf_grads[id(t)] = _acc(leaf_grads.get(id(t)), g)
        leaf_tensors[id(t)] = t

    if accumulate_leaf:
        for tid, t in leaf_tensors.items():
            t._accumulate_grad(leaf_grads[tid])

    if inputs is not None:
        out = []
        for t in inputs:
            g = leaf_grads.get(id(t))
            if g is None and not allow_unused:
                raise RuntimeError(
                    "one of the differentiated tensors appears to not have been "
                    "used in the graph; set allow_unused=True to return None")
            out.append(g)
        return out
    return None


def _acc(existing, new):
    if existing is None:
        return new
    from .function import apply
    return apply(jnp.add, existing, new, name="grad_accumulate")


def _run_hooks(t, g):
    for h in t._hooks:
        r = h(g)
        if r is not None:
            g = r
    return g


def backward(tensors, grad_tensors=None, retain_graph=False):
    """`paddle.autograd.backward` equivalent."""
    run_backward(tensors, grad_tensors, retain_graph=retain_graph)


def grad(outputs, inputs, grad_outputs=None, retain_graph=None, create_graph=False,
         only_inputs=True, allow_unused=False, no_grad_vars=None):
    """`paddle.grad` equivalent: returns cotangents for ``inputs`` without
    touching ``.grad`` (reference: eager_functions.cc run_partial_grad /
    general_grad in backward.cc)."""
    from ..core.tensor import Tensor
    if isinstance(inputs, Tensor):
        inputs = [inputs]
    if retain_graph is None:
        retain_graph = create_graph
    if no_grad_vars:
        saved = [(v, v.stop_gradient) for v in no_grad_vars]
        for v in no_grad_vars:
            v.stop_gradient = True
    try:
        res = run_backward(outputs, grad_outputs, retain_graph=retain_graph,
                           create_graph=create_graph, inputs=inputs,
                           accumulate_leaf=False, allow_unused=allow_unused)
    finally:
        if no_grad_vars:
            for v, sg in saved:
                v.stop_gradient = sg
    return res
