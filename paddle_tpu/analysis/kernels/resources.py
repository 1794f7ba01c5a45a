"""Static RESOURCE SHEETS for Pallas kernels.

One sheet per :class:`~.model.KernelModel`: how much VMEM one grid step
holds resident, how many FLOPs the whole launch performs, how many HBM
bytes the pipeline moves, and the resulting arithmetic intensity —
derived purely from the traced model, no device, no timer. The sheet is
the analyzer→cost-model bridge: ``cost_model.kernel_cost(...)`` returns
these dicts, ``bench.py`` joins them with the measured ``kernel_ab``
rows, and the future block-shape autotuner uses ``fits_vmem`` as its
admissibility filter before any measured trial.

Accounting conventions (documented because the numbers are *estimates*):

* ``vmem_bytes`` (the PK200 operand) is SINGLE-buffered residency:
  input+output block bytes + scratch + the body's peak intermediate
  liveness. The Pallas pipeline double-buffers blocks to overlap DMA
  with compute, so ``vmem_pipelined_bytes`` (2x blocks + scratch +
  intermediates) is also carried — kernels are budgeted against the
  single-buffered figure, matching how the in-tree block pickers size
  their blocks against ``chip_vmem_bytes()``-derived budgets.
* ``flops`` charges the body jaxpr once per grid step via the graph
  tier's per-primitive roofline model; ``fori_loop``/``scan`` bodies are
  charged once per step (a documented undercount for kernels that loop
  over an in-kernel K dimension).
* ``hbm_bytes`` counts DISTINCT (ref, block-index) pairs over the
  enumerated grid times block bytes (a block revisited consecutively is
  not re-fetched); grids past ``GRID_ENUM_CAP`` fall back to the
  steps x block-bytes upper bound.
"""

from __future__ import annotations

import dataclasses

from .model import KernelModel

__all__ = ["ResourceSheet", "resource_sheet", "body_intermediate_bytes",
           "body_flops"]


def _aval_nbytes(aval) -> int:
    import numpy as np
    aval = getattr(aval, "inner_aval", aval)
    shape = tuple(getattr(aval, "shape", ()) or ())
    try:
        dtype = np.dtype(getattr(aval, "dtype", np.float32))
    except TypeError:       # a semaphore: no vector memory
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * dtype.itemsize


def body_flops(body) -> float:
    """Roofline FLOPs of one body execution (graph-tier primitive
    model, applied recursively through call-like/loop sub-jaxprs)."""
    from ..graph.ir import _INLINE_PARAMS, _flops_of
    total = 0.0
    seen = set()

    def walk(jx):
        nonlocal total
        if id(jx) in seen:
            return
        seen.add(id(jx))
        for eqn in jx.eqns:
            prim = eqn.primitive.name
            subs = []
            key = _INLINE_PARAMS.get(prim)
            if key is not None and key in eqn.params:
                subs = [eqn.params[key]]
            else:
                for p in ("jaxpr", "call_jaxpr", "cond_jaxpr",
                          "body_jaxpr", "branches"):
                    sub = eqn.params.get(p)
                    if sub is not None:
                        subs.extend(sub if isinstance(sub, (tuple, list))
                                    else [sub])
            if subs:
                for s in subs:
                    walk(getattr(s, "jaxpr", s))
                continue
            out_elems = sum(
                max(1, int(_size(v.aval))) for v in eqn.outvars)
            in_elems = sum(
                max(1, int(_size(getattr(v, "aval", None))))
                for v in eqn.invars if hasattr(v, "aval"))
            try:
                total += float(_flops_of(prim, eqn, out_elems, in_elems))
            except Exception:
                pass

    def _size(aval):
        shape = tuple(getattr(aval, "shape", ()) or ())
        n = 1
        for d in shape:
            n *= int(d)
        return n

    walk(body)
    return total


def body_intermediate_bytes(body) -> int:
    """Peak bytes of live non-ref intermediates across the body — the
    accumulator term of the VMEM residency model. A straight-line
    liveness scan: a value is live from its defining eqn to its last
    use; ref-typed values (the blocks, already counted) are excluded."""
    last_use: dict = {}
    ref_ids = set()
    for v in body.invars + body.constvars:
        if "Ref" in type(v.aval).__name__:
            ref_ids.add(id(v))
    for i, eqn in enumerate(body.eqns):
        for v in eqn.invars:
            if hasattr(v, "aval"):
                last_use[id(v)] = i
    n_eqns = len(body.eqns)
    for v in body.outvars:
        if hasattr(v, "aval"):
            last_use[id(v)] = n_eqns

    alive: dict = {}
    peak = 0
    for i, eqn in enumerate(body.eqns):
        for v in eqn.outvars:
            if not hasattr(v, "aval") or id(v) in ref_ids:
                continue
            if "Ref" in type(v.aval).__name__:
                continue
            # dead-on-arrival results (e.g. swap's unused old value)
            # are never materialized — only future-used values count
            if last_use.get(id(v), -1) > i:
                alive[id(v)] = _aval_nbytes(v.aval)
        peak = max(peak, sum(alive.values()))
        alive = {k: b for k, b in alive.items() if last_use.get(k, -1) > i}
    return int(peak)


@dataclasses.dataclass
class ResourceSheet:
    """The static per-kernel cost sheet (see module docstring for the
    accounting conventions behind each figure)."""
    kernel: str
    label: str
    file: str
    line: int
    grid: tuple
    steps: int
    block_bytes: int            # input+output blocks, one grid step
    scratch_bytes: int
    intermediate_bytes: int     # body peak liveness (accumulators)
    vmem_bytes: int             # single-buffered residency (PK200)
    vmem_pipelined_bytes: int   # with the pipeline's double buffering
    vmem_budget: int
    fits_vmem: bool
    flops: float
    hbm_bytes: int
    arithmetic_intensity: float
    notes: list

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["grid"] = list(self.grid)
        return d


def resource_sheet(m: KernelModel, vmem_budget: int) -> ResourceSheet:
    notes: list = []
    block_bytes = sum(b.block_bytes for b in m.inputs + m.outputs)
    scratch_bytes = sum(_aval_nbytes(a) for a in m.scratch_avals)
    inter_bytes = body_intermediate_bytes(m.body)
    vmem = block_bytes + scratch_bytes + inter_bytes
    vmem_pipe = 2 * block_bytes + scratch_bytes + inter_bytes

    flops = body_flops(m.body) * m.steps

    hbm = 0
    if m.enumerable:
        steps = list(m.grid_steps())
        for b in m.inputs + m.outputs:
            idxs = set()
            ok = True
            for s in steps:
                idx = b.eval_index(s)
                if idx is None:
                    ok = False
                    break
                idxs.add(idx)
            if ok:
                hbm += len(idxs) * b.block_bytes
            else:
                hbm += min(m.steps * b.block_bytes,
                           max(b.array_bytes, b.block_bytes))
                notes.append(f"{b.origin}: index map not host-evaluable; "
                             "HBM term approximated")
    else:
        hbm = sum(m.steps * b.block_bytes for b in m.inputs + m.outputs)
        notes.append(f"grid has {m.steps} steps (> enum cap): HBM bytes "
                     "are the steps x block upper bound")

    return ResourceSheet(
        kernel=m.name, label=m.label, file=m.file, line=m.line,
        grid=m.grid, steps=m.steps,
        block_bytes=block_bytes, scratch_bytes=scratch_bytes,
        intermediate_bytes=inter_bytes,
        vmem_bytes=vmem, vmem_pipelined_bytes=vmem_pipe,
        vmem_budget=int(vmem_budget),
        fits_vmem=vmem <= int(vmem_budget),
        flops=flops, hbm_bytes=int(hbm),
        arithmetic_intensity=round(flops / max(hbm, 1), 3),
        notes=notes)
