"""Fusion-boundary model: which op edges cost an HBM round trip.

A deliberately small model of XLA's loop fusion ("Operator Fusion in XLA:
Analysis and Evaluation" — boundaries, not schedules, decide HBM traffic):

* **elementwise / layout / RNG-hash / sharding-constraint** ops fuse with
  their producers and consumers (one loop, intermediates stay in
  registers/VMEM);
* a **reduce** fuses its *producers* (it is a fusion root) but its output
  materializes: consumers start a new fusion group — this is why an
  unfused layernorm reads its input twice;
* **matmul / conv, gather/scatter, collectives, transfers, control flow,
  pallas_call** are fusion breakers: their operands and results live in
  HBM by contract.

Groups are computed by union-find over fusible def-use edges in program
order. Every edge that crosses a group boundary is an HBM round trip
(producer writes, consumer re-reads). A **fusion candidate** is a cluster
of adjacent *kernelizable* regions — fusible groups, pallas kernels, AND
matmuls: XLA loop fusion stops at the MXU, but a hand-written mega-kernel
(flash attention being the canonical example) streams through it, which
is exactly the ROADMAP item-2 opportunity the candidate list ranks.
Fusing a cluster into one VMEM-resident pass (guides: VMEM ~16 MB/core)
saves a write+read per internal crossing value. Candidates are named from
the op patterns they contain (attention, softmax, layernorm, dropout-add,
gelu, ...) so the bench's top-3 list reads as kernel work items.
"""

from __future__ import annotations

from dataclasses import dataclass, field

from .ir import (DataflowGraph, KIND_ELEMENTWISE, KIND_LAYOUT, KIND_MATMUL,
                 KIND_PALLAS, KIND_REDUCE, KIND_RNG, KIND_SHARDING,
                 aval_bytes)

__all__ = ["FusionGroup", "FusionCandidate", "fusion_groups",
           "fusion_candidates", "boundary_edges", "is_mega_kernel",
           "MEGA_KERNEL_MARKERS"]

_FUSE_THROUGH = {KIND_ELEMENTWISE, KIND_LAYOUT, KIND_RNG, KIND_SHARDING}
_FUSIBLE_NODE = _FUSE_THROUGH | {KIND_REDUCE}

#: pallas kernel-name markers of hand-written mega-kernels
#: (ops/kernels/block_fused_pallas.py names its calls ``block_*_epilogue``).
#: A candidate containing one of these regions is already HARVESTED: the
#: epilogue chain it advertises runs as a single VMEM-resident pass, so it
#: must stop advertising saved bytes in GA100's ranking and instead carry
#: ``fused: true`` in the fusion_targets table.
MEGA_KERNEL_MARKERS = ("block_attn_epilogue", "block_mlp_epilogue",
                       "block_decode_epilogue")


def is_mega_kernel(name) -> bool:
    """True when a pallas_call name identifies a block mega-kernel."""
    n = str(name or "")
    return any(m in n for m in MEGA_KERNEL_MARKERS)


@dataclass
class FusionGroup:
    gid: int
    nodes: list = field(default_factory=list)
    kind: str = "fused"          # "fused" | "breaker"
    label: str = ""
    has_reduce: bool = False

    @property
    def first(self):
        return self.nodes[0]

    def prims(self) -> set:
        return {n.prim for n in self.nodes}


@dataclass
class FusionCandidate:
    name: str
    saved_bytes: int
    groups: list = field(default_factory=list)
    n_ops: int = 0
    file: str = ""
    line: int = 0
    fused: bool = False   # a region is already a block mega-kernel

    def to_dict(self) -> dict:
        return {"name": self.name, "saved_bytes": int(self.saved_bytes),
                "n_ops": int(self.n_ops), "n_regions": len(self.groups),
                "span": f"{self.file}:{self.line}" if self.file else "",
                "fused": bool(self.fused)}


class _UnionFind:
    def __init__(self):
        self.parent: dict = {}

    def find(self, x):
        p = self.parent.setdefault(x, x)
        while p != x:
            self.parent[x] = p = self.parent.setdefault(p, p)
            x, p = p, self.parent[p]
        return x

    def union(self, a, b):
        ra, rb = self.find(a), self.find(b)
        if ra != rb:
            self.parent[max(ra, rb)] = min(ra, rb)


def fusion_groups(g: DataflowGraph) -> tuple[list[FusionGroup], dict]:
    """(groups, node_index -> FusionGroup) under the model above."""
    uf = _UnionFind()
    for node in g.nodes:
        if node.kind not in _FUSIBLE_NODE:
            continue
        for v in node.invars:
            p = g.producer_of(v)
            if p is None:
                continue
            # producer-side fusion: reduce outputs materialize, so edges
            # OUT of a reduce (or out of any non-fusible node) break
            if p.kind in _FUSE_THROUGH:
                uf.union(p.index, node.index)

    by_root: dict = {}
    node_group: dict = {}
    groups: list[FusionGroup] = []
    for node in g.nodes:
        if node.kind in _FUSIBLE_NODE:
            root = uf.find(node.index)
            grp = by_root.get(root)
            if grp is None:
                grp = FusionGroup(gid=len(groups), kind="fused")
                by_root[root] = grp
                groups.append(grp)
        else:
            grp = FusionGroup(gid=len(groups), kind="breaker")
            groups.append(grp)
        grp.nodes.append(node)
        grp.has_reduce |= node.kind == KIND_REDUCE
        node_group[node.index] = grp
    for grp in groups:
        grp.label = _label_group(grp)
    return groups, node_group


# -- naming ----------------------------------------------------------------

def _label_group(grp: FusionGroup) -> str:
    if grp.kind == "breaker":
        n = grp.first
        if n.kind == KIND_PALLAS:
            return n.name or "pallas-kernel"
        if n.prim == "dot_general":
            return "matmul"
        return n.prim
    prims = grp.prims()
    lbl = _pattern_name(prims)
    if lbl:
        return lbl
    n_compute = sum(1 for n in grp.nodes
                    if n.kind in (KIND_ELEMENTWISE, KIND_REDUCE))
    return f"elementwise×{max(n_compute, 1)}"


def _pattern_name(prims: set) -> str | None:
    """Kernel-vocabulary name for a prim set (region or whole candidate)."""
    has_rng = bool(prims & {"threefry2x32", "random_bits",
                            "rng_bit_generator"})
    reduce_like = bool(prims & {"reduce_sum", "reduce_max"})
    if "exp" in prims and reduce_like:
        if "dot_general" in prims:
            return "attention"    # QK^T -> softmax -> @V, flash-style
        return "softmax"
    if "rsqrt" in prims and "mul" in prims:
        if "reduce_sum" in prims and "sub" not in prims:
            return "rmsnorm"
        return "layernorm" if ("sub" in prims or "reduce_sum" in prims) \
            else "norm-apply"
    if has_rng and ("add" in prims or "add_any" in prims):
        return "dropout-add"
    if has_rng:
        return "dropout"
    if "erf" in prims or ("tanh" in prims and
                          prims & {"pow", "integer_pow"}):
        return "gelu"
    if "logistic" in prims:
        return "silu"
    if prims & {"reduce_sum", "reduce_max", "reduce_min"}:
        return None
    return None


def _pallas_hint(chain: list[FusionGroup]) -> str | None:
    """Pattern name recovered from pallas kernel names in the chain (a
    pallas body is opaque — its prims never reach _pattern_name, but the
    kernel NAME says what it computes). Attention first: the flash /
    mmha / attn-epilogue cluster is the table's headline row."""
    names = [str(grp.first.name or "") for grp in chain
             if grp.kind == "breaker" and grp.first.kind == KIND_PALLAS]
    joined = " ".join(names)
    if any(k in joined for k in ("attn", "mmha", "flash")):
        return "attention"
    if "mlp_epilogue" in joined:
        return "mlp-epilogue"
    if "decode_epilogue" in joined:
        return "decode-epilogue"
    return None


def _candidate_name(chain: list[FusionGroup]) -> str:
    merged: set = set()
    for grp in chain:
        merged |= grp.prims()
    whole = _pattern_name(merged) or _pallas_hint(chain)
    labels: list[str] = []
    for grp in chain:
        if not labels or labels[-1] != grp.label:
            labels.append(grp.label)
    if whole and len(set(labels)) > 1:
        return whole
    if len(labels) > 4:
        labels = labels[:4] + [f"+{len(labels) - 4} more"]
    return "→".join(labels)


# -- boundaries and candidates ---------------------------------------------

def boundary_edges(g: DataflowGraph, node_group: dict):
    """Yield (producer_node, consumer_node, var, bytes) for every def-use
    edge that crosses a fusion-group boundary — each is one HBM round
    trip (write + re-read) in the unfused program."""
    seen = set()
    for node in g.nodes:
        for v in node.invars:
            p = g.producer_of(v)
            if p is None:
                continue
            gp, gc = node_group[p.index], node_group[node.index]
            if gp.gid == gc.gid:
                continue
            key = (id(v), gc.gid)
            if key in seen:   # one read per consumer group
                continue
            seen.add(key)
            yield p, node, v, aval_bytes(v.aval)


def fusion_candidates(g: DataflowGraph, groups, node_group,
                      min_bytes: int = 1, top: int | None = None,
                      max_regions: int = 4) -> list[FusionCandidate]:
    """Clusters of adjacent kernelizable regions, ranked by HBM bytes a
    VMEM-resident fused pass would save (2x every internal crossing:
    the producer's write and the consumer's re-read both disappear).

    Greedy agglomerative merge, hottest boundary first, capped at
    ``max_regions`` regions per candidate: in a transformer every fused
    region connects to the next through a reduce boundary, so the
    transitive closure is the whole model — useless as a kernel work
    item. The cap keeps candidates local (attention→dropout-add→norm
    sized), which is the shape a Pallas mega-kernel can actually take.
    """
    kernelizable = {grp.gid for grp in groups
                    if grp.kind == "fused" or
                    grp.first.kind in (KIND_PALLAS, KIND_MATMUL)}
    saved: dict = {}
    for p, c, v, nbytes in boundary_edges(g, node_group):
        gp, gc = node_group[p.index].gid, node_group[c.index].gid
        if gp in kernelizable and gc in kernelizable:
            key = (min(gp, gc), max(gp, gc))
            saved[key] = saved.get(key, 0) + 2 * nbytes

    cluster: dict = {gid: {gid} for k in saved for gid in k}
    # hottest edge first; program order (gid) breaks ties deterministically
    for (a, b), nbytes in sorted(saved.items(),
                                 key=lambda kv: (-kv[1], kv[0])):
        ca, cb = cluster[a], cluster[b]
        if ca is cb or len(ca) + len(cb) > max_regions:
            continue
        ca |= cb
        for gid in cb:
            cluster[gid] = ca

    out: list[FusionCandidate] = []
    seen: set = set()
    for comp_set in cluster.values():
        if id(comp_set) in seen or len(comp_set) < 2:
            continue
        seen.add(id(comp_set))
        comp = sorted(comp_set)
        chain = [groups[i] for i in comp]
        total = sum(b for (a, c2), b in saved.items()
                    if a in comp_set and c2 in comp_set)
        if total < min_bytes:
            continue
        first = chain[0].first
        out.append(FusionCandidate(
            name=_candidate_name(chain), saved_bytes=total, groups=chain,
            n_ops=sum(len(grp.nodes) for grp in chain),
            file=first.file, line=first.line,
            fused=any(grp.kind == "breaker"
                      and grp.first.kind == KIND_PALLAS
                      and is_mega_kernel(grp.first.name)
                      for grp in chain)))
    out.sort(key=lambda c: (-c.saved_bytes, c.file, c.line))
    return out[:top] if top else out
