"""Plan validation: every emitted plan is PROVED, not trusted.

Two checks, in the PR 6 proof style (compiled HLO is the ground truth):

* **collective-count proof** — for each parallel axis the plan uses, a
  minimal probe program exercising that axis's implied collective is
  compiled ON THE TEST MESH (the plan's mesh shape over the local
  devices) and the collectives in the HLO text are counted per
  (op-class, axis-group). The observed count must EQUAL the predicted
  count, and the instances' ``replica_groups`` must be exactly the
  axis's communication groups (:class:`CommunicateTopology` semantics:
  groups vary one axis, fix the others). Op classes absorb backend
  lowering freedom the same way PR 6's proofs do — XLA:CPU lowers
  reduce-scatter as all-reduce(+slice) and may lower all-to-all as
  all-gather(+slice); either is still exactly ONE reshard collective.

* **memory-fit proof** — the plan's predicted per-chip HBM claim must
  fit the topology's budget (the search already filtered on this; the
  validator re-asserts it so a hand-edited/deserialized plan cannot
  smuggle an OOM config past the gate).

Probes (each compiled with ``jax.jit`` + ``NamedSharding`` avals, no
device execution):

=========  =====================================================  ========
axis       probe program                                          predicts
=========  =====================================================  ========
mp         Megatron pair: x @ W_col -> constraint -> @ W_row      1 all-reduce
dp         grad of sum((x_dp @ W)^2) wrt replicated W             1 all-reduce
sharding   forward gather of a dim-0-sharded param (ZeRO-3)       1 all-gather
sharding   grad wrt a dim-0-sharded param, batch sharded          1 grad-reduce
sep        reshard [b,s,h,d] seq-shard -> head-shard (Ulysses)    1 reshard
pp         shard_map ppermute ring over the pp axis               1 permute
=========  =====================================================  ========
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

import numpy as np

from .plan import Plan
from .topology import MESH_AXES

__all__ = ["validate_plan", "ValidationReport", "count_hlo_collectives",
           "axis_groups"]

#: op equivalence classes: predicted op -> the HLO op names that satisfy it
OP_CLASSES = {
    "all-reduce": ("all-reduce",),
    "all-gather": ("all-gather",),
    # XLA:CPU lowers reduce-scatter as all-reduce + slice
    "grad-reduce": ("reduce-scatter", "all-reduce"),
    # some lowerings use all-gather (+ local slice) for a reshard
    "reshard": ("all-to-all", "all-gather"),
    "permute": ("collective-permute",),
}

_COLLECTIVE_OPS = ("all-reduce", "all-gather", "all-to-all",
                   "reduce-scatter", "collective-permute")
# opcode occurrences only: `all-reduce(`, not the instruction NAME
# (`%all-reduce.1 = ...`, excluded by the lookbehind) and not metadata
# op_names (underscored). Async pairs count once: -start is the
# instance, -done the completion marker. Tuple-typed instructions print
# `/*index=N*/` comments inside the result type, so the opcode cannot be
# anchored on the `=` sign.
_DEF_RE = re.compile(
    r"(?<!%)\b(" + "|".join(_COLLECTIVE_OPS) + r")(-start|-done)?\(")
_GROUPS_ATTR_RE = re.compile(
    r"(replica_groups|source_target_pairs)=(\{\{[^}]*(?:\},\{[^}]*)*\}\}"
    r"|\{[0-9, ]*\}|\[[0-9,]+\]<=\[[0-9,]+\](?:T\([0-9,]+\))?)")


def axis_groups(dims: dict, axis: str) -> frozenset:
    """Communication groups along ``axis`` for a mesh with ``dims`` laid
    out in MESH_AXES order, as a frozenset of device-id tuples — the
    same groups ``CommunicateTopology.get_comm_list`` derives."""
    shape = tuple(int(dims.get(a, 1)) for a in MESH_AXES)
    grid = np.arange(int(np.prod(shape))).reshape(shape)
    ax = MESH_AXES.index(axis)
    moved = np.moveaxis(grid, ax, -1).reshape(-1, shape[ax])
    return frozenset(tuple(int(r) for r in row) for row in moved)


def _parse_groups(attr: str):
    """``replica_groups`` / ``source_target_pairs`` text -> frozenset of
    tuples. Handles the explicit ``{{0,1},{2,3}}`` form and the iota form
    ``[G,S]<=[A,B]T(perm)``."""
    attr = attr.strip()
    if attr.startswith("{"):
        rows = re.findall(r"\{([0-9,\s]+)\}", attr)
        if not rows and attr != "{}":
            inner = attr.strip("{}").strip()
            rows = [inner] if inner else []
        return frozenset(
            tuple(int(x) for x in row.replace(" ", "").split(",") if x)
            for row in rows)
    m = re.match(r"\[([0-9,]+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?", attr)
    if not m:
        return frozenset()
    dst = [int(x) for x in m.group(1).split(",")]
    src = [int(x) for x in m.group(2).split(",")]
    arr = np.arange(int(np.prod(src))).reshape(src)
    if m.group(3):
        arr = arr.transpose([int(x) for x in m.group(3).split(",")])
    arr = arr.reshape(dst)
    return frozenset(tuple(int(x) for x in row) for row in arr)


def count_hlo_collectives(hlo_text: str):
    """[(op_name, groups_frozenset), ...] — one entry per defining
    collective instruction in the HLO module text."""
    out = []
    for line in hlo_text.splitlines():
        m = _DEF_RE.search(line)
        if not m or m.group(2) == "-done":
            continue
        gm = _GROUPS_ATTR_RE.search(line)
        out.append((m.group(1),
                    _parse_groups(gm.group(2)) if gm else frozenset()))
    return out


def _groups_match(observed: frozenset, expected: frozenset,
                  op: str) -> bool:
    if not observed:
        # a missing replica_groups attr means "all devices": accept only
        # when the axis group IS the whole mesh
        return len(expected) == 1
    if op == "collective-permute":
        # source_target_pairs: every (src, dst) must stay inside one
        # expected axis group
        return all(any(s in g and d in g for g in expected)
                   for s, d in observed)
    return observed == expected


# ---------------------------------------------------------------------------
# probes
# ---------------------------------------------------------------------------

def _build_mesh(dims: dict, devices=None):
    import jax
    from jax.sharding import Mesh
    devices = list(devices if devices is not None else jax.devices())
    shape = tuple(int(dims.get(a, 1)) for a in MESH_AXES)
    world = int(np.prod(shape))
    if world > len(devices):
        raise ValueError(
            f"plan world {world} exceeds the {len(devices)} local "
            f"devices; validate on a matching test mesh (e.g. "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={world}) "
            f"or validate a same-shaped smaller plan")
    return Mesh(np.array(devices[:world]).reshape(shape), MESH_AXES)


def _compile_text(f, in_specs, out_spec, avals, mesh):
    import jax
    from jax.sharding import NamedSharding

    ns = [NamedSharding(mesh, s) for s in in_specs]
    out = NamedSharding(mesh, out_spec)
    return jax.jit(f, in_shardings=tuple(ns), out_shardings=out) \
        .lower(*avals).compile().as_text()


def _probe_mp(mesh, dims):
    """Column-parallel then row-parallel matmul: the partial sums the
    row contraction produces force exactly one all-reduce over mp."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    h = 16

    def f(x, w1, w2):
        y = jax.lax.with_sharding_constraint(
            x @ w1, NamedSharding(mesh, P(None, "mp")))
        return y @ w2

    avals = [jax.ShapeDtypeStruct((8, h), jnp.float32),
             jax.ShapeDtypeStruct((h, 4 * h), jnp.float32),
             jax.ShapeDtypeStruct((4 * h, h), jnp.float32)]
    txt = _compile_text(f, [P(), P(None, "mp"), P("mp", None)], P(),
                        avals, mesh)
    return txt, [("all-reduce", "mp", 1)]


def _probe_dp(mesh, dims):
    """Weight grad with the batch sharded over dp: the contraction over
    the sharded batch dim yields partials -> one all-reduce over dp."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    h = 16

    def f(x, w):
        return jax.grad(lambda w_: ((x @ w_) ** 2).sum())(w)

    avals = [jax.ShapeDtypeStruct((8, h), jnp.float32),
             jax.ShapeDtypeStruct((h, h), jnp.float32)]
    txt = _compile_text(f, [P("dp", None), P()], P(), avals, mesh)
    return txt, [("all-reduce", "dp", 1)]


def _probe_sharding_gather(mesh, dims):
    """ZeRO-3 forward: a dim-0-sharded parameter materialized replicated
    before use costs exactly one all-gather over the sharding axis. The
    replicated constraint pins the ZeRO semantics — without it GSPMD may
    legally prefer a partial-sum contraction (all-reduce) instead."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    h = 16

    def f(x, w):
        w_full = jax.lax.with_sharding_constraint(
            w, NamedSharding(mesh, P(None, None)))
        return x @ w_full

    avals = [jax.ShapeDtypeStruct((8, h), jnp.float32),
             jax.ShapeDtypeStruct((h, h), jnp.float32)]
    txt = _compile_text(f, [P(), P("sharding", None)], P(), avals, mesh)
    return txt, [("all-gather", "sharding", 1)]


def _probe_sharding_reduce(mesh, dims):
    """ZeRO-3 backward: batch sharded over the sharding axis, grad
    emitted in the param's dim-0 shards -> one reduce-scatter (XLA:CPU:
    all-reduce + slice — still one grad-reduce)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    h = 16

    def f(x, w):
        return jax.grad(lambda w_: ((x @ w_) ** 2).sum())(w)

    avals = [jax.ShapeDtypeStruct((8, h), jnp.float32),
             jax.ShapeDtypeStruct((h, h), jnp.float32)]
    txt = _compile_text(f, [P("sharding", None), P()],
                        P("sharding", None), avals, mesh)
    # the all-reduce+slice lowering renumbers shards with a
    # collective-permute — data movement inside the lowering, not an
    # extra reduction: allowed as a companion, never counted
    return txt, [("grad-reduce", "sharding", 1, ("collective-permute",))]


def _probe_sep(mesh, dims):
    """Ulysses boundary: reshard [b, s, heads, d] from seq-sharded to
    head-sharded over sep — one all-to-all (or its all-gather lowering)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    sep = int(dims.get("sep", 1))
    heads = 2 * sep

    def f(x):
        return jax.lax.with_sharding_constraint(
            x * 1.0, NamedSharding(mesh, P(None, None, "sep", None)))

    avals = [jax.ShapeDtypeStruct((2, 4 * sep, heads, 8), jnp.float32)]
    txt = _compile_text(f, [P(None, "sep", None, None)],
                        P(None, None, "sep", None), avals, mesh)
    return txt, [("reshard", "sep", 1)]


def _probe_pp(mesh, dims):
    """Pipeline boundary: a ppermute ring over pp — one
    collective-permute whose source-target pairs stay inside pp groups."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    pp = int(dims.get("pp", 1))
    from jax import shard_map

    perm = [(i, (i + 1) % pp) for i in range(pp)]

    def f(x):
        return shard_map(
            lambda t: jax.lax.ppermute(t, "pp", perm),
            mesh=mesh, in_specs=P("pp"), out_specs=P("pp"))(x)

    avals = [jax.ShapeDtypeStruct((8 * pp,), jnp.float32)]
    txt = _compile_text(f, [P("pp")], P("pp"), avals, mesh)
    return txt, [("permute", "pp", 1)]


_PROBES = (
    ("mp", "megatron-pair", _probe_mp),
    ("dp", "grad-allreduce", _probe_dp),
    ("sharding", "zero3-param-gather", _probe_sharding_gather),
    ("sharding", "zero3-grad-reduce", _probe_sharding_reduce),
    ("sep", "ulysses-reshard", _probe_sep),
    ("pp", "pipeline-permute", _probe_pp),
)


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

@dataclass
class ValidationReport:
    checks: list = field(default_factory=list)
    memory_ok: bool = True
    memory_detail: str = ""

    @property
    def ok(self) -> bool:
        return self.memory_ok and all(c["ok"] for c in self.checks)

    def failures(self) -> list:
        out = [c for c in self.checks if not c["ok"]]
        if not self.memory_ok:
            out.append({"probe": "memory-fit", "ok": False,
                        "detail": self.memory_detail})
        return out

    def to_dict(self) -> dict:
        return {"ok": self.ok, "memory_ok": self.memory_ok,
                "memory_detail": self.memory_detail,
                "checks": list(self.checks)}


def validate_plan(plan: Plan, devices=None) -> ValidationReport:
    """Prove a plan on the local test mesh. Compiles one probe per used
    parallel axis and counts collectives per (op-class, axis-group)
    against the prediction; re-asserts the memory-fit. Increments
    ``paddle_tpu_planner_validations_total{result=}``."""
    from .search import HBM_UTIL

    report = ValidationReport()
    dims = {a: plan.degree(a) for a in MESH_AXES}

    # memory-fit re-assertion (deserialized plans can't smuggle an OOM).
    # A bare probe plan (no topology, no predictions) has nothing to
    # verify; a plan that DOES carry either side but is missing the
    # other must FAIL — stripping the predicted block is exactly the
    # smuggling path this check closes.
    budget = plan.topology.get("hbm_bytes", 0)
    claimed = plan.predicted.get("per_chip_hbm_bytes", 0)
    if not plan.topology and not plan.predicted:
        report.memory_detail = "no memory claim (bare plan)"
    elif not (budget and claimed):
        report.memory_ok = False
        report.memory_detail = (
            f"unverifiable memory claim: per_chip_hbm_bytes={claimed!r}, "
            f"topology hbm_bytes={budget!r} (both required)")
    else:
        limit = budget * HBM_UTIL
        report.memory_ok = claimed <= limit
        report.memory_detail = (
            f"per-chip claim {claimed} vs budget {int(limit)} "
            f"({'fits' if report.memory_ok else 'DOES NOT FIT'})")

    active = [(axis, name, probe) for axis, name, probe in _PROBES
              if dims.get(axis, 1) > 1]
    if active:
        mesh = _build_mesh(dims, devices)
        for axis, name, probe in active:
            txt, expectations = probe(mesh, dims)
            found = count_hlo_collectives(txt)
            for exp in expectations:
                op_class, exp_axis, exp_count = exp[:3]
                allowed = exp[3] if len(exp) > 3 else ()
                accepted = OP_CLASSES[op_class]
                expected_groups = axis_groups(dims, exp_axis)
                hits = [
                    (op, g) for op, g in found
                    if op in accepted and
                    _groups_match(g, expected_groups, op)]
                # every collective in the probe must be accounted for:
                # extra instances on OTHER axes/ops are a model miss too
                # (minus declared lowering companions)
                extras = [(op, sorted(map(list, g))) for op, g in found
                          if (op, g) not in hits and op not in allowed]
                ok = len(hits) == exp_count and not extras
                report.checks.append({
                    "probe": name, "axis": exp_axis, "op": op_class,
                    "predicted": exp_count, "observed": len(hits),
                    "unexpected": extras, "ok": ok})

    from ..observability import metrics as m
    m.counter("paddle_tpu_planner_validations_total",
              "plan validations by result").inc(
        result="ok" if report.ok else "mismatch")
    return report
