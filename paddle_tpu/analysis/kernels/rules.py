"""PK200-PK209: the Pallas kernel safety rules.

Two planes share the rule table. The MODEL plane (PK200-PK204,
PK207-PK209) runs on :class:`~.model.KernelModel`s — concrete grids,
block shapes and evaluable index maps extracted from ``pk_examples()``
traces — so VMEM residency, output coverage/overlap and index bounds are
checked by abstract evaluation over the real grid, not by pattern
matching. The AST plane (PK206) runs on source: a ``pallas_call`` traced
outside the package's ``x64_off()`` discipline (the framework's global
x64 hands Mosaic 64-bit index arithmetic it aborts on) is caught where it
is written, before any jaxpr exists.

Severity policy mirrors the other tiers: ERROR = the kernel is wrong or
will not survive Mosaic (lost writes, garbage output, OOB blocks, VMEM
overflow, 64-bit types reaching Mosaic); WARNING = legal but against the
package's discipline (unmasked tails, bf16 accumulation, dead operands).
"""

from __future__ import annotations

import ast
import itertools
from dataclasses import dataclass

from ..diagnostics import ERROR, INFO, WARNING, Finding
from .model import KernelModel

__all__ = ["Rule", "RULES", "check_model", "check_source"]


@dataclass(frozen=True)
class Rule:
    id: str
    name: str
    severity: str
    summary: str
    hint: str


RULES = {r.id: r for r in [
    Rule("PK200", "vmem-residency-overflow", ERROR,
         "one grid step's blocks + accumulators + scratch exceed the "
         "chip preset's VMEM budget — Mosaic will spill or refuse to "
         "compile",
         "shrink the block shapes (pick_row_block against "
         "chip_vmem_bytes()) or move large carries to scratch refs"),
    Rule("PK201", "output-block-overlap", ERROR,
         "an output block is written at non-consecutive grid steps — "
         "the revisit races the pipeline's write-back and loses one of "
         "the writes (consecutive revisits, the accumulation pattern, "
         "are legal)",
         "reorder the grid so revisits are adjacent (innermost "
         "reduction axis) or give each step its own output block"),
    Rule("PK202", "output-coverage-gap", ERROR,
         "the grid never writes some output block positions — those "
         "regions are returned as uninitialized garbage",
         "make the output index map cover every block (nblocks per dim "
         "= ceil(dim/block)) or shrink out_shape to what is written"),
    Rule("PK203", "index-map-out-of-bounds", ERROR,
         "an index map yields a block index outside the ref's extent "
         "for some grid step — reads wrap/clamp to garbage and writes "
         "corrupt neighbouring blocks",
         "clamp the map (idx % nblocks) or fix the grid so every step "
         "maps inside ceil(dim/block)"),
    Rule("PK204", "unmasked-tail", WARNING,
         "a ref dimension is not block-divisible and the kernel body "
         "shows no masking (iota+compare / select / pl.when) — the "
         "padded tail lanes are read or written unmasked",
         "pad the operand with pad_to_block() at the wrapper (the "
         "package discipline) or mask tail lanes in the body"),
    Rule("PK206", "mosaic-trace-compat", ERROR,
         "a pallas_call traced outside x64_off()/jit_x64_off: the "
         "framework's global x64 turns index-map/loop literals into "
         "64-bit types, and the chip's Mosaic compiler aborts on them",
         "wrap every pallas_call in `with x64_off():` or decorate the "
         "caller with jit_x64_off"),
    Rule("PK207", "vjp-dtype-discipline", WARNING,
         "low-precision accumulation inside the kernel: a dot_general "
         "on bf16/f16 operands without preferred_element_type=float32, "
         "or a reduce_sum carried in bf16 — gradients lose ~8 mantissa "
         "bits per step",
         "accumulate in f32 (preferred_element_type=jnp.float32, or "
         "astype(f32) before the reduce) and cast dx back to the "
         "primal dtype on store"),
    Rule("PK208", "scalar-prefetch-misuse", WARNING,
         "a scalar-prefetch operand no index map and no body equation "
         "ever reads, or a prefetch operand with a non-integer dtype — "
         "prefetch exists to steer blocking, not to smuggle payload",
         "drop the dead prefetch operand (shrinks the SMEM footprint) "
         "or move float payload to a proper SMEM input"),
    Rule("PK209", "kernel-hygiene", WARNING,
         "a dead operand: a scratch ref or input block the body never "
         "touches — every unused input block still costs its HBM->VMEM "
         "DMA on every grid step",
         "remove the operand from the pallas_call (and its BlockSpec) "
         "or use it"),
]}


def _find(rule_id, message, file, line=0, symbol="", severity=None):
    r = RULES[rule_id]
    return Finding(rule_id=rule_id,
                   severity=severity or r.severity,
                   message=message, file=file, line=line,
                   symbol=symbol, hint=r.hint)


# ---------------------------------------------------------------------------
# body-jaxpr helpers
# ---------------------------------------------------------------------------

def _sub_jaxprs(eqn):
    from ..graph.ir import _INLINE_PARAMS
    key = _INLINE_PARAMS.get(eqn.primitive.name)
    if key is not None and key in eqn.params:
        sub = eqn.params[key]
        return [getattr(sub, "jaxpr", sub)]
    out = []
    for p in ("jaxpr", "call_jaxpr", "cond_jaxpr", "body_jaxpr",
              "branches"):
        sub = eqn.params.get(p)
        if sub is None:
            continue
        for s in (sub if isinstance(sub, (tuple, list)) else (sub,)):
            out.append(getattr(s, "jaxpr", s))
    return out


def _walk_jaxprs(jaxpr):
    """Yield ``jaxpr`` and every reachable sub-jaxpr, once each."""
    seen, stack = set(), [jaxpr]
    while stack:
        jx = stack.pop()
        if id(jx) in seen:
            continue
        seen.add(id(jx))
        yield jx
        for eqn in jx.eqns:
            stack.extend(_sub_jaxprs(eqn))


def _used_vars(body):
    """ids of every var read by some equation or returned."""
    used = set()
    for eqn in body.eqns:
        for v in eqn.invars:
            if hasattr(v, "aval"):
                used.add(id(v))
    for v in body.outvars:
        if hasattr(v, "aval"):
            used.add(id(v))
    return used


def _dtype_name(v) -> str:
    import numpy as np
    try:
        return np.dtype(v.aval.dtype).name
    except Exception:
        return ""


def _has_mask_pattern(body) -> bool:
    """True when the body shows any masking idiom: select, pl.when
    (cond), or an iota feeding a comparison."""
    saw_iota = saw_cmp = False
    for jx in _walk_jaxprs(body):
        for eqn in jx.eqns:
            p = eqn.primitive.name
            if p in ("select_n", "select", "cond"):
                return True
            if p in ("iota", "broadcasted_iota"):
                saw_iota = True
            if p in ("lt", "le", "gt", "ge", "eq", "ne"):
                saw_cmp = True
            if saw_iota and saw_cmp:
                return True
    return False


# ---------------------------------------------------------------------------
# the model plane
# ---------------------------------------------------------------------------

def check_model(m: KernelModel, sheet, findings=None) -> list:
    """All model-plane rules over one kernel (sheet supplies the PK200
    residency figures so it is computed once)."""
    out = findings if findings is not None else []
    where = dict(file=m.file, line=m.line, symbol=m.name)

    # PK200 — VMEM residency
    if not sheet.fits_vmem:
        out.append(_find(
            "PK200",
            f"kernel '{m.name}' holds {sheet.vmem_bytes:,} B resident "
            f"per grid step (blocks {sheet.block_bytes:,} + scratch "
            f"{sheet.scratch_bytes:,} + intermediates "
            f"{sheet.intermediate_bytes:,}) > VMEM budget "
            f"{sheet.vmem_budget:,} B", **where))

    # PK201/PK202/PK203 — abstract evaluation over the grid
    if m.enumerable:
        steps = list(m.grid_steps())
        for b in m.inputs + m.outputs:
            seq = []
            for s in steps:
                idx = b.eval_index(s)
                if idx is None:
                    seq = None
                    break
                seq.append(idx)
            if seq is None:
                continue  # data-dependent blocking: not abstractable
            nb = b.nblocks
            oob = next((
                (t, idx) for t, idx in enumerate(seq)
                if any(i < 0 or i >= n
                       for i, n in zip(idx, nb))), None)
            if oob is not None:
                t, idx = oob
                out.append(_find(
                    "PK203",
                    f"kernel '{m.name}': {b.origin or 'operand'} index "
                    f"map yields block {idx} at grid step "
                    f"{steps[t]} but the ref only has {nb} blocks",
                    **where))
                continue
            if not b.is_output:
                continue
            last_at = {}
            overlap = None
            for t, idx in enumerate(seq):
                if idx in last_at and last_at[idx] != t - 1:
                    overlap = (idx, last_at[idx], t)
                last_at[idx] = t
            if overlap:
                idx, t0, t1 = overlap
                out.append(_find(
                    "PK201",
                    f"kernel '{m.name}': output block {idx} written at "
                    f"grid steps {steps[t0]} and {steps[t1]} with other "
                    f"blocks in between — non-consecutive revisit "
                    f"(lost-write race)", **where))
            expected = set(itertools.product(*(range(n) for n in nb)))
            missing = expected - set(seq)
            if missing:
                ex = sorted(missing)[:3]
                out.append(_find(
                    "PK202",
                    f"kernel '{m.name}': grid never writes "
                    f"{len(missing)}/{len(expected)} output block(s) "
                    f"(e.g. {ex}) — uncovered regions are returned as "
                    f"garbage", **where))
    else:
        for b in m.inputs + m.outputs:
            for s in (next(iter(m.grid_steps())),
                      tuple(g - 1 for g in m.grid)):
                idx = b.eval_index(s)
                if idx is not None and any(
                        i < 0 or i >= n
                        for i, n in zip(idx, b.nblocks)):
                    out.append(_find(
                        "PK203",
                        f"kernel '{m.name}': {b.origin or 'operand'} "
                        f"index map yields block {idx} at grid corner "
                        f"{s} but the ref only has {b.nblocks} blocks "
                        f"(grid too large to enumerate fully)", **where))
                    break

    # PK204 — unmasked tails
    tails = [b for b in m.inputs + m.outputs if b.has_tail]
    if tails and not _has_mask_pattern(m.body):
        names = ", ".join(
            f"{b.origin or ('out' if b.is_output else 'in')}"
            f"{tuple(b.array_shape)}%{tuple(b.block_shape)}"
            for b in tails[:3])
        out.append(_find(
            "PK204",
            f"kernel '{m.name}': non-block-divisible dim(s) on {names} "
            f"reach the kernel with no masking in the body — tail "
            f"lanes are processed as garbage", **where))

    # PK207 — low-precision accumulation
    lowp = ("bfloat16", "float16")
    for jx in _walk_jaxprs(m.body):
        for eqn in jx.eqns:
            p = eqn.primitive.name
            if p == "dot_general":
                in_dts = {_dtype_name(v) for v in eqn.invars
                          if hasattr(v, "aval")}
                out_dt = _dtype_name(eqn.outvars[0])
                if in_dts & set(lowp) and out_dt in lowp:
                    out.append(_find(
                        "PK207",
                        f"kernel '{m.name}': dot_general on "
                        f"{sorted(in_dts & set(lowp))[0]} accumulates "
                        f"in {out_dt} (no f32 "
                        f"preferred_element_type)", **where))
            elif p == "reduce_sum":
                if _dtype_name(eqn.outvars[0]) in lowp:
                    out.append(_find(
                        "PK207",
                        f"kernel '{m.name}': reduce_sum carried in "
                        f"{_dtype_name(eqn.outvars[0])} — accumulate "
                        f"in f32 and cast on store", **where))

    # PK208 — scalar-prefetch misuse
    if m.num_scalar_prefetch:
        import numpy as np
        used = _used_vars(m.body)
        for i, (ref, aval) in enumerate(zip(
                m.prefetch_refs,
                m.prefetch_avals + [None] * len(m.prefetch_refs))):
            body_uses = id(ref) in used
            map_uses = False
            for b in m.inputs + m.outputs:
                imj = b.index_map_jaxpr.jaxpr
                n_grid = len(m.grid)
                pref_invars = imj.invars[n_grid:]
                if i < len(pref_invars):
                    v = pref_invars[i]
                    if any(v in eqn.invars for eqn in imj.eqns):
                        map_uses = True
                        break
            if not body_uses and not map_uses:
                out.append(_find(
                    "PK208",
                    f"kernel '{m.name}': scalar-prefetch operand #{i} "
                    f"is read by no index map and no body equation",
                    **where))
            dt = getattr(aval, "dtype", None)
            if dt is not None and not np.issubdtype(np.dtype(dt),
                                                   np.integer):
                out.append(_find(
                    "PK208",
                    f"kernel '{m.name}': scalar-prefetch operand #{i} "
                    f"has dtype {np.dtype(dt).name} — prefetch steers "
                    f"blocking and must be integer", **where))

    # PK209 — dead operands
    used = _used_vars(m.body)
    for i, ref in enumerate(m.scratch_refs):
        if id(ref) not in used:
            out.append(_find(
                "PK209",
                f"kernel '{m.name}': scratch operand #{i} is never "
                f"touched by the body", **where))
    for b, ref in zip(m.inputs, m.input_refs):
        if id(ref) not in used:
            out.append(_find(
                "PK209",
                f"kernel '{m.name}': input block "
                f"'{b.origin or b.position}' is never read — its "
                f"HBM->VMEM DMA still runs every grid step", **where))
    return out


# ---------------------------------------------------------------------------
# the AST plane (PK206)
# ---------------------------------------------------------------------------

def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _decorated_x64(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        for node in ast.walk(dec):
            if isinstance(node, ast.Name) and node.id == "jit_x64_off":
                return True
            if isinstance(node, ast.Attribute) \
                    and node.attr == "jit_x64_off":
                return True
    return False


def _with_x64(stack) -> bool:
    for node in stack:
        if isinstance(node, ast.With):
            for item in node.items:
                ce = item.context_expr
                if isinstance(ce, ast.Call) \
                        and _call_name(ce) == "x64_off":
                    return True
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and _decorated_x64(node):
            return True
    return False


def check_source(source: str, filename: str = "<string>") -> list:
    """The AST plane: PK206 over one module's source."""
    out: list = []
    try:
        tree = ast.parse(source)
    except SyntaxError:
        return out  # the TS tier owns parse errors

    # annotate parents for ancestry walks
    parents: dict = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node

    def ancestry(node):
        stack = []
        while node in parents:
            node = parents[node]
            stack.append(node)
        return stack

    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _call_name(node)
        if name == "pallas_call":
            stack = ancestry(node)
            if not _with_x64(stack):
                enc = next((a.name for a in stack
                            if isinstance(a, ast.FunctionDef)), "")
                out.append(_find(
                    "PK206",
                    "pallas_call traced outside x64_off(): the "
                    "framework's global x64 turns index-map/loop "
                    "literals into i64 types Mosaic aborts on",
                    file=filename, line=node.lineno, symbol=enc))
    out.sort(key=lambda f: f.sort_key())
    return out
