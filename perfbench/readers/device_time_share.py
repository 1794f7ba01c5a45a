"""Share of the device's busy time, in percent, taken by the leaf operations
that the code under a name made: those whose `op_name` (the jit and
`jax.named_scope` path of the code that made them, e.g.
`jit(pure_arrays__serving_decode_step)/kv_gather/gather`) matches one of
`patterns`.

A device trace names a leaf operation by its instruction alone. The program
keeps, for each program it compiled with tracing on, the table from
instruction to `op_name` (`paddle_tpu.observability.tracing.programs`); an
operation is looked up in the tables of the program (`XLA Modules` event) it
ran inside. Programs of one name (a signature each: the prefill's buckets)
name their instructions each in its own way, and the trace does not say which
of them ran: an operation counts as matched where every table that has its
instruction says so, as not matched where none does, and is undecided where
they differ or where the tracer has dropped tables of that name. Operations
the compiler made itself carry no `op_name` and match nothing. Matched time
is the union of the matched intervals, over `busy_s`.

None, never 0, where the program keeps no such table (a parent commit) or
nothing matches; and None, not a share that reads low, where any operation
is undecided.
"""

import bisect
import re

from perfbench.harness import trace


def _tables():
    try:
        from paddle_tpu.observability import tracing
        return tracing.programs()
    except (ImportError, AttributeError):
        return None


def op_names(red, tables):
    """[(start, end, program or None, op_names)] of the leaf operations of a
    reduced trace, each with the program (`XLA Modules` event, its id
    dropped) it ran inside and the `op_name`s that program's tables give its
    instruction: none for an operation the compiler made, one where the
    tables agree, several where they differ; None where tables of that name
    were dropped, so that those left may not cover what ran."""
    mods = sorted(red.get("modules") or [])
    starts = [m[0] for m in mods]
    merged = {}
    for module, entry in tables.items():
        if entry["dropped"]:
            merged[module] = None
            continue
        names = merged[module] = {}
        for table in entry["variants"]:
            for inst, op in table.items():
                names.setdefault(inst, set()).add(op)
    out = []
    for s, e, label in red.get("ops") or []:
        i = bisect.bisect_right(starts, s) - 1
        module, ops = None, frozenset()
        if i >= 0 and s < mods[i][1]:
            module = re.sub(r"\(\d+\)$", "", mods[i][2].split(" | ")[0])
            names = merged.get(module, {})
            ops = None if names is None else \
                frozenset(names.get(label.split(" | ")[0], ()))
        out.append((s, e, module, ops))
    return out


def read(observed, patterns, tables=None):
    red = observed.get("trace")
    if not red or not red.get("busy_s") or not red.get("ops"):
        return None
    tables = tables if tables is not None else _tables()
    if not tables:
        return None
    regs = [re.compile(p) for p in patterns]
    matched = []
    for s, e, _, ops in op_names(red, tables):
        hits = None if ops is None else \
            {any(r.search(op) for r in regs) for op in ops}
        if hits is None or len(hits) > 1:
            return None         # undecided: a share would read low
        if True in hits:
            matched.append((s, e))
    seconds = sum(e - s for s, e in trace.union(matched))
    if seconds <= 0.0:
        return None
    return 100.0 * seconds / red["busy_s"]
