"""Share of one program's device time, in percent, taken by the leaf
operations whose `op_name` matches one of `patterns`: `device_time_share`
cut to the programs (`XLA Modules` events) whose name matches `program`,
over the device time of those programs' own operations.

The whole trace's share reads nothing where programs of one name disagree
about an instruction (the prefill's buckets each name theirs in their own
way). A program with one signature, the decode step, can still be read, and
this reads it alone. None where the program keeps no tables, where nothing
of the program ran or nothing matches, and where any operation of the
program is undecided.
"""

import re

from perfbench.harness import trace
from perfbench.readers import device_time_share


def read(observed, patterns, program, tables=None):
    red = observed.get("trace")
    if not red or not red.get("ops"):
        return None
    tables = tables if tables is not None else device_time_share._tables()
    if not tables:
        return None
    regs = [re.compile(p) for p in patterns]
    prog = re.compile(program)
    inside, matched = [], []
    for s, e, module, ops in device_time_share.op_names(red, tables):
        if module is None or not prog.search(module):
            continue
        hits = None if ops is None else \
            {any(r.search(op) for r in regs) for op in ops}
        if hits is None or len(hits) > 1:
            return None         # undecided: a share would read low
        inside.append((s, e))
        if True in hits:
            matched.append((s, e))
    seconds = sum(e - s for s, e in trace.union(matched))
    if seconds <= 0.0:
        return None
    return 100.0 * seconds / sum(e - s for s, e in trace.union(inside))
