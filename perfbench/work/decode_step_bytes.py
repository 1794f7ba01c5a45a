"""Bytes a decode step has to move: every weight once and the live KV
context once, for each decode step inside the traced window. The positions
the program gathers beyond the live context are its own and are not counted,
so the gap shows."""

from perfbench.work.mmha_decode import calls_in_trace


def count(observed):
    calls, sv = calls_in_trace(observed), observed.get("serve")
    if not calls or not sv:
        return None
    ctx = float(sum(c[3] for c in calls))
    nbytes = len(calls) * float(sv["weight_bytes"]) \
        + ctx * sv["kv_bytes_per_position"]
    return 0.0, nbytes
