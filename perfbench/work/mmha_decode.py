"""Decode attention over the live context only, of every decode step whose
call fell inside the traced window.

One new token per live row attends to the ctx positions cached for it: per
layer 2 products of 2 ctx (heads x head_dim) operations, and every cached K
and V position read once (`kv_bytes_per_position` covers K and V of all
layers). The steps and their live context are the benchmark's own spans round
`engine.decode` (host clock), cut to the traced interval.
"""


def calls_in_trace(observed):
    calls, iv = observed.get("decode_calls"), observed.get("trace_interval")
    if not calls or not iv or iv[0] is None or iv[1] is None:
        return []
    return [c for c in calls if iv[0] <= c[0] and c[1] <= iv[1]]


def count(observed):
    calls, sv = calls_in_trace(observed), observed.get("serve")
    if not calls or not sv:
        return None
    ctx = float(sum(c[3] for c in calls))
    flops = 4.0 * ctx * sv["hidden"] * sv["layers"]
    return flops, ctx * sv["kv_bytes_per_position"]
