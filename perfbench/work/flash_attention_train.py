"""Causal self-attention, forward and backward, of every layer of every
training step inside the traced window.

Per layer and step, with b rows of t tokens, h heads of width d: the forward
is two matrix products (Q K^T, P V) and the backward four (dV, dP, dQ, dK);
recomputing P in the backward is the implementation's choice and is not
counted. Each product is 2 b h t^2 d operations, halved by the causal mask.
Bytes: q, k, v read and o written forward (4 tensors); q, k, v, o, do read
and dq, dk, dv written backward (8 tensors), b t h d elements of 2 bytes each.
Steps are the executions of the program matching `step_module` in the trace
(the driver syncs before the trace starts and before it stops, so each is
whole).
"""

from perfbench.harness import trace


def count(observed, step_module):
    red, tr = observed.get("trace"), observed.get("train")
    if not red or not tr:
        return None
    _, steps = trace.time_by_pattern(red["modules"], [step_module])
    if steps == 0:
        return None
    b, t, h, d, layers = (tr["batch"], tr["seq"], tr["heads"],
                          tr["head_dim"], tr["layers"])
    flops = 6 * (2.0 * b * h * t * t * d) / 2 * layers * steps
    nbytes = 12 * (b * t * h * d * 2.0) * layers * steps
    return flops, nbytes
