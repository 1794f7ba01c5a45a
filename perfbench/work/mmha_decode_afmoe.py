"""Decode attention of the AFMoE layout over what each layer's query can
see, for every decode step inside the traced window: the whole context in a
global layer (`positions` of the step span), `min(context, window)` of it in
a window layer (`positions_window`). Per layer and position two products of
2 x (heads x head_dim) operations, and K and V read once."""

from perfbench.work.afmoe_spans import decode_steps


def count(observed):
    sz, steps = decode_steps(observed)
    if not steps:
        return None
    seen = float(sum(sz["global_layers"] * s["counts"]["positions"]
                     + sz["window_layers"] * s["counts"]["positions_window"]
                     for s in steps))
    return 4.0 * seen * sz["q_width"], \
        seen * sz["kv_bytes_per_position_layer"]
