"""Bytes a decode step of the AFMoE layout has to read, for each decode step
inside the traced window: the experts really hit (`experts_hit` x one
expert's three matrices), every other weight once (attention of every layer,
the dense MLPs, the shared experts, the routers, the output head), and the
live cache: the whole context in the global layers, what lies inside the
window in the window layers. All in bf16. What the program reads beyond that
(idle slots, positions behind the window, a pool copied whole) is its own and
is not counted, so the gap shows."""

from perfbench.work.afmoe_spans import decode_steps


def count(observed):
    sz, steps = decode_steps(observed)
    if not steps:
        return None
    fixed = 2.0 * (sz["layers"] * sz["attention"]
                   + sz["dense_layers"] * sz["dense_mlp"]
                   + sz["routed_layers"] * (sz["shared"] + sz["router"])
                   + sz["head"])
    nbytes = 0.0
    for s in steps:
        c = s["counts"]
        nbytes += fixed + 2.0 * c["experts_hit"] * sz["expert"] \
            + sz["kv_bytes_per_position_layer"] * (
                sz["global_layers"] * c["positions"]
                + sz["window_layers"] * c["positions_window"])
    return 0.0, nbytes
