"""The routed experts' two grouped matmuls (gate and up in one, then down)
of every decode step and prefill inside the traced window: the FLOPs of the
rows really routed (each live token to `top_k` experts in each routed
layer), the bytes of the experts really hit (`experts_hit` of the step span:
distinct experts that got a row, summed over the routed layers; 3 matrices of
hidden x width in bf16 each, read once a call whatever the passes the
implementation cuts a long prompt into) plus each routed row read and
written once at the model's width."""

from perfbench.work.afmoe_spans import spans_in_trace


def count(observed):
    sz = observed.get("serve_afmoe")
    spans = [s for s in spans_in_trace(
        observed, {"serving.decode", "serving.prefill"})
        if "experts_hit" in s["counts"]]
    if not sz or not spans:
        return None
    flops = nbytes = 0.0
    for s in spans:
        c = s["counts"]
        tokens = c.get("rows", c.get("tokens", 0))
        routed = tokens * sz["top_k"] * sz["routed_layers"]
        flops += 2.0 * routed * sz["expert"]
        nbytes += 2.0 * c["experts_hit"] * sz["expert"] \
            + 2.0 * 2.0 * routed * sz["hidden"]
    return flops, nbytes
