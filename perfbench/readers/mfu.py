"""The whole step's share of the chip's bf16 peak over the traced window, in
percent: the model's FLOPs of the work done there (counted from shapes by the
driver, recomputation not counted) over the window's seconds times the peak.

Training gives `flops_per_step`; the steps are the executions of the program
matching `step_module` in the trace, the window the device's traced window.
Serving gives `model_flops_traced` over `traced_s` (host clock)."""

from perfbench.harness import trace


def read(observed, step_module=None):
    peaks, red = observed.get("peaks"), observed.get("trace")
    if not peaks or not red or not red.get("window_s"):
        return None
    if step_module is not None:
        _, steps = trace.time_by_pattern(red["modules"], [step_module])
        flops, seconds = steps * observed["flops_per_step"], red["window_s"]
    else:
        flops = observed.get("model_flops_traced")
        seconds = observed.get("traced_s")
    if not flops or not seconds:
        return None
    return 100.0 * flops / (seconds * peaks["bf16_flops_per_s"])
