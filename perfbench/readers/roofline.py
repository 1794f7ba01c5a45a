"""Share of the roofline that a kernel (or a whole program) reached over the
traced window, in percent: the least time the chip could take for the work the
algorithm needs (operations over peak FLOP/s, bytes over peak bytes/s, the
larger of the two; `bound="hbm"` takes the bytes alone) over the device time
of the events whose label matches `patterns`.

`events` is "ops" (leaf operations, e.g. a Pallas kernel) or "modules"
(whole programs; `containing` keeps those in whose interval a leaf operation
matching that regex ran: `to_static` gives every program one name). `work` names a module under `perfbench/work/` whose
`count(observed, **work_args)` gives (operations, bytes) of the traced window.
"""

import importlib

from perfbench.harness import trace


def read(observed, patterns, work, events="ops", bound="roofline",
         work_args=None, containing=None):
    red, peaks = observed.get("trace"), observed.get("peaks")
    if not red or not peaks or not red.get(events):
        return None
    chosen = red[events]
    if containing is not None:
        chosen = trace.containing(chosen, red["ops"], containing)
    seconds, n = trace.time_by_pattern(chosen, patterns)
    if n == 0 or seconds <= 0.0:
        return None
    counted = importlib.import_module("perfbench.work." + work).count(
        observed, **(work_args or {}))
    if counted is None:
        return None
    flops, nbytes = counted
    least = nbytes / peaks["hbm_bytes_per_s"]
    if bound == "roofline":
        least = max(least, flops / peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
