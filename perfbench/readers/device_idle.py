"""Idle share of the device over the traced window, in percent: 1 minus the
union of the intervals in which an operation ran on the device."""


def read(observed):
    red = observed.get("trace")
    if not red or not red.get("busy_s") or not red.get("window_s"):
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
