"""A statistic over the durations (seconds) of one kind of span."""

from perfbench.harness.common import percentile


def read(observed, span, stat, scale=1.0):
    vals = observed.get("spans", {}).get(span)
    if not vals:
        return None
    if stat == "mean":
        out = sum(vals) / len(vals)
    elif stat == "median":
        out = percentile(vals, 50)
    elif stat.startswith("p"):
        out = percentile(vals, float(stat[1:]))
    else:
        raise ValueError(f"unknown statistic {stat!r}")
    return out * scale
