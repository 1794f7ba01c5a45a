"""What the AFMoE work counts share: the program's step spans inside the
traced interval, with the counts they carry (`experts_hit`, `positions`,
`positions_window`, `rows`, `tokens`), and the sizes the driver observed."""

from perfbench.readers.program_span import _buffer


def spans_in_trace(observed, names):
    """The step spans named in `names` that lie wholly inside the traced
    interval, or [] where the program keeps none (a parent commit), the
    buffer dropped some of the interval, or no trace was taken."""
    iv, buf = observed.get("trace_interval"), _buffer()
    if not buf or not buf.get("spans") or not iv or None in iv:
        return []
    lost = buf.get("dropped_until")
    if lost is not None and lost >= iv[0]:
        return []
    return [s for s in buf["spans"] if s["name"] in names
            and iv[0] <= s["t_start"] and s["t_end"] <= iv[1]]


def decode_steps(observed):
    """(sizes, decode spans that carry the routed and window counts)."""
    sz = observed.get("serve_afmoe")
    steps = [s for s in spans_in_trace(observed, {"serving.decode"})
             if "experts_hit" in s["counts"]
             and "positions_window" in s["counts"]]
    return (sz, steps) if sz and steps else (None, [])
