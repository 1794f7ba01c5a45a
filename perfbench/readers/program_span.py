"""A statistic over the program's own step spans
(`paddle_tpu.observability.tracing.step_spans`, on the clock of
`observed["trace_interval"]`).

The samples are the spans named `span` (a name, or a list of names: the
monolithic and the chunked prefill, the decode and the verify step) whose
start lies in the interval and whose attributes match `where` ({attribute:
regex}); `has` (a name or a list) keeps those with such a span somewhere
under them. A sample's value is `value` ("duration", "self" = duration less
the direct children's, or the name of a count) summed over the spans named in
`sum_of` within the sample's subtree (itself included; default: itself
alone), divided by its count `per` where given. The reading is the median of
the samples' values, or with `ratio` = [a, b] the samples' summed count a
over their summed count b.

`interval` is "trace" (the traced interval) or "steady": from the end of the
last `jit.run` span that ran eagerly or compiled, i.e. since warm-up ended,
which is the set of calls the benchmark's own spans round the engine cover.
A compile that the run counted inside its window, or that ended after the
trace began, makes "steady" read None: warm-up has then not ended where the
window began.

None, never 0, where the program has no step spans (a parent commit), no
sample is found, or the buffer dropped spans of the interval.
"""

import re

from perfbench.harness.common import percentile


def _names(x):
    return {x} if isinstance(x, str) else set(x)


def _buffer():
    try:
        from paddle_tpu.observability import tracing
        return tracing.step_spans()
    except (ImportError, AttributeError):
        return None


def _interval(observed, kind, spans):
    iv = observed.get("trace_interval")
    if not iv or iv[0] is None or iv[1] is None:
        return None
    if kind == "trace":
        return float(iv[0]), float(iv[1])
    if kind == "steady":
        warm = max((s["t_end"] for s in spans if s["name"] == "jit.run"
                    and s["attributes"].get("phase") != "run"),
                   default=float("-inf"))
        if warm >= iv[0] or (observed.get("counters") or {}).get("compiles"):
            return None
        return warm, float("inf")
    raise ValueError(f"unknown interval {kind!r}")


def _value(span, kind, children):
    if kind == "duration":
        return span["t_end"] - span["t_start"]
    if kind == "self":
        return (span["t_end"] - span["t_start"]) - sum(
            c["t_end"] - c["t_start"]
            for c in children.get(span["span_id"], ()))
    return float(span["counts"].get(kind, 0.0))


def _subtree(span, children):
    yield span
    for c in children.get(span["span_id"], ()):
        yield from _subtree(c, children)


def read(observed, span, value="duration", sum_of=None, per=None,
         ratio=None, where=None, has=None, interval="trace", scale=1.0,
         buffer=None):
    buf = buffer if buffer is not None else _buffer()
    if not buf or not buf.get("spans"):
        return None
    spans = buf["spans"]
    iv = _interval(observed, interval, spans)
    if iv is None:
        return None
    lost = buf.get("dropped_until")
    if lost is not None and lost >= iv[0]:
        return None         # the interval is not whole
    children = {}
    for s in spans:
        children.setdefault(s["parent_id"], []).append(s)
    regs = {k: re.compile(v) for k, v in (where or {}).items()}
    named, under = _names(span), _names(has or ())
    chosen = [
        s for s in spans
        if s["name"] in named and iv[0] <= s["t_start"] <= iv[1]
        and all(r.search(str(s["attributes"].get(k, "")))
                for k, r in regs.items())
        and (not under
             or any(d["name"] in under for d in _subtree(s, children)))]
    if not chosen:
        return None
    if ratio is not None:
        num = sum(float(s["counts"].get(ratio[0], 0.0)) for s in chosen)
        den = sum(float(s["counts"].get(ratio[1], 0.0)) for s in chosen)
        return num / den * scale if den else None
    vals = []
    for s in chosen:
        names = set(sum_of) if sum_of else {s["name"]}
        v = sum(_value(d, value, children) for d in _subtree(s, children)
                if d["name"] in names)
        if per is not None:
            n = float(s["counts"].get(per, 0.0))
            if not n:
                continue
            v /= n
        vals.append(v)
    if not vals:
        return None
    return percentile(vals, 50) * scale
