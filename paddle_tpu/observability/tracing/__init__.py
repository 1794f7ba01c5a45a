"""Request traces and step spans: the program's own tracing.

Two axes, one switch (``PADDLE_TPU_TRACE`` / :func:`enable`), one clock.

**Step spans** (:func:`span`, :func:`step_spans`): a process-wide, bounded,
drop-oldest buffer of named intervals at the layer boundaries of the
program (``serving.step``, ``serving.decode``, ``engine.dispatch``,
``jit.run``, ``io.next``, ...; :data:`STEP_SPANS`). A span records its
name, start, end, its own id, the id of the span that caused it (the one
open on the thread when it began), a small dict of counts and a few
attributes. While tracing is on every step span is also a
``jax.profiler.TraceAnnotation("paddle_tpu/<name>")``, so in any profiler
trace the spans lie on the profiler's own time axis over the device ops.

**Clock.** Every span, of a request or of a step, is stamped with
``time.perf_counter()``: monotonic, and the clock a benchmark stamps its
own latencies with. What shows wall-clock times (:func:`to_chrome_trace`,
the request log, the flight snapshot) converts with the one
``(time.time(), time.perf_counter())`` pair taken at import
(:func:`to_wall`).

**Request traces** (ISSUE 16). The request axis: every
``LLMEngine.submit`` opens a **root span** carrying a 128-bit trace id,
and the scheduler emits **child spans** for each lifecycle stage (queue
wait, admission, prefill chunks, burst-aggregated decode/speculate
iterations, eviction, COW copies, stream emission). A p99 TTFT outlier
becomes explainable: its histogram exemplar names a trace id, and
``GET /trace/<id>`` returns the span tree that says where the time went.

Design rules (shared with the rest of the observability stack):

* **zero dependencies** — stdlib only;
* **type-identity no-op when off** — ``PADDLE_TPU_TRACE=0`` makes
  :func:`start_request` return the module-level :data:`NOOP_TRACE`
  singleton whose methods return :data:`NOOP_SPAN`; hot call sites guard
  with an identity check (``trace is NOOP_TRACE``) so the disabled cost
  is one pointer comparison;
* **cost measured from outside** — the tracer does not time itself; what
  tracing costs is the difference between a run with it on and one with
  it off (PERF.md);
* **bounded everywhere** — the step-span buffer
  (:data:`STEP_CAPACITY`), the compiled programs' tables
  (:data:`PROGRAM_VARIANTS` a name), per-request span buffer
  (``PADDLE_TPU_TRACE_SPANS``), completed-trace reservoir
  (``PADDLE_TPU_TRACE_RESERVOIR``), request-log ring
  (``PADDLE_TPU_TRACE_REQUESTS``) and the live-trace table all evict
  oldest-first; nothing grows without bound on a leaked request;
* **leaf locks** — the tracer's locks are leaves: no code path calls
  back into the scheduler, pool or metrics registry while holding one,
  and the :class:`Tracer` lock and a :class:`RequestTrace` lock are
  never held at the same time (no edges for the lock-order analyzer).

Context propagation uses the W3C ``traceparent`` wire format
(``00-<32 hex trace id>-<16 hex parent span id>-<2 hex flags>``) so a
future fleet router can carry a request across prefill/decode pools;
malformed values are rejected (→ fresh trace), never fail the request.

``python -m paddle_tpu.observability.tracing <flight_dump.json>
--chrome-trace out.json`` renders the spans a dying process carried in
its flight dump — open spans become ``ph:"B"`` begin events, the same
unmatched-span convention the flight exporter uses for death spans.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import OrderedDict, deque

from ...analysis.concurrency import tsan as _tsan

__all__ = [
    "TraceContext",
    "parse_traceparent",
    "Span",
    "RequestTrace",
    "Tracer",
    "get_tracer",
    "tracing_enabled",
    "enable",
    "to_wall",
    "span",
    "step_spans",
    "note_program",
    "programs",
    "start_request",
    "get_trace",
    "requests",
    "open_spans",
    "note_exemplar",
    "exemplars",
    "flight_snapshot",
    "to_chrome_trace",
    "render_request_log",
    "stats",
    "reset",
    "main",
]

TRACEPARENT_VERSION = "00"

#: child-span names the serving path emits (the docs' span kinds)
SPAN_KINDS = ("queue_wait", "admit", "prefill", "prefill_chunk", "decode",
              "speculate", "evict", "cow", "stream")

#: step-span names the program emits, by the module that opens them
STEP_SPANS = ("serving.step", "serving.admit", "serving.prefill",
              "serving.prefill_chunk", "serving.decode", "serving.verify",
              "serving.emit", "engine.upload", "engine.dispatch",
              "engine.pull", "jit.run", "io.next")

#: step spans the buffer holds before it drops the oldest: some two hours
#: of decode steps at nine spans each
STEP_CAPACITY = 65536

#: instruction tables kept for one program name (a signature each)
PROGRAM_VARIANTS = 16

# the one pair that ties the span clock to the wall clock
_WALL0, _PERF0 = time.time(), time.perf_counter()


def to_wall(t):
    """A span's stamp (``time.perf_counter()``) as seconds since the epoch."""
    return None if t is None else float(t) - _PERF0 + _WALL0


def _env_int(name, default):
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


def _gen_trace_id() -> str:
    return os.urandom(16).hex()


def _gen_span_id() -> str:
    return os.urandom(8).hex()


def _is_hex(s: str) -> bool:
    try:
        int(s, 16)
    except ValueError:
        return False
    return s == s.lower()


class TraceContext:
    """Serializable trace position: (trace id, parent span id, flags)."""

    __slots__ = ("trace_id", "span_id", "flags")

    def __init__(self, trace_id: str, span_id: str, flags: int = 1):
        self.trace_id = trace_id
        self.span_id = span_id
        self.flags = int(flags)

    def to_traceparent(self) -> str:
        return (f"{TRACEPARENT_VERSION}-{self.trace_id}-{self.span_id}"
                f"-{self.flags & 0xFF:02x}")

    def __repr__(self):  # pragma: no cover - debugging aid
        return f"TraceContext({self.to_traceparent()!r})"


def parse_traceparent(value) -> TraceContext | None:
    """Parse a W3C ``traceparent`` header. Returns ``None`` (never
    raises) on anything malformed — a bad inbound header must degrade to
    a fresh trace, not fail the request."""
    if not isinstance(value, str):
        return None
    parts = value.strip().split("-")
    if len(parts) != 4:
        return None
    version, trace_id, span_id, flags = parts
    if len(version) != 2 or not _is_hex(version) or version == "ff":
        return None
    if len(trace_id) != 32 or not _is_hex(trace_id) or set(trace_id) == {"0"}:
        return None
    if len(span_id) != 16 or not _is_hex(span_id) or set(span_id) == {"0"}:
        return None
    if len(flags) != 2 or not _is_hex(flags):
        return None
    return TraceContext(trace_id, span_id, int(flags, 16))


class Span:
    """One timed, attributed interval inside a request trace."""

    __slots__ = ("name", "span_id", "parent_id", "t_start", "t_end",
                 "attributes", "_trace")

    def __init__(self, name, parent_id=None, t_start=None, attributes=None,
                 _trace=None):
        self.name = name
        self.span_id = _gen_span_id()
        self.parent_id = parent_id
        self.t_start = time.perf_counter() if t_start is None \
            else float(t_start)
        self.t_end = None
        self.attributes = dict(attributes) if attributes else {}
        self._trace = _trace

    def set(self, **attrs) -> "Span":
        self.attributes.update(attrs)
        return self

    def end(self, t_end=None, **attrs) -> None:
        if attrs:
            self.attributes.update(attrs)
        tr = self._trace
        if tr is not None:
            tr._end_span(self, t_end)

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None and "error" not in self.attributes:
            self.attributes["error"] = repr(exc)
        self.end()

    def to_dict(self, wall: bool = False) -> dict:
        """``wall=True`` converts the stamps for a reader of wall-clock
        times (a flight dump)."""
        conv = to_wall if wall else (lambda t: t)
        d = {"name": self.name, "span_id": self.span_id,
             "parent_id": self.parent_id, "t_start": conv(self.t_start),
             "t_end": conv(self.t_end)}
        if self.attributes:
            d["attributes"] = dict(self.attributes)
        return d


class _NoopSpan:
    """Disabled-mode span: every method is a no-op returning a singleton
    (type identity: ``trace.span(...) is NOOP_SPAN`` always holds)."""

    __slots__ = ()
    name = None
    span_id = None
    parent_id = None
    t_start = None
    t_end = None
    attributes: dict = {}

    def set(self, **attrs):
        return self

    def count(self, **counts):
        return self

    def end(self, t_end=None, **attrs):
        return None

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        return None

    def to_dict(self, wall=False):
        return {}


class _NoopTrace:
    """Disabled-mode request trace (singleton, see :data:`NOOP_TRACE`)."""

    __slots__ = ()
    trace_id = None
    request_id = None

    def context(self):
        return None

    def span(self, name, parent=None, t_start=None, **attrs):
        return NOOP_SPAN

    def add_span(self, name, t_start, t_end, parent=None, **attrs):
        return NOOP_SPAN

    def finish(self, state="completed", **fields):
        return None

    def snapshot(self):
        return {}

    def open_spans(self):
        return []


NOOP_SPAN = _NoopSpan()
NOOP_TRACE = _NoopTrace()


class RequestTrace:
    """Span buffer for one request: a root span plus a bounded list of
    children. Thread-safe; the lock is a leaf (methods never call out
    of this module while holding it)."""

    def __init__(self, tracer, request_id=None, name="request",
                 parent: TraceContext | None = None, max_spans=256,
                 attributes=None):
        self._tracer = tracer
        self._lock = _tsan.lock("observability.tracing.RequestTrace")
        self.trace_id = parent.trace_id if parent else _gen_trace_id()
        self.request_id = request_id
        self.max_spans = int(max_spans)
        self.root = Span(name, parent_id=parent.span_id if parent else None,
                         attributes=attributes, _trace=self)
        if request_id is not None:
            self.root.attributes.setdefault("request_id", request_id)
        self._spans: list[Span] = []      # finished children, bounded
        self._open: dict[str, Span] = {}  # span_id -> open child
        self._dropped = 0
        self._finished = False

    # -- span lifecycle -------------------------------------------------
    def context(self) -> TraceContext:
        """Context to propagate downstream (child of the root span)."""
        return TraceContext(self.trace_id, self.root.span_id)

    def span(self, name, parent=None, t_start=None, **attrs) -> Span:
        """Open a child span (ended via ``.end()`` / context manager)."""
        parent_id = parent.span_id if parent is not None else self.root.span_id
        s = Span(name, parent_id=parent_id, t_start=t_start,
                 attributes=attrs or None, _trace=self)
        with self._lock:
            if self._finished or \
                    len(self._spans) + len(self._open) >= self.max_spans:
                self._dropped += 1
                s._trace = None  # still usable, just not recorded
            else:
                self._open[s.span_id] = s
        return s

    def add_span(self, name, t_start, t_end, parent=None, **attrs) -> Span:
        """Record an already-timed span in one call (burst flushes)."""
        parent_id = parent.span_id if parent is not None else self.root.span_id
        s = Span(name, parent_id=parent_id, t_start=t_start,
                 attributes=attrs or None, _trace=None)
        s.t_end = float(t_end)
        with self._lock:
            if self._finished or len(self._spans) >= self.max_spans:
                self._dropped += 1
            else:
                self._spans.append(s)
        return s

    def _end_span(self, span: Span, t_end=None) -> None:
        end = time.perf_counter() if t_end is None else float(t_end)
        with self._lock:
            if span.t_end is None:
                span.t_end = end
            live = self._open.pop(span.span_id, None)
            if live is not None and not self._finished and \
                    len(self._spans) < self.max_spans:
                self._spans.append(span)
            elif live is not None:
                self._dropped += 1

    def finish(self, state="completed", **fields) -> dict | None:
        """Close the root span, build the request record and hand the
        trace to the tracer's reservoir + request log. Idempotent."""
        now = time.perf_counter()
        with self._lock:
            if self._finished:
                return None
            self._finished = True
            self.root.t_end = now
            # a still-open child at finish is a bug upstream, but the
            # trace must stay renderable: close it at root end
            for s in self._open.values():
                s.t_end = now
                s.attributes.setdefault("unfinished", True)
                if len(self._spans) < self.max_spans:
                    self._spans.append(s)
                else:
                    self._dropped += 1
            self._open.clear()
            spans = list(self._spans)
            dropped = self._dropped
        record = self._build_record(state, spans, dropped, fields)
        # tracer lock taken strictly after the trace lock was released:
        # the two lock classes are never nested in either order
        self._tracer._complete(self, record, len(spans))
        return record

    # -- introspection --------------------------------------------------
    def _build_record(self, state, spans, dropped, fields) -> dict:
        root = self.root
        e2e_s = (root.t_end or time.perf_counter()) - root.t_start
        record = {      # a line of the request log: wall-clock stamps
            "trace_id": self.trace_id,
            "request_id": self.request_id,
            "state": state,
            "t_start": to_wall(root.t_start),
            "t_end": to_wall(root.t_end),
            "e2e_ms": round(e2e_s * 1000.0, 3),
            "spans": len(spans),
            "dropped_spans": dropped,
            "span_kinds": sorted({s.name for s in spans}),
            "span_coverage": round(_coverage(root, spans), 4),
        }
        proposed = sum(s.attributes.get("proposed", 0) for s in spans
                       if s.name == "speculate")
        if proposed:
            record["spec"] = {
                "proposed": proposed,
                "accepted": sum(s.attributes.get("accepted", 0)
                                for s in spans if s.name == "speculate")}
        for k, v in fields.items():
            if v is not None:
                record[k] = v
        return record

    def snapshot(self) -> dict:
        """Full span tree (finished + still-open children), stamps on
        the span clock (``"clock": "perf_counter"``; :func:`to_wall`)."""
        with self._lock:
            spans = [s.to_dict() for s in self._spans]
            open_ = [s.to_dict() for s in self._open.values()]
            dropped = self._dropped
        d = {"trace_id": self.trace_id, "request_id": self.request_id,
             "clock": "perf_counter",
             "root": self.root.to_dict(), "spans": spans}
        if open_:
            d["open"] = open_
        if dropped:
            d["dropped_spans"] = dropped
        return d

    def open_spans(self) -> list[dict]:
        """Spans without an end time (root included while unfinished),
        each stamped with trace/request ids — this is what a flight dump
        carries for an in-flight request at death, so its stamps are
        wall-clock times."""
        out = []
        with self._lock:
            if self._finished:
                return out
            for s in [self.root] + list(self._open.values()):
                d = s.to_dict(wall=True)
                d["trace_id"] = self.trace_id
                d["request_id"] = self.request_id
                out.append(d)
        return out


def _coverage(root, spans) -> float:
    """Fraction of the root span's wall covered by the union of child
    span intervals (the bench's span-coverage acceptance stat)."""
    t0, t1 = root.t_start, root.t_end or time.perf_counter()
    if t1 <= t0:
        return 1.0 if spans else 0.0
    ivals = []
    for s in spans:
        a = max(s.t_start, t0)
        b = min(s.t_end if s.t_end is not None else t1, t1)
        if b > a:
            ivals.append((a, b))
    ivals.sort()
    covered = 0.0
    cur_a = cur_b = None
    for a, b in ivals:
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                covered += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        covered += cur_b - cur_a
    return min(1.0, covered / (t1 - t0))


# ---------------------------------------------------------------------------
# Step spans

_step_ids = itertools.count(1)
_thread = threading.local()       # .cur: the step span open on this thread
_ANNOTATION = None                # jax.profiler.TraceAnnotation, or False


def _annotation():
    """The profiler's annotation class, looked up at the first span (this
    package imports nothing but the standard library at import)."""
    global _ANNOTATION
    if _ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
            _ANNOTATION = TraceAnnotation
        except Exception:       # noqa: BLE001 - no jax: spans without them
            _ANNOTATION = False
    return _ANNOTATION


class StepSpan:
    """One interval of the program's own work, open from its creation to
    ``end()`` (or the end of its ``with`` block). The span open on the
    thread when it began is its cause (``parent_id``)."""

    __slots__ = ("name", "span_id", "parent_id", "t_start", "t_end",
                 "counts", "attributes", "_buffer", "_prev", "_ann")

    def __init__(self, buffer, name, attributes):
        self.name = name
        self.span_id = next(_step_ids)
        self._buffer = buffer
        self._prev = prev = getattr(_thread, "cur", None)
        self.parent_id = prev.span_id if prev is not None else None
        self.counts = {}
        self.attributes = attributes
        self.t_end = None
        _thread.cur = self
        ann = _annotation()
        self._ann = ann("paddle_tpu/" + name) if ann else None
        if self._ann is not None:
            self._ann.__enter__()
        self.t_start = time.perf_counter()

    def count(self, **counts) -> "StepSpan":
        self.counts.update(counts)
        return self

    def set(self, **attrs) -> "StepSpan":
        self.attributes.update(attrs)
        return self

    def end(self) -> None:
        if self.t_end is not None:
            return
        self.t_end = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if getattr(_thread, "cur", None) is self:
            _thread.cur = self._prev
        self._prev = None
        self._buffer.push(self)

    def __enter__(self) -> "StepSpan":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        if exc is not None:
            self.attributes.setdefault("error", repr(exc))
        self.end()

    def to_dict(self) -> dict:
        return {"name": self.name, "span_id": self.span_id,
                "parent_id": self.parent_id, "t_start": self.t_start,
                "t_end": self.t_end, "counts": dict(self.counts),
                "attributes": dict(self.attributes)}


class _StepBuffer:
    """Finished step spans, newest kept: a bounded ring that counts what it
    drops and remembers the latest start among the dropped, so a reader
    knows whether an interval it asks for is whole. The lock is a leaf."""

    def __init__(self, capacity):
        self.capacity = max(1, int(capacity))
        self._lock = _tsan.lock("observability.tracing.StepBuffer")
        self._spans: deque = deque(maxlen=self.capacity)
        self._dropped = 0
        self._dropped_until = None

    def push(self, span) -> None:
        with self._lock:
            if len(self._spans) == self.capacity:
                old = self._spans[0]
                self._dropped += 1
                if self._dropped_until is None or \
                        old.t_start > self._dropped_until:
                    self._dropped_until = old.t_start
            self._spans.append(span)

    def read(self, since=None, until=None) -> dict:
        with self._lock:
            spans = list(self._spans)
            dropped, dropped_until = self._dropped, self._dropped_until
        return {"spans": [s.to_dict() for s in spans
                          if (since is None or s.t_start >= since)
                          and (until is None or s.t_start <= until)],
                "dropped": dropped, "dropped_until": dropped_until}

    def __len__(self):
        return len(self._spans)

    def reset(self) -> None:
        with self._lock:
            self._spans.clear()
            self._dropped = 0
            self._dropped_until = None


class Tracer:
    """Process-global trace collector: the step-span buffer, live request
    traces, a sampled reservoir of completed traces, a ring of request-log
    records and histogram exemplars. Request state behind one leaf lock,
    the step buffer behind its own."""

    def __init__(self, enabled=None, max_spans=None, reservoir=None,
                 log_capacity=None, sample_every=None, step_capacity=None):
        if enabled is None:
            enabled = os.environ.get("PADDLE_TPU_TRACE", "1") != "0"
        self.enabled = bool(enabled)
        self.max_spans = max_spans if max_spans is not None else \
            _env_int("PADDLE_TPU_TRACE_SPANS", 256)
        self.reservoir_capacity = reservoir if reservoir is not None else \
            _env_int("PADDLE_TPU_TRACE_RESERVOIR", 256)
        self.log_capacity = log_capacity if log_capacity is not None else \
            _env_int("PADDLE_TPU_TRACE_REQUESTS", 512)
        #: keep every Nth completed trace's full span tree (the request
        #: log line is always written); deterministic counter sampling
        self.sample_every = max(1, sample_every if sample_every is not None
                                else _env_int("PADDLE_TPU_TRACE_SAMPLE", 1))
        self._steps = _StepBuffer(
            step_capacity if step_capacity is not None else STEP_CAPACITY)
        #: {module name: {"variants": [{instruction: op_name}, ...],
        #: "dropped": n}} of the compiled programs (:meth:`note_program`)
        self._programs: "OrderedDict[str, dict]" = OrderedDict()
        self._lock = _tsan.lock("observability.tracing.Tracer")
        self._live: "OrderedDict[str, RequestTrace]" = OrderedDict()
        self._live_capacity = max(64, self.reservoir_capacity * 4)
        self._reservoir: "OrderedDict[str, dict]" = OrderedDict()
        self._log: deque = deque(maxlen=self.log_capacity)
        self._exemplars: dict[str, dict] = {}
        self._completions = 0
        self._spans_total = 0
        self._dropped_live = 0

    # -- step spans -------------------------------------------------------
    def span(self, name, **attrs):
        """Open a step span (``with`` it, or call its ``end()``):
        :data:`NOOP_SPAN` after one flag check when tracing is off.
        ``attrs`` are its attributes; ``.count(...)`` sets counts."""
        if not self.enabled:
            return NOOP_SPAN
        return StepSpan(self._steps, name, attrs)

    def step_spans(self, since=None, until=None) -> dict:
        """``{"spans": [...], "dropped": n, "dropped_until": t}``: the
        finished step spans whose start lies in ``[since, until]`` (span
        clock; None = open end), as dicts; how many spans the buffer has
        dropped since the process began (or the last :meth:`reset`); and
        the latest start among those, None if none was dropped. An
        interval that begins after ``dropped_until`` is whole."""
        return self._steps.read(since, until)

    def note_program(self, module: str, ops: dict) -> None:
        """Remember, for one compiled program, which code made each of its
        instructions: ``ops`` maps an instruction's name in the optimized
        HLO to its ``op_name`` (the jit and ``jax.named_scope`` path). A
        device trace names only the instruction; with this table a reader
        gives it the scope. Programs of one name (a signature each) are
        kept side by side, the newest :data:`PROGRAM_VARIANTS` of them,
        and the tables dropped are counted: a reader then knows that the
        name's tables no longer cover what ran. The oldest names go
        first."""
        with self._lock:
            entry = self._programs.setdefault(
                module, {"variants": [], "dropped": 0})
            entry["variants"].append(dict(ops))
            if len(entry["variants"]) > PROGRAM_VARIANTS:
                del entry["variants"][0]
                entry["dropped"] += 1
            self._programs.move_to_end(module)
            while len(self._programs) > 64:
                self._programs.popitem(last=False)

    def programs(self) -> dict:
        """``{module: {"variants": [table, ...], "dropped": n}}``."""
        with self._lock:
            return {m: {"variants": list(e["variants"]),
                        "dropped": e["dropped"]}
                    for m, e in self._programs.items()}

    # -- request lifecycle ----------------------------------------------
    def start_request(self, request_id=None, traceparent=None, **attrs):
        """Open a root span. Returns :data:`NOOP_TRACE` when disabled
        (identity-checkable by hot call sites). A malformed
        ``traceparent`` yields a fresh trace, never an error."""
        if not self.enabled:
            return NOOP_TRACE
        parent = parse_traceparent(traceparent) if traceparent else None
        tr = RequestTrace(self, request_id=request_id, parent=parent,
                          max_spans=self.max_spans, attributes=attrs or None)
        with self._lock:
            self._live[tr.trace_id] = tr
            while len(self._live) > self._live_capacity:
                self._live.popitem(last=False)
                self._dropped_live += 1
        return tr

    def _complete(self, tr, record, n_spans) -> None:
        with self._lock:
            self._live.pop(tr.trace_id, None)
            self._completions += 1
            self._spans_total += n_spans
            self._log.append(record)
            if (self._completions - 1) % self.sample_every == 0:
                self._reservoir[tr.trace_id] = None  # snapshot outside lock
                while len(self._reservoir) > self.reservoir_capacity:
                    self._reservoir.popitem(last=False)
            keep = tr.trace_id in self._reservoir
        if keep:
            snap = tr.snapshot()
            snap["record"] = record
            with self._lock:
                if tr.trace_id in self._reservoir:
                    self._reservoir[tr.trace_id] = snap

    # -- lookups ---------------------------------------------------------
    def get_trace(self, trace_id, wall: bool = False) -> dict | None:
        """Span tree for a trace id: completed (reservoir) or live.
        ``wall=True`` gives the stamps as wall-clock times, for a reader
        outside the process."""
        with self._lock:
            snap = self._reservoir.get(trace_id)
            live = self._live.get(trace_id)
        if snap is None and live is not None:
            snap = live.snapshot()
        if snap is not None and wall:
            snap = _snapshot_to_wall(snap)
        return snap

    def requests(self, last=None) -> list[dict]:
        """Most recent request-log records, oldest first."""
        with self._lock:
            out = list(self._log)
        if last is not None and last >= 0:
            out = out[-last:]
        return out

    def open_spans(self) -> list[dict]:
        """Open spans of every in-flight trace (flight-dump payload)."""
        with self._lock:
            live = list(self._live.values())
        out = []
        for tr in live:
            out.extend(tr.open_spans())
        return out

    # -- exemplars --------------------------------------------------------
    def note_exemplar(self, metric, value, trace_id, buckets=()) -> None:
        """Link ``value`` observed on ``metric`` to a trace id, keyed by
        the histogram bucket it falls in (latest observation per bucket
        wins; bounded by the bucket count)."""
        if trace_id is None:
            return
        le = "+Inf"
        for b in buckets:
            if value <= b:
                le = b
                break
        with self._lock:
            self._exemplars.setdefault(metric, {})[str(le)] = {
                "bucket_le": le, "value": round(float(value), 3),
                "trace_id": trace_id, "t": time.time()}

    def exemplars(self) -> dict:
        """Per metric: exemplar per occupied bucket plus a ``top``
        pointer at the highest occupied bucket (the p99 explainer)."""
        with self._lock:
            snap = {m: dict(bs) for m, bs in self._exemplars.items()}
        out = {}
        for metric, bs in snap.items():
            def _key(item):
                le = item[1]["bucket_le"]
                return float("inf") if le == "+Inf" else float(le)
            top = max(bs.items(), key=_key)[1]
            out[metric] = {"buckets": bs, "top": top}
        return out

    # -- maintenance ------------------------------------------------------
    def stats(self) -> dict:
        with self._lock:
            out = {
                "enabled": self.enabled,
                "live": len(self._live),
                "reservoir": len(self._reservoir),
                "completions": self._completions,
                "spans_total": self._spans_total,
                "dropped_live": self._dropped_live,
            }
        out["step_spans"] = len(self._steps)
        out["step_spans_dropped"] = self._steps._dropped
        return out

    def flight_snapshot(self) -> dict:
        """Bounded payload the flight recorder embeds in every dump:
        open spans of in-flight requests + a tail of recent traces, all
        in wall-clock times (a dump is read after the process is gone)."""
        with self._lock:
            recent = [_snapshot_to_wall(s)
                      for s in list(self._reservoir.values())[-8:]
                      if s is not None]
            log_tail = list(self._log)[-16:]
        return {"open_spans": self.open_spans(), "traces": recent,
                "requests": log_tail, "stats": self.stats()}

    def reset(self) -> None:
        with self._lock:
            self._live.clear()
            self._reservoir.clear()
            self._log.clear()
            self._exemplars.clear()
            self._programs.clear()
            self._completions = 0
            self._spans_total = 0
            self._dropped_live = 0
        self._steps.reset()


def _snapshot_to_wall(snap: dict) -> dict:
    """A trace snapshot with its stamps as wall-clock times; one that is
    not on the span clock (read back from a dump) is returned as it is."""
    if snap.get("clock") != "perf_counter":
        return snap

    def conv(span):
        return dict(span, t_start=to_wall(span.get("t_start")),
                    t_end=to_wall(span.get("t_end")))

    out = dict(snap, clock="wall")
    if out.get("root"):
        out["root"] = conv(out["root"])
    for key in ("spans", "open"):
        if out.get(key):
            out[key] = [conv(sp) for sp in out[key]]
    return out


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def tracing_enabled() -> bool:
    return _TRACER.enabled


def enable(on: bool = True) -> None:
    """Flip tracing at runtime (``PADDLE_TPU_TRACE`` sets the default).
    Already-open traces keep recording; new requests observe the flag."""
    _TRACER.enabled = bool(on)


def span(name, **attrs):
    """:meth:`Tracer.span` of the process-wide tracer, inlined: the hot
    paths call this, and off it costs one call and one flag check."""
    if not _TRACER.enabled:
        return NOOP_SPAN
    return StepSpan(_TRACER._steps, name, attrs)


def step_spans(since=None, until=None) -> dict:
    return _TRACER.step_spans(since, until)


def note_program(module, ops) -> None:
    _TRACER.note_program(module, ops)


def programs() -> dict:
    return _TRACER.programs()


def start_request(request_id=None, traceparent=None, **attrs):
    return _TRACER.start_request(request_id=request_id,
                                 traceparent=traceparent, **attrs)


def get_trace(trace_id, wall=False):
    return _TRACER.get_trace(trace_id, wall=wall)


def requests(last=None):
    return _TRACER.requests(last)


def open_spans():
    return _TRACER.open_spans()


def note_exemplar(metric, value, trace_id, buckets=()):
    _TRACER.note_exemplar(metric, value, trace_id, buckets)


def exemplars():
    return _TRACER.exemplars()


def flight_snapshot():
    return _TRACER.flight_snapshot()


def stats():
    return _TRACER.stats()


def reset():
    _TRACER.reset()


#: burst length for decode/speculate span aggregation (spans per burst)
def decode_burst() -> int:
    return max(1, _env_int("PADDLE_TPU_TRACE_BURST", 32))


# ---------------------------------------------------------------------------
# Exporters


def render_request_log(last=None) -> str:
    """The structured request log: one strict-JSON (RFC 8259) line per
    completed request, sanitised with the flight recorder's encoders."""
    from .. import flight as _flight
    lines = []
    for rec in _TRACER.requests(last):
        lines.append(json.dumps(_flight._finite(rec), sort_keys=True,
                                allow_nan=False,
                                default=_flight._json_safe))
    return "\n".join(lines) + ("\n" if lines else "")


def to_chrome_trace(traces, open_spans=(), trace=None, steps=()) -> dict:
    """Render trace snapshots (+ loose open spans, + step spans as
    :func:`step_spans` gives them) as Chrome-trace JSON in wall-clock
    microseconds, merged into ``trace`` if given. Snapshots on the span
    clock are converted; those read back from a dump already are wall
    clock. Conventions match the flight exporter: closed spans are
    ``ph:"X"`` complete events; spans without an end (a dying process's
    in-flight requests) are kept as ``ph:"B"`` begin events rather than
    dropped."""
    out = trace if trace is not None else {"traceEvents": [],
                                           "displayTimeUnit": "ms"}
    events = out.setdefault("traceEvents", [])
    tids: dict[str, int] = {}

    def _tid(trace_id):
        return tids.setdefault(trace_id, len(tids) + 1)

    def _emit(span, trace_id, request_id):
        args = dict(span.get("attributes") or {})
        args["trace_id"] = trace_id
        args["span_id"] = span.get("span_id")
        if request_id is not None:
            args.setdefault("request_id", request_id)
        ev = {"name": span.get("name"), "cat": "request", "pid": 1,
              "tid": _tid(trace_id),
              "ts": round(float(span["t_start"]) * 1e6, 1), "args": args}
        if span.get("t_end") is not None:
            ev["ph"] = "X"
            ev["dur"] = round((float(span["t_end"]) -
                               float(span["t_start"])) * 1e6, 1)
        else:
            ev["ph"] = "B"  # open at death: keep, flight-style
        events.append(ev)

    for sp in steps or ():
        events.append({
            "name": sp["name"], "cat": "step", "pid": 1, "tid": 0, "ph": "X",
            "ts": round(to_wall(sp["t_start"]) * 1e6, 1),
            "dur": round((sp["t_end"] - sp["t_start"]) * 1e6, 1),
            "args": dict(sp.get("attributes") or {}, span_id=sp["span_id"],
                         parent_id=sp["parent_id"],
                         **(sp.get("counts") or {}))})
    for snap in traces or ():
        snap = _snapshot_to_wall(snap)
        trace_id = snap.get("trace_id")
        request_id = snap.get("request_id")
        root = snap.get("root")
        if root:
            _emit(root, trace_id, request_id)
        for s in snap.get("spans") or ():
            _emit(s, trace_id, request_id)
        for s in snap.get("open") or ():
            _emit(s, trace_id, request_id)
    for s in open_spans or ():
        _emit(s, s.get("trace_id"), s.get("request_id"))
    return out


def _tracing_sections(payload: dict) -> tuple[list, list]:
    """Pull (traces, open_spans) out of a flight dump payload — both the
    dump-time snapshot and the at-preemption snapshot the engine stashes
    in ``extra`` — or out of a raw ``flight_snapshot()`` file."""
    traces, spans = [], []
    for section in (payload.get("tracing"),
                    (payload.get("extra") or {}).get("tracing_at_preempt"),
                    payload if "open_spans" in payload or "traces" in payload
                    else None):
        if not isinstance(section, dict):
            continue
        traces.extend(section.get("traces") or ())
        # open spans stay even when the same trace also completed later
        # (a drain finishing the request does not erase what was in
        # flight at the signal) — the keep-unmatched-spans convention
        spans.extend(section.get("open_spans") or ())
    return traces, spans


def main(argv=None) -> int:
    """CLI: summarize / re-render the tracing payload of a flight dump.

    ``python -m paddle_tpu.observability.tracing dump.json`` prints the
    request records and open spans; ``--chrome-trace out.json`` writes a
    chrome://tracing file (open spans kept as ``B`` events); ``--json``
    dumps the raw sections.
    """
    import argparse
    ap = argparse.ArgumentParser(prog="paddle_tpu.observability.tracing",
                                 description=main.__doc__)
    ap.add_argument("path", help="flight dump json (or a raw "
                                 "flight_snapshot() file)")
    ap.add_argument("--chrome-trace", metavar="OUT",
                    help="write Chrome-trace JSON to OUT")
    ap.add_argument("--json", action="store_true",
                    help="print the raw tracing sections as JSON")
    ap.add_argument("--last", type=int, default=None,
                    help="only the most recent N request records")
    args = ap.parse_args(argv)
    try:
        with open(args.path) as f:
            payload = json.load(f)
    except (OSError, ValueError) as e:
        print(f"tracing: cannot read {args.path!r}: {e}")
        return 2
    traces, spans = _tracing_sections(payload)
    records = []
    for section in (payload.get("tracing"),
                    (payload.get("extra") or {}).get("tracing_at_preempt"),
                    payload if "requests" in payload else None):
        if isinstance(section, dict):
            records.extend(section.get("requests") or ())
    if args.last is not None:
        records = records[-args.last:]
    if args.json:
        print(json.dumps({"traces": traces, "open_spans": spans,
                          "requests": records}, indent=2, sort_keys=True))
    else:
        print(f"tracing: {len(records)} request record(s), "
              f"{len(traces)} trace snapshot(s), "
              f"{len(spans)} open span(s)")
        for r in records:
            print(f"  [{r.get('state', '?'):>9}] trace={r.get('trace_id')} "
                  f"req={r.get('request_id')} e2e={r.get('e2e_ms')}ms "
                  f"queue={r.get('queue_ms')}ms "
                  f"prefill={r.get('prefill_ms')}ms "
                  f"decode={r.get('decode_ms')}ms "
                  f"coverage={r.get('span_coverage')}")
        for s in spans:
            print(f"  [open] {s.get('name')} trace={s.get('trace_id')} "
                  f"req={s.get('request_id')} since={s.get('t_start')}")
    if args.chrome_trace:
        ct = to_chrome_trace(traces, spans)
        with open(args.chrome_trace, "w") as f:
            json.dump(ct, f)
        print(f"tracing: wrote {len(ct['traceEvents'])} event(s) to "
              f"{args.chrome_trace}")
    return 0
