"""Iteration-level (continuous-batching) scheduler over the paged pool.

Policy layer of the serving runtime — no device code here. Each
:meth:`Scheduler.step` is one engine iteration:

1. **Admission** (FIFO): while a decode slot AND enough available pages
   for the request's context (+1 headroom page for its first decode
   write) exist, pop the oldest waiting request. With a
   :class:`~.prefix_cache.PrefixCache`, the longest cached page-aligned
   prefix is **claimed** first (refcounts bumped, pages mapped straight
   into the page table) so prefill only computes the *suffix*; the rest
   is allocated fresh. Monolithic mode then runs the compiled prefill
   program inline (which also samples the request's first token — TTFT
   is prefill-bounded, not batch-bounded); chunked mode just seats the
   request and lets step 2 interleave its chunks with decode steps.
   Head-of-line blocking is deliberate: the oldest request is never
   overtaken, so FIFO admission cannot starve.
2. **Chunked prefill** (when ``prefill_chunk`` is set): each seated
   not-yet-prefilled request advances by fixed-size chunks under a
   per-iteration token budget, so a long-prompt arrival never stalls
   in-flight decodes for its whole prompt — the final chunk samples the
   first token. Any write that would land in a refcount>1 (shared) page
   copy-on-writes first: **a shared page is never mutated**.
3. **Growth**: every active request whose next write position crosses a
   page boundary allocates a page (``alloc`` reclaims LRU refcount-0
   cached pages before declaring exhaustion, so cache residency never
   blocks admission). On true exhaustion the **youngest** active request
   is evicted — its references dropped (shared pages survive with their
   other owners; exclusive keyed pages fall back to the cached state, so
   re-admission is mostly cache hits), request requeued in arrival order
   with its generated prefix kept — so the oldest request always makes
   progress (the no-livelock argument).
4. **Decode**: ONE batched decode step over all prefill-complete slots
   (inactive and still-prefilling slots ride along pointed at the trash
   page); sampled tokens stream to per-request callbacks; finished
   requests (eos / ``max_new_tokens`` / context limit) release their
   page references. With **speculative decoding**
   (``ServingConfig(spec_k=K)``), the :class:`~.speculative.NgramDrafter`
   first proposes up to K draft tokens per request from its own
   prompt+generation history; whenever any request drafted, the batched
   step runs the single fused VERIFY program instead (scoring all K+1
   positions in one sweep — rows without drafts ride along at
   ``draft_len=0`` and still advance exactly one token), draft KV is
   written speculatively (copy-on-write first: a shared page is never
   mutated), and rejected-draft pages are **rolled back** — the
   per-request cursor rewinds to the accepted length and pages that
   only ever held rejected drafts are freed. Per-request adaptive K
   (acceptance-rate EWMA) degrades an unpredictable stream to K=0 =
   the untouched plain decode program.

Requests whose *total* page need exceeds the pool (or whose total length
exceeds the model/config limit) can never run and are rejected at
``submit`` — the admission-control rejection path.
"""

from __future__ import annotations

import itertools
import queue
import threading
import time
import uuid

import numpy as np

from ..analysis.concurrency import tsan as _tsan
from ..observability import (counter as _obs_counter, gauge as _obs_gauge,
                             histogram as _obs_histogram)
from ..observability import flight as _flight
from ..observability import tracing as _tracing
from .kv_cache import PagePoolExhausted, TRASH_PAGE, window_first_page
from .speculative import NgramDrafter, SpecState

__all__ = ["Request", "Scheduler", "RequestRejected", "ServingError",
           "QUEUED", "RUNNING", "COMPLETED", "FAILED", "REJECTED",
           "CANCELLED"]

QUEUED = "queued"
RUNNING = "running"
COMPLETED = "completed"
FAILED = "failed"
REJECTED = "rejected"
CANCELLED = "cancelled"

_TERMINAL = (COMPLETED, FAILED, REJECTED, CANCELLED)

_MS_BUCKETS = (1.0, 2.5, 5.0, 10.0, 25.0, 50.0, 100.0, 250.0, 500.0,
               1000.0, 2500.0, 5000.0, 10000.0, 30000.0)

_REQS = _obs_counter("paddle_tpu_serving_requests_total",
                     "serving requests by terminal status")
_SUBMITS = _obs_counter("paddle_tpu_serving_submissions_total",
                        "requests submitted to the engine")
_TOKENS = _obs_counter("paddle_tpu_serving_tokens_total",
                       "tokens processed (kind=prompt|generated)",
                       windowed=True)
_STEPS = _obs_counter("paddle_tpu_serving_decode_steps_total",
                      "batched decode steps executed", windowed=True)
_PREFILLS = _obs_counter("paddle_tpu_serving_prefills_total",
                         "prefill program runs by compile bucket")
_EVICTIONS = _obs_counter("paddle_tpu_serving_evictions_total",
                          "requests evicted (pages reclaimed, requeued)")
_COW = _obs_counter("paddle_tpu_serving_cow_copies_total",
                    "copy-on-write page copies (a write was about to "
                    "land in a shared page)")
_PREFILL_TOKENS = _obs_counter(
    "paddle_tpu_serving_prefill_tokens_total",
    "tokens through the prefill programs: kind=real those of the prompt "
    "that were computed, kind=padded the bucket they were padded to")
_KV_POSITIONS = _obs_counter(
    "paddle_tpu_serving_kv_positions_total",
    "KV positions per layer over all decode and verify steps: kind=live "
    "those of the contexts in the batch (group=window: those inside each "
    "row's window), kind=gathered those the program read of the pool (a "
    "page-table gather: every slot of every table row; the paged decode "
    "kernel: the live rows' positions rounded up to its block, in "
    "group=window from the block that holds the window's first position)")
_QUEUE = _obs_gauge("paddle_tpu_serving_queue_depth",
                    "requests waiting for admission")
_ACTIVE = _obs_gauge("paddle_tpu_serving_active_requests",
                     "requests holding a decode slot")
_OCC = _obs_gauge("paddle_tpu_serving_batch_occupancy",
                  "active decode slots / max_batch")
_TTFT = _obs_histogram("paddle_tpu_serving_ttft_ms",
                       "submit -> first token (ms)", buckets=_MS_BUCKETS)
_TPOT = _obs_histogram("paddle_tpu_serving_tpot_ms",
                       "inter-token latency after the first (ms; a "
                       "multi-token speculative burst amortizes the "
                       "step gap over its tokens)",
                       buckets=_MS_BUCKETS)
_E2E = _obs_histogram("paddle_tpu_serving_e2e_ms",
                      "submit -> completion (ms)", buckets=_MS_BUCKETS)
_QUEUE_WAIT = _obs_histogram(
    "paddle_tpu_serving_queue_wait_ms",
    "enqueue -> admission wait (ms; a re-admission after eviction "
    "counts each wait segment) — the scheduler-delay share of TTFT",
    buckets=_MS_BUCKETS)
_SPEC_PROPOSED = _obs_counter(
    "paddle_tpu_serving_spec_proposed_tokens_total",
    "draft tokens proposed to the verify program", windowed=True)
_SPEC_ACCEPTED = _obs_counter(
    "paddle_tpu_serving_spec_accepted_tokens_total",
    "draft tokens accepted by verification", windowed=True)
_SPEC_REJECTED = _obs_counter(
    "paddle_tpu_serving_spec_rejected_tokens_total",
    "draft tokens rejected by verification (KV rolled back)")
_SPEC_RATE = _obs_gauge(
    "paddle_tpu_serving_spec_acceptance_rate",
    "windowed draft acceptance rate (accepted/proposed over the last "
    "60s of verify steps)")
_SPEC_K = _obs_gauge(
    "paddle_tpu_serving_spec_k",
    "current adaptive draft length K by decode slot")

_arrival = itertools.count()


class ServingError(RuntimeError):
    """A request failed inside the engine (carried on Request.error)."""


class RequestRejected(ServingError):
    """Admission control: the request can never fit (prompt + max_new
    exceeds the pool or the length limit)."""


class Request:
    """One generation request and its runtime state (engine-owned; user
    code holds it as a handle: ``result()``, ``events``, timing fields)."""

    def __init__(self, prompt, max_new_tokens, temperature=0.0,
                 eos_token_id=None, request_id=None, on_token=None,
                 traceparent=None):
        self.prompt = [int(t) for t in prompt]
        if not self.prompt:
            raise ValueError("empty prompt")
        self.max_new_tokens = int(max_new_tokens)
        if self.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        self.temperature = float(temperature)
        self.eos_token_id = eos_token_id
        self.request_id = request_id or uuid.uuid4().hex[:12]
        self.on_token = on_token
        self.state = QUEUED
        self.tokens: list[int] = []
        self.error: str | None = None
        self.pages: list[int] = []
        # the window group's pages by logical page, the trash page where
        # one was released behind the window (or never held: a long prompt)
        self.window_pages: list[int] = []
        self.window_held = 0      # those of them that are real pages
        self.slot: int | None = None
        self.arrival = next(_arrival)
        self.evictions = 0
        # speculative-decoding state (engine-thread-owned): created at
        # admission when the engine speculates; survives eviction so a
        # re-admitted request keeps its learned acceptance EWMA
        self.spec: SpecState | None = None
        # prefill progress: context tokens whose KV is resident (prefix
        # cache hits count; chunked prefill advances it chunk by chunk)
        self.prefilled = 0
        self._prefill_target = 0     # context length at admission
        self._cached_tokens = 0      # prefix-cache hit size at admission
        self._chain_keys: list = []  # prefix-cache chain keys of that ctx
        self.events: queue.Queue = queue.Queue()
        self._done = threading.Event()
        # timing (wall seconds; ms aggregates computed at finish)
        self.t_submit = time.monotonic()
        self.t_first_token: float | None = None
        self.t_done: float | None = None
        self._t_last = None
        self.ttft_ms: float | None = None
        self.e2e_ms: float | None = None
        self.tpot_ms: list[float] = []
        # lifecycle split (scheduler queue wait vs prefill compute vs
        # decode wall) — tracked with tracing on OR off: the TTFT
        # attribution fields in the request log / summary need them
        self.queue_ms = 0.0
        self.prefill_ms = 0.0
        self.decode_ms: float | None = None
        self._t_enqueued = time.perf_counter()    # the span clock
        # request trace: NOOP_TRACE when PADDLE_TPU_TRACE=0 — hot paths
        # identity-check it before building span attributes
        self.trace = _tracing.start_request(
            request_id=self.request_id, traceparent=traceparent,
            prompt_tokens=len(self.prompt),
            max_new_tokens=self.max_new_tokens)
        self._tr_burst: dict | None = None   # engine-thread-owned
        self._stream_span = None

    # -- engine side ---------------------------------------------------------

    def context(self) -> list[int]:
        """Token ids whose KV must be resident: prompt + generated so far
        (re-prefilled wholesale after an eviction)."""
        return self.prompt + self.tokens

    def context_tail(self, n: int) -> list[int]:
        """Last ``n`` context tokens WITHOUT materializing the full
        prompt+generation concatenation — the drafter's per-step lookback
        must stay O(window), not O(context length)."""
        n = int(n)
        if n <= 0:
            return []
        if len(self.tokens) >= n:
            return self.tokens[-n:]
        return self.prompt[-(n - len(self.tokens)):] + self.tokens

    def cur_len(self) -> int:
        return len(self.prompt) + len(self.tokens)

    @property
    def prefill_done(self) -> bool:
        """True once the admission context is fully resident and the
        first token has been sampled — only then may decode pick the
        slot up."""
        return self._prefill_target > 0 and \
            self.prefilled >= self._prefill_target

    def _emit(self, token: int) -> None:
        self._emit_burst([token])

    def _emit_burst(self, toks) -> None:
        """Emit one step's generated token(s). A verify step lands up to
        K+1 accepted tokens AT ONCE — per-token latency accounting must
        count TOKENS, not steps: the gap since the previous emission is
        amortized over the burst (TPOT = time per output token), so the
        TPOT histograms and tokens_total stay truthful instead of
        silently understating throughput when speculation lands."""
        toks = [int(t) for t in toks]
        if not toks:
            return
        now = time.monotonic()
        if self.t_first_token is None:
            self.t_first_token = now
            self.ttft_ms = (now - self.t_submit) * 1000.0
            _TTFT.observe(self.ttft_ms)
            if self.trace is not _tracing.NOOP_TRACE:
                # stream-emission span: first delivered token -> finish
                self._stream_span = self.trace.span("stream")
            self.tokens.append(toks[0])
            self._deliver(toks[0])
            self._t_last = now       # burst tail gaps measure from here
            rest = toks[1:]
        else:
            rest = toks
        if rest:
            gap = (now - self._t_last) * 1000.0 / len(rest)
            for t in rest:
                self.tokens.append(t)
                self.tpot_ms.append(gap)
                _TPOT.observe(gap)
                self._deliver(t)
        self._t_last = now

    def _deliver(self, token: int) -> None:
        self.events.put(("token", token))
        if self.on_token is not None:
            try:
                self.on_token(token)
            except Exception:
                pass  # a user callback must never kill the engine loop

    def _trace_step(self, kind: str, t_start: float | None,
                    tokens: int = 1, **extra) -> None:
        """Fold one decode/verify iteration into the current span burst.
        Per-token spans would dominate tracer cost, so consecutive
        same-kind steps aggregate into ONE span until the kind changes
        or the burst cap (``PADDLE_TPU_TRACE_BURST``) is hit; numeric
        extras (proposed/accepted/rollback_pages) sum across the burst.
        ``t_start`` is the step's start on the span clock (None: now).
        Engine-thread-owned state — never touched from user threads."""
        if self.trace is _tracing.NOOP_TRACE:
            return
        if t_start is None:
            t_start = time.perf_counter()
        b = self._tr_burst
        if b is not None and b["kind"] != kind:
            self._trace_flush()
            b = None
        if b is None:
            b = self._tr_burst = {"kind": kind, "t0": t_start,
                                  "steps": 0, "tokens": 0, "extra": {}}
        b["steps"] += 1
        b["tokens"] += tokens
        for k, v in extra.items():
            b["extra"][k] = b["extra"].get(k, 0) + v
        if b["steps"] >= _tracing.decode_burst():
            self._trace_flush()

    def _trace_flush(self) -> None:
        b = self._tr_burst
        if b is None:
            return
        self._tr_burst = None
        self.trace.add_span(b["kind"], t_start=b["t0"],
                            t_end=time.perf_counter(),
                            steps=b["steps"], tokens=b["tokens"],
                            **b["extra"])

    def _finish(self, state: str, error: str | None = None) -> None:
        if self.state in _TERMINAL:
            return
        self.state = state
        self.error = error
        self.t_done = time.monotonic()
        self.e2e_ms = (self.t_done - self.t_submit) * 1000.0
        if self.t_first_token is not None:
            self.decode_ms = (self.t_done - self.t_first_token) * 1000.0
        _REQS.inc(status=state)
        if state == COMPLETED:
            _E2E.observe(self.e2e_ms)
        if self.trace is not _tracing.NOOP_TRACE:
            self._trace_flush()
            if self._stream_span is not None:
                self._stream_span.end(tokens=len(self.tokens))
                self._stream_span = None
            if state == COMPLETED:
                # exemplars: the TTFT/TPOT histograms' buckets gain a
                # trace id, so a p99 outlier names its trace
                if self.ttft_ms is not None:
                    _tracing.note_exemplar(
                        "paddle_tpu_serving_ttft_ms", self.ttft_ms,
                        self.trace.trace_id, buckets=_MS_BUCKETS)
                if self.tpot_ms:
                    _tracing.note_exemplar(
                        "paddle_tpu_serving_tpot_ms", max(self.tpot_ms),
                        self.trace.trace_id, buckets=_MS_BUCKETS)
            self.trace.finish(
                state=state, error=error,
                prompt_tokens=len(self.prompt),
                generated=len(self.tokens),
                cached_tokens=self._cached_tokens or None,
                evictions=self.evictions or None,
                ttft_ms=round(self.ttft_ms, 3)
                if self.ttft_ms is not None else None,
                queue_ms=round(self.queue_ms, 3),
                prefill_ms=round(self.prefill_ms, 3),
                decode_ms=round(self.decode_ms, 3)
                if self.decode_ms is not None else None)
        self.events.put(("error", error) if error else ("done", None))
        self._done.set()

    # -- user side -----------------------------------------------------------

    @property
    def finished(self) -> bool:
        return self.state in _TERMINAL

    def result(self, timeout: float | None = None) -> list[int]:
        """Block until terminal; generated tokens, or raises ServingError."""
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not finished in {timeout}s "
                f"(state={self.state})")
        if self.error:
            raise ServingError(self.error)
        return list(self.tokens)

    def __repr__(self):
        return (f"Request({self.request_id}, state={self.state}, "
                f"prompt={len(self.prompt)}, generated={len(self.tokens)})")


class Scheduler:
    """Admission + continuous batching over ``max_batch`` decode slots.

    ``programs`` is the engine's device side:
    ``programs.prefill(request) -> int`` (runs the bucketed prefill
    program, returns the first sampled token) and
    ``programs.decode(tokens, positions, tables, temps) -> np.ndarray``
    (one batched decode step). The scheduler owns everything else:
    queues, slots, page tables, eviction, metrics, streaming.
    """

    def __init__(self, pool, programs, max_batch: int, max_seq_len: int,
                 eos_token_id=None, prefix_cache=None,
                 prefill_chunk: int | None = None,
                 prefill_budget: int | None = None,
                 spec_k: int = 0, spec_adaptive: bool = True,
                 drafter=None, window_pool=None, window: int | None = None):
        self.pool = pool
        # the window layers' group (a model that has them): its own pool
        # and page table; a row holds there only the pages inside `window`
        self.window_pool = window_pool
        self.window = int(window) if window_pool is not None else None
        self.programs = programs
        self.max_batch = int(max_batch)
        self.max_seq_len = int(max_seq_len)
        self.max_pages = pool.pages_for(self.max_seq_len)
        self.eos_token_id = eos_token_id
        self.prefix_cache = prefix_cache
        self.chunk = int(prefill_chunk) if prefill_chunk else None
        self.prefill_budget = int(prefill_budget) \
            if prefill_budget is not None else self.chunk
        self.spec_k = int(spec_k)
        self.spec_adaptive = bool(spec_adaptive)
        self.drafter = drafter if drafter is not None else \
            (NgramDrafter() if self.spec_k else None)
        self.lock = _tsan.rlock("serving.Scheduler")
        self.waiting: list[Request] = []      # kept sorted by arrival
        self.slots: list[Request | None] = [None] * self.max_batch
        self.tables = np.zeros((self.max_batch, self.max_pages), np.int32)
        self.window_tables = None if window_pool is None \
            else np.zeros_like(self.tables)
        self.decode_steps = 0
        self.occupancy_sum = 0.0
        self.completed = 0
        self.evictions = 0
        # prefix-cache / chunked-prefill accounting (all under self.lock)
        self.prefix_page_hits = 0
        self.prefix_page_misses = 0
        self.prefix_tokens_saved = 0
        self.prompt_tokens = 0           # context tokens at admissions
        self.prefill_tokens_computed = 0
        self.cow_copies = 0
        self.chunks = 0
        # speculative-decoding accounting (under self.lock). step_tokens
        # / step_rows count (generated tokens, participating rows) over
        # BOTH decode and verify steps — their ratio is the measured
        # tokens-per-step-per-request the bench's A/B reports (1.0
        # exactly without speculation)
        self.spec_steps = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_rejected = 0
        self.step_tokens = 0
        self.step_rows = 0
        # lifecycle-split accounting (under self.lock): queue wait sums
        # at each admission; prefill/decode sums fold at completion
        self.queue_wait_ms_sum = 0.0
        self.admissions = 0
        self.prefill_ms_sum = 0.0
        self.decode_ms_sum = 0.0
        self.finished_timed = 0

    # -- submission ----------------------------------------------------------

    def submit(self, req: Request) -> Request:
        total = len(req.prompt) + req.max_new_tokens
        if total > self.max_seq_len:
            req._finish(REJECTED, None)
            raise RequestRejected(
                f"prompt ({len(req.prompt)}) + max_new_tokens "
                f"({req.max_new_tokens}) = {total} exceeds max_seq_len "
                f"{self.max_seq_len}")
        if self.pool.pages_for(total) > self.pool.allocatable:
            req._finish(REJECTED, None)
            raise RequestRejected(
                f"request needs {self.pool.pages_for(total)} pages at "
                f"full length; pool holds {self.pool.allocatable}")
        if req.eos_token_id is None:
            req.eos_token_id = self.eos_token_id
        _SUBMITS.inc()
        _TOKENS.inc(len(req.prompt), kind="prompt")
        _flight.record("serving_submit", request=req.request_id,
                       prompt=len(req.prompt), max_new=req.max_new_tokens)
        with self.lock:
            self._enqueue(req)
        return req

    def _enqueue(self, req: Request) -> None:
        """Insert keeping arrival order (evicted requests keep their
        original position in line)."""
        i = len(self.waiting)
        while i > 0 and self.waiting[i - 1].arrival > req.arrival:
            i -= 1
        self.waiting.insert(i, req)
        req.state = QUEUED
        req._t_enqueued = time.perf_counter()
        _QUEUE.set(len(self.waiting))

    # -- introspection -------------------------------------------------------

    def has_work(self) -> bool:
        with self.lock:
            return bool(self.waiting) or any(
                r is not None for r in self.slots)

    def active_requests(self) -> list[Request]:
        with self.lock:
            return [r for r in self.slots if r is not None]

    def queue_depth(self) -> int:
        with self.lock:
            return len(self.waiting)

    def prefix_hit_rate(self):
        """Token-level prefill reduction: context tokens served from the
        prefix cache / context tokens admitted (None before any
        admission or without a cache)."""
        with self.lock:
            if self.prefix_cache is None or not self.prompt_tokens:
                return None
            return self.prefix_tokens_saved / self.prompt_tokens

    def prefix_stats(self) -> dict:
        with self.lock:
            stats = {
                "page_hits": self.prefix_page_hits,
                "page_misses": self.prefix_page_misses,
                "tokens_saved": self.prefix_tokens_saved,
                "prompt_tokens": self.prompt_tokens,
                "prefill_tokens_computed": self.prefill_tokens_computed,
                "cow_copies": self.cow_copies,
                "enabled": self.prefix_cache is not None,
            }
        rate = self.prefix_hit_rate()
        stats["hit_rate"] = round(rate, 4) if rate is not None else None
        if self.prefix_cache is not None:
            stats["entries"] = len(self.prefix_cache)
        return stats

    def timing_split(self) -> dict:
        """Per-request lifecycle split: scheduler queue wait vs prefill
        compute vs decode wall — the TTFT attribution fix (queue wait
        used to be invisibly folded into TTFT). Means from the
        scheduler's own sums, p50s straight off the latency histograms
        via the registry's shared ``Histogram.quantile``. Surfaced in
        the ``/healthz`` serving payload."""
        with self.lock:
            adm, fin = self.admissions, self.finished_timed
            out = {
                "queue_wait_ms_mean": round(
                    self.queue_wait_ms_sum / adm, 3) if adm else None,
                "prefill_ms_mean": round(
                    self.prefill_ms_sum / fin, 3) if fin else None,
                "decode_ms_mean": round(
                    self.decode_ms_sum / fin, 3) if fin else None,
            }
        for key, hist in (("queue_wait_p50_ms", _QUEUE_WAIT),
                          ("ttft_p50_ms", _TTFT),
                          ("tpot_p50_ms", _TPOT)):
            q = hist.quantile(0.5)
            out[key] = round(q, 3) if q is not None else None
        return out

    def spec_acceptance_rate(self):
        """Cumulative draft acceptance (accepted/proposed), None before
        any proposal or with speculation off."""
        with self.lock:
            if not self.spec_proposed:
                return None
            return self.spec_accepted / self.spec_proposed

    def tokens_per_step(self):
        """Measured generated tokens per (decode|verify) step per
        participating request — exactly 1.0 without speculation, the
        speedup multiplier with it. None before any step."""
        with self.lock:
            if not self.step_rows:
                return None
            return self.step_tokens / self.step_rows

    def spec_stats(self) -> dict:
        with self.lock:
            stats = {
                "enabled": self.spec_k > 0,
                "spec_k": self.spec_k,
                "adaptive": self.spec_adaptive,
                "verify_steps": self.spec_steps,
                "proposed_tokens": self.spec_proposed,
                "accepted_tokens": self.spec_accepted,
                "rejected_tokens": self.spec_rejected,
            }
        rate = self.spec_acceptance_rate()
        stats["acceptance_rate"] = round(rate, 4) if rate is not None \
            else None
        tps = self.tokens_per_step()
        stats["tokens_per_step"] = round(tps, 4) if tps is not None else None
        return stats

    # -- the iteration -------------------------------------------------------

    def step(self) -> bool:
        """One scheduler iteration (admit → chunked prefill → grow/evict
        → batched decode). Returns True when any device work ran."""
        with _tracing.span("serving.step"):
            with _tracing.span("serving.admit"):
                admitted = self._admit()
            chunked = self._prefill_chunks()
            ran_decode = self._decode()
        return bool(admitted or chunked or ran_decode)

    def drain_step(self) -> bool:
        """Shutdown-drain iteration: finish chunks and decode, admission
        stays closed (the engine already aborted the queue)."""
        chunked = self._prefill_chunks()
        return bool(self._decode() or chunked)

    def _free_slot(self):
        for i, r in enumerate(self.slots):
            if r is None:
                return i
        return None

    def _claim_prefix(self, ctx, req=None):
        """(claimed_pages, chain_keys, matched_tokens) for one admission:
        claim the longest cached page-aligned prefix of ``ctx`` (page
        references taken). A FULL cover is capped at ``len(ctx) - 1``
        tokens — the last token must be recomputed because its logits
        seed generation; its KV write then copy-on-writes the shared
        tail page. Hit/miss accounting happens at ADMISSION (the claims
        here are handed back when admission fails, and the head-of-line
        request retries every iteration — counting here would inflate
        the metrics unboundedly while it waits). The chain keys are
        memoized on ``req`` so a blocked request does not re-hash its
        whole context each scheduler iteration. Called under self.lock."""
        cache = self.prefix_cache
        if cache is None:
            return [], [], 0
        if req is not None and getattr(req, "_pending_keys_len", -1) == len(ctx):
            keys = req._pending_keys
        else:
            keys = cache.keys_for(ctx)
            if req is not None:
                req._pending_keys = keys
                req._pending_keys_len = len(ctx)
        claimed = cache.claim(keys) if keys else []
        matched = len(claimed) * self.pool.page_size
        if matched >= len(ctx):
            matched = len(ctx) - 1
        return claimed, keys, matched

    def _insert_prefix(self, req: Request) -> None:
        """Register the now fully-written full pages of ``req``'s context
        so later requests can claim them."""
        cache = self.prefix_cache
        if cache is None:
            return
        n_full = req._prefill_target // self.pool.page_size
        if n_full:
            cache.insert(req._chain_keys[:n_full], req.pages[:n_full])

    def _admit(self) -> int:
        admitted = 0
        while True:
            # read for the request's `admit` span alone
            t_adm0 = time.perf_counter() if _tracing.tracing_enabled() \
                else None
            with self.lock:
                if not self.waiting:
                    break
                slot = self._free_slot()
                if slot is None:
                    break
                req = self.waiting[0]
                ctx = req.context()
                ctx_len = len(ctx)
                claimed, keys, matched = self._claim_prefix(ctx, req)
                # +1: headroom so the request's FIRST decode write (the
                # token prefill just sampled) cannot immediately evict
                need_new = self.pool.pages_for(ctx_len + 1) - len(claimed)
                if claimed and len(claimed) * self.pool.page_size >= ctx_len:
                    # full-cover cap: the recomputed last token's KV
                    # write lands MID-PAGE in a claimed page; if that
                    # page is shared, _make_writable will copy it,
                    # consuming one more page than the fresh-alloc count
                    tail = claimed[(ctx_len - 1) // self.pool.page_size]
                    if self.pool.refcount(tail) > 1:
                        need_new += 1
                # by group: the window layers need the pages from the
                # context's last window on (and the same headroom page)
                w_first = 0 if self.window_pool is None else \
                    window_first_page(ctx_len, self.window,
                                      self.pool.page_size)
                if need_new > self.pool.available_pages or (
                        self.window_pool is not None
                        and self.pool.pages_for(ctx_len + 1) - w_first
                        > self.window_pool.available_pages):
                    if claimed:        # hand the claims back (they fall
                        self.pool.free(claimed)   # to the cached state)
                    break                      # FIFO head-of-line wait
                try:
                    fresh = self.pool.alloc(
                        self.pool.pages_for(ctx_len) - len(claimed))
                except PagePoolExhausted:
                    if claimed:
                        self.pool.free(claimed)
                    break
                if self.window_pool is not None:
                    req.window_pages = [TRASH_PAGE] * w_first \
                        + self.window_pool.alloc(
                            self.pool.pages_for(ctx_len) - w_first)
                    req.window_held = len(req.window_pages) - w_first
                self.waiting.pop(0)
                _QUEUE.set(len(self.waiting))
                if self.prefix_cache is not None:
                    # admission succeeded — NOW the claim outcome counts
                    self.prefix_cache.note_result(
                        len(claimed), len(keys) - len(claimed))
                    self.prefix_page_hits += len(claimed)
                    self.prefix_page_misses += len(keys) - len(claimed)
                req.pages = claimed + fresh
                req.slot = slot
                req.prefilled = matched
                req._prefill_target = ctx_len
                req._cached_tokens = matched
                req._chain_keys = keys
                self.prefix_tokens_saved += matched
                self.prompt_tokens += ctx_len
                self.prefill_tokens_computed += ctx_len - matched
                row = self.tables[slot]
                row[:] = 0
                row[:len(req.pages)] = req.pages
                if self.window_pool is not None:
                    row = self.window_tables[slot]
                    row[:] = 0
                    row[:len(req.window_pages)] = req.window_pages
                self.slots[slot] = req
                req.state = RUNNING
                if self.spec_k and req.spec is None:
                    req.spec = SpecState(self.spec_k, self.spec_adaptive)
                wait_ms = (time.perf_counter() - req._t_enqueued) * 1000.0
                req.queue_ms += wait_ms
                self.queue_wait_ms_sum += wait_ms
                self.admissions += 1
                _ACTIVE.set(len([r for r in self.slots if r is not None]))
            _QUEUE_WAIT.observe(wait_ms)
            if req.trace is not _tracing.NOOP_TRACE:
                t_now = time.perf_counter()
                req.trace.add_span("queue_wait",
                                   t_start=req._t_enqueued, t_end=t_now)
                req.trace.add_span("admit",
                                   t_start=t_now if t_adm0 is None
                                   else t_adm0, t_end=t_now,
                                   cached_tokens=matched,
                                   claimed_pages=len(claimed),
                                   pages=len(req.pages), context=ctx_len,
                                   evictions=req.evictions)
            if matched:
                _flight.record("serving_prefix_hit", request=req.request_id,
                               pages=len(claimed), tokens=matched,
                               context=ctx_len)
            # a mid-page suffix start (full-cover cap) or any other write
            # into a shared page must copy-on-write BEFORE device work
            if not self._make_writable(req, req.prefilled,
                                       ctx_len - req.prefilled):
                continue      # req was evicted while creating headroom
            if self.chunk:
                admitted += 1     # chunked mode: device work interleaves
                continue
            n_real = ctx_len - matched
            bucket = self.programs.bucket_for(n_real)
            _PREFILL_TOKENS.inc(n_real, kind="real")
            _PREFILL_TOKENS.inc(bucket, kind="padded")
            with _tracing.span("serving.prefill",
                               request_id=req.request_id) as sp:
                sp.count(tokens=n_real, bucket=bucket)
                t_pf0 = time.perf_counter()
                try:
                    first = self.programs.prefill(req)
                except Exception as e:   # noqa: BLE001 — request-scoped
                    self._release(req)
                    req._finish(FAILED, f"prefill failed: {e!r}")
                    continue
                self._count_experts(sp)
                t_pf1 = time.perf_counter()
            req.prefill_ms += (t_pf1 - t_pf0) * 1000.0
            if req.trace is not _tracing.NOOP_TRACE:
                # step_span: the id of the step span that served it
                req.trace.add_span("prefill", t_start=t_pf0, t_end=t_pf1,
                                   tokens=n_real, cached_tokens=matched,
                                   step_span=sp.span_id)
            with self.lock:
                # the SCHEDULER owns prefill progress — a programs
                # implementation only runs device work (the engine
                # advances req.prefilled too, but a bare fake must not
                # have to), and decode may only pick the slot up once
                # this is set
                req.prefilled = req._prefill_target
                if matched:   # the suffix ran as one chunk-program call
                    self.chunks += 1
            self._finish_prefill(req, first, matched)
            admitted += 1
        return admitted

    def _finish_prefill(self, req: Request, first: int,
                        cached_tokens: int) -> None:
        """Shared tail of a completed prefill (monolithic or final
        chunk): register cacheable pages, emit the first token."""
        self._insert_prefix(req)
        _PREFILLS.inc(bucket=str(self.programs.bucket_for(
            req._prefill_target)))
        _flight.record("serving_prefill", request=req.request_id,
                       prompt=req.cur_len(), pages=len(req.pages),
                       cached_tokens=cached_tokens)
        with _tracing.span("serving.emit"):
            req._emit(first)
            _TOKENS.inc(kind="generated")
            self._maybe_complete(req)

    def _release(self, req: Request) -> None:
        """Take req out of its slot and drop its page references (a
        decref per page: shared pages stay live for their other owners,
        exclusive keyed pages fall back to the reclaimable cached
        state)."""
        req._trace_flush()        # a slot change ends the current burst
        with self.lock:
            if req.pages:
                self.pool.free(req.pages)
                req.pages = []
            if req.window_pages:
                self.window_pool.free(
                    [p for p in req.window_pages if p != TRASH_PAGE])
                req.window_pages, req.window_held = [], 0
            if req.slot is not None:
                self.tables[req.slot][:] = 0
                if self.window_tables is not None:
                    self.window_tables[req.slot][:] = 0
                self.slots[req.slot] = None
                if self.spec_k:
                    # the vacated slot no longer drafts: a stale K here
                    # would read as live speculation on an empty slot
                    _SPEC_K.set(0, slot=str(req.slot))
                req.slot = None
            req.prefilled = 0
            req._prefill_target = 0
            _ACTIVE.set(len([r for r in self.slots if r is not None]))

    def _maybe_complete(self, req: Request) -> bool:
        done_eos = (req.eos_token_id is not None and req.tokens
                    and req.tokens[-1] == req.eos_token_id)
        done_len = (len(req.tokens) >= req.max_new_tokens
                    or req.cur_len() >= self.max_seq_len)
        if done_eos or done_len:
            self._release(req)
            req._finish(COMPLETED)
            with self.lock:
                # accounting is read by stats()/health() from server
                # threads while the engine thread steps — same lock as
                # the slot tables, no torn counters
                self.completed += 1
                self.prefill_ms_sum += req.prefill_ms
                self.decode_ms_sum += req.decode_ms or 0.0
                self.finished_timed += 1
            _flight.record("serving_complete", request=req.request_id,
                           generated=len(req.tokens),
                           reason="eos" if done_eos else "length")
            return True
        return False

    def _evict(self, victim: Request) -> None:
        self._release(victim)
        victim.evictions += 1
        _EVICTIONS.inc()
        _flight.record("serving_evict", request=victim.request_id,
                       generated=len(victim.tokens))
        if victim.trace is not _tracing.NOOP_TRACE:
            now = time.perf_counter()
            victim.trace.add_span("evict", t_start=now, t_end=now,
                                  generated=len(victim.tokens),
                                  evictions=victim.evictions)
        with self.lock:
            self.evictions += 1
            self._enqueue(victim)

    def _evict_for(self, req: Request) -> bool:
        """Pool exhausted while growing/copying for ``req``: evict the
        youngest OTHER active request to make room. False when req
        itself is the youngest (or alone) — req yields and is evicted.
        Eviction only drops the victim's REFERENCES: pages shared with
        other requests stay allocated for them (the refcount-aware
        no-still-referenced-page-freed guarantee)."""
        with self.lock:
            others = [r for r in self.slots
                      if r is not None and r is not req]
        victim = max(others, key=lambda r: r.arrival, default=None)
        if victim is None or victim.arrival < req.arrival:
            self._evict(req)
            return False
        self._evict(victim)
        return True

    def _make_writable(self, req: Request, pos: int, n: int) -> bool:
        """Copy-on-write guard: every page holding positions
        ``[pos, pos + n)`` of ``req`` must be exclusively owned before a
        KV write lands there — a refcount>1 page is copied to a fresh
        page and remapped in req's table; the shared original (and its
        cache entry) stays intact for its other owners. False when req
        lost its slot while creating headroom for a copy."""
        if n <= 0:
            return req.slot is not None
        ps = self.pool.page_size
        for idx in range(pos // ps, (pos + n - 1) // ps + 1):
            while True:
                with self.lock:
                    if req.slot is None:
                        return False
                    if idx >= len(req.pages):
                        break        # not allocated yet: growth allocs fresh
                    page = req.pages[idx]
                    if self.pool.refcount(page) <= 1:
                        break        # exclusive already
                try:
                    fresh = self.pool.alloc(1)[0]
                except PagePoolExhausted:
                    if not self._evict_for(req):
                        return False
                    continue
                t_cp0 = time.perf_counter() \
                    if req.trace is not _tracing.NOOP_TRACE else None
                self.pool.copy_page(page, fresh)
                with self.lock:
                    if req.slot is None:      # evicted meanwhile
                        self.pool.free([fresh])
                        return False
                    self.pool.free([req.pages[idx]])    # drop shared ref
                    req.pages[idx] = fresh
                    self.tables[req.slot][idx] = fresh
                    self.cow_copies += 1
                _COW.inc()
                _flight.record("serving_cow", request=req.request_id,
                               src=int(page), page=int(fresh))
                if req.trace is not _tracing.NOOP_TRACE:
                    req.trace.add_span("cow", t_start=t_cp0,
                                       t_end=time.perf_counter(),
                                       src=int(page),
                                       page=int(fresh))
                break
        return True

    def _prefill_chunks(self) -> int:
        """Chunked-prefill pass: advance seated not-yet-prefilled
        requests by fixed-size chunks, oldest first, spending at most
        ``prefill_budget`` prefill tokens this iteration — the knob that
        bounds how much a decode step can be delayed by prompt work."""
        if not self.chunk:
            return 0
        budget = self.prefill_budget or self.chunk
        ran = 0
        with self.lock:
            pending = sorted(
                (r for r in self.slots
                 if r is not None and not r.prefill_done),
                key=lambda r: r.arrival)
        for req in pending:
            if budget <= 0:
                break
            with self.lock:
                if req.slot is None or req.prefill_done:
                    continue
                start = req.prefilled
                n = min(self.chunk, req._prefill_target - start, budget)
            if n <= 0:
                continue
            if not self._make_writable(req, start, n):
                continue             # evicted while making room
            bucket = self.programs.bucket_for(n)
            _PREFILL_TOKENS.inc(n, kind="real")
            _PREFILL_TOKENS.inc(bucket, kind="padded")
            with _tracing.span("serving.prefill_chunk",
                               request_id=req.request_id) as sp:
                sp.count(tokens=n, bucket=bucket)
                t_ch0 = time.perf_counter()
                try:
                    tok = self.programs.prefill_chunk(req, n)
                except Exception as e:   # noqa: BLE001 — request-scoped
                    self._release(req)
                    req._finish(FAILED, f"prefill failed: {e!r}")
                    continue
                t_ch1 = time.perf_counter()
            req.prefill_ms += (t_ch1 - t_ch0) * 1000.0
            if req.trace is not _tracing.NOOP_TRACE:
                req.trace.add_span("prefill_chunk", t_start=t_ch0,
                                   t_end=t_ch1, start=start, n=n,
                                   step_span=sp.span_id)
            budget -= n
            ran += 1
            with self.lock:
                # scheduler-owned progress (the engine advances it too;
                # idempotent either way)
                req.prefilled = max(req.prefilled, start + n)
                self.chunks += 1
            if tok is not None:      # final chunk sampled the first token
                self._finish_prefill(req, tok, req._cached_tokens)
        return ran

    def _ensure_pages(self, req: Request) -> bool:
        """Grow req's page table to cover its next write position
        (evicting the youngest active request on true exhaustion) and
        copy-on-write the write page if it is shared. False when req is
        no longer in a slot (evicted here — or already evicted as a
        VICTIM of an earlier request's growth this same iteration)."""
        if req.slot is None:
            return False
        while len(req.pages) < self.pool.pages_for(req.cur_len()):
            try:
                page = self.pool.alloc(1)[0]
            except PagePoolExhausted:
                if not self._evict_for(req):
                    return False
                continue
            with self.lock:
                req.pages.append(page)
                self.tables[req.slot][len(req.pages) - 1] = page
        if self.window_pool is not None:
            self._slide_window(req)
        # the decode write position must be exclusively owned
        return self._make_writable(req, req.cur_len() - 1, 1)

    def _slide_window(self, req: Request) -> None:
        """The window group's side of :meth:`_ensure_pages`: a page for the
        next write position, and the pages that have fallen behind the
        window (no query from here on can see a key in them) released. The
        group holds a window's pages for every slot, so it is never short."""
        with self.lock:
            while len(req.window_pages) < self.pool.pages_for(req.cur_len()):
                page = self.window_pool.alloc(1)[0]
                req.window_pages.append(page)
                req.window_held += 1
                self.window_tables[req.slot][len(req.window_pages) - 1] = page
            # only the newest pages behind the window can still be held
            i = window_first_page(req.cur_len(), self.window,
                                  self.pool.page_size) - 1
            behind = []
            while i >= 0 and req.window_pages[i] != TRASH_PAGE:
                behind.append(req.window_pages[i])
                req.window_pages[i] = TRASH_PAGE
                self.window_tables[req.slot][i] = TRASH_PAGE
                i -= 1
            if behind:
                req.window_held -= len(behind)
                self.window_pool.free(behind)

    def _masked_tables(self):
        """Page-table snapshots (global group, window group or None) for
        one batched step: empty AND
        still-prefilling slots ride with an all-zero row — their batched
        writes land on the trash page and a mid-prefill table never
        takes a write at position 0. Caller holds the lock."""
        tables = self.tables.copy()
        window = None if self.window_tables is None \
            else self.window_tables.copy()
        for i, r in enumerate(self.slots):
            if r is None or not r.prefill_done:
                tables[i][:] = 0
                if window is not None:
                    window[i][:] = 0
        return tables, window

    def _account_step(self, occ: float, emitted: int, rows: int,
                      proposed: int = 0, accepted: int = 0,
                      verify: bool = False) -> None:
        """Per-iteration accounting shared by the plain decode and
        speculative verify paths — decode_steps/occupancy plus the
        tokens-vs-rows ratio (`tokens_per_step`), and the speculative
        totals when this step ran the verify program."""
        with self.lock:
            self.decode_steps += 1
            self.occupancy_sum += occ
            self.step_tokens += emitted
            self.step_rows += rows
            if verify:
                self.spec_steps += 1
                self.spec_proposed += proposed
                self.spec_accepted += accepted
                self.spec_rejected += proposed - accepted
            if _tsan.active():
                _tsan.note_write(self, "decode_steps", self.lock)
                _tsan.note_write(self, "occupancy_sum", self.lock)
        _STEPS.inc()
        _OCC.set(occ)

    def _decode(self) -> bool:
        with self.lock:
            active = [r for r in self.slots
                      if r is not None and r.prefill_done]
        if not active:
            return False
        drafts = self._propose(active) if self.spec_k else {}
        ensured = False
        if any(drafts.values()):
            # plain decode headroom FIRST for every row (_propose covers
            # all of `active`), speculative growth after: optional draft
            # pages must never consume the last free page a neighbor
            # needs to decode (which would force an eviction
            # speculation-off would not have caused)
            for req in list(drafts.keys()):
                self._ensure_pages(req)
            ensured = True
            for req, d in list(drafts.items()):
                if d and not self._ensure_spec_pages(req, len(d)):
                    drafts[req] = []
                    # a failed span alloc wasted this row's proposal:
                    # feed the EWMA so K backs off under sustained
                    # memory pressure instead of re-paying the failed
                    # growth every iteration (the K=0 probe re-enters
                    # once pressure lifts). NOT on eviction (slot is
                    # None): a victim's learned acceptance rate says
                    # nothing about its draft quality and must survive
                    # re-admission uncorrupted
                    if req.spec is not None and req.slot is not None:
                        req.spec.update(len(d), 0)
                        _SPEC_K.set(req.spec.k, slot=str(req.slot))
            if any(drafts.values()):
                return self._spec_decode(drafts)
            # every draft was dropped: fall through to the plain decode
            # program rather than paying the (K+1)-wide verify sweep to
            # advance each row one token
        if not ensured:
            for req in list(active):
                self._ensure_pages(req)
        with self.lock:
            active = [r for r in self.slots
                      if r is not None and r.prefill_done]
            if not active:
                return False
            b = self.max_batch
            tokens = np.zeros(b, np.int32)
            positions = np.zeros(b, np.int32)
            temps = np.zeros(b, np.float32)
            for req in active:
                tokens[req.slot] = req.tokens[-1]
                positions[req.slot] = req.cur_len() - 1
                temps[req.slot] = max(req.temperature, 0.0)
            tables, window_tables = self._masked_tables()
        with _tracing.span("serving.decode") as sp:
            out = self.programs.decode(
                tokens, positions, tables, temps,
                *([] if window_tables is None else [window_tables]))
        self._count_positions(
            sp, "decode", [positions[req.slot] + 1 for req in active])
        sp.count(sampling_rows=int((temps > 0).sum()))
        self._count_experts(sp)
        self._account_step(len(active) / float(self.max_batch),
                           emitted=len(active), rows=len(active))
        with _tracing.span("serving.emit"):
            for req in active:
                req._emit(int(out[req.slot]))
                _TOKENS.inc(kind="generated")
                req._trace_step("decode", sp.t_start)
                self._maybe_complete(req)
        return True

    def _count_positions(self, sp, program: str, lengths) -> None:
        """One batched step's KV positions per layer, live (`lengths` of
        the live rows) against what the program read of the pool, on its
        step span and on the operator's counters. What the program reads
        is the engine's to say: the shapes its forward gathers or, on the
        paged decode path, the rows' lengths rounded up to the kernel's
        block (a bare fake of `programs` reads nothing)."""
        fn = getattr(self.programs, "gathered_positions", None)
        gathered = int(fn(program, lengths)) if fn is not None else 0
        live = int(sum(lengths))
        sp.count(rows=len(lengths), positions=live, gathered=gathered)
        _KV_POSITIONS.inc(live, kind="live", group="global")
        _KV_POSITIONS.inc(gathered, kind="gathered", group="global")
        if self.window_pool is None:
            return
        # the window group: what lies inside each row's window, what the
        # kernel read of it, and the pages held against whole contexts
        live = int(sum(min(int(n), self.window) for n in lengths))
        gathered = int(fn(program, lengths, window=True)) \
            if fn is not None else 0
        with self.lock:
            rows = [r for r in self.slots if r is not None]
            held = sum(r.window_held for r in rows)
            whole = sum(len(r.window_pages) for r in rows)
        sp.count(positions_window=live, gathered_window=gathered,
                 window_pages_held=held, window_pages_whole=whole)
        _KV_POSITIONS.inc(live, kind="live", group="window")
        _KV_POSITIONS.inc(gathered, kind="gathered", group="window")

    def _count_experts(self, sp) -> None:
        """On a step span of a model with routed layers: the experts that
        got a row in the program just run (the engine pulled the count out
        with the tokens) against the experts of all its routed layers."""
        counts = getattr(self.programs, "last_counts", None)
        if counts:
            sp.count(experts_hit=counts["experts_hit"],
                     experts_total=self.programs.experts_total)

    # -- speculative decoding ------------------------------------------------

    def _propose(self, active) -> dict:
        """Draft up to K tokens per active request from its own history
        (prompt-lookup n-gram matching — no model, no device work).
        Per-request adaptive K decides how much to ask for; hard caps
        keep a fully-accepted burst inside max_new_tokens and
        max_seq_len. Returns {request: [draft tokens]}."""
        drafts: dict = {}
        # a window-bounded drafter only looks at the context tail: hand
        # it just that (full history for custom drafters without one)
        window = getattr(self.drafter, "window", None)
        for req in active:
            st = req.spec
            k = st.draft_k() if st is not None else self.spec_k
            k = min(k, req.max_new_tokens - len(req.tokens) - 1,
                    self.max_seq_len - req.cur_len() - 1, self.spec_k)
            if k <= 0:
                drafts[req] = []
                continue
            hist = req.context_tail(window) if window else req.context()
            # truncate defensively: a custom drafter ignoring the k hint
            # must not overflow the verify program's static [B, K+1] slab
            drafts[req] = list(self.drafter.propose(hist, k))[:k]
        return drafts

    def _ensure_spec_pages(self, req: Request, dlen: int) -> bool:
        """Grow req's table to hold the speculative span (positions
        ``cur_len-1 .. cur_len-1+dlen``) and copy-on-write any shared
        page in it. Speculation must never cost ANOTHER request its
        slot: on pool exhaustion the span is rolled back and False is
        returned — the caller drops the drafts and the request decodes
        plainly (where the normal eviction policy applies)."""
        if req.slot is None:
            return False
        target = self.pool.pages_for(req.cur_len() + dlen)
        while len(req.pages) < target:
            try:
                page = self.pool.alloc(1)[0]
            except PagePoolExhausted:
                self._rollback(req)
                return False
            with self.lock:
                if req.slot is None:      # evicted meanwhile
                    self.pool.free([page])
                    return False
                req.pages.append(page)
                self.tables[req.slot][len(req.pages) - 1] = page
        return self._make_writable(req, req.cur_len() - 1, dlen + 1)

    def _rollback(self, req: Request) -> int:
        """Rewind speculative page growth: free pages beyond what the
        request's ACCEPTED length needs (``pages_for(cur_len)`` keeps
        the next write position's page). Freed pages were allocated
        fresh for draft positions — never claimed/shared, never keyed
        (chain hashing only ever covers accepted full context pages) —
        so the decref sends them straight back to the free list.
        Returns the number of pages rolled back (a span attribute)."""
        with self.lock:
            if req.slot is None:
                return 0
            need = self.pool.pages_for(req.cur_len())
            extra = req.pages[need:]
            if not extra:
                return 0
            del req.pages[need:]
            self.tables[req.slot][need:need + len(extra)] = 0
            self.pool.free(extra)
        _flight.record("serving_spec_rollback", request=req.request_id,
                       pages=len(extra))
        return len(extra)

    def _spec_decode(self, drafts: dict) -> bool:
        """One speculative engine iteration: write the draft span
        (COW-guarded), run the fused K+1-token verify program over the
        whole batch, emit each row's accepted tokens + correction as one
        burst, roll rejected pages back, and feed the adaptive-K state.
        Rows that drafted nothing ride along at draft_len=0 (one token,
        exactly a decode step). ``_decode`` has already secured every
        row's plain-decode pages and grown/COW'd the surviving draft
        spans — at least one row still carries drafts here."""
        with self.lock:
            active = [r for r in self.slots
                      if r is not None and r.prefill_done]
            if not active:
                return False
            b, s = self.max_batch, self.spec_k + 1
            tokens = np.zeros((b, s), np.int32)
            positions = np.zeros(b, np.int32)
            dlens = np.zeros(b, np.int32)
            temps = np.zeros(b, np.float32)
            for req in active:
                d = drafts.get(req) or []
                tokens[req.slot, 0] = req.tokens[-1]
                tokens[req.slot, 1:1 + len(d)] = d
                positions[req.slot] = req.cur_len() - 1
                dlens[req.slot] = len(d)
                temps[req.slot] = max(req.temperature, 0.0)
            tables, _ = self._masked_tables()
            n_prop = int(dlens.sum())
        _flight.record("serving_spec_propose", rows=len(active),
                       proposed=n_prop)
        with _tracing.span("serving.verify") as sp:
            out, acc = self.programs.verify(tokens, positions, dlens, tables,
                                            temps)
        self._count_positions(
            sp, "verify", [positions[req.slot] + 1 for req in active])
        sp.count(sampling_rows=int((temps > 0).sum()))
        occ = len(active) / float(self.max_batch)
        n_acc = n_emit = 0
        with _tracing.span("serving.emit"):
            for req in active:
                a = int(acc[req.slot])
                d_n = int(dlens[req.slot])
                emitted = [int(t) for t in out[req.slot, :a + 1]]
                # the burst must stop exactly where sequential decode would
                emitted = emitted[:req.max_new_tokens - len(req.tokens)]
                eos = req.eos_token_id
                if eos is not None and eos in emitted:
                    emitted = emitted[:emitted.index(eos) + 1]
                req._emit_burst(emitted)
                _TOKENS.inc(len(emitted), kind="generated")
                n_acc += a
                n_emit += len(emitted)
                st = req.spec
                if st is not None and d_n:
                    st.update(d_n, a)
                if st is not None and req.slot is not None:
                    # every step, not just drafting ones: the gauge must
                    # track adaptive K falling to 0 (and _release zeroes it
                    # when the slot empties)
                    _SPEC_K.set(st.k, slot=str(req.slot))
                rb = self._rollback(req)
                req._trace_step("speculate", sp.t_start, tokens=len(emitted),
                                proposed=d_n, accepted=a, rollback_pages=rb)
                self._maybe_complete(req)
        self._account_step(occ, emitted=n_emit, rows=len(active),
                           proposed=n_prop, accepted=n_acc, verify=True)
        if n_prop:
            _SPEC_PROPOSED.inc(n_prop)
        if n_acc:
            _SPEC_ACCEPTED.inc(n_acc)
        if n_prop - n_acc:
            _SPEC_REJECTED.inc(n_prop - n_acc)
        # windowed deltas, not rate()/rate(): the two counters snapshot
        # their window bases on independent ticks, so a ratio of rates
        # (each divided by its OWN elapsed) can read > 1; clamp for the
        # residual base-tick skew
        prop_delta = _SPEC_PROPOSED.delta(60.0)
        if prop_delta > 0:
            _SPEC_RATE.set(round(
                min(1.0, _SPEC_ACCEPTED.delta(60.0) / prop_delta), 4))
        _flight.record("serving_spec_verify", accepted=n_acc,
                       rejected=n_prop - n_acc, emitted=n_emit)
        return True

    # -- shutdown ------------------------------------------------------------

    def abort_queued(self, error: str) -> int:
        with self.lock:
            doomed, self.waiting = self.waiting, []
            _QUEUE.set(0)
        for req in doomed:
            req._finish(FAILED, error)
        return len(doomed)

    def abort_active(self, error: str) -> int:
        n = 0
        for req in self.active_requests():
            self._release(req)
            req._finish(FAILED, error)
            n += 1
        return n
