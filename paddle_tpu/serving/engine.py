"""LLMEngine: the threaded serving front over the paged-KV scheduler.

Owns the device side of the runtime: the ONE compiled decode-step program
(static ``[max_batch]`` shapes over the paged pool — joins, leaves, and
growth never retrace it) and the bucketed prefill program (one compiled
signature per prompt bucket, prompt length traced so every length in a
bucket shares the program). Both are ``to_static`` functions, so the
repo's jit telemetry (``paddle_tpu_jit_trace_cache_*`` labeled
``fn="serving.decode_step"`` / ``"serving.prefill"``) counts their
compiles and retraces (:meth:`LLMEngine.program_stats`; the benchmark's
``compiles_in_window.serve`` reads the same counters) — and the page pool
+ model weights thread through them as state.

User surface::

    engine = LLMEngine(model, ServingConfig(max_batch=8))
    req = engine.submit([1, 2, 3], max_new_tokens=16)    # non-blocking
    for tok in engine.stream([1, 2, 3]):                  # token stream
        ...
    toks = engine.generate([1, 2, 3])                     # blocking
    engine.shutdown(drain=True)

A background thread runs scheduler iterations whenever work exists.
``install_preemption()`` arms SIGTERM/SIGINT to drain in-flight requests,
dump the flight recorder (reason ``serving_preempted``), shut the
telemetry server down and exit 143 — the serving analog of the training
preemption handler, gated by the chaos serving profile.
"""

from __future__ import annotations

import signal
import threading
import time
from dataclasses import dataclass, replace

import numpy as np

from ..analysis.concurrency import tsan as _tsan
from ..autograd.grad_mode import no_grad
from ..core.tensor import Tensor
from ..jit.api import to_static
from ..observability import counter as _obs_counter
from ..observability import flight as _flight
from ..observability import tracing as _tracing
from .kv_cache import PagePool
from .model import ServingModel
from .scheduler import Request, Scheduler, ServingError

__all__ = ["ServingConfig", "LLMEngine", "DECODE_PROGRAM",
           "PREFILL_PROGRAM", "CHUNK_PROGRAM", "VERIFY_PROGRAM"]

#: telemetry labels of the compiled programs (paddle_tpu_jit_* counters)
DECODE_PROGRAM = "serving.decode_step"
PREFILL_PROGRAM = "serving.prefill"
CHUNK_PROGRAM = "serving.prefill_chunk"
VERIFY_PROGRAM = "serving.spec_verify"

_CHUNKS = _obs_counter("paddle_tpu_serving_prefill_chunks_total",
                       "chunked-prefill program runs (incl. cache-hit "
                       "suffix chunks)")


@dataclass
class ServingConfig:
    """Static knobs of the serving runtime. Everything here shapes a
    compiled program or the pool — per-request variation (prompt length,
    max_new_tokens, temperature) rides in VALUES, never in shapes."""
    page_size: int = 16          # token positions per KV page
    num_pages: int = 64          # pool pages incl. the reserved trash page
    #                              (a model with window layers: the pages of
    #                              the GLOBAL group, every page of a row; the
    #                              window group holds what max_batch rows can
    #                              hold at once, so it never runs out)
    max_batch: int = 8           # decode slots (the continuous batch)
    max_seq_len: int | None = None   # default: model max_position_embeddings
    prefill_buckets: tuple | None = None  # default: powers of two
    max_new_tokens: int = 32     # per-request default
    temperature: float = 0.0     # per-request default (0 = greedy)
    top_k: int | None = None     # static sampling filter (compiled in)
    eos_token_id: int | None = None
    quant: str | None = None     # None | weight_only_int8 | weight_only_int4
    quant_group_size: int = -1
    fused_block: bool = True     # a plain layer's two residual junctions
    #                              (projection output -> residual add ->
    #                              rmsnorm) each one block_decode_epilogue
    #                              kernel, in every program (TPU; shape-
    #                              static, zero-retrace preserved)
    prefix_cache: bool = True    # copy-on-write KV page sharing across
    #                              requests with a common prompt prefix
    prefill_chunk: int | None = None   # tokens per prefill chunk: chunks
    #                              interleave with decode steps so a long
    #                              prompt cannot stall in-flight TPOT
    #                              (None = monolithic one-shot prefill)
    prefill_budget: int | None = None  # max prefill tokens per engine
    #                              iteration (default: one chunk's worth)
    spec_k: int = 0              # speculative decoding: max draft tokens
    #                              per request per step (n-gram prompt-
    #                              lookup drafting + one fused K+1-token
    #                              verify program; 0 = off, decode
    #                              program untouched)
    spec_adaptive: bool = True   # shrink/grow per-request K on the
    #                              measured acceptance-rate EWMA (K=0
    #                              falls back to plain decode)
    dtype: str = "float32"       # KV pool dtype
    seed: int = 0
    donate_state: bool = False   # donate pool/weights into the programs
    flight_every: int = 50       # decode-step flight event cadence
    drain_timeout_s: float = 30.0


def _auto_buckets(max_seq_len: int) -> tuple:
    out, b = [], 8
    while b < max_seq_len:
        out.append(b)
        b *= 2
    out.append(max_seq_len)
    return tuple(sorted(set(out)))


class LLMEngine:
    """Continuous-batching serving engine over a paged KV cache."""

    def __init__(self, model, config: ServingConfig | None = None,
                 **overrides):
        cfg = config or ServingConfig()
        if overrides:
            cfg = replace(cfg, **overrides)
        self.config = cfg
        self._sm = ServingModel(model, quant=cfg.quant,
                                quant_group_size=cfg.quant_group_size,
                                fused_block=cfg.fused_block)
        max_seq = cfg.max_seq_len or self._sm.max_pos
        if max_seq > self._sm.max_pos:
            raise ValueError(
                f"max_seq_len {max_seq} exceeds the model's "
                f"max_position_embeddings {self._sm.max_pos}")
        self.max_seq_len = int(max_seq)
        sm = self._sm
        if not sm.plain:
            # what each of these would need first stands in ROADMAP B4
            refused = [
                ("prefix_cache=True", cfg.prefix_cache,
                 "one sharing rule for every layer cannot hold where window "
                 "pages are freed behind the window, and a hit's suffix runs "
                 "as a prefill chunk" if sm.window else
                 "a hit's suffix runs as a prefill chunk"),
                (f"prefill_chunk={cfg.prefill_chunk}",
                 cfg.prefill_chunk is not None,
                 "the chunk program knows the plain Llama layer and one "
                 "page table alone"),
                (f"spec_k={cfg.spec_k}", cfg.spec_k > 0,
                 "the verify program knows the plain Llama layer and one "
                 "page table alone")]
            for what, asked, why in refused:
                if asked:
                    raise ValueError(
                        f"{what} is refused for {type(model).__name__}: "
                        f"{why}")
        kv = dict(num_kv_heads=sm.n_kv, page_size=cfg.page_size,
                  head_dim=sm.head_dim, dtype=cfg.dtype)
        # pages by layer kind: the global layers keep every page of a row,
        # the window layers those inside the window (each group its own
        # tensors, allocator and page table)
        self.pool = PagePool(num_layers=sm.n_global_layers,
                             num_pages=cfg.num_pages, **kv)
        self.window_pool = None
        if sm.window:
            from .kv_cache import window_pages
            self.window_pool = PagePool(
                num_layers=sm.n_window_layers,
                num_pages=1 + cfg.max_batch
                * window_pages(sm.window, cfg.page_size), **kv)
        sm.bind_pool(self.pool, self.window_pool)
        if cfg.prefill_chunk is not None and cfg.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 tokens, got {cfg.prefill_chunk}")
        if cfg.prefill_budget is not None and cfg.prefill_budget < 1:
            raise ValueError(
                f"prefill_budget must be >= 1 tokens, got {cfg.prefill_budget}")
        if cfg.prefill_budget is not None and cfg.prefill_chunk is None:
            raise ValueError(
                "prefill_budget only caps CHUNKED prefill — set "
                "prefill_chunk too (monolithic prefill cannot be budgeted)")
        if cfg.spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {cfg.spec_k}")
        if cfg.spec_k >= self.max_seq_len:
            raise ValueError(
                f"spec_k {cfg.spec_k} >= max_seq_len {self.max_seq_len}: "
                f"a draft span could never fit a sequence")
        self.prefix_cache = None
        if cfg.prefix_cache:
            from .prefix_cache import PrefixCache, model_fingerprint
            self.prefix_cache = PrefixCache(
                self.pool, model_fingerprint(
                    model, quant=cfg.quant,
                    quant_group_size=cfg.quant_group_size,
                    dtype=cfg.dtype, page_size=cfg.page_size))
        self.scheduler = Scheduler(self.pool, self, cfg.max_batch,
                                   self.max_seq_len,
                                   eos_token_id=cfg.eos_token_id,
                                   prefix_cache=self.prefix_cache,
                                   prefill_chunk=cfg.prefill_chunk,
                                   prefill_budget=cfg.prefill_budget,
                                   spec_k=cfg.spec_k,
                                   spec_adaptive=cfg.spec_adaptive,
                                   window_pool=self.window_pool,
                                   window=sm.window)
        self.buckets = tuple(sorted(cfg.prefill_buckets)) \
            if cfg.prefill_buckets else _auto_buckets(self.max_seq_len)
        if self.buckets[-1] < self.max_seq_len:
            raise ValueError(
                f"largest prefill bucket {self.buckets[-1]} < max_seq_len "
                f"{self.max_seq_len}: long prompts would have no program")
        import jax
        self._key_t = Tensor(np.asarray(
            jax.random.PRNGKey(cfg.seed), dtype=np.uint32))
        self._step_seq = 0
        self.last_counts: dict = {}
        self._prog_base = self._raw_program_stats()
        self._build_programs()

        self._cond = _tsan.condition("serving.LLMEngine")
        self._thread: threading.Thread | None = None
        self._stop_mode: str | None = None
        self._drain_deadline = 0.0
        self._t_started: float | None = None
        self._last_step_wall: float | None = None
        self._old_handlers: dict = {}
        # preemption plumbing: the SIGNAL handler only writes
        # _preempt_code and waits on _drained; the engine thread sees the
        # flag within one loop tick, drains, dumps, and sets the event
        self._preempt_code: int | None = None
        self._drained = threading.Event()
        # open-span snapshot taken on the engine thread when the drain
        # arms: the post-drain flight dump must still carry the spans
        # that were in flight AT the signal, not after draining
        self._preempt_spans: list | None = None

    # -- compiled programs ---------------------------------------------------

    def _build_programs(self):
        sm, eng = self._sm, self

        def with_counts(nxt):
            # the forward's counts (experts hit) ride out behind the tokens
            import jax.numpy as jnp
            counts = sm.take_counts()
            return Tensor(nxt if counts is None
                          else jnp.concatenate([nxt, counts]))

        def serving_decode_step(tokens, positions, tables, temps, key,
                                step, *window_tables):
            with no_grad():
                logits = sm.decode_forward(tokens, positions, tables,
                                           *window_tables)
            return with_counts(eng._sample(logits._data, temps._data,
                                           key._data, step._data))

        serving_decode_step.__qualname__ = DECODE_PROGRAM
        self._decode_sf = to_static(serving_decode_step,
                                    donate_state=self.config.donate_state)

        def serving_prefill(tokens, prompt_len, table_row, temp, key,
                            step, *window_row):
            with no_grad():
                logits = sm.prefill_forward(tokens, prompt_len, table_row,
                                            *window_row)
            return with_counts(eng._sample(
                logits._data, temp._data.reshape(1), key._data, step._data))

        serving_prefill.__qualname__ = PREFILL_PROGRAM
        self._prefill_sf = to_static(serving_prefill,
                                     donate_state=self.config.donate_state)

        def serving_prefill_chunk(tokens, start, chunk_len, table_row,
                                  temp, key, step):
            with no_grad():
                logits = sm.prefill_chunk_forward(tokens, start, chunk_len,
                                                  table_row)
            nxt = eng._sample(logits._data, temp._data.reshape(1),
                              key._data, step._data)
            return Tensor(nxt)

        serving_prefill_chunk.__qualname__ = CHUNK_PROGRAM
        self._chunk_sf = to_static(serving_prefill_chunk,
                                   donate_state=self.config.donate_state)

        # speculative verify: ONE program scoring all K+1 positions of a
        # draft hypothesis per batch row in a single forward. Static
        # [max_batch, spec_k + 1] shapes; positions / draft lengths /
        # tables / temps ride as values — like the decode program it
        # compiles once and never retraces across join/leave/variable
        # acceptance. Built only when speculation is configured: a
        # spec_k=0 engine's decode path is byte-identical to before.
        self._verify_sf = None
        if self.config.spec_k > 0:
            import jax.numpy as jnp

            from . import speculative as _spec

            def serving_spec_verify(tokens, positions, dlens, tables,
                                    temps, key, step):
                with no_grad():
                    logits = sm.verify_forward(tokens, positions, dlens,
                                               tables)
                out, acc = _spec.verify_tokens(
                    logits._data, tokens._data[:, 1:], dlens._data,
                    temps._data, key._data, step._data,
                    top_k=eng.config.top_k)
                return Tensor(jnp.concatenate([out, acc[:, None]], axis=1))

            serving_spec_verify.__qualname__ = VERIFY_PROGRAM
            self._verify_sf = to_static(
                serving_spec_verify, donate_state=self.config.donate_state)

    def _sample(self, logits, temps, key, step):
        """On-device next-token selection: greedy where temp == 0, else
        temperature (+ static top_k) gumbel sampling. logits [N, V],
        temps [N]; returns int32 [N]. The noise is drawn only in a step
        that has a sampling row (a batch of greedy rows pays one argmax),
        and then in float32 as the logits it is added to: the process's
        x64 would make the draw float64, which the chip emulates. The
        scaling/filtering step is shared with the speculative verify
        acceptance — the spec-on == spec-off exactness guarantee depends
        on the two never drifting."""
        import jax
        import jax.numpy as jnp

        from .speculative import scaled_filtered_logits

        with jax.named_scope("head_sample"):
            last = logits.ndim - 1
            greedy = jax.lax.argmax(logits, last, jnp.int32)

            def with_noise():
                arr = scaled_filtered_logits(logits, temps,
                                             self.config.top_k)
                kk = jax.random.fold_in(key, step.astype(jnp.uint32))
                g = jax.random.gumbel(kk, arr.shape, dtype=jnp.float32)
                sampled = jax.lax.argmax(arr + g, last, jnp.int32)
                return jnp.where(temps > 0, sampled, greedy)

            return jax.lax.cond(jnp.any(temps > 0), with_noise,
                                lambda: greedy)

    # -- programs interface the scheduler drives -----------------------------

    def bucket_for(self, n: int) -> int:
        for b in self.buckets:
            if b >= n:
                return b
        raise ServingError(f"no prefill bucket holds length {n} "
                           f"(buckets={self.buckets})")

    def prefill(self, req: Request) -> int:
        """Whole-context prefill for one admission. With a prefix-cache
        hit (``req.prefilled > 0``) only the SUFFIX is computed — one
        chunk-program call over ``context[prefilled:]`` against the
        claimed pages; otherwise the monolithic bucketed program runs as
        before. Returns the first sampled token."""
        import paddle_tpu as paddle
        ctx = req.context()
        if req.prefilled:
            tok = self.prefill_chunk(req, len(ctx) - req.prefilled)
            assert tok is not None      # suffix == final chunk
            return tok
        bucket = self.bucket_for(len(ctx))
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :len(ctx)] = ctx
        def row_of(pages):
            row = np.zeros(self.scheduler.max_pages, np.int32)
            row[:len(pages)] = pages
            return paddle.to_tensor(row)

        step = self._step_seq
        self._step_seq += 1
        out = self._prefill_sf(
            paddle.to_tensor(toks),
            paddle.to_tensor(np.int32(len(ctx))),
            row_of(req.pages),
            paddle.to_tensor(np.float32(max(req.temperature, 0.0))),
            self._key_t,
            paddle.to_tensor(np.int32(step)),
            *([row_of(req.window_pages)] if self.window_pool else []))
        self._last_step_wall = time.time()
        req.prefilled = len(ctx)
        return self._tokens_of(out, 1)[0]

    def prefill_chunk(self, req: Request, n: int):
        """Run ONE chunk of ``req``'s prefill: ``n`` context tokens from
        position ``req.prefilled``, padded to the power-of-2 bucket (the
        same bucket machinery as monolithic prefill — ``start`` and the
        valid length ride as traced values, so every chunk of a bucket
        shares one compiled signature). Returns the first sampled token
        when this was the final chunk, else None."""
        import paddle_tpu as paddle
        ctx = req.context()
        n = int(n)
        start = req.prefilled
        bucket = self.bucket_for(n)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :n] = ctx[start:start + n]
        row = np.zeros(self.scheduler.max_pages, np.int32)
        row[:len(req.pages)] = req.pages
        step = self._step_seq
        self._step_seq += 1
        out = self._chunk_sf(
            paddle.to_tensor(toks),
            paddle.to_tensor(np.int32(start)),
            paddle.to_tensor(np.int32(n)),
            paddle.to_tensor(row),
            paddle.to_tensor(np.float32(max(req.temperature, 0.0))),
            self._key_t,
            paddle.to_tensor(np.int32(step)))
        self._last_step_wall = time.time()
        req.prefilled = start + n
        _CHUNKS.inc()
        if req.prefilled >= len(ctx):
            return int(np.asarray(out.numpy()).reshape(-1)[0])
        return None

    @property
    def experts_total(self) -> int:
        """Experts of all the model's routed layers: what `experts_hit` of
        a step span is counted against."""
        return self._sm.routed_layers * self._sm.n_experts

    def _tokens_of(self, out, n: int):
        """The first `n` values of a program's output as token ids; what
        rides behind them (`ServingModel.take_counts`) is kept as
        :attr:`last_counts` for the scheduler's step span."""
        arr = np.asarray(out.numpy()).reshape(-1)
        self.last_counts = {"experts_hit": int(arr[n])} if arr.size > n \
            else {}
        return arr[:n]

    def gathered_positions(self, program: str, lengths=(),
                           window: bool = False) -> int:
        """Positions of the pool that one call of `program` ("decode",
        "verify", "chunk") reads per layer for live rows holding
        `lengths` positions each (0 before its first call). A page-table
        gather reads every slot of every table row whatever is live, from
        the shapes its forward gathers; the paged decode kernel reads each
        live row's positions rounded up to its block and, in a layer of the
        `window` group, from the block that holds the first position inside
        the row's window on."""
        whole, block = self._sm.gathered.get(program, (0, 0))
        if not block:
            return whole
        n = np.asarray(lengths, np.int64)
        first = np.maximum(n - self._sm.window, 0) // block if window \
            else 0
        return int(((-(-n // block) - first) * block).sum())

    def decode(self, tokens, positions, tables, temps, window_tables=None):
        import paddle_tpu as paddle
        step = self._step_seq
        self._step_seq += 1
        with _tracing.span("engine.upload"):
            args = (paddle.to_tensor(tokens), paddle.to_tensor(positions),
                    paddle.to_tensor(tables), paddle.to_tensor(temps),
                    self._key_t, paddle.to_tensor(np.int32(step)))
            if window_tables is not None:
                args += (paddle.to_tensor(window_tables),)
        with _tracing.span("engine.dispatch"):
            out = self._decode_sf(*args)
        self._last_step_wall = time.time()
        if _flight.enabled() and self.scheduler.decode_steps % \
                max(1, self.config.flight_every) == 0:
            _flight.record("serving_decode",
                           step=self.scheduler.decode_steps,
                           active=len(self.scheduler.active_requests()),
                           free_pages=self.pool.free_pages)
        with _tracing.span("engine.pull"):   # device time + the copy back
            return self._tokens_of(out, len(tokens))

    def verify(self, tokens, positions, dlens, tables, temps):
        """One speculative verify step: tokens ``[B, spec_k+1]`` (last
        emitted token + drafts per row), positions/dlens/temps ``[B]``,
        tables ``[B, max_pages]``. Returns ``(out_tokens [B, spec_k+1],
        accepted [B])`` — row ``b`` emits ``out_tokens[b, :accepted[b]+1]``
        (accepted drafts + one correction/bonus token)."""
        import paddle_tpu as paddle
        step = self._step_seq
        self._step_seq += 1
        with _tracing.span("engine.upload"):
            args = (paddle.to_tensor(tokens), paddle.to_tensor(positions),
                    paddle.to_tensor(dlens), paddle.to_tensor(tables),
                    paddle.to_tensor(temps), self._key_t,
                    paddle.to_tensor(np.int32(step)))
        with _tracing.span("engine.dispatch"):
            out = self._verify_sf(*args)
        self._last_step_wall = time.time()
        with _tracing.span("engine.pull"):
            arr = np.asarray(out.numpy())
        return arr[:, :-1], arr[:, -1]

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> "LLMEngine":
        with self._cond:
            if self._thread is not None and self._thread.is_alive():
                return self
            self._stop_mode = None
            self._t_started = time.time()
            self._thread = threading.Thread(
                target=self._loop, name="paddle-tpu-serving", daemon=True)
            self._thread.start()
        return self

    @property
    def running(self) -> bool:
        return self._thread is not None and self._thread.is_alive()

    def _loop(self):
        sched = self.scheduler
        while True:
            if self._preempt_code is not None and self._stop_mode is None:
                # signal-requested drain: the handler only set a flag
                # (async-signal context may not take locks); the heavy
                # lifting happens here, on the engine thread
                armed = False
                with self._cond:
                    if self._stop_mode is None:
                        self._drain_deadline = time.monotonic() + \
                            self.config.drain_timeout_s
                        self._stop_mode = "drain"
                        armed = True
                if armed:
                    # engine thread, not the signal handler (CS102):
                    # tracer locks are safe to take here
                    try:
                        self._preempt_spans = _tracing.open_spans()
                    except Exception:
                        self._preempt_spans = None
            with self._cond:
                while self._stop_mode is None and not sched.has_work():
                    self._cond.wait(0.05)
                    if self._preempt_code is not None:
                        break
                mode = self._stop_mode
            if mode is None and self._preempt_code is not None:
                continue    # arm the drain at the top of the loop
            if mode == "abort":
                break
            if mode == "drain":
                sched.abort_queued("engine draining (shutdown)")
                if not sched.active_requests() or \
                        time.monotonic() > self._drain_deadline:
                    break
                try:
                    # chunk + decode: a mid-prefill request must finish
                    # its chunks to drain, admission stays closed
                    sched.drain_step()
                except Exception as e:   # noqa: BLE001
                    self._engine_error(e)
                    break
                continue
            try:
                sched.step()
            except Exception as e:       # noqa: BLE001
                self._engine_error(e)
                break
        if self._preempt_code is not None:
            self._finish_preemption()

    def _finish_preemption(self):
        """Post-drain bookkeeping of a signal-requested shutdown, on the
        engine thread: fail leftovers, dump the black box, close the
        telemetry server, then release the waiting signal handler."""
        try:
            self._finalize(drain=True)
            extra = {"serving": self.stats()}
            if self._preempt_spans is not None:
                extra["tracing_at_preempt"] = {
                    "open_spans": self._preempt_spans}
            _flight.dump("serving_preempted",
                         step=self.scheduler.decode_steps,
                         extra=extra)
            try:
                from ..observability.continuous import shutdown_server
                shutdown_server()
            except Exception:
                pass
        finally:
            self._drained.set()

    def _engine_error(self, e: Exception):
        """A device/program failure is engine-fatal: every request is
        failed loudly rather than left hanging."""
        msg = f"serving engine error: {type(e).__name__}: {e}"
        _flight.record("serving_engine_error", error=repr(e)[:300])
        self.scheduler.abort_active(msg)
        self.scheduler.abort_queued(msg)
        with self._cond:
            self._stop_mode = "abort"

    def shutdown(self, drain: bool = True,
                 timeout: float | None = None) -> dict:
        """Stop the engine. ``drain=True`` finishes in-flight requests
        (bounded by ``timeout``/config drain_timeout_s) and fails queued
        ones; ``drain=False`` fails everything immediately. Returns a
        summary dict; always leaves the pool leak-free."""
        timeout = self.config.drain_timeout_s if timeout is None \
            else float(timeout)
        with self._cond:
            self._drain_deadline = time.monotonic() + timeout
            self._stop_mode = "drain" if drain else "abort"
            self._cond.notify_all()
        if self._thread is not None and self._thread.is_alive() and \
                threading.current_thread() is not self._thread:
            self._thread.join(timeout + 5.0)
            if self._thread.is_alive():
                import warnings
                warnings.warn(
                    f"serving engine thread did not exit within "
                    f"{timeout + 5.0:.1f}s of shutdown (a decode step "
                    f"may be wedged); failing requests anyway",
                    RuntimeWarning, stacklevel=2)
        return self._finalize(drain)

    def _finalize(self, drain: bool) -> dict:
        """Fail whatever remains, assert pool accounting, record the
        drain event; shared by shutdown() and the preemption path."""
        n_queued = self.scheduler.abort_queued("engine shut down")
        n_active = self.scheduler.abort_active(
            "engine shut down before completion" if not drain
            else "drain timeout exceeded")
        leaked = sum(p.leaked() for p in self._pools())
        summary = {"drained": drain, "failed_queued": n_queued,
                   "failed_active": n_active,
                   "completed": self.scheduler.completed,
                   "pages_leaked": leaked}
        _flight.record("serving_drain", **summary)
        return summary

    def _pools(self):
        """The cache's groups: global, then window where the model has it."""
        return [p for p in (self.pool, self.window_pool) if p is not None]

    def close(self):
        self.shutdown(drain=False)

    def __enter__(self) -> "LLMEngine":
        return self.start()

    def __exit__(self, *exc):
        self.shutdown(drain=exc == (None, None, None))
        return False

    # -- request surface -----------------------------------------------------

    def submit(self, prompt_ids, max_new_tokens: int | None = None,
               temperature: float | None = None, eos_token_id=None,
               request_id: str | None = None, on_token=None,
               traceparent: str | None = None) -> Request:
        """Enqueue one request (auto-starts the engine thread). Raises
        :class:`RequestRejected` when the request can never fit.
        ``traceparent`` joins an inbound W3C trace context (malformed
        values are ignored — the request gets a fresh trace)."""
        cfg = self.config
        req = Request(
            prompt_ids,
            cfg.max_new_tokens if max_new_tokens is None else max_new_tokens,
            cfg.temperature if temperature is None else temperature,
            eos_token_id=eos_token_id, request_id=request_id,
            on_token=on_token, traceparent=traceparent)
        self.scheduler.submit(req)
        self.start()
        with self._cond:
            self._cond.notify_all()
        return req

    def stream(self, prompt_ids, timeout: float = 300.0, **kw):
        """Generator of generated token ids; raises ServingError on a
        failed request, TimeoutError when no token arrives within
        ``timeout`` seconds."""
        import queue as _queue
        req = self.submit(prompt_ids, **kw)
        while True:
            try:
                kind, val = req.events.get(timeout=timeout)
            except _queue.Empty:
                raise TimeoutError(
                    f"request {req.request_id} produced no token in "
                    f"{timeout}s (state={req.state})") from None
            if kind == "token":
                yield val
            elif kind == "done":
                return
            else:
                raise ServingError(val)

    def generate(self, prompt_ids, timeout: float = 300.0, **kw):
        """Blocking convenience: submit + result."""
        return self.submit(prompt_ids, **kw).result(timeout)

    # -- introspection -------------------------------------------------------

    @staticmethod
    def _raw_program_stats() -> dict:
        import paddle_tpu.observability as obs

        def one(label):
            return {
                "discoveries": int(obs.value(
                    "paddle_tpu_jit_trace_cache_misses_total", fn=label)),
                "compiles": int(obs.value(
                    "paddle_tpu_jit_compiles_total", fn=label)),
                "retraces": int(obs.value(
                    "paddle_tpu_jit_trace_cache_retraces_total", fn=label)),
            }

        return {"decode": one(DECODE_PROGRAM),
                "prefill": one(PREFILL_PROGRAM),
                "chunk": one(CHUNK_PROGRAM),
                "verify": one(VERIFY_PROGRAM)}

    def program_stats(self) -> dict:
        """Trace/compile/retrace counts of THIS engine's compiled
        programs — the jit telemetry labels are shared process-wide, so
        counts are deltas since engine construction (the bench's
        zero-retrace proof reads this) — and, under ``path``, which
        kernel or composite each traced program's stages took."""
        raw = self._raw_program_stats()
        return {prog: dict({k: v - self._prog_base[prog][k]
                            for k, v in vals.items()},
                           path=dict(self._sm.paths.get(prog, {})))
                for prog, vals in raw.items()}

    def stats(self) -> dict:
        sched = self.scheduler
        steps = sched.decode_steps
        groups = self._page_stats()
        return {
            "queue_depth": sched.queue_depth(),
            "active_requests": len(sched.active_requests()),
            "max_batch": sched.max_batch,
            "decode_steps": steps,
            "completed": sched.completed,
            "evictions": sched.evictions,
            "occupancy_mean": (sched.occupancy_sum / steps) if steps else 0.0,
            # summed over the cache's groups; `groups` gives each
            "pages": dict(
                {k: sum(g[k] for g in groups.values())
                 for k in ("free", "used", "cached", "shared", "lost",
                           "total")}, groups=groups),
            "prefix_cache": sched.prefix_stats(),
            "prefill_chunks": sched.chunks,
            "speculative": sched.spec_stats(),
            "programs": self.program_stats(),
        }

    def _page_stats(self) -> dict:
        names = ("global", "window")
        return {name: {"free": p.free_pages, "used": p.used_pages,
                       "cached": p.cached_pages, "shared": p.shared_pages,
                       "lost": p.lost(), "total": p.allocatable}
                for name, p in zip(names, self._pools())}

    def health(self, stall_after_s: float = 120.0) -> tuple[int, dict]:
        """Serving liveness: (http_code, payload). Healthy while idle;
        stalled (503) when work exists but no prefill/decode step has run
        within ``stall_after_s``."""
        import paddle_tpu.observability as obs
        sched = self.scheduler
        active = len(sched.active_requests())
        depth = sched.queue_depth()
        busy = bool(active or depth)
        ref = self._last_step_wall or self._t_started
        age = (time.time() - ref) if ref is not None else None
        if not busy:
            status = "idle"
        elif age is None:
            status = "stalled" if not self.running else "starting"
        else:
            status = "ok" if age <= stall_after_s else "stalled"
        reg = obs.get_registry()
        tok = reg.get("paddle_tpu_serving_tokens_total")
        payload = {
            "mode": "serving",
            "status": status,
            "decode_steps": sched.decode_steps,
            "last_step_age_s": round(age, 3) if age is not None else None,
            "stall_after_s": stall_after_s,
            "active_requests": active,
            "queue_depth": depth,
            "tokens_per_s": round(
                tok.rate(60.0, kind="generated"), 4) if tok else 0.0,
            "kv_pages_free": self.pool.free_pages,
            "kv_pages_used": self.pool.used_pages,
            "kv_pages_cached": self.pool.cached_pages,
            "prefix_hit_rate": sched.prefix_hit_rate(),
            "spec_acceptance_rate": sched.spec_acceptance_rate(),
            # TTFT attribution: queue wait vs prefill vs decode means
            "timing_split": sched.timing_split(),
        }
        return (503 if status == "stalled" else 200), payload

    # -- preemption ----------------------------------------------------------

    def install_preemption(self, exit_code: int = 143,
                           signals=(signal.SIGTERM,)) -> "LLMEngine":
        """Arm signal-driven drain: on SIGTERM the engine drains (or
        cleanly errors) in-flight requests, dumps the flight recorder
        (reason ``serving_preempted``), shuts the telemetry server down
        and exits ``exit_code`` — the chaos serving profile's contract.

        The handler body is async-signal-safe by construction (CS102):
        it records a flight event (lock-free), writes one attribute, and
        waits — bounded — for the ENGINE thread to do the draining,
        dumping and server shutdown. Taking the engine condition or the
        scheduler lock here would deadlock whenever the signal lands
        while the interrupted main-thread frame holds it."""

        def _handler(signum, frame):
            _flight.record("serving_preempt", signum=int(signum))
            self._preempt_code = int(exit_code)
            # slice the wait so an engine thread that exits WITHOUT
            # running the preemption tail (its loop passed the flag
            # check just before the signal landed) is noticed within
            # one slice instead of burning the whole drain window
            deadline = time.monotonic() + \
                self.config.drain_timeout_s + 30.0
            drained = False
            last_steps = self.scheduler.decode_steps
            stalled = 0
            while time.monotonic() < deadline:
                if self._drained.wait(0.2):
                    drained = True
                    break
                if not self.running:
                    break
                steps = self.scheduler.decode_steps
                if steps == last_steps:
                    stalled += 1
                    if stalled >= 50:
                        # ~10s with ZERO decode progress: the signal
                        # likely interrupted a main-thread frame that
                        # holds a lock the drain needs (submit/stream
                        # mid-critical-section) — burning the rest of
                        # the window cannot help; exit with the dump
                        break
                else:
                    stalled, last_steps = 0, steps
            # the engine thread may finish its dump in the gap between
            # the last wait slice and the running check — don't write a
            # second, stats-free dump over its richer one
            drained = drained or self._drained.is_set()
            if not drained:
                if self.running:
                    _flight.record("serving_drain_timeout",
                                   timeout_s=self.config.drain_timeout_s)
                # nothing mid-decode (or wedged past the deadline) —
                # leave the black box ourselves (dump is sanctioned)
                _flight.dump("serving_preempted",
                             step=self.scheduler.decode_steps)
            raise SystemExit(exit_code)

        for sig in signals:
            self._old_handlers[sig] = signal.signal(sig, _handler)
        return self

    def uninstall_preemption(self) -> None:
        for sig, old in self._old_handlers.items():
            try:
                signal.signal(sig, old)
            except (ValueError, OSError):
                pass
        self._old_handlers.clear()
