"""`paddle.jit.to_static` equivalent: compile dygraph code with XLA.

Reference analog: the SOT bytecode JIT + dy2static AST path
(python/paddle/jit/api.py:242, jit/sot/translate.py:31). On TPU the IR is the
jaxpr/StableHLO produced by tracing, so "dynamic-to-static" becomes:

1. **Discovery call** — run the function eagerly once while a tracker records
   every concrete Tensor whose storage is read or written (parameters,
   optimizer accumulators, RNG keys, buffers). This is the analog of SOT's
   FunctionGraph capture; Python control flow just runs.
2. **Compile** — build a pure function (state, args) -> (state', outputs) by
   temporarily binding tracers into those same Tensor objects, and `jax.jit`
   it. The eager autograd engine, optimizers, and RNG all trace cleanly
   because they are jnp programs underneath.
3. **Execute** — subsequent calls run the compiled program and write the new
   state arrays back into the live objects.

Shape/dtype changes retrace (a new cache entry), mirroring SOT guards.
"""

from __future__ import annotations

import gc
import os
import re
import threading
import time
import weakref
from functools import wraps

import jax
import jax.numpy as jnp

from ..core import tensor as tensor_mod
from ..core.tensor import Tensor
from ..observability import counter as _obs_counter, gauge as _obs_gauge
from ..observability import continuous as _cont
from ..observability import flight as _flight
from ..observability import tracing as _tracing

__all__ = ["to_static", "not_to_static", "in_to_static_trace", "ignore_module",
           "enable_to_static"]

# Trace-cache telemetry (paddle_tpu.observability): a silent retrace storm —
# fluctuating shapes recompiling every step — shows up here as a climbing
# retraces counter instead of an unexplained 100x step-time regression.
_OBS_HITS = _obs_counter(
    "paddle_tpu_jit_trace_cache_hits_total",
    "to_static calls served by an already-discovered signature")
_OBS_MISSES = _obs_counter(
    "paddle_tpu_jit_trace_cache_misses_total",
    "to_static calls that traced a new signature (discovery run)")
_OBS_RETRACES = _obs_counter(
    "paddle_tpu_jit_trace_cache_retraces_total",
    "trace-cache misses AFTER a function's first signature (recompile storms)")
_OBS_COMPILES = _obs_counter(
    "paddle_tpu_jit_compiles_total",
    "XLA program builds (whole-step jit compiles per signature)")
_OBS_TRACE_SECONDS = _obs_counter(
    "paddle_tpu_jit_trace_seconds_total",
    "wall seconds spent in discovery tracing + program building")
_OBS_CACHE_SIZE = _obs_gauge(
    "paddle_tpu_jit_trace_cache_entries",
    "live signatures per to_static function")

_trace_state = threading.local()
_to_static_enabled = True


def enable_to_static(flag: bool):
    global _to_static_enabled
    _to_static_enabled = bool(flag)


def in_to_static_trace() -> bool:
    return getattr(_trace_state, "active", False)


def dedup_for_donation(arrays, taken_ids=None):
    """Copy any array object that appears twice in a donated argument list
    (or that aliases a non-donated argument in `taken_ids`): XLA rejects
    donating one buffer twice, and freshly-built state can alias INSIDE a
    state list — two zeros_like accumulators may share a cached constant
    buffer; a tied weight read through two tensors. Shared by
    StaticFunction's donated execute and the fused optimizer dispatch."""
    seen = set(taken_ids) if taken_ids else set()
    out = []
    for a in arrays:
        if id(a) in seen:
            a = jnp.copy(a)
        else:
            seen.add(id(a))
        out.append(a)
    return out


def stream_state_in(t, a):
    """Host-pinned state (ZeRO-offload) streams to device for a compiled
    step — the transfer lives outside the jit boundary so the program
    itself stays all-device. Shared by StaticFunction and the fused
    optimizer dispatch."""
    if getattr(t, "_pin_memory_kind", None) is not None and \
            getattr(a, "sharding", None) is not None and \
            a.sharding.memory_kind != "device":
        a = jax.device_put(a, a.sharding.with_memory_kind("device"))
    return a


def stream_state_out(t, a):
    """Park updated state back in its pinned host memory kind after a
    compiled step (the inverse of :func:`stream_state_in`)."""
    kind = getattr(t, "_pin_memory_kind", None)
    if kind is not None and getattr(a, "sharding", None) is not None \
            and a.sharding.memory_kind != kind:
        a = jax.device_put(a, a.sharding.with_memory_kind(kind))
    return a


def _aval_or_value(x):
    """ShapeDtypeStruct of an array-like (Tensor or jax.Array), or the
    raw value for non-array leaves — the abstract form analyze_cached()
    re-traces a cached signature with."""
    d = getattr(x, "_d", x)
    if hasattr(d, "shape") and hasattr(d, "dtype"):
        return jax.ShapeDtypeStruct(d.shape, d.dtype)
    return d


class _Tracker:
    """Records concrete Tensors touched during the discovery call.

    The discovery call runs eagerly, so every intermediate (activations,
    residuals saved for backward, gradients) is a concrete Tensor that gets
    read too. Only what OUTLIVES the call is state: the tracker holds weak
    references, and :meth:`survivors` keeps the tensors something else
    still owns (parameters, optimizer accumulators, RNG and pool state).
    Holding the intermediates instead pinned every activation of the step
    in device memory for the life of the compiled function and threaded
    them through the program as dead arguments."""

    def __init__(self):
        self._order: list = []          # weakrefs, first-touch order
        self._seen: dict = {}           # id -> weakref (ids get reused)

    def _record(self, t: Tensor):
        r = self._seen.get(id(t))
        if r is not None and r() is t:
            return
        arr = t._d
        if isinstance(arr, jax.core.Tracer):
            return  # intermediate value created during this call
        r = weakref.ref(t)
        self._seen[id(t)] = r
        self._order.append(r)

    def on_read(self, t: Tensor):
        self._record(t)

    def on_write(self, t: Tensor):
        self._record(t)

    def survivors(self) -> list:
        """Tracked tensors still alive, in first-touch order. Call after
        the discovery call has returned (its locals are gone)."""
        gc.collect()    # autograd nodes and their tensors form cycles
        return [t for t in (r() for r in self._order) if t is not None]


#: an instruction of optimized HLO text with its name and `op_name`
_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"',
    re.M)


def _is_floatlike(x):
    return isinstance(x, (Tensor, jax.Array)) or hasattr(x, "__array__")


class StaticFunction:
    def __init__(self, fn, input_spec=None, build_strategy=None,
                 backend=None, donate_state=False, static_argnames=None,
                 fallback=True, analyze=None):
        self._fn = fn
        self._cache: dict = {}
        self._state: list[Tensor] | None = None
        self._state_by_key: dict = {}
        self._donate = donate_state
        # graph-tier analysis (paddle_tpu.analysis.graph) at first compile
        # of each signature; None defers to PADDLE_TPU_JIT_ANALYZE=1
        self._analyze = analyze
        self._analyzed: set = set()
        self._last_graph_report = None
        # SOT graph-break analog (reference python/paddle/jit/sot/): when
        # tracing hits data-dependent Python control flow, permanently run
        # this function eagerly instead of raising
        self._fallback = fallback
        self._fell_back = False
        # telemetry label: __qualname__ disambiguates methods that
        # share a bare __name__ (every Layer's 'forward')
        self._obs_name = getattr(fn, "__qualname__", None) or \
            getattr(fn, "__name__", "fn")
        # the compiled program's name: XLA modules read
        # `jit_pure_arrays__<this>`, host dispatches
        # `PjitFunction(pure_arrays__<this>)`
        self._program_name = "pure_arrays__" + re.sub(
            r"[^A-Za-z0-9_]", "_", self._obs_name)
        self._segmented: set = set()    # signature keys compiled in segments
        self._seg_cache: dict = {}
        wraps(fn)(self)

    def recapture(self):
        """Drop every compiled program and rediscover state on next call.

        Needed when new state appears mid-training WITHOUT a new input
        signature (e.g. a fresh optimizer over the same batch shape):
        signature-keyed rediscovery cannot see it, since the cached program
        for the old signature keeps being reused."""
        self._cache.clear()
        self._state_by_key.clear()
        self._state = None
        _OBS_CACHE_SIZE.set(0, fn=self._obs_name)

    # -- helpers ------------------------------------------------------------
    @staticmethod
    def _sig_of(args_flat):
        return tuple(
            (tuple(a.shape), str(a.dtype)) if hasattr(a, "shape") else ("#", repr(a))
            for a in args_flat)

    def _discover(self, args, kwargs):
        """Eagerly run fn once, recording every framework Tensor it touches.

        Re-run per NEW call signature (shapes/kwargs), not just once: state
        created lazily after the first call — a second optimizer, fresh
        accumulators after a schedule change — would otherwise be baked in
        as constants and silently stop updating (VERDICT r1 weak #11)."""
        tracker = _Tracker()
        prev = tensor_mod._TRACKER
        tensor_mod._TRACKER = tracker
        try:
            out = self._fn(*args, **kwargs)
        finally:
            tensor_mod._TRACKER = prev
        self._state = tracker.survivors()
        return out

    def _compile(self, treedef, sig, kwargs_static, state_tensors=None):
        if state_tensors is None:
            state_tensors = self._state
        fn = self._fn

        def pure(state_arrays, arg_arrays):
            saved = [t._d for t in state_tensors]
            saved_nodes = [(t._node, t._out_index) for t in state_tensors]
            # _grad POINTERS are restored too: backward during tracing
            # rebinds p._grad to trace-time Tensors, and a tracer left on a
            # param after the trace poisons the next eager backward
            # (UnexpectedTracerError). Persistent grads still thread: their
            # Tensor objects are themselves in state_tensors, so restoring
            # the pointer brings back the object whose _d is threaded.
            saved_grads = [t._grad for t in state_tensors]
            _trace_state.active = True
            try:
                for t, a in zip(state_tensors, state_arrays):
                    t._d = a
                    t._node = None
                args = jax.tree_util.tree_unflatten(treedef, arg_arrays)
                out = fn(*args, **kwargs_static)
                new_state = [t._d for t in state_tensors]
                out_flat, out_tree = jax.tree_util.tree_flatten(out)
            finally:
                _trace_state.active = False
                for t, s, (n, oi), g in zip(state_tensors, saved,
                                            saved_nodes, saved_grads):
                    t._d = s
                    t._node, t._out_index = n, oi
                    t._grad = g
            return new_state, out_flat, out_tree

        # capture out_tree via a mutable cell; jit the array part
        cell = {}

        donate = self._donate

        def pure_arrays(state_arrays, arg_arrays):
            new_state, out_flat, out_tree = pure(state_arrays, arg_arrays)
            cell["out_tree"] = out_tree
            # state the step only READ (a serving engine's weights) is not
            # returned: jit copies a forwarded input into a fresh output
            # buffer on every call, which doubled the weights' memory and
            # re-wrote them each step. Donated state aliases its output at
            # no cost and must come back (its input buffer is consumed).
            cell["written"] = [
                i for i, (n, a) in enumerate(zip(new_state, state_arrays))
                if donate or n is not a]
            return [new_state[i] for i in cell["written"]], out_flat

        pure_arrays.__name__ = pure_arrays.__qualname__ = self._program_name
        jitted = jax.jit(pure_arrays,
                         donate_argnums=(0,) if self._donate else ())
        return jitted, cell

    # -- graph-tier analysis (paddle_tpu.analysis.graph) --------------------
    def _analyze_enabled(self) -> bool:
        if self._analyze is not None:
            return bool(self._analyze)
        return os.environ.get("PADDLE_TPU_JIT_ANALYZE", "") == "1"

    def _maybe_analyze(self, key, jitted, state_list, arg_arrays):
        """Run rules GA100-GA109 on the jaxpr of a freshly compiled
        signature (abstract trace — no device execution) and surface the
        findings as GraphAnalysisWarning. Never blocks compilation."""
        if not self._analyze_enabled() or key in self._analyzed:
            return
        self._analyzed.add(key)
        try:
            import warnings

            from ..analysis import format_text
            from ..analysis.diagnostics import GraphAnalysisWarning
            from ..analysis.graph import analyze_graph
            from ..analysis.graph.trace import aval_of, source_file_of
            state_avals = [aval_of(t) for t in state_list]
            arg_avals = [aval_of(a) for a in arg_arrays]
            cj = jitted.trace(state_avals, arg_avals).jaxpr
            report = analyze_graph(cj, name=self._obs_name,
                                   prefer_file=source_file_of(self._fn))
            self._last_graph_report = report
            for f in report.findings:
                warnings.warn(f"to_static analyze: {format_text(f)}",
                              GraphAnalysisWarning, stacklevel=5)
        except Exception:  # analysis must never break the train step
            return

    def graph_report(self):
        """The :class:`~paddle_tpu.analysis.graph.GraphReport` from the
        most recent ``analyze=True`` compile (None before first compile
        or when analysis is off)."""
        return self._last_graph_report

    def analyze_cached(self, key=None, config=None, fresh=False):
        """Graph-analyze an ALREADY-compiled signature from its cached
        avals — an abstract re-trace, no device execution, no concrete
        arguments needed. This is the programmatic join API the
        continuous profiler's reconciliation calls to turn a measured
        program into ranked fusion targets. ``key=None`` uses the most
        recently dispatched signature. Returns the
        :class:`~paddle_tpu.analysis.graph.GraphReport` (cached per
        signature) or None when nothing is compiled yet."""
        explicit = key is not None
        key = key if explicit else getattr(self, "_last_key", None)
        entry = self._cache.get(key)
        if entry is None:
            # a key that misses (evicted, stale) must NOT be silently
            # substituted with another signature's analysis; the implicit
            # form only falls back when there is exactly one candidate
            if explicit or len(self._cache) != 1:
                return None
            entry = next(iter(self._cache.values()))
        jitted, cell, _state_list = entry
        if config is None and not fresh:
            report = cell.get("graph_report")
            if report is not None:
                return report
        avals = cell.get("avals")
        if avals is None:
            return None
        from ..analysis.graph import analyze_graph
        from ..analysis.graph.trace import source_file_of
        if fresh:
            # force a RE-TRACE under the CURRENT dispatch globals (jax's
            # trace cache keys on the function object, so a kernel-flag
            # flip would otherwise hand back the stale jaxpr). A new
            # closure over the unwrapped fn defeats the cache; used by the
            # reconciliation's as-fused / composite views.
            inner = getattr(jitted, "__wrapped__", None)
            tracer = jax.jit(lambda *a: inner(*a)) if inner is not None \
                else jitted
        else:
            tracer = jitted
        cj = tracer.trace(avals[0], avals[1]).jaxpr
        report = analyze_graph(cj, name=self._obs_name, config=config,
                               prefer_file=source_file_of(self._fn))
        if config is None and not fresh:  # only the default report caches
            cell["graph_report"] = report
        return report

    # -- call ---------------------------------------------------------------
    def __call__(self, *args, **kwargs):
        if not _to_static_enabled or in_to_static_trace() or self._fell_back:
            return self._fn(*args, **kwargs)
        # kwargs that are Tensors participate as traced args
        args_flat, treedef = jax.tree_util.tree_flatten(args)
        arg_arrays = [a for a in args_flat]
        sig = self._sig_of(args_flat)
        kw_key = tuple(sorted(kwargs.items(), key=lambda kv: kv[0])) \
            if all(not isinstance(v, Tensor) for v in kwargs.values()) else None
        if kw_key is None:
            # Tensor kwargs: fold into args via sorted binding
            raise TypeError("to_static: pass Tensors positionally")
        key = (treedef, sig, kw_key)
        fn_name = self._obs_name
        if key in self._segmented:
            return self._call_segmented(key, treedef, kwargs, args,
                                        arg_arrays)
        if key not in self._state_by_key:
            # first time this signature is seen: one eager step that also
            # (re)discovers the state set, catching Tensors created lazily
            # after earlier signatures were traced (VERDICT r1 weak #11).
            # Limitation: state created later under an ALREADY-compiled
            # signature stays invisible — call .recapture() for that.
            retrace = bool(self._state_by_key)
            if retrace:
                _OBS_RETRACES.inc(fn=fn_name)
            _OBS_MISSES.inc(fn=fn_name)
            t0 = time.perf_counter()
            with _tracing.span("jit.run", fn=fn_name, phase="eager"):
                out = self._discover(args, kwargs)
            dt = time.perf_counter() - t0
            _OBS_TRACE_SECONDS.inc(dt, fn=fn_name)
            self._state_by_key[key] = list(self._state)
            _OBS_CACHE_SIZE.set(len(self._state_by_key), fn=fn_name)
            if _flight.enabled():  # cold path: once per new signature
                _flight.record("jit_trace", fn=fn_name, retrace=retrace,
                               seconds=round(dt, 4),
                               cache_entries=len(self._state_by_key))
            return out
        _OBS_HITS.inc(fn=fn_name)
        entry = self._cache.get(key)
        with _tracing.span("jit.run", fn=fn_name,
                           phase="run" if entry is not None else "compile"):
            return self._call_compiled(key, entry, treedef, sig, kwargs,
                                       args, arg_arrays)

    def _call_compiled(self, key, entry, treedef, sig, kwargs, args,
                       arg_arrays):
        """Run the compiled program of a discovered signature, building it
        first where `entry` (its cache entry) is None."""
        fn_name = self._obs_name
        fresh = entry is None
        if fresh:
            state_list = self._state_by_key[key]
            t0 = time.perf_counter()
            jitted, cell = self._compile(treedef, sig, dict(kwargs),
                                         state_list)
            _OBS_TRACE_SECONDS.inc(time.perf_counter() - t0, fn=fn_name)
            _OBS_COMPILES.inc(fn=fn_name)
            if _flight.enabled():
                _flight.record("jit_compile", fn=fn_name)
            # abstract shapes of this signature, kept so analyze_cached()
            # (the continuous profiler's reconciliation) can re-trace the
            # program later without the concrete call arguments
            cell["avals"] = ([_aval_or_value(t._d) for t in state_list],
                             [_aval_or_value(a) for a in arg_arrays])
            entry = (jitted, cell, state_list)
            self._cache[key] = entry
            self._maybe_analyze(key, jitted, state_list, arg_arrays)
        self._last_key = key
        jitted, cell, state_list = entry
        try:
            out = self._run_compiled(jitted, cell, state_list, arg_arrays)
            if fresh and _tracing.tracing_enabled():
                self._note_program(jitted, cell)
            return out
        except (jax.errors.TracerBoolConversionError,
                jax.errors.TracerIntegerConversionError,
                jax.errors.TracerArrayConversionError,
                jax.errors.ConcretizationTypeError) as e:
            # data-dependent Python control flow: the branch condition is a
            # tracer under jit. Reference SOT breaks the graph and keeps
            # compiling around the break (jit/sot/translate.py:31); the
            # segment path below does the same at op-stream granularity:
            # compiled prefix + replay + span-compiled continuation.
            if not self._fallback:
                raise
            del self._cache[key]
            if getattr(self, "_last_key", None) == key:
                self._last_key = None   # analyze_cached must not dangle
            self._segmented.add(key)
            import warnings
            warnings.warn(
                f"to_static: {getattr(self._fn, '__name__', self._fn)!r} "
                "uses data-dependent Python control flow; compiling in "
                "SEGMENTS around the graph break (SOT analog). Cause: "
                f"{type(e).__name__}", UserWarning, stacklevel=3)
            return self._call_segmented(key, treedef, kwargs, args,
                                        arg_arrays)

    def _note_program(self, jitted, cell):
        """With tracing on, hand the tracer the table that gives each
        instruction of the program that just ran the name of the code that
        made it (`jax.named_scope` path included): a device trace names
        the instruction alone. The lowering and its executable are the
        ones jax cached for the call."""
        try:
            text = jitted.lower(*cell["avals"]).compile().as_text()
        except Exception:   # noqa: BLE001 - a table less, never a step less
            return
        _tracing.note_program("jit_" + self._program_name,
                              dict(_HLO_OP_NAME.findall(text)))

    def _run_compiled(self, jitted, cell, state_list, arg_arrays):
        # NOTE the donation contract: Tensors aliasing state from OUTSIDE
        # the compiled fn (detach() views, EMA snapshots) are invalidated
        # by the donated execute — standard jax donation semantics; keep
        # donate_state=False if such aliases must stay live.
        from ..ops.kernels import _common as _kern
        if _kern.interpret_mode():
            # interpret-mode pallas (the CPU test hook) re-traces its grid
            # emulation at the OUTER program's first-call lowering; that
            # retrace must see the kernels' 32-bit world too
            with _kern.x64_off():
                return self._run_compiled_inner(jitted, cell, state_list,
                                                arg_arrays)
        return self._run_compiled_inner(jitted, cell, state_list, arg_arrays)

    def _run_compiled_inner(self, jitted, cell, state_list, arg_arrays):
        state_arrays = [stream_state_in(t, t._d) for t in state_list]
        if self._donate:
            state_arrays = dedup_for_donation(
                state_arrays, {id(a) for a in arg_arrays})
        from ..profiler.profiler import op_timing_active, record_program
        timed = op_timing_active()
        sampled = _cont.sampling_active()
        if timed or sampled:
            # profiled dispatch: block on EVERYTHING the program produced
            # (state updates included) so the wall time is the program's
            # device time, not the enqueue cost
            t0 = time.perf_counter()
            new_state, out_flat = jitted(state_arrays, arg_arrays)
            jax.block_until_ready((new_state, out_flat))
            dt = time.perf_counter() - t0
            if timed:
                record_program(
                    f"to_static:{getattr(self._fn, '__name__', 'fn')}", dt)
            if sampled:
                _cont.record_program(f"to_static:{self._obs_name}", dt)
                _cont.note_program(f"to_static:{self._obs_name}", self)
        else:
            new_state, out_flat = jitted(state_arrays, arg_arrays)
        for i, a in zip(cell["written"], new_state):
            t = state_list[i]
            t._d = stream_state_out(t, a)
            t._node = None
        return jax.tree_util.tree_unflatten(cell["out_tree"], out_flat)

    # -- graph-break segments (SOT analog; jit/sot.py) ---------------------
    def _compile_prefix(self, treedef, kwargs_static, state_tensors):
        """Trace fn until its first concretization request; the compiled
        program returns (partial state, every op output so far)."""
        from . import sot
        fn = self._fn

        def pure_prefix(state_arrays, arg_arrays):
            saved = [t._d for t in state_tensors]
            saved_nodes = [(t._node, t._out_index) for t in state_tensors]
            saved_grads = [t._grad for t in state_tensors]
            _trace_state.active = True
            sot._S.mode = "probe"
            sot._S.records = []
            sot._S.probe_grad_ops = False
            sot._S.probe_backward_ran = False
            completed = False
            out_flat, out_tree = [], None
            try:
                for t, a in zip(state_tensors, state_arrays):
                    t._d = a
                    t._node = None
                args = jax.tree_util.tree_unflatten(treedef, arg_arrays)
                try:
                    out = fn(*args, **kwargs_static)
                    completed = True
                    out_flat, out_tree = jax.tree_util.tree_flatten(out)
                except sot.GraphBreak:
                    pass
                new_state = [t._d for t in state_tensors]
                recs = sot._S.records
                rec_meta = [(n, len(outs)) for n, outs in recs]
                rec_flat = [o for _, outs in recs for o in outs]
            finally:
                sot._S.mode = None
                sot._S.records = None
                _trace_state.active = False
                for t, sv, (n, oi), g in zip(state_tensors, saved,
                                             saved_nodes, saved_grads):
                    t._d = sv
                    t._node, t._out_index = n, oi
                    t._grad = g
            cell["rec_meta"] = rec_meta
            cell["completed"] = completed
            cell["out_tree"] = out_tree
            # a break that truncates a LIVE grad graph (need-grad ops
            # recorded but backward not yet run) would silently detach the
            # replayed prefix from autograd — refuse segmentation there
            cell["unsound"] = (not completed and sot._S.probe_grad_ops
                               and not sot._S.probe_backward_ran)
            return new_state, rec_flat, out_flat

        cell = {}
        return jax.jit(pure_prefix), cell

    def _abandon_segments(self, key, state_list, init_state, args, kwargs):
        """Graph break inside a live grad graph: segments would detach the
        prefix from autograd (silent missing grads). Restore state and run
        this function eagerly from now on — loudly."""
        import warnings
        warnings.warn(
            f"to_static: {getattr(self._fn, '__name__', self._fn)!r} "
            "breaks the graph BEFORE backward() consumes it; segment "
            "replay would detach gradients, so this function runs EAGERLY "
            "from now on", UserWarning, stacklevel=3)
        for t, a in zip(state_list, init_state):
            t._d = a
            t._node = None
        self._fell_back = True
        self._segmented.discard(key)
        return self._fn(*args, **kwargs)

    def _call_segmented(self, key, treedef, kwargs, args, arg_arrays):
        """Run: compiled prefix -> positional replay -> span-compiled
        continuation. Any replay divergence restores state and reruns the
        whole call eagerly (sound fallback)."""
        from collections import deque

        from . import sot

        if key not in self._state_by_key:
            fn_name = self._obs_name
            retrace = bool(self._state_by_key)
            if retrace:
                _OBS_RETRACES.inc(fn=fn_name)
            _OBS_MISSES.inc(fn=fn_name)
            t0 = time.perf_counter()
            out = self._discover(args, kwargs)
            dt = time.perf_counter() - t0
            _OBS_TRACE_SECONDS.inc(dt, fn=fn_name)
            self._state_by_key[key] = list(self._state)
            _OBS_CACHE_SIZE.set(len(self._state_by_key), fn=fn_name)
            if _flight.enabled():
                _flight.record("jit_trace", fn=fn_name, retrace=retrace,
                               seconds=round(dt, 4), segmented=True,
                               cache_entries=len(self._state_by_key))
            return out
        _OBS_HITS.inc(fn=self._obs_name)
        state_list = self._state_by_key[key]
        entry = self._seg_cache.get(key)
        if entry is None:
            entry = self._compile_prefix(treedef, dict(kwargs), state_list)
            self._seg_cache[key] = entry
            sot._STATS["prefix_compiles"] += 1
        jitted, cell = entry
        init_state = [t._d for t in state_list]
        state_arrays = list(init_state)
        if cell.get("unsound"):
            return self._abandon_segments(key, state_list, init_state,
                                          args, kwargs)
        from ..profiler.profiler import op_timing_active, record_program
        if op_timing_active():
            import time as _t
            t0 = _t.perf_counter()
            new_state, rec_flat, out_flat = jitted(state_arrays, arg_arrays)
            jax.block_until_ready(new_state)
            record_program(
                f"to_static_prefix:{getattr(self._fn, '__name__', 'fn')}",
                _t.perf_counter() - t0)
        else:
            new_state, rec_flat, out_flat = jitted(state_arrays, arg_arrays)
        sot._STATS["prefix_runs"] += 1
        for t, a in zip(state_list, new_state):
            t._d = a
            t._node = None
        if cell.get("unsound"):
            # first call: the trace just ran inside jitted() and marked the
            # break as grad-truncating; the prefix already mutated state —
            # restore and run eagerly, permanently
            return self._abandon_segments(key, state_list, init_state,
                                          args, kwargs)
        if cell["completed"]:
            return jax.tree_util.tree_unflatten(cell["out_tree"], out_flat)
        queue = deque()
        i = 0
        for n, c in cell["rec_meta"]:
            queue.append((n, list(rec_flat[i:i + c])))
            i += c
        sot._S.mode = "replay"
        sot._S.queue = queue
        sot._S.spans_enabled = True
        try:
            out = self._fn(*args, **kwargs)
            sot.flush_current_span()
            return out
        except sot._ReplayMismatch as e:
            import warnings
            warnings.warn(
                f"to_static: segment replay diverged ({e}); falling back "
                "to one eager re-run with restored state", UserWarning,
                stacklevel=2)
            for t, a in zip(state_list, init_state):
                t._d = a
                t._node = None
            sot._S.mode = None
            sot._S.queue = None
            sot._S.spans_enabled = False
            sot._S.span = None
            return self._fn(*args, **kwargs)
        finally:
            sot._S.mode = None
            sot._S.queue = None
            sot._S.spans_enabled = False
            sot._S.span = None

    def memory_analysis(self, *args, **kwargs):
        """Compile the step for these args and return XLA's memory analysis
        (argument/output/temp/generated-code bytes). The signature must have
        been called at least once (so state is discovered)."""
        args_flat, treedef = jax.tree_util.tree_flatten(args)
        sig = self._sig_of(args_flat)
        kw_key = tuple(sorted(kwargs.items(), key=lambda kv: kv[0]))
        key = (treedef, sig, kw_key)
        if key not in self._state_by_key:
            self(*args, **kwargs)
        if not hasattr(self, "_mem_analysis_cache"):
            self._mem_analysis_cache = {}
        if key in self._mem_analysis_cache:
            return self._mem_analysis_cache[key]
        state_list = self._state_by_key[key]
        jitted, _ = self._compile(treedef, sig, dict(kwargs), state_list)
        state_arrays = [t._d for t in state_list]
        compiled = jitted.lower(state_arrays, list(args_flat)).compile()
        ma = compiled.memory_analysis()
        self._mem_analysis_cache[key] = ma
        return ma

    def compiled_text(self, *args, **kwargs):
        """Compile the step for these args and return the optimized HLO text
        (collective-inspection hook; the analog of the reference's
        program-desc dump for verifying pass behavior)."""
        args_flat, treedef = jax.tree_util.tree_flatten(args)
        sig = self._sig_of(args_flat)
        kw_key = tuple(sorted(kwargs.items(), key=lambda kv: kv[0]))
        key = (treedef, sig, kw_key)
        if key not in self._state_by_key:
            self(*args, **kwargs)
        state_list = self._state_by_key[key]
        jitted, _ = self._compile(treedef, sig, dict(kwargs), state_list)
        state_arrays = [t._d for t in state_list]
        return jitted.lower(state_arrays, list(args_flat)).compile().as_text()

    def compiled_text_cached(self) -> list:
        """Optimized HLO text of every program this function has compiled,
        rebuilt from the abstract shapes kept with each cache entry (no
        call arguments needed; the compile hits jax's compilation cache)."""
        return [jitted.lower(*cell["avals"]).compile().as_text()
                for jitted, cell, _ in self._cache.values()]

    # -- parity surface -----------------------------------------------------
    def concrete_program(self):
        return None

    @property
    def code(self):
        import inspect
        return inspect.getsource(self._fn)


def _maybe_lint(fn, lint):
    """Decoration-time trace-safety lint (paddle_tpu.analysis): opt in per
    call site with ``lint=True`` or process-wide with
    ``PADDLE_TPU_JIT_LINT=1``. Findings surface as TraceSafetyWarning
    BEFORE the first trace; lint failures never block compilation."""
    import os
    if lint is None:
        lint = os.environ.get("PADDLE_TPU_JIT_LINT", "") == "1"
    if not lint:
        return
    try:
        from ..analysis import analyze_function, format_text
        from ..analysis.diagnostics import TraceSafetyWarning
        findings = analyze_function(fn)
    except Exception:
        return
    import warnings
    for f in findings:
        warnings.warn(f"to_static lint: {format_text(f)}",
                      TraceSafetyWarning, stacklevel=4)


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, lint=None, analyze=None, **kwargs):
    """Decorator/wrapper compiling a dygraph callable (reference:
    python/paddle/jit/api.py:242).

    ``lint``: run the trace-safety analyzer (paddle_tpu.analysis) on the
    function's source at decoration time and warn on findings; defaults
    to the PADDLE_TPU_JIT_LINT=1 env switch.

    ``analyze``: run the graph-tier analyzer (paddle_tpu.analysis.graph,
    rules GA100-GA109) on the traced jaxpr at first compile of each
    signature and warn on findings (GraphAnalysisWarning); defaults to
    the PADDLE_TPU_JIT_ANALYZE=1 env switch. The report is retrievable
    via ``.graph_report()`` on the StaticFunction."""
    from ..nn.layer import Layer

    def decorate(fn):
        if isinstance(fn, Layer):
            layer = fn
            _maybe_lint(layer.forward, lint)
            sf = StaticFunction(layer.forward, input_spec, build_strategy,
                                backend, analyze=analyze, **kwargs)
            layer.forward = sf
            return layer
        _maybe_lint(fn, lint)
        return StaticFunction(fn, input_spec, build_strategy, backend,
                              analyze=analyze, **kwargs)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    return None
