"""Real static-graph Programs: a long-lived jaxpr trace as the Program.

Reference analog: `python/paddle/static/` — `Program`, `program_guard`,
`static.data`, `Executor.run(program, feed, fetch_list)` and the
`optimizer.minimize(loss)` graph-mode training loop (reference call stack
SURVEY.md §3.3). The reference records ops into a ProgramDesc as Python
executes; the TPU-native equivalent records them into a jaxpr: entering a
`program_guard` installs a `DynamicJaxprTrace` as the ambient JAX trace, so
every framework op between `data()` and the guard's exit traces into the
Program instead of executing. `Executor.run` then closes the trace over the
requested fetch targets (non-destructively — later runs may fetch different
subsets), lifts parameter constants into inputs, and compiles the replay
with XLA via the existing `jit.to_static` machinery (which also lifts
optimizer state and writes updates back into the live Parameters).

Faithfulness notes:
- `exe.run(startup_program)` restores every parameter created under the
  guard to its initialization-time value (the reference re-runs the
  initializer ops recorded in the startup program; we snapshot instead —
  parameter initializers execute eagerly under a suspended trace so
  Parameters stay concrete, see `suspend_trace`).
- `minimize(loss)` under a guard records the optimizer instead of stepping;
  the backward graph is generated at compile time by `jax.value_and_grad`
  over the replayed forward jaxpr (the reference appends backward ops via
  `append_backward` — on TPU the AD transform owns that).
- Dynamic dims: `static.data(shape=[None, ...])` declares jax.export
  symbolic dimensions — batch-dependent values (e.g. `mean`'s divisor)
  trace symbolically and `Executor.run` / `save_inference_model` refine
  per concrete feed. Fetch-only execution and export support this;
  `minimize()` requires concrete shapes (the compiled backward goes
  through concrete-shape tracing) and says so.
"""

from __future__ import annotations

import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax._src import api_util
from jax._src import core as jcore
from jax._src import source_info_util
from jax._src.interpreters import partial_eval as pe

from ..core import dtype as dtypes
from ..core.tensor import Parameter, Tensor

__all__ = ["Program", "program_guard", "data", "Executor"]

_GUARDS: list[tuple["Program", "Program"]] = []  # (main, startup) stack


def _tracer_of(t):
    arr = t._d if isinstance(t, Tensor) else t
    return arr if isinstance(arr, jcore.Tracer) else None


class _StateTracker:
    """Records writes of traced values into pre-existing concrete Tensors
    during the guard (BatchNorm running stats, RNG generator keys, any
    buffer a layer mutates). Those tensors become threaded state of the
    compiled program: lifted to inputs, emitted as extra outputs, and the
    concrete value advanced after every Executor.run — the analog of the
    reference static graph's persistable variables living in the Scope."""

    def __init__(self):
        self.initial: dict[int, tuple[Tensor, jax.Array]] = {}
        self.written: dict[int, Tensor] = {}

    def on_read(self, t):
        pass

    def on_write(self, t):
        arr = t._d  # value BEFORE this write lands
        if id(t) not in self.initial and not isinstance(arr, jcore.Tracer):
            self.initial[id(t)] = (t, arr)
        self.written[id(t)] = t


_ALL_PROGRAMS: list = []  # weakrefs; the Scope searches across programs


def all_programs():
    """Live Programs, newest last (compat.Scope's search space — the
    reference's global scope likewise spans every program run). Dead
    weakrefs are pruned so a build-programs-in-a-loop process never
    scans an unbounded history."""
    alive = []
    live_refs = []
    for ref in _ALL_PROGRAMS:
        p = ref()
        if p is not None:
            alive.append(p)
            live_refs.append(ref)
    _ALL_PROGRAMS[:] = live_refs
    return alive


class Program:
    """A recorded computation: feeds, parameters, optimizer, fetch targets.

    The underlying storage is one `DynamicJaxprTrace` that stays open for
    the Program's lifetime; `Executor.run` closes it per fetch set.
    """

    def __init__(self):
        import weakref
        _ALL_PROGRAMS.append(weakref.ref(self))
        self._dbg = api_util.debug_info("static_program", lambda *a: a,
                                        (), {})
        self._trace = None
        self._ambient_cm = None       # entered set_current_trace context
        self._prev_tracker = None
        self._feed_order: list[str] = []
        self._feeds: dict[str, Tensor] = {}
        self._params: list[Parameter] = []
        self._param_init: list[tuple[Parameter, jax.Array]] = []
        self._state = _StateTracker()
        self._state_shadow: dict[int, Tensor] = {}   # id -> live value
        self._state_tracer: dict[int, jcore.Tracer] = {}
        self._for_test = False
        self._opt = None
        self._loss: Tensor | None = None
        self._runners: dict = {}
        self._text = ""               # legacy save_inference_model text
        self._symbolic = False        # any feed carries a dynamic dim
        self._n_sym = 0
        self._sym_dims: dict = {}     # symbol name -> dimension object
        self._warned_state = False
        from jax import export as jax_export
        self._sym_scope = jax_export.SymbolicScope()

    def _sym_dim(self, name):
        """A symbolic dimension in this Program's scope; named symbols
        (axis-0 'batch', user strings) are shared so feeds combine."""
        from jax import export as jax_export
        if name is None:
            self._n_sym += 1
            name = f"d{self._n_sym}"
        if name not in self._sym_dims:
            sym, = jax_export.symbolic_shape(name, scope=self._sym_scope)
            self._sym_dims[name] = sym
        return self._sym_dims[name]

    # -- trace lifecycle ----------------------------------------------------
    def _ensure_trace(self):
        if self._trace is None:
            self._trace = pe.DynamicJaxprTrace(self._dbg)
        return self._trace

    def _activate(self):
        """Make this Program's trace the ambient JAX trace."""
        if self._ambient_cm is None:
            from ..core import tensor as tensor_mod
            self._ambient_cm = jcore.set_current_trace(self._ensure_trace())
            self._ambient_cm.__enter__()
            self._prev_tracker = tensor_mod._TRACKER
            tensor_mod._TRACKER = self._state

    def _deactivate(self):
        if self._ambient_cm is not None:
            from ..core import tensor as tensor_mod
            self._ambient_cm.__exit__(None, None, None)
            self._ambient_cm = None
            tensor_mod._TRACKER = self._prev_tracker
            self._prev_tracker = None

    # -- recording ----------------------------------------------------------
    def _new_feed(self, name, shape, dtype) -> Tensor:
        if name in self._feeds:
            raise ValueError(f"static.data name {name!r} already declared "
                             f"in this Program")
        dims = []
        for ax, s in enumerate(shape):
            if s is None or isinstance(s, str) or \
                    (isinstance(s, int) and s < 0):
                # dynamic dim -> a jax.export symbolic dimension: ops trace
                # shape-polymorphically (mean's divisor etc. stay symbolic)
                # and Executor.run refines per concrete feed (batch >= 1;
                # symbolic dims cannot be zero). Training (minimize) still
                # requires concrete shapes — _build_runner raises there.
                # Axis 0 shares ONE "batch" symbol across feeds so
                # x + y / paired input-label programs combine; other axes
                # get fresh symbols unless named via a string dim.
                dims.append(self._sym_dim(
                    s if isinstance(s, str) else
                    ("batch" if ax == 0 else None)))
                self._symbolic = True
            else:
                dims.append(int(s))
        dt = dtypes.dtype_from_any(dtype)
        aval = jcore.ShapedArray(tuple(dims), dt.np_dtype)
        tracer = self._ensure_trace().new_arg(
            aval, source_info=source_info_util.current())
        t = Tensor(tracer, stop_gradient=True, name=name)
        self._feed_order.append(name)
        self._feeds[name] = t
        return t

    def _record_minimize(self, opt, loss):
        if self._opt is not None and self._opt is not opt:
            raise RuntimeError("a Program supports one optimizer; "
                               "minimize() was called with a second one")
        # static-mode optimizers are built without a parameters= list (the
        # reference pulls trainables from the program); adopt ours
        if not getattr(opt, "_parameter_list", None):
            opt._parameter_list = [p for p in self._params
                                   if getattr(p, "trainable", True)]
        self._opt = opt
        self._loss = loss
        self._runners.clear()

    # -- inspection ---------------------------------------------------------
    def global_block(self):
        return self

    def clone(self, for_test=False):
        """Reference Program.clone(for_test=True) strips backward/optimize
        ops; here fetch-only runs never trace the optimizer anyway, so the
        eval clone shares the trace but drops the recorded minimize."""
        c = Program.__new__(Program)
        c.__dict__ = dict(self.__dict__)
        c._runners = {}
        if for_test:
            c._opt, c._loss = None, None
            c._for_test = True   # skip state write-back (reference strips
            #                      the moving-stat update ops from the clone)
        return c

    def list_vars(self):
        return list(self._feeds.values())

    def __str__(self):
        if self._trace is None:
            return self._text or "<empty Program>"
        outs = [t for t in (_tracer_of(x) for x in self._feeds.values())
                if t is not None]
        if self._loss is not None and _tracer_of(self._loss) is not None:
            outs.append(_tracer_of(self._loss))
        try:
            jaxpr, _ = self._close(outs)
            return str(jaxpr)
        except Exception:
            return self._text or "<open Program (close failed to render)>"

    # -- closing & compilation ---------------------------------------------
    def _close(self, out_tracers):
        dbg = self._dbg._replace(
            arg_names=tuple(self._feed_order),
            result_paths=tuple(
                f"out{i}" for i in range(len(out_tracers))))
        return self._trace.to_jaxpr(list(out_tracers), dbg,
                                    source_info_util.current())

    def _close_pruned(self, out_tracers):
        """Close over `out_tracers` and DCE: (jaxpr, live consts, names of
        the feeds the pruned program still consumes). The invars-order ==
        _feed_order invariant lives HERE only (both the runner build and
        inference export depend on it)."""
        jaxpr, consts = self._close(out_tracers)
        jaxpr, used_consts, used_invars = pe.dce_jaxpr_consts(
            jaxpr, [True] * len(out_tracers), instantiate=False)
        consts = [c for c, u in zip(consts, used_consts) if u]
        used_names = [n for n, u in zip(self._feed_order, used_invars) if u]
        return jaxpr, consts, used_names

    def _resolve_fetches(self, fetch_list):
        fetch_info = []               # (kind, payload) per fetch entry
        out_tracers = []
        for f in fetch_list:
            if isinstance(f, str):
                got = self._feeds.get(f)
                f = got if got is not None else self._by_name(f)
            tr = _tracer_of(f)
            if tr is not None:
                fetch_info.append(("traced", len(out_tracers)))
                out_tracers.append(tr)
            elif isinstance(f, Tensor):
                fetch_info.append(("concrete", f))
            else:
                raise TypeError(f"cannot fetch {type(f).__name__}: "
                                f"{f!r} is not part of this Program")
        return fetch_info, out_tracers

    def _build_runner(self, fetch_list, train):
        """Compile (feeds) -> fetches [+ param/opt updates via to_static]."""
        from ..jit.api import to_static

        if self._symbolic:
            if train:
                raise ValueError(
                    "minimize() requires concrete static.data shapes; "
                    "dynamic (None) dims support fetch-only execution — "
                    "declare the batch size to train, or train through "
                    "paddle.jit.to_static")
            return self._build_symbolic_runner(fetch_list)

        fetch_info, out_tracers = self._resolve_fetches(fetch_list)
        n_fetch = len(out_tracers)
        loss_idx = None
        if train:
            tr = _tracer_of(self._loss)
            if tr is None:
                raise RuntimeError("minimize() was recorded but the loss "
                                   "is not a traced value of this Program")
            loss_idx = len(out_tracers)
            out_tracers.append(tr)

        # threaded state (BatchNorm stats, RNG keys, mutated buffers): the
        # final traced value written into each pre-existing Tensor becomes
        # an extra program output; its concrete value lives in a shadow
        # Tensor the compiled step reads and writes (to_static lifts it)
        state_items = []   # (tid, live tensor, initial array, final tracer)
        if not self._for_test:
            for tid, t in self._state.written.items():
                tr = self._state_tracer.get(tid)
                if tr is None and isinstance(t._d, jcore.Tracer):
                    tr = t._d
                    self._state_tracer[tid] = tr
                if tr is not None and tid in self._state.initial:
                    init = self._state.initial[tid][1]
                    state_items.append((tid, t, init, tr))
                    self._state_shadow.setdefault(tid, Tensor(init))
            out_tracers += [tr for _, _, _, tr in state_items]

        # prune eqns (and thereby consts and feeds) this fetch set doesn't
        # need; state outputs of untouched tensors survive harmlessly
        jaxpr, consts, used_names = self._close_pruned(out_tracers)

        # lift parameter and state-initial constants into inputs so (a)
        # training can update params, (b) later eager updates stay visible,
        # (c) state threads run-to-run instead of restarting at its
        # initialization value
        # consts are matched against creation-time snapshots too: an eval
        # clone compiled after training sees new p._d arrays
        plist = (self._opt._parameter_list if train and self._opt
                 else self._params)
        lifted, lift_vars, kept_vars, kept_consts = self._lift_consts(
            jaxpr, consts, plist)
        # remaining consts become explicit per-call inputs too: leaving
        # them as closure constants makes jax hoist them as hidden jit
        # parameters, which breaks the C++ fastpath on repeat executions
        # (buffer-count mismatch)
        jaxpr = jaxpr.replace(
            constvars=[],
            invars=lift_vars + kept_vars + list(jaxpr.invars))
        # consts ride through Tensor reads so the to_static tracker lifts
        # them into the compiled step's REAL argument list (they must not
        # be jit closure constants: jax hoists those as hidden parameters
        # and its C++ fastpath miscounts buffers on repeat executions)
        const_tensors = [Tensor(jnp.asarray(c)) for c in kept_consts]
        replay0 = jcore.jaxpr_as_fun(jcore.ClosedJaxpr(jaxpr, []))

        def replay(*lift_and_feeds):
            n = len(lift_vars)
            return replay0(*lift_and_feeds[:n],
                           *[t._data for t in const_tensors],
                           *lift_and_feeds[n:])
        shadows = self._state_shadow
        state_tids = [tid for tid, _, _, _ in state_items]
        n_state = len(state_items)

        def _read_lifted():
            vals = []
            for kind, key in lifted:
                vals.append(key._data if kind == "param"
                            else shadows[key]._data)
            return vals

        def _writeback_state(outs):
            for tid, val in zip(state_tids, outs[len(outs) - n_state:]):
                shadows[tid]._data = val

        if train:
            opt, params = self._opt, self._opt._parameter_list
            # params not reached by the fetch+loss graph get zero grads
            lifted_params = [p for k, p in lifted if k == "param"]
            pos_of = {id(p): i for i, p in enumerate(lifted_params)}
            grad_mask = [k == "param" for k, _ in lifted]

            def _step_fn(*feed_tensors):
                feeds = [t._data for t in feed_tensors]

                def loss_fn(param_arrays):
                    vals, it = [], iter(param_arrays)
                    for (kind, key), is_p in zip(lifted, grad_mask):
                        vals.append(next(it) if is_p
                                    else shadows[key]._data)
                    outs = replay(*vals, *feeds)
                    return outs[loss_idx], outs

                (_, outs), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(
                        [p._data for k, p in lifted if k == "param"])
                for p in params:
                    i = pos_of.get(id(p))
                    gi = g[i] if i is not None else jnp.zeros_like(p._data)
                    p._grad = Tensor(gi)
                opt.step()
                opt.clear_grad()
                if n_state:
                    _writeback_state(outs)
                return tuple(Tensor(outs[i]) for i in range(n_fetch))
        else:
            def _step_fn(*feed_tensors):
                feeds = [t._data for t in feed_tensors]
                outs = replay(*_read_lifted(), *feeds)
                if n_state:
                    _writeback_state(outs)
                return tuple(Tensor(outs[i]) for i in range(n_fetch))

        compiled = to_static(_step_fn)

        def runner(feed: dict):
            missing = [n for n in used_names if n not in (feed or {})]
            if missing:
                raise KeyError(f"Executor.run: feed is missing {missing} "
                               f"(required by the requested fetch_list)")
            args = []
            for n in used_names:
                want = self._feeds[n]
                arr = feed[n]
                arr = arr._data if isinstance(arr, Tensor) else jnp.asarray(
                    np.asarray(arr))
                if tuple(arr.shape) != tuple(want.shape):
                    raise ValueError(
                        f"feed {n!r}: shape {tuple(arr.shape)} does not "
                        f"match declared {tuple(want.shape)}")
                args.append(Tensor(arr.astype(want._d.dtype)))
            outs = compiled(*args)
            res = []
            for kind, payload in fetch_info:
                if kind == "traced":
                    res.append(outs[payload].numpy())
                else:
                    res.append(payload.numpy())
            return res

        return runner

    def export_inference(self, path_prefix, feed_vars, fetch_vars):
        """Serialize `feeds -> fetches` as a deployable artifact in the
        jit.save payload format (StableHLO via jax.export), with the
        CURRENT parameter and threaded-state values baked in as constants
        — `jit.load` / `load_inference_model` then executes it without
        this Program (reference static.save_inference_model writes the
        pruned inference ProgramDesc + persistables the same way)."""
        import jax
        from jax import export as jax_export

        feed_vars = list(feed_vars or [])
        fetch_vars = list(fetch_vars or [])
        if not fetch_vars:
            raise ValueError("save_inference_model needs fetch_vars")
        out_tracers = []
        for f in fetch_vars:
            tr = _tracer_of(f)
            if tr is None:
                raise TypeError("fetch_vars must be traced Tensors of this "
                                "Program")
            out_tracers.append(tr)
        jaxpr, consts, used_names = self._close_pruned(out_tracers)
        feed_names = [t.name for t in feed_vars]
        missing = [n for n in used_names if n not in feed_names]
        if missing:
            raise ValueError(f"fetch_vars depend on feeds {missing} not "
                             f"listed in feed_vars")

        # bake CURRENT values: trace-time const arrays belonging to
        # parameters / threaded state are swapped for their live values
        cur = {}
        for p, init in self._param_init:
            cur[id(init)] = lambda p=p: p._d
        for tid, (t, init) in self._state.initial.items():
            sh = self._state_shadow.get(tid)
            if sh is not None:
                cur[id(init)] = lambda sh=sh: sh._d
        consts = [cur[id(c)]() if id(c) in cur else c for c in consts]
        replay = jcore.jaxpr_as_fun(jcore.ClosedJaxpr(jaxpr, consts))

        feed_by_name = {t.name: t for t in feed_vars}
        order = [feed_by_name[n] for n in used_names]

        def fn(params, *feeds):
            del params  # baked; empty dict keeps the jit.load convention
            outs = replay(*feeds)
            return tuple(outs)

        structs = [jax.ShapeDtypeStruct(tuple(t.shape), t._d.dtype)
                   for t in order]
        with suspend_trace():
            exported = jax_export.export(jax.jit(fn))({}, *structs)
        payload = {
            "state": {}, "param_dtypes": {}, "class": "StaticProgram",
            "out_is_tuple": True, "feed_names": used_names,
            "exported": exported.serialize(),
            "stablehlo": exported.mlir_module(),
        }
        from ..jit.save_load import _write_payload
        _write_payload(path_prefix, payload)
        self._text = payload["stablehlo"]

    def _lift_consts(self, jaxpr, consts, plist):
        """Match jaxpr consts against parameters (live or creation-time
        arrays) and threaded-state initials. Returns (lifted entries,
        lift vars, kept constvars, kept consts) — callers decide how the
        kept consts enter the rebuilt jaxpr. Shared by the compiled
        runner and the symbolic/export paths."""
        p_cand = {id(p._d): p for p in plist}
        for q, init in self._param_init:
            if any(q is p for p in plist):
                p_cand.setdefault(id(init), q)
        def _sig(a):
            # canonicalize typed PRNG keys to their raw uint32 data so a
            # key captured post-random_wrap matches its raw initial
            try:
                if jnp.issubdtype(a.dtype, jax.dtypes.prng_key):
                    a = jax.random.key_data(a)
                return (a.shape, str(a.dtype), np.asarray(a).tobytes())
            except Exception:
                return None

        s_cand = {}
        s_by_value = {}   # canonical signature -> [tid]; fallback match
        for tid, (t, init) in self._state.initial.items():
            self._state_shadow.setdefault(tid, Tensor(init))
            s_cand[id(init)] = tid
            sig = _sig(init)
            if sig is not None:
                s_by_value.setdefault(sig, []).append(tid)

        def state_for(c):
            tid = s_cand.get(id(c))
            if tid is not None:
                return tid
            # jax.random RE-WRAPS keys (random_wrap), so a key const is a
            # different OBJECT than its raw initial — value-match, but
            # ONLY for typed PRNG keys: a plain array that happens to
            # equal a state initial (e.g. ones[C] both as BN stat and as
            # a user constant) must never be lifted as state
            try:
                is_key = jnp.issubdtype(c.dtype, jax.dtypes.prng_key)
            except Exception:
                is_key = False
            if not is_key:
                return None
            sig = _sig(c)
            cands = s_by_value.get(sig, []) if sig else []
            return cands[0] if len(cands) == 1 else None

        lifted, lift_vars, kept_vars, kept_consts = [], [], [], []
        seen_lift = set()
        for v, c in zip(jaxpr.constvars, consts):
            p = p_cand.get(id(c))
            tid = state_for(c)
            if p is not None and id(p) not in seen_lift:
                seen_lift.add(id(p))
                lifted.append(("param", p))
                lift_vars.append(v)
            elif tid is not None and ("s", tid) not in seen_lift:
                seen_lift.add(("s", tid))
                lifted.append(("state", tid))
                lift_vars.append(v)
            else:
                kept_vars.append(v)
                kept_consts.append(c)
        return lifted, lift_vars, kept_vars, kept_consts

    def _build_symbolic_runner(self, fetch_list):
        """Runner for programs with dynamic (None) feed dims: the pruned
        jaxpr is exported shape-polymorphically (jax.export over this
        Program's symbolic scope) and refined per concrete batch at call
        time. Parameters and read state lift to inputs (live values stay
        visible); state WRITES are not threaded on this path — a symbolic
        program is a fetch/serving surface, not a train loop."""
        from jax import export as jax_export

        fetch_info, out_tracers = self._resolve_fetches(fetch_list)
        jaxpr, consts, used_names = self._close_pruned(out_tracers)
        traced_writes = any(
            isinstance(t._d, jcore.Tracer) or tid in self._state_tracer
            for tid, t in self._state.written.items())
        if traced_writes and not self._warned_state:
            self._warned_state = True
            import warnings
            warnings.warn(
                "this dynamic-dim Program mutates state (e.g. BatchNorm "
                "running stats); the symbolic fetch path does NOT thread "
                "those writes — stats stay at their current values. Use "
                "concrete shapes if the mutation must persist.",
                RuntimeWarning, stacklevel=4)
        shadows = self._state_shadow
        lifted, lift_vars, kept_vars, kept_consts = self._lift_consts(
            jaxpr, consts, self._params)
        jaxpr = jaxpr.replace(constvars=kept_vars,
                              invars=lift_vars + list(jaxpr.invars))
        replay = jcore.jaxpr_as_fun(jcore.ClosedJaxpr(jaxpr, kept_consts))

        def read_lifted():
            vals = []
            for kind, key in lifted:
                vals.append(key._d if kind == "param" else shadows[key]._d)
            return vals

        lift_specs = [jax.ShapeDtypeStruct(a.shape, a.dtype)
                      for a in read_lifted()]
        feed_specs = [jax.ShapeDtypeStruct(self._feeds[n]._d.aval.shape,
                                           self._feeds[n]._d.aval.dtype)
                      for n in used_names]
        with suspend_trace():
            exported = jax_export.export(
                jax.jit(lambda *a: tuple(replay(*a))))(
                    *lift_specs, *feed_specs)

        def runner(feed: dict):
            missing = [n for n in used_names if n not in (feed or {})]
            if missing:
                raise KeyError(f"Executor.run: feed is missing {missing} "
                               f"(required by the requested fetch_list)")
            args = list(read_lifted())
            for n in used_names:
                want = self._feeds[n]
                arr = feed[n]
                arr = arr._data if isinstance(arr, Tensor) else jnp.asarray(
                    np.asarray(arr))
                decl = want._d.aval.shape
                if arr.ndim != len(decl):
                    raise ValueError(
                        f"feed {n!r}: rank {arr.ndim} does not match "
                        f"declared shape {tuple(decl)}")
                for ax, d in enumerate(decl):
                    if isinstance(d, int) and arr.shape[ax] != d:
                        raise ValueError(
                            f"feed {n!r}: dim {ax} is {arr.shape[ax]}, "
                            f"declared {d}")
                    if not isinstance(d, int) and arr.shape[ax] == 0:
                        raise ValueError(
                            f"feed {n!r}: dynamic dim {ax} cannot be 0 "
                            f"(jax.export symbolic dims are >= 1); skip "
                            f"empty batches before Executor.run")
                args.append(arr.astype(want._d.dtype))
            with suspend_trace():
                outs = exported.call(*args)
            res = []
            for kind, payload in fetch_info:
                if kind == "traced":
                    res.append(np.asarray(outs[payload]))
                else:
                    res.append(payload.numpy())
            return res

        return runner

    def _by_name(self, name):
        for t in self._feeds.values():
            if t.name == name:
                return t
        raise KeyError(f"no Variable named {name!r} in this Program "
                       f"(fetch by the Tensor object, or by a feed name)")

    # -- execution ----------------------------------------------------------
    def _is_pure_startup(self):
        return not self._feed_order and self._opt is None

    def _run_startup(self):
        for p, init in self._param_init:
            p._data = init
        self._reset_run_state()
        main = getattr(self, "_paired_main", None)
        if main is not None and main is not self:
            main._reset_run_state()
        return []

    def _reset_run_state(self):
        """Fresh training run: reset optimizer accumulators and threaded
        state, and drop compiled runners (their to_static state lists
        captured the OLD accumulator tensors)."""
        if self._opt is not None:
            from collections import defaultdict
            self._opt._accumulators = defaultdict(dict)
            self._opt._master_weights = {}
            self._opt._step_count = 0
            st = getattr(self._opt, "_step_tensor", None)
            if st is not None:
                st._data = jnp.zeros_like(st._d)  # bias correction restarts
        for tid, (t, init) in self._state.initial.items():
            if tid in self._state_shadow:
                self._state_shadow[tid]._data = init
        self._runners.clear()

    def _execute(self, feed, fetch_list):
        with suspend_trace():
            if self._is_pure_startup():
                # a startup program's only job is (re)initialization; a main
                # program with feeds/optimizer must NOT reset on a bare
                # exe.run(main) — missing feeds surface as KeyError below
                return self._run_startup()
            fetch_list = list(fetch_list or [])
            train = self._opt is not None
            key = (train, tuple(
                f if isinstance(f, str) else id(f) for f in fetch_list))
            runner = self._runners.get(key)
            if runner is None:
                runner = self._runners[key] = self._build_runner(
                    fetch_list, train)
            return runner(feed or {})


@contextlib.contextmanager
def suspend_trace():
    """Run eagerly even while a Program trace is ambient (parameter
    initializers, Executor internals)."""
    with jcore.set_current_trace(jcore.eval_trace):
        yield


def _active_pair():
    """(main, startup) currently recording, or (None, None)."""
    if _GUARDS:
        return _GUARDS[-1]
    from ..framework import framework as fw
    if fw._static_mode:
        from . import default_main_program, default_startup_program
        return default_main_program(), default_startup_program()
    return None, None


def current_main_program() -> Program | None:
    return _active_pair()[0]


def on_parameter_created(p: Parameter):
    """Called by framework.create_parameter: snapshot initial values onto
    the active startup program (exe.run(startup) restores them)."""
    main, startup = _active_pair()
    if main is not None:
        main._params.append(p)
        main._param_init.append((p, p._d))   # trace-time array, for const
        #                                       matching at compile time
        if startup is not None:
            startup._param_init.append((p, p._d))


def maybe_record_minimize(opt, loss) -> bool:
    """Optimizer.minimize hook: True if recorded into an active Program
    (dygraph minimize must not run)."""
    main, _ = _active_pair()
    tr = _tracer_of(loss)
    if main is not None and tr is not None:
        main._record_minimize(opt, loss)
        return True
    return False


@contextlib.contextmanager
def program_guard(main_program, startup_program=None):
    """Reference static.program_guard: ops recorded between enter and exit
    belong to `main_program`; parameter initializations are snapshotted
    onto `startup_program`."""
    if not isinstance(main_program, Program):
        raise TypeError("program_guard expects a paddle.static.Program")
    if startup_program is not None:
        startup_program._paired_main = main_program
    _GUARDS.append((main_program, startup_program))
    main_program._activate()
    try:
        yield
    finally:
        main_program._deactivate()
        _GUARDS.pop()


def data(name, shape, dtype="float32", lod_level=0) -> Tensor:
    """Reference static.data: declare a feed Variable of the active
    Program (program_guard, or the default main program under
    paddle.enable_static())."""
    main, _ = _active_pair()
    if main is None:
        raise RuntimeError(
            "static.data() needs an active Program: wrap the build code in "
            "paddle.static.program_guard(...), or call "
            "paddle.enable_static() first")
    main._activate()
    return main._new_feed(name, shape, dtype)


class Executor:
    """Reference static.Executor over recorded Programs (and, for backward
    compatibility, any compiled callable such as a to_static function or a
    loaded TranslatedLayer)."""

    def __init__(self, place=None):
        self.place = place

    def run(self, program=None, feed=None, fetch_list=None, **kw):
        if program is None:
            from . import default_main_program
            program = default_main_program()
        if isinstance(program, Program):
            return program._execute(feed, fetch_list)
        if callable(program):
            return self._run_callable(program, feed or {})
        raise TypeError(
            "static.Executor.run expects a paddle.static.Program or a "
            "compiled callable (a jit.to_static function or loaded "
            "TranslatedLayer)")

    @staticmethod
    def _run_callable(program, feed):
        names = getattr(program, "_feed_names", None)
        if names:
            missing = [n for n in names if n not in feed]
            if missing:
                raise KeyError(f"feed missing inputs {missing}; "
                               f"expected {names}")
            args = [feed[n] for n in names]
        else:
            args = list(feed.values())
        outs = program(*args)
        if isinstance(outs, (list, tuple)):
            return [o.numpy() for o in outs]
        return [outs.numpy()]

    def close(self):
        pass
