"""Optimizer base class (reference: python/paddle/optimizer/optimizer.py:93).

Semantics mirror the reference: optimizers hold a parameter list, read
``param.grad`` filled by ``loss.backward()``, apply grad clip / weight decay,
and update parameters in place. The learning rate lives in a device scalar
(`_lr_tensor`) so a jitted train step never recompiles when a scheduler steps.

All update math is jnp elementwise — XLA fuses the whole optimizer into a few
kernels under jit, which is the TPU analog of the reference's fused
multi-tensor AdamW kernels (paddle/phi/kernels/fusion/gpu/fused_adam_kernel.cu).
"""

from __future__ import annotations

from collections import defaultdict

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor, Parameter
from ..core import dtype as dtypes

__all__ = ["Optimizer"]


class Optimizer:
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, name=None, multi_precision=False, fuse=None):
        from .lr import LRScheduler
        if parameters is None:
            # allowed while a static Program is recording: minimize() adopts
            # the program's trainable parameters (reference static mode pulls
            # them from the Program the same way)
            from ..static.program import current_main_program
            if current_main_program() is None:
                raise ValueError("parameters must be provided (dygraph mode)")
            parameters = []
        self._parameter_list = list(parameters)
        # support param groups: [{'params': [...], 'learning_rate': ...}, ...]
        self._param_groups = None
        if self._parameter_list and isinstance(self._parameter_list[0], dict):
            self._param_groups = self._parameter_list
            flat = []
            for g in self._param_groups:
                flat.extend(g["params"])
            self._parameter_list = flat
        self._lr_scheduler = None
        if isinstance(learning_rate, LRScheduler):
            self._lr_scheduler = learning_rate
            lr0 = float(learning_rate())
        else:
            lr0 = float(learning_rate)
        # eager scalars even inside a static program_guard: an ambient
        # Program trace would otherwise turn these into foreign tracers
        # poisoning the later compiled step (reference static mode keeps
        # optimizer scalars in the global scope the same way)
        from ..static.program import suspend_trace
        with suspend_trace():
            self._lr_tensor = Tensor(jnp.asarray(lr0, jnp.float32))
            # device-side step counter so bias correction is data, not a
            # baked constant, inside a jitted train step
            self._step_tensor = Tensor(jnp.zeros((), jnp.float32))
        if self._lr_scheduler is not None:
            self._lr_scheduler.bind(self)
        # a bare float weight_decay means coupled L2 decay (reference
        # semantics); decoupled optimizers (AdamW) bypass this and use
        # self._weight_decay directly
        self._weight_decay = weight_decay if isinstance(weight_decay, (int, float)) \
            else getattr(weight_decay, "_coeff", None)
        if isinstance(weight_decay, (int, float)) and weight_decay:
            from ..regularizer import L2Decay
            self._regularization = L2Decay(float(weight_decay))
        elif weight_decay is None or isinstance(weight_decay, (int, float)):
            self._regularization = None
        else:  # L1Decay / L2Decay object
            self._regularization = weight_decay
        self._grad_clip = grad_clip
        self._multi_precision = multi_precision
        # accumulators: name -> {param_name: Tensor}
        self._accumulators: dict[str, dict[int, Tensor]] = defaultdict(dict)
        self._master_weights: dict[int, Tensor] = {}
        self._step_count = 0
        # fused multi-tensor update (optimizer/fused.py): one jitted,
        # structure-cached device computation per step instead of a kernel
        # chain per parameter. fuse=None defers to PADDLE_TPU_FUSED_OPT.
        from .fused import fuse_default
        self._fuse = bool(fuse) if fuse is not None else fuse_default()
        self._fused_impl = None

    # -- lr -----------------------------------------------------------------
    def get_lr(self) -> float:
        return float(self._lr_tensor._data)

    def set_lr(self, value: float):
        if self._lr_scheduler is not None:
            raise RuntimeError("cannot set_lr when an LRScheduler is in use")
        self._lr_tensor._data = jnp.asarray(float(value), jnp.float32)

    def _set_lr_value(self, value: float):
        self._lr_tensor._data = jnp.asarray(float(value), jnp.float32)

    def _lr(self, param=None):
        lr = self._lr_tensor._data
        if param is not None and getattr(param, "optimize_attr", None):
            lr = lr * param.optimize_attr.get("learning_rate", 1.0)
        return lr

    # -- accumulators -------------------------------------------------------
    def _add_accumulator(self, name, param, fill_value=0.0, dtype=None):
        key = id(param)
        if key not in self._accumulators[name]:
            dt = dtype if dtype is not None else (
                jnp.float32 if self._multi_precision else param._data.dtype)
            # moments follow their parameter's sharding (ZeRO/semi-auto),
            # in placement and not only in annotation: created unplaced,
            # every full-size moment of a sharded model landed on the first
            # device (10 GB on one chip of four at Llama-8B widths)
            placed = getattr(param._data, "sharding", None)
            if placed is not None and len(placed.device_set) == 1:
                placed = None       # one device: stay uncommitted
            acc = Tensor(jnp.full(param._data.shape, fill_value, dt,
                                  device=placed))
            acc._sharding_spec = param._sharding_spec
            self._accumulators[name][key] = acc
        return self._accumulators[name][key]

    def _get_master(self, param):
        if not self._multi_precision or param._data.dtype == jnp.float32.dtype:
            return None
        key = id(param)
        if key not in self._master_weights:
            self._master_weights[key] = Tensor(param._data.astype(jnp.float32))
        return self._master_weights[key]

    # -- core update --------------------------------------------------------
    def step(self):
        from ..jit.api import in_to_static_trace
        from ..profiler.profiler import host_self_span
        with host_self_span("optimizer_step(host)"), \
                jax.named_scope("optimizer"):
            if self._fuse and not in_to_static_trace():
                self._fused().step()
                return
            if self._fuse:
                # inside an enclosing to_static trace the unrolled loop IS
                # fused — into the whole-train-step program; fires once per
                # trace, not per step (host-side counter)
                from .fused import note_outer_jit_step
                note_outer_jit_step()
            self._step_unfused()

    def _fused(self):
        if self._fused_impl is None:
            from .fused import FusedOptimizerStep
            self._fused_impl = FusedOptimizerStep(self)
        return self._fused_impl

    def _fused_scale_step(self, scale):
        """GradScaler hook: fused unscale + found_inf + inf-skipped update in
        one device computation. Returns the host found_inf bool, or None when
        the fused path can't take it (fusion off, inside a trace, or the
        state structure is cold) — the caller then runs the legacy
        unscale_/step path."""
        from ..jit.api import in_to_static_trace
        if not self._fuse or in_to_static_trace():
            return None
        from ..profiler.profiler import host_self_span
        with host_self_span("optimizer_step(host)"):
            return self._fused().step(scale=scale)

    def _step_unfused(self):
        """The per-parameter update loop (the fused path's warm-up/escape
        hatch, and the body every enclosing to_static trace unrolls)."""
        params_grads = []
        for p in self._parameter_list:
            if p.stop_gradient or p._grad is None:
                continue
            params_grads.append((p, p._grad))
        if self._grad_clip is not None:
            params_grads = self._grad_clip(params_grads)
        self._step_count += 1
        self._step_tensor._data = self._step_tensor._data + 1.0
        for p, g in params_grads:
            if g is None:
                continue
            self._append_optimize_op(p, g)

    def _append_optimize_op(self, param, grad):
        raise NotImplementedError

    def _fused_state_names(self, param):
        """Accumulator names `_append_optimize_op` lazily creates for
        `param`, or None when unknown. The fused path uses this to tell
        "state restored in place by set_state_dict — fuse immediately, a
        resumed run must be bit-identical to the uninterrupted one" apart
        from "state missing — run one eager warm-up step to create it".
        Subclasses that don't declare fall back to the warm-up heuristic."""
        return None

    def clear_grad(self, set_to_zero: bool = False):
        for p in self._parameter_list:
            p.clear_gradient(set_to_zero)

    clear_gradients = clear_grad

    def minimize(self, loss, startup_program=None, parameters=None, no_grad_set=None):
        from ..static.program import maybe_record_minimize
        if maybe_record_minimize(self, loss):
            # static-graph mode: the backward + update ops are generated at
            # Executor compile time (jax.value_and_grad over the replayed
            # program), not appended here
            return None, []
        if not self._parameter_list:
            # parameters=None was allowed because a Program was recording,
            # but this loss is not traced into it — stepping nothing would
            # be a silent no-op
            raise ValueError(
                "minimize() on a non-traced loss with an empty parameter "
                "list: pass parameters= to the optimizer (dygraph mode), or "
                "compute the loss inside the active static Program")
        loss.backward()
        self.step()
        return None, [(p, p._grad) for p in self._parameter_list]

    # -- state dict ---------------------------------------------------------
    def state_dict(self) -> dict:
        state = {}
        for acc_name, accs in self._accumulators.items():
            for p in self._parameter_list:
                if id(p) in accs:
                    state[f"{p.name}_{acc_name}"] = accs[id(p)]
        if self._master_weights:
            state["master_weights"] = {
                p.name: self._master_weights[id(p)]
                for p in self._parameter_list if id(p) in self._master_weights}
        if self._lr_scheduler is not None:
            state["LR_Scheduler"] = self._lr_scheduler.state_dict()
        state["@step"] = self._step_count
        return state

    def set_state_dict(self, state_dict):
        state_dict = dict(state_dict)
        self._step_count = int(state_dict.pop("@step", 0))
        # the device-side step counter drives Adam bias correction inside
        # jitted steps; resyncing it from @step makes a restored run
        # bit-identical to the uninterrupted one (it advances in lockstep
        # with _step_count in step())
        self._step_tensor._data = jnp.asarray(float(self._step_count),
                                              jnp.float32)
        sched = state_dict.pop("LR_Scheduler", None)
        if sched is not None and self._lr_scheduler is not None:
            self._lr_scheduler.set_state_dict(sched)
        masters = state_dict.pop("master_weights", None)
        if masters:
            by_name = {p.name: p for p in self._parameter_list}
            for n, w in masters.items():
                if n in by_name:
                    arr = w._data if isinstance(w, Tensor) else jnp.asarray(w)
                    existing = self._master_weights.get(id(by_name[n]))
                    if existing is not None:
                        existing._data = arr
                    else:
                        self._master_weights[id(by_name[n])] = Tensor(arr)
        by_name = {p.name: p for p in self._parameter_list}
        unbound = []
        for k, v in state_dict.items():
            # longest-prefix match: with params 'w' and 'w_1', key
            # 'w_1_moment1' must bind to 'w_1' (ADVICE r1: arbitrary-order
            # startswith matching could assign state to the wrong param)
            best = None
            for p_name in by_name:
                if k.startswith(p_name + "_") and \
                        (best is None or len(p_name) > len(best)):
                    best = p_name
            if best is None:
                unbound.append(k)
                continue
            p = by_name[best]
            acc_name = k[len(best) + 1:]
            arr = v._data if isinstance(v, Tensor) else jnp.asarray(v)
            existing = self._accumulators[acc_name].get(id(p))
            if existing is not None:
                # in place: a mid-run rewind (NaN sentinel) must not orphan
                # accumulator handles already lifted into a jitted step
                existing._data = arr
            else:
                self._accumulators[acc_name][id(p)] = Tensor(arr)
        if unbound:
            # silently dropping moments would resume Adam from zeroed state
            # — numerically plausible but wrong; a resumed run must KNOW
            # its accumulators didn't bind (auto-generated tensor names
            # only reproduce in a fresh process with identical construction
            # order; pass explicit parameter names for anything else)
            import warnings
            warnings.warn(
                f"optimizer.set_state_dict: {len(unbound)} state entr"
                f"{'y' if len(unbound) == 1 else 'ies'} matched no "
                f"parameter (e.g. {unbound[0]!r}); accumulators for those "
                f"parameters start fresh", RuntimeWarning)

    # -- state tensors for jit lifting -------------------------------------
    def _state_tensors(self) -> list[Tensor]:
        out = [self._lr_tensor]
        for accs in self._accumulators.values():
            out.extend(accs.values())
        out.extend(self._master_weights.values())
        return out

    # weight decay helper: returns decayed grad (decoupled handled per-opt)
    def _apply_coupled_weight_decay(self, param, g_arr):
        if self._regularization is not None:
            return self._regularization._apply(param._data, g_arr)
        return g_arr
