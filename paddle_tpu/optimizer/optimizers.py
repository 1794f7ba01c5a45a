"""Concrete optimizers (reference: python/paddle/optimizer/{sgd,momentum,adam,
adamw,adagrad,adadelta,adamax,rmsprop,lamb,lbfgs}.py).

Each `_append_optimize_op` is pure jnp math over arrays; under jit XLA fuses
the whole family into fused update kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from .optimizer import Optimizer

__all__ = ["SGD", "Momentum", "Adam", "AdamW", "Adagrad", "Adadelta", "Adamax",
           "RMSProp", "Lamb", "LBFGS"]


def _wd_coeff(weight_decay):
    if weight_decay is None:
        return 0.0
    if isinstance(weight_decay, (int, float)):
        return float(weight_decay)
    return float(getattr(weight_decay, "_coeff", 0.0))


class SGD(Optimizer):
    def __init__(self, learning_rate=0.001, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None, fuse=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision, fuse=fuse)

    def _fused_state_names(self, p):
        return []

    def _append_optimize_op(self, p, grad):
        g = self._apply_coupled_weight_decay(p, grad._data.astype(jnp.float32))
        master = self._get_master(p)
        w = master._data if master is not None else p._data
        new_w = w - self._lr(p) * g.astype(w.dtype)
        if master is not None:
            master._data = new_w
            p._data = new_w.astype(p._data.dtype)
        else:
            p._data = new_w


class Momentum(Optimizer):
    def __init__(self, learning_rate=0.001, momentum=0.9, parameters=None,
                 use_nesterov=False, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None, fuse=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision, fuse=fuse)
        self._momentum = momentum
        self._use_nesterov = use_nesterov

    def _fused_state_names(self, p):
        return ["velocity"]

    def _append_optimize_op(self, p, grad):
        g = self._apply_coupled_weight_decay(p, grad._data.astype(jnp.float32))
        master = self._get_master(p)
        w = master._data if master is not None else p._data
        vel = self._add_accumulator("velocity", p, dtype=jnp.float32)
        v_new = self._momentum * vel._data + g
        if self._use_nesterov:
            upd = g + self._momentum * v_new
        else:
            upd = v_new
        vel._data = v_new
        new_w = w - self._lr(p) * upd.astype(w.dtype)
        if master is not None:
            master._data = new_w
            p._data = new_w.astype(p._data.dtype)
        else:
            p._data = new_w


@functools.partial(jax.jit, static_argnames=(
    "beta1", "beta2", "eps", "decay", "out_dtype"))
def _adam_update(w, g, m, v, vmax, lr, t, *, beta1, beta2, eps, decay,
                 out_dtype):
    """One Adam / AdamW step of one parameter, every operand in the shape
    and layout it has. `w` is the float32 master, or the parameter itself
    where there is none (`out_dtype` None); `decay` is the decoupled one;
    `vmax` is amsgrad's running maximum or None; `lr` and `t` are float32
    device scalars, so nothing here is float64 under the process's x64.

    Inside a compiled train step XLA makes one loop of the chain that reads
    `w, g, m, v` and writes `w', m', v'` and the low-precision copy in place,
    and for a matrix puts that loop at the end of the matmul that makes its
    gradient, which then never reaches HBM (PERF.md section 6, PR 32: a
    Pallas kernel here cost 8.7 % of GPT-2 medium's tokens/s against it, and
    one over `[rows, 128]` views 30 %). Called eagerly it is one program a
    parameter, so that an eager step holds none of the chain's temporaries."""
    w32 = w.astype(jnp.float32)
    g = g.astype(jnp.float32)
    m = beta1 * m + (1 - beta1) * g
    v = beta2 * v + (1 - beta2) * jnp.square(g)
    mhat = m / (1 - beta1 ** t)
    vhat = v / (1 - beta2 ** t)
    if vmax is not None:
        vmax = vhat = jnp.maximum(vmax, vhat)
    if decay:
        w32 = w32 * (1.0 - lr * decay)
    new_w = w32 - lr * mhat / (jnp.sqrt(vhat) + eps)
    p_out = None if out_dtype is None else new_w.astype(out_dtype)
    return new_w.astype(w.dtype), m, v, vmax, p_out


class Adam(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None, lazy_mode=False,
                 multi_precision=False, use_multi_tensor=False, name=None,
                 amsgrad=False, fuse=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision, fuse=fuse)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._amsgrad = amsgrad
        self._decoupled = False

    def _fused_state_names(self, p):
        return ["moment1", "moment2", "moment2_max"] if self._amsgrad \
            else ["moment1", "moment2"]

    def _lr_for(self, p):
        return self._lr(p)

    def _decoupled_decay_for(self, p) -> float:
        return 0.0  # plain Adam couples decay into the gradient instead

    def _append_optimize_op(self, p, grad):
        """Shared Adam/AdamW body: the only behavioral fork is whether decay
        is coupled into the gradient (Adam) or applied to the weights
        (AdamW, via `_decoupled_decay_for`)."""
        g = grad._data
        if not self._decoupled:
            g = self._apply_coupled_weight_decay(p, g.astype(jnp.float32))
        master = self._get_master(p)
        m = self._add_accumulator("moment1", p, dtype=jnp.float32)
        v = self._add_accumulator("moment2", p, dtype=jnp.float32)
        vmax = self._add_accumulator("moment2_max", p, dtype=jnp.float32) \
            if self._amsgrad else None
        new_w, m._data, v._data, new_vmax, p_out = _adam_update(
            p._data if master is None else master._data, g, m._data, v._data,
            None if vmax is None else vmax._data, self._lr_for(p),
            self._step_tensor._data,    # one counter corrects every bias
            beta1=float(self._beta1), beta2=float(self._beta2),
            eps=float(self._epsilon),
            decay=float(self._decoupled_decay_for(p)),
            out_dtype=None if master is None else p._data.dtype)
        if vmax is not None:
            vmax._data = new_vmax
        if master is None:
            p._data = new_w
        else:
            master._data, p._data = new_w, p_out

    @property
    def _wd_value(self):
        return _wd_coeff(self._weight_decay)


class AdamW(Adam):
    """Decoupled weight decay (reference: python/paddle/optimizer/adamw.py).
    `apply_decay_param_fun` filters which params decay, as in the reference."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=0.01, lr_ratio=None,
                 apply_decay_param_fun=None, grad_clip=None, lazy_mode=False,
                 multi_precision=False, name=None, amsgrad=False, fuse=None):
        super().__init__(learning_rate, beta1, beta2, epsilon, parameters,
                         weight_decay, grad_clip, lazy_mode, multi_precision,
                         name=name, amsgrad=amsgrad, fuse=fuse)
        self._decoupled = True
        self._regularization = None  # decay is decoupled, never coupled
        self._apply_decay_param_fun = apply_decay_param_fun
        self._lr_ratio = lr_ratio

    def _lr_for(self, p):
        lr = self._lr(p)
        if self._lr_ratio is not None:
            lr = lr * self._lr_ratio(p)
        return lr

    def _decoupled_decay_for(self, p) -> float:
        if self._apply_decay_param_fun is not None and \
                not self._apply_decay_param_fun(p.name):
            return 0.0
        return self._wd_value


class Adagrad(Optimizer):
    def __init__(self, learning_rate, epsilon=1e-6, parameters=None,
                 weight_decay=None, grad_clip=None, initial_accumulator_value=0.0,
                 multi_precision=False, name=None, fuse=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision, fuse=fuse)
        self._epsilon = epsilon
        self._initial = initial_accumulator_value

    def _fused_state_names(self, p):
        return ["moment"]

    def _append_optimize_op(self, p, grad):
        g = self._apply_coupled_weight_decay(p, grad._data.astype(jnp.float32))
        acc = self._add_accumulator("moment", p, fill_value=self._initial,
                                    dtype=jnp.float32)
        acc._data = acc._data + jnp.square(g)
        p._data = (p._data.astype(jnp.float32) -
                   self._lr(p) * g / (jnp.sqrt(acc._data) + self._epsilon)
                   ).astype(p._data.dtype)


class Adadelta(Optimizer):
    def __init__(self, learning_rate=0.001, epsilon=1e-6, rho=0.95,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None, fuse=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision, fuse=fuse)
        self._epsilon, self._rho = epsilon, rho

    def _fused_state_names(self, p):
        return ["avg_squared_grad", "avg_squared_update"]

    def _append_optimize_op(self, p, grad):
        g = self._apply_coupled_weight_decay(p, grad._data.astype(jnp.float32))
        avg_sq = self._add_accumulator("avg_squared_grad", p, dtype=jnp.float32)
        avg_up = self._add_accumulator("avg_squared_update", p, dtype=jnp.float32)
        avg_sq._data = self._rho * avg_sq._data + (1 - self._rho) * jnp.square(g)
        upd = jnp.sqrt(avg_up._data + self._epsilon) / \
            jnp.sqrt(avg_sq._data + self._epsilon) * g
        avg_up._data = self._rho * avg_up._data + (1 - self._rho) * jnp.square(upd)
        p._data = (p._data.astype(jnp.float32) - self._lr(p) * upd) \
            .astype(p._data.dtype)


class Adamax(Optimizer):
    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 parameters=None, weight_decay=None, grad_clip=None,
                 multi_precision=False, name=None, fuse=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision, fuse=fuse)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon

    def _fused_state_names(self, p):
        return ["moment", "inf_norm"]

    def _append_optimize_op(self, p, grad):
        g = self._apply_coupled_weight_decay(p, grad._data.astype(jnp.float32))
        m = self._add_accumulator("moment", p, dtype=jnp.float32)
        u = self._add_accumulator("inf_norm", p, dtype=jnp.float32)
        t = self._step_tensor._data
        m._data = self._beta1 * m._data + (1 - self._beta1) * g
        u._data = jnp.maximum(self._beta2 * u._data, jnp.abs(g))
        lr = self._lr(p) / (1 - self._beta1 ** self._step_tensor._data)
        p._data = (p._data.astype(jnp.float32) -
                   lr * m._data / (u._data + self._epsilon)).astype(p._data.dtype)


class RMSProp(Optimizer):
    def __init__(self, learning_rate, rho=0.95, epsilon=1e-6, momentum=0.0,
                 centered=False, parameters=None, weight_decay=None,
                 grad_clip=None, multi_precision=False, name=None, fuse=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip,
                         name, multi_precision, fuse=fuse)
        self._rho, self._epsilon = rho, epsilon
        self._momentum, self._centered = momentum, centered

    def _fused_state_names(self, p):
        names = ["mean_square", "momentum"]
        return names + ["mean_grad"] if self._centered else names

    def _append_optimize_op(self, p, grad):
        g = self._apply_coupled_weight_decay(p, grad._data.astype(jnp.float32))
        ms = self._add_accumulator("mean_square", p, dtype=jnp.float32)
        mom = self._add_accumulator("momentum", p, dtype=jnp.float32)
        ms._data = self._rho * ms._data + (1 - self._rho) * jnp.square(g)
        if self._centered:
            mg = self._add_accumulator("mean_grad", p, dtype=jnp.float32)
            mg._data = self._rho * mg._data + (1 - self._rho) * g
            denom = jnp.sqrt(ms._data - jnp.square(mg._data) + self._epsilon)
        else:
            denom = jnp.sqrt(ms._data + self._epsilon)
        mom._data = self._momentum * mom._data + self._lr(p) * g / denom
        p._data = (p._data.astype(jnp.float32) - mom._data).astype(p._data.dtype)


class Lamb(Optimizer):
    def __init__(self, learning_rate=0.001, lamb_weight_decay=0.01, beta1=0.9,
                 beta2=0.999, epsilon=1e-6, parameters=None, grad_clip=None,
                 exclude_from_weight_decay_fn=None, multi_precision=False,
                 name=None, fuse=None):
        super().__init__(learning_rate, parameters, None, grad_clip, name,
                         multi_precision, fuse=fuse)
        self._beta1, self._beta2, self._epsilon = beta1, beta2, epsilon
        self._lamb_wd = lamb_weight_decay
        self._exclude_fn = exclude_from_weight_decay_fn

    def _fused_state_names(self, p):
        return ["moment1", "moment2"]

    def _use_fused_kernel(self, p) -> bool:
        from ..core.flags import flag
        from ..ops.kernels import _common as kern
        return (kern.available() and flag("use_pallas_kernels")
                and p._data.size >= 8192)

    def _append_optimize_op(self, p, grad):
        g = grad._data.astype(jnp.float32)
        w32 = p._data.astype(jnp.float32)
        m = self._add_accumulator("moment1", p, dtype=jnp.float32)
        v = self._add_accumulator("moment2", p, dtype=jnp.float32)
        t = self._step_tensor._data

        if self._use_fused_kernel(p):
            from ..ops.kernels import _common as kern
            from ..ops.kernels import lamb_pallas as lp
            wd = self._lamb_wd
            if self._exclude_fn is not None and self._exclude_fn(p):
                wd = 0.0
            master = self._get_master(p)
            if master is not None:
                w32 = master._data
            new_w, m._data, v._data, p_out, _ = lp.lamb_update(
                w32, g, m._data, v._data, self._lr(p), t,
                beta1=self._beta1, beta2=self._beta2, eps=self._epsilon,
                wd=float(wd), out_dtype=p._data.dtype,
                interpret=kern.interpret_mode(),
                emit_w32=master is not None)
            if master is not None:
                master._data = new_w
            p._data = p_out
            return

        m._data = self._beta1 * m._data + (1 - self._beta1) * g
        v._data = self._beta2 * v._data + (1 - self._beta2) * jnp.square(g)
        mhat = m._data / (1 - self._beta1 ** t)
        vhat = v._data / (1 - self._beta2 ** t)
        r = mhat / (jnp.sqrt(vhat) + self._epsilon)
        wd = self._lamb_wd
        if self._exclude_fn is not None and self._exclude_fn(p):
            wd = 0.0
        master = self._get_master(p)
        if master is not None:
            w32 = master._data
        update = r + wd * w32
        w_norm = jnp.linalg.norm(w32)
        u_norm = jnp.linalg.norm(update)
        trust = jnp.where((w_norm > 0) & (u_norm > 0), w_norm / u_norm, 1.0)
        new_w = w32 - self._lr(p) * trust * update
        if master is not None:
            master._data = new_w
        p._data = new_w.astype(p._data.dtype)


class LBFGS(Optimizer):
    """L-BFGS with strong-Wolfe line search (reference:
    python/paddle/optimizer/lbfgs.py). Requires a closure like the reference."""

    def __init__(self, learning_rate=1.0, max_iter=20, max_eval=None,
                 tolerance_grad=1e-7, tolerance_change=1e-9, history_size=100,
                 line_search_fn=None, parameters=None, weight_decay=None,
                 grad_clip=None, name=None):
        super().__init__(learning_rate, parameters, weight_decay, grad_clip, name)
        self._max_iter = max_iter
        self._tol_grad = tolerance_grad
        self._tol_change = tolerance_change
        self._history_size = history_size
        self._line_search_fn = line_search_fn
        self._s, self._y = [], []
        self._prev_flat_grad = None

    def _gather_flat_grad(self):
        return jnp.concatenate([
            (p._grad._data if p._grad is not None else jnp.zeros_like(p._data))
            .astype(jnp.float32).reshape(-1) for p in self._parameter_list])

    def _add_to_params(self, step, direction):
        offset = 0
        for p in self._parameter_list:
            n = p._data.size
            upd = direction[offset:offset + n].reshape(p._data.shape)
            p._data = (p._data.astype(jnp.float32) + step * upd).astype(p._data.dtype)
            offset += n

    def step(self, closure=None):
        if closure is None:
            raise ValueError("LBFGS.step requires a closure")
        loss = closure()
        flat_grad = self._gather_flat_grad()
        for _ in range(self._max_iter):
            if float(jnp.max(jnp.abs(flat_grad))) <= self._tol_grad:
                break
            # two-loop recursion
            q = flat_grad
            alphas = []
            for s, y in zip(reversed(self._s), reversed(self._y)):
                rho = 1.0 / jnp.maximum(jnp.dot(y, s), 1e-10)
                a = rho * jnp.dot(s, q)
                q = q - a * y
                alphas.append((a, rho, s, y))
            if self._y:
                gamma = jnp.dot(self._s[-1], self._y[-1]) / jnp.maximum(
                    jnp.dot(self._y[-1], self._y[-1]), 1e-10)
                q = gamma * q
            for a, rho, s, y in reversed(alphas):
                b = rho * jnp.dot(y, q)
                q = q + (a - b) * s
            direction = -q
            step = float(self._lr(None))
            old_params = [p._data for p in self._parameter_list]
            self._add_to_params(step, direction)
            self.clear_grad()
            new_loss = closure()
            new_flat = self._gather_flat_grad()
            s_vec = step * direction
            y_vec = new_flat - flat_grad
            if float(jnp.dot(s_vec, y_vec)) > 1e-10:
                self._s.append(s_vec)
                self._y.append(y_vec)
                if len(self._s) > self._history_size:
                    self._s.pop(0)
                    self._y.pop(0)
            if abs(float(new_loss._data) - float(loss._data)) < self._tol_change:
                loss = new_loss
                break
            loss, flat_grad = new_loss, new_flat
        return loss
