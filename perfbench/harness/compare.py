"""The comparison that decides `correct`: each number beside its limit.

Limits live in `limits/<workload>.json`, one file per cell, set from chip
readings (PERF.md gives the readings). A number with the limit null is
printed and not judged.
"""

from __future__ import annotations

import math

import numpy as np


#: a leaf is compared in this many equal slices of its last axis, where
#: that divides: GPT-2 keeps q, k and v in one leaf, and the key third of
#: its bias has a gradient of nought under softmax (see `moved_leaves`)
SLICES = 3


def slice_norms(a):
    """Norms [SLICES] of an array's slices along its last axis (jax or
    numpy; [1] where the axis does not divide). A leading stacked axis is
    the caller's to map over."""
    import jax.numpy as jnp
    a = a.astype(jnp.float32)
    k = SLICES if a.ndim and a.shape[-1] % SLICES == 0 else 1
    parts = a.reshape(-1, k, a.shape[-1] // k) if a.ndim else \
        a.reshape(1, 1, 1)
    return jnp.sqrt(jnp.sum(parts * parts, axis=(0, 2)))


def slice_sums(a):
    """Sums [SLICES] of an array's slices, cut as `slice_norms` cuts them."""
    import jax.numpy as jnp
    a = a.astype(jnp.float32)
    k = SLICES if a.ndim and a.shape[-1] % SLICES == 0 else 1
    parts = a.reshape(-1, k, a.shape[-1] // k) if a.ndim else \
        a.reshape(1, 1, 1)
    return jnp.sum(parts, axis=(0, 2))


def grad_norms_from_moment2(before, after, beta2):
    """Norms of the gradient a step gave Adam, worked out from the sums of
    its second moment before and after that step: v' = b2 v + (1 - b2) g^2,
    so sum(v') - b2 sum(v) = (1 - b2) |g|^2. Needs no copy of the state, and
    reads the step that ran, compiled or not. A step that left its state
    unchanged reads sqrt(1 - b2) of the last gradient's norm, all but 0."""
    out = {}
    for name, now in after.items():
        was = 0.0 if before is None else np.asarray(before[name], np.float64)
        sq = (np.asarray(now, np.float64) - beta2 * was) / (1.0 - beta2)
        out[name] = np.sqrt(np.maximum(sq, 0.0))
    return out


def _flat(norms: dict) -> dict:
    """{leaf[index]: float} from {leaf: array of norms}."""
    out = {}
    for name, val in norms.items():
        for idx, x in np.ndenumerate(np.asarray(val, np.float64)):
            out[name + "".join(f"[{i}]" for i in idx)] = float(x)
    return out


def worst_norm_gap(got: dict, ref: dict, keep=None):
    """(gap, leaf): the worst leaf's | ||got|| - ||ref|| | over the larger of
    the reference's norm of that leaf and of the median leaf."""
    got, ref = _flat(got), _flat(ref)
    if set(got) != set(ref):
        raise KeyError(f"leaves differ: {sorted(set(got) ^ set(ref))[:6]}")
    names = [n for n in ref if keep is None or n in keep]
    med = float(np.median([ref[n] for n in names]))
    worst, where = 0.0, None
    for n in names:
        gap = abs(got[n] - ref[n]) / max(ref[n], med, 1e-30)
        if not math.isfinite(gap):
            gap = math.inf
        if gap >= worst:
            worst, where = gap, n
    return worst, where


def moved_leaves(ref_grad: dict, floor=1e-3) -> set:
    """Leaves whose reference gradient is not nought to rounding: at least
    `floor` of the median leaf's. The others move under Adam by round-off
    alone and are left out of the parameters' change."""
    flat = _flat(ref_grad)
    med = float(np.median(list(flat.values())))
    return {n for n, v in flat.items() if v >= floor * med}


def training_numbers(got: dict, ref: dict) -> dict:
    """got/ref: {"losses": [..], "grads": [{leaf: norm}, ..] (one a step),
    "delta": {leaf: norm}}."""
    nums = {}
    for i, (a, b) in enumerate(zip(got["losses"], ref["losses"]), 1):
        gap = abs(a - b) / abs(b)
        nums[f"loss{i}_gap"] = gap if math.isfinite(gap) else math.inf
    for i, (a, b) in enumerate(zip(got["grads"], ref["grads"]), 1):
        nums[f"grad{i}_norm_gap"], nums[f"grad{i}_norm_leaf"] = \
            worst_norm_gap(a, b)
    nums["delta_norm_gap"], nums["delta_norm_leaf"] = \
        worst_norm_gap(got["delta"], ref["delta"],
                       keep=moved_leaves(ref["grads"][0]))
    return nums


def as_program(ref_run: dict) -> dict:
    """A run of the reference (a control or a planted fault) put in the
    program's place: its gradients' norms are read from its optimizer's
    state, as the program's are."""
    return dict(ref_run, grads=ref_run["grads_from_state"])


def judge(numbers: dict, limits: dict):
    """(correct, [[name, number, limit], ...]) over the numbers a limits
    file names; a name it lacks is an error, a null limit is not judged."""
    rows, ok = [], True
    for name, limit in limits.items():
        if name.startswith("_"):
            continue
        val = numbers[name]
        rows.append([name, val, limit])
        if limit is not None and not (val <= limit):
            ok = False
    return ok, rows
