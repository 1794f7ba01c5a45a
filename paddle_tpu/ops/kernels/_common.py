"""Shared state for Pallas TPU kernels: availability + interpret-mode hook.

Every kernel module (flash attention, fused rmsnorm, ...) dispatches on
`available()`; tests flip `force_interpret(True)` to run the real kernel
jaxprs through the Pallas interpreter on CPU.
"""

from __future__ import annotations

import functools

import jax

_INTERPRET = False  # test hook: run the Pallas kernels in interpret mode
_FORCE_DISPATCH = False  # test hook: dispatch real kernels off-TPU (for
#                          cross-platform TPU *lowering* tests — the traced
#                          program is never executed on the host platform)


def force_interpret(enable: bool) -> None:
    global _INTERPRET
    _INTERPRET = bool(enable)


def force_dispatch(enable: bool) -> None:
    """Make `available()` True with interpret_mode() False, so live paths
    trace the REAL pallas_call even on CPU. Only valid for lowering-only
    traces (jit(...).trace(...).lower(lowering_platforms=("tpu",)))."""
    global _FORCE_DISPATCH
    _FORCE_DISPATCH = bool(enable)


def interpret_mode() -> bool:
    return _INTERPRET


def x64_off():
    """``jax.enable_x64(False)``. Every pallas_call in this package traces
    under it — the framework enables x64 globally, which turns
    index-map/loop literals into i64/f64 types Mosaic cannot legalize."""
    return jax.enable_x64(False)


def jit_x64_off(fn, **jit_kwargs):
    """``jax.jit`` whose CALLS run under :func:`x64_off` — so the trace
    AND the compile/lowering see the same 32-bit world."""
    jitted = jax.jit(fn, **jit_kwargs)

    @functools.wraps(fn)
    def call(*args, **kwargs):
        with x64_off():
            return jitted(*args, **kwargs)
    return call


def round_up(n, multiple):
    """Ceil `n` to a multiple (Mosaic block-alignment arithmetic)."""
    return -(-n // multiple) * multiple


def pad_tail(a, pad, axis=0, value=0.0):
    """Append ``pad`` fill rows along ``axis``."""
    import jax.numpy as jnp
    if not pad:
        return a
    shape = list(a.shape)
    shape[axis] = pad
    return jnp.concatenate([a, jnp.full(shape, value, a.dtype)], axis=axis)


def pad_to_block(a, block, axis=0):
    """Zero-pad `axis` of `a` up to a multiple of `block` (Mosaic requires
    sublane/lane-divisible blocks; callers slice the result back)."""
    return pad_tail(a, (-a.shape[axis]) % block, axis=axis)


_BLOCK_OVERRIDES: dict = {}  # kernel key -> measured row-block choice


def set_block_override(key, rows) -> None:
    """Install a measured row-block size for a kernel family (the
    auto_tuner's Pallas block tuning writes here; None clears)."""
    if rows is None:
        _BLOCK_OVERRIDES.pop(key, None)
    else:
        if rows % 8 or rows <= 0:
            raise ValueError(f"block override must be a positive multiple "
                             f"of 8, got {rows}")
        _BLOCK_OVERRIDES[key] = int(rows)


def get_block_override(key):
    return _BLOCK_OVERRIDES.get(key)


_LAST_PICK: dict = {}  # kernel key -> rows actually chosen at last pick


def get_last_pick(key):
    """Effective row-block pick_row_block last returned for `key` (the
    auto-tuner reads this to detect VMEM-cap clamping: a candidate above
    the cap runs the same program as the cap itself)."""
    return _LAST_PICK.get(key)


def pick_row_block(n_rows, row_bytes, budget, key=None):
    """Row-block size under a VMEM byte budget: a multiple of 8 (Mosaic
    sublane rule — degraded rows=1 blocks fail TPU lowering), capped at 256
    and at the padded input extent. No divisor search: callers zero-pad
    indivisible inputs via pad_to_block (≤ rows-1 wasted rows beats
    shrinking the block and multiplying grid steps). A measured override
    (auto_tuner.tune_pallas_blocks) takes precedence over the heuristic.

    NOTE for kernel authors: the result must reach the pallas_call as a
    STATIC jit argument — computing it inside a shape-keyed jit would let
    a changed override silently reuse the stale compiled program."""
    cap = max(8, min(256, (budget // max(row_bytes, 1)) // 8 * 8))
    o = _BLOCK_OVERRIDES.get(key)
    # the VMEM budget stays a HARD ceiling: an override tuned on one shape
    # must not blow VMEM on a wider hidden size (tuning explores below it)
    rows = min(o, cap) if o is not None else cap
    rows = min(rows, round_up(n_rows, 8))
    if key is not None:
        _LAST_PICK[key] = rows
    return rows


def padded_rows(rows):
    """(padded_rows, block_rows) for flat (rows, 128) optimizer layouts:
    pad the row count UP to the block size rather than shrinking the block
    — Mosaic requires sublane blocks in multiples of 8, so an awkward row
    count (e.g. 2·17·23) must not degrade the block (or fail lowering
    outright at block<8). Waste is ≤ 511 zero rows (256 KB f32)."""
    if rows >= 512:
        return -(-rows // 512) * 512, 512
    rp = -(-rows // 8) * 8
    return rp, rp


@functools.cache
def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def partitioned() -> bool:
    """True under a multi-device mesh. The chip's compiler refuses a Mosaic
    kernel in a partitioned program ("cannot be automatically partitioned;
    wrap the call in a shard_map"), and no kernel here carries a shard_map
    yet — so a mesh-sharded program runs the XLA composites, which GSPMD
    partitions."""
    import sys
    topo = sys.modules.get("paddle_tpu.distributed.topology")
    mesh = topo.get_mesh() if topo is not None else None
    return mesh is not None and mesh.size > 1


def available() -> bool:
    """Do the Pallas kernels dispatch? On a TPU outside a multi-device
    mesh; always under the interpret/force-dispatch test hooks."""
    if _INTERPRET or _FORCE_DISPATCH:
        return True
    return _on_tpu() and not partitioned()
