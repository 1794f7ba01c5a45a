"""Planted PK violations: proof that every ERROR-severity rule fires.

Each function below embeds exactly one deliberate kernel bug (PK200
VMEM overflow, PK201 overlapping writes, PK202 coverage gap, PK203
out-of-bounds index map, PK206 pallas_call outside ``x64_off()``),
isolated so the analyzer's
finding list maps 1:1 onto the plants. ``tests/test_kernel_analysis.py``
asserts the mapping; running the module analyzes itself:

    python -m paddle_tpu.analysis.kernels.demo

Nothing here is ever executed or lowered — the analyzer only traces.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ...ops.kernels._common import x64_off

F32 = jnp.float32


def _double_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...] + x_ref[...]


def _copy_kernel(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def vmem_overflow(x):
    """PK200: the whole 32 MiB operand (plus its 32 MiB output) as one
    resident block — 4x the 16 MiB v5e budget in a single grid step."""
    with x64_off():
        return pl.pallas_call(
            _double_kernel,
            out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


def overlapping_writes(x):
    """PK201: out map ignores ``i``, so block (0,0) is written at grid
    steps (0,0) and (1,0) with (0,1) in between — a non-consecutive
    revisit the pipeline's write-back races."""
    with x64_off():
        return pl.pallas_call(
            _copy_kernel,
            grid=(2, 2),
            in_specs=[pl.BlockSpec((64, 128), lambda i, j: (j, 0))],
            out_specs=pl.BlockSpec((64, 128), lambda i, j: (j, 0)),
            out_shape=jax.ShapeDtypeStruct((128, 128), F32))(x)


def coverage_gap(x):
    """PK202: four output blocks, a two-step grid writing blocks 0-1 —
    blocks 2-3 come back as uninitialized garbage."""
    with x64_off():
        return pl.pallas_call(
            _copy_kernel,
            grid=(2,),
            in_specs=[pl.BlockSpec((64, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((64, 128), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((256, 128), F32))(x)


def oob_read(x):
    """PK203: a four-step grid indexes a two-block input — steps 2 and
    3 read past the ref's extent."""
    with x64_off():
        return pl.pallas_call(
            _copy_kernel,
            grid=(4,),
            in_specs=[pl.BlockSpec((64, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((64, 128), lambda i: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((64, 128), F32))(x)


def missing_x64_off(x):
    """PK206 (AST): a pallas_call with no ``x64_off()`` discipline in
    sight — x64 literals reach Mosaic. Never traced; the AST plane
    catches it from source alone."""
    return pl.pallas_call(
        _double_kernel,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype))(x)


def pk_examples():
    """The traced plants (PK206's is AST-only, so not traced)."""
    S = jax.ShapeDtypeStruct
    return [
        ("vmem_overflow", vmem_overflow, (S((4096, 2048), F32),), {}),
        ("overlapping_writes", overlapping_writes,
         (S((128, 128), F32),), {}),
        ("coverage_gap", coverage_gap, (S((128, 128), F32),), {}),
        ("oob_read", oob_read, (S((128, 128), F32),), {}),
    ]


if __name__ == "__main__":
    import sys

    from paddle_tpu.analysis.kernels.__main__ import main
    print("analyzing the planted demo (errors EXPECTED):",
          file=sys.stderr)
    sys.exit(main([__file__, "--no-allowlist"]))
