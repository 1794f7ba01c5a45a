"""Paths, the peaks table, device identity, seeds and small statistics."""

from __future__ import annotations

import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


class NoChip(RuntimeError):
    """The accelerator this cell asks for is not attached."""


def load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def require_chips(n: int) -> dict:
    """The device as jax reports it; NoChip unless it is `n` TPU chips."""
    info = device_info()
    if info["platform"] != "tpu" or info["count"] != n:
        raise NoChip(f"cell needs {n} TPU chip(s), jax sees {info}")
    return info


def peaks(kind: str) -> dict:
    """Published peaks of one chip, by `device_kind`. An unknown kind is an
    error: a share of a peak nobody wrote down is no number."""
    table = load_json("peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json "
                       f"(have {sorted(table)})")
    return table[kind]


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def seed_words(seed: int):
    """--seed as two 32-bit words: a traced argument of the weight
    generators, so one compiled program serves every seed."""
    import numpy as np
    seed = int(seed)
    return np.asarray([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                      np.uint32)


def percentile(values, q: float) -> float:
    """Linear-interpolated percentile (numpy's default rule), q in 0..100."""
    v = sorted(values)
    if not v:
        raise ValueError("percentile of nothing")
    pos = (len(v) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(v) - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)


def median(values) -> float:
    return percentile(values, 50.0)


def start_program(chips: int | None, trace: bool = False) -> dict:
    """What every process of the benchmark does before it touches the
    program: the program's own observers stay out of the timed window
    (request tracing on only in a traced run); the package is imported (it
    fixes the compile cache's directory: jax's JAX_COMPILATION_CACHE_DIR, or
    `<checkout>/.jax_cache`); every compiled program is persisted, however
    short its compile (the eager discovery steps of `to_static` are thousands
    of small programs); and the chips are there (`chips=None`: no look for a
    chip, for the CPU tests). Returns the device as jax reports it."""
    import os
    os.environ["PADDLE_TPU_PROF"] = "0"
    os.environ["PADDLE_TPU_FLIGHT"] = "0"
    os.environ["PADDLE_TPU_TRACE"] = "1" if trace else "0"
    import jax

    import paddle_tpu  # noqa: F401
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return device_info() if chips is None else require_chips(chips)
