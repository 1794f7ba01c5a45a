"""StepTimer: per-step latency / throughput / MFU telemetry.

Holds the analytic-FLOPs MFU math and the per-generation peak-FLOPs table
that bench.py established (BASELINE.md discipline: MFU = tokens/s x
FLOPs/token / spec-sheet peak), so the bench and any training loop report
the same number from the same formula.

Stdlib-only: the device argument is duck-typed on ``.platform`` /
``.device_kind`` — no jax import here.
"""

from __future__ import annotations

import os
import time

from . import flight as _flight
from . import metrics as _m

__all__ = ["StepTimer", "device_peak_flops", "analytic_mfu",
           "PEAK_FLOPS_TABLE"]

# bf16 peak FLOPs per chip by generation (spec sheets).
PEAK_FLOPS_TABLE = {
    "v6e": 918e12, "v6": 918e12, "v5p": 459e12, "v5e": 197e12,
    "v5litepod": 197e12, "v5 lite": 197e12, "v5lite": 197e12,
    "v4": 275e12, "v3": 123e12, "v2": 45e12,
}


def device_peak_flops(device=None, device_kind=None, platform=None):
    """(bf16 peak FLOPs, source string) for a device (duck-typed) or an
    explicit (device_kind, platform) pair. Non-TPU platforms report 0.0 so
    MFU degrades to 0 rather than garbage; a TPU kind that is not in
    ``PEAK_FLOPS_TABLE`` raises."""
    if device is not None:
        device_kind = getattr(device, "device_kind", "") or ""
        platform = getattr(device, "platform", "")
    kind = (device_kind or "").lower()
    if platform != "tpu":
        return 0.0, "cpu"
    for k, v in PEAK_FLOPS_TABLE.items():
        if k in kind:
            return v, f"device_kind:{kind}"
    raise KeyError(f"no peak-FLOPs entry for TPU device kind "
                   f"{device_kind!r} (have {sorted(PEAK_FLOPS_TABLE)})")


def analytic_mfu(tokens_per_sec, flops_per_token, peak_flops):
    """Model-FLOPs utilization from analytic per-token FLOPs (bench.py's
    6N + attention-correction counts) against the spec-sheet peak."""
    if not peak_flops or not flops_per_token:
        return 0.0
    return tokens_per_sec * flops_per_token / peak_flops


class StepTimer:
    """Record train/serve step telemetry into the registry.

    Two usage modes:

    * per-step context manager — each ``with`` block is one step::

          timer = StepTimer("train", tokens_per_step=b * s,
                            flops_per_token=model.flops_per_token(s) * 3,
                            peak_flops=peak)
          for batch in loader:
              with timer:
                  train_step(batch)

    * externally-timed window (the bench pattern: N steps timed around a
      single device sync, no per-step blocking)::

          timer.record_window(steps=N, tokens=b * s * N, seconds=dt)

    Metrics: ``paddle_tpu_step_seconds`` histogram (per-step latency),
    ``paddle_tpu_step_total`` counter, ``paddle_tpu_step_tokens_per_second``
    + ``paddle_tpu_step_mfu_ratio`` gauges, and
    ``paddle_tpu_step_transfer_bytes_total`` for host<->device traffic fed
    in via :meth:`record_transfer`. All carry a ``name`` label.
    """

    def __init__(self, name: str = "train", tokens_per_step=None,
                 flops_per_token=None, peak_flops=None, registry=None):
        self.name = name
        self.tokens_per_step = tokens_per_step
        self.flops_per_token = flops_per_token
        self.peak_flops = peak_flops
        reg = registry or _m.get_registry()
        self._h_step = reg.histogram(
            "paddle_tpu_step_seconds", "per-step wall latency")
        self._c_steps = reg.counter(
            "paddle_tpu_step_total", "steps recorded")
        self._g_tps = reg.gauge(
            "paddle_tpu_step_tokens_per_second",
            "throughput of the most recent recorded step/window")
        self._g_mfu = reg.gauge(
            "paddle_tpu_step_mfu_ratio",
            "analytic-FLOPs model-FLOPs utilization of the most recent "
            "recorded step/window")
        self._c_transfer = reg.counter(
            "paddle_tpu_step_transfer_bytes_total",
            "host<->device transfer bytes attributed to steps")
        self.last_step_s = None
        self.tokens_per_sec = None
        self.mfu = None
        self._t0 = None

    def __enter__(self):
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.record_window(1, self.tokens_per_step,
                           time.perf_counter() - self._t0)
        self._t0 = None
        return False

    def record_window(self, steps: int, tokens, seconds: float) -> dict:
        """Fold an externally-timed window of ``steps`` steps covering
        ``tokens`` tokens (None if tokens don't apply) into the metrics;
        returns the derived stats."""
        steps = max(int(steps), 1)
        step_s = seconds / steps
        self.last_step_s = step_s
        self._h_step.observe(step_s, name=self.name)
        self._c_steps.inc(steps, name=self.name)
        stats = {"step_seconds": step_s, "steps": steps}
        if _flight.enabled():  # one event per step/window: the black box's
            # step-timing heartbeat
            _flight.record("step", name=self.name, steps=steps,
                           step_seconds=round(step_s, 6))
        if tokens and seconds > 0:
            self.tokens_per_sec = tokens / seconds
            self._g_tps.set(self.tokens_per_sec, name=self.name)
            stats["tokens_per_sec"] = self.tokens_per_sec
            self.mfu = analytic_mfu(self.tokens_per_sec,
                                    self.flops_per_token, self.peak_flops)
            if self.mfu:
                self._g_mfu.set(self.mfu, name=self.name)
                stats["mfu"] = self.mfu
        return stats

    def record_transfer(self, nbytes: int):
        """Attribute host<->device transfer bytes to this timer's step."""
        self._c_transfer.inc(int(nbytes), name=self.name)
