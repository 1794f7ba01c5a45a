#!/usr/bin/env python
"""Perf regression gate (reference analog: tools/check_op_benchmark_result.py
:30 — parse speed logs, compare ratios against a baseline, fail the build on
regressions).

Usage:
  python tools/perf_gate.py --baseline BENCH_old.json --current BENCH_new.json
      [--tolerance 0.03]
  python tools/perf_gate.py --history "BENCH_r*.json" --current BENCH_new.json

Each file is the bench.py one-line JSON ({"metric", "value", ...}); value is
throughput (higher better). Exit 1 if current < baseline * (1 - tolerance).

Round-over-round discipline (VERDICT r4 #10): with --history, the baseline
is the BEST of the last 3 recorded rounds for the same metric — a slow
round cannot quietly lower the bar for the next one — tolerance tightens
to 3%, and the signed delta is printed so a regression fails loudly.

Beyond throughput, four soft gates ride the same baseline (all lower-is-
better, all env-tunable, value <= 0 disables):

  steady-state step latency  extra.step_breakdown.step_ms, tolerance
                             PERF_GATE_STEP_TOL_PCT (default 10%)
  host dispatch per step     extra.step_breakdown.host_dispatch_ms,
                             tolerance PERF_GATE_DISPATCH_TOL_PCT (default
                             150% — the measurement is scheduler-noisy; the
                             gate exists to catch a per-param optimizer
                             dispatch loop creeping back, a ~10x jump)
  peak HBM                   extra.peak_hbm_bytes (bench memory census),
                             tolerance PERF_GATE_HBM_TOL_PCT (default 5%)
  data-loader wait p50       telemetry.data_pipeline.wait_p50_ms (consumer
                             blocked on the input pipeline), tolerance
                             PERF_GATE_DATA_WAIT_TOL_PCT (default 50% —
                             sub-ms p50s are host-noisy; the gate catches
                             prefetch ceasing to hide the load, a ~10x
                             jump)

so the BENCH_*.json trajectory guards latency and memory regressions
instead of just accumulating them. Rounds that predate either field pass
(nothing to compare).

The continuous profiler rides its own hard gate: a round whose
``telemetry.prof_overhead_pct`` exceeds 2x ``telemetry.prof_budget_pct``
fails outright (the sampler's cadence backoff broke its contract), and
peak-HBM failures print the top-3 MEASURED fusion targets
(``extra.fusion_targets``) next to the static top-owner hint.

The serving runtime (``extra.serve``, from `bench.py serve` or the full
run) adds three HARD gates, checked in EVERY serve sub-block (the
independent workload, shared-prefix cache-on/off, chunked/monolithic,
speculative spec-on/spec-off): any decode- OR verify-program retrace
after warmup, any leaked KV page (refcount >= 1 after drain), and any
LOST page (refcount accounting dropped it) fail the round — plus soft
serve-tokens/s (PERF_GATE_SERVE_TOL_PCT, default 30%), shared-prefix
cache-on p50 TTFT comparisons (PERF_GATE_PREFIX_TTFT_TOL_PCT, default
25%: within-round vs cache-off AND against the baseline round), and the
speculative A/B's spec-on p50 TPOT vs spec-off within-round
(PERF_GATE_SPEC_TPOT_TOL_PCT, default 25% — speculation that costs
latency on its own workload is a regression). The request-tracing probe
(``extra.serve.tracing``) joins the hard sub-block sweep (tracing must
not flip SERVE-RETRACE/SERVE-LEAK/SERVE-LOST).

The mega-kernel harvest (``extra.fusion_targets``) adds a soft gate: the
top remaining (not ``fused``) target's est_saved_bytes must stay below
the pre-PR attention cluster (PERF_GATE_FUSION_MAX_MIB, default 48) —
i.e. the block fusion stays applied round over round.

The training-health monitor (``telemetry.health_overhead_pct``, from the
HealthMonitor riding inside the bench's measured loop) adds an ABSOLUTE
soft gate: the monitor's measured host cost must stay under
PERF_GATE_HEALTH_TOL_PCT (default 1) percent of window wall time —
mirroring the continuous profiler's budget contract. <= 0 disables;
rounds that predate the field pass.

After the gates, a non-fatal trend report (tools/perf_trend.py) renders
the BENCH_*.json trajectory with per-metric sparkline + verdict lines —
purely informational, never changes the exit status.
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def load_bench(path):
    """Full bench dict from a BENCH_*.json file (accepts the raw one-line
    form, the driver's wrapped form, and the `tail`-embedded form)."""
    with open(path) as f:
        txt = f.read()
    try:
        d = json.loads(txt)
    except json.JSONDecodeError:
        lines = [l for l in txt.splitlines() if l.strip().startswith("{")]
        if not lines:
            return {}
        d = json.loads(lines[-1])
    if "tail" in d and isinstance(d.get("tail"), str):
        for line in reversed(d["tail"].splitlines()):
            line = line.strip()
            if line.startswith("{"):
                d = json.loads(line)
                break
    return d if isinstance(d, dict) else {}


def metric_value(d):
    """(metric, value) from a bench dict — the one extraction every gate
    path shares ((None, 0.0) when the dict is empty/unusable)."""
    if not d:
        return None, 0.0  # no usable value: caller passes
    return d.get("metric"), float(d.get("value") or 0.0)


def load_value(path):
    return metric_value(load_bench(path))


def _steady_state(d):
    tel = d.get("telemetry")
    if not isinstance(tel, dict):
        return None
    ss = tel.get("steady_state")
    return ss if isinstance(ss, dict) else None


def telemetry_retraces(d):
    """Steady-state retrace count from a bench dict's telemetry block, or
    None when the block is absent/null (older rounds, disabled metrics)."""
    ss = _steady_state(d)
    if ss is None:
        return None
    r = ss.get("trace_cache_retraces")
    return int(r) if r is not None else None


def retraces_by_fn(d):
    """{__qualname__: retraces} for the steady-state window ({} when the
    bench predates per-fn attribution)."""
    ss = _steady_state(d)
    by_fn = (ss or {}).get("retraces_by_fn")
    return dict(by_fn) if isinstance(by_fn, dict) else {}


def retrace_diagnosis(d) -> str:
    """Human-actionable retrace failure text: names the offending
    function(s) and the exact trace-safety-analyzer command to run
    (paddle_tpu.analysis — the static side of this runtime counter)."""
    by_fn = retraces_by_fn(d)
    lines = []
    if by_fn:
        worst = sorted(by_fn.items(), key=lambda kv: -kv[1])
        lines.append("  offending fn(s): " + ", ".join(
            f"{fn} ({int(n)}x)" for fn, n in worst))
    lines.append(
        "  diagnose: python -m paddle_tpu.analysis examples/ "
        "paddle_tpu/models/ bench.py"
        + (f"   # then inspect the source of {worst[0][0]!r}"
           if by_fn else ""))
    lines.append(
        "  (retrace-prone signatures are rule TS003; "
        "see docs/static_analysis.md — or decorate with "
        "to_static(lint=True) / PADDLE_TPU_JIT_LINT=1)")
    return "\n".join(lines)


def graph_analysis(d):
    """The bench's embedded graph-analyzer block (extra.graph_analysis),
    or {} when the round predates it / analysis errored."""
    try:
        ga = d["extra"]["graph_analysis"]
        return ga if isinstance(ga, dict) and "error" not in ga else {}
    except (KeyError, TypeError):
        return {}


def fusion_targets(d):
    """The bench's MEASURED fusion-target table (extra.fusion_targets,
    the continuous profiler's reconciliation), [] when absent."""
    try:
        ft = d["extra"]["fusion_targets"]
        return [t for t in ft if isinstance(t, dict)] \
            if isinstance(ft, list) else []
    except (KeyError, TypeError):
        return []


def prof_overhead(d):
    """(overhead_pct, budget_pct) of the continuous sampler from the
    bench telemetry block, or (None, None) when the round predates it."""
    tel = d.get("telemetry")
    if not isinstance(tel, dict):
        return None, None
    v = tel.get("prof_overhead_pct")
    if v is None:
        return None, None
    try:
        return float(v), float(tel.get("prof_budget_pct", 1.0))
    except (TypeError, ValueError):
        return None, None


def hbm_diagnosis(d) -> str:
    """Human-actionable peak-HBM failure text: the static analyzer's top
    memory-owner estimate next to the measured regression, and the exact
    graph-analyzer command to reproduce it (paddle_tpu.analysis.graph —
    the static side of this runtime census). Mirrors retrace_diagnosis."""
    ga = graph_analysis(d)
    lines = []
    static = ga.get("static_peak_hbm_bytes")
    if static:
        lines.append(f"  static peak estimate: {int(static):,} bytes"
                     + (f" ({ga['static_vs_measured']}x measured)"
                        if ga.get("static_vs_measured") else ""))
    owners = ga.get("static_top_owners") or []
    if owners:
        o = owners[0]
        span = f" at {o['file']}:{o['line']}" if o.get("file") else ""
        lines.append(f"  top static memory owner: {int(o['bytes']):,} "
                     f"bytes {o.get('prim', '?')}{span}")
    # measured side: the continuous profiler's reconciled work queue — the
    # candidates whose fusion actually buys back the regressed bytes/time
    for t in fusion_targets(d)[:3]:
        lines.append(
            f"  measured fusion target: '{t.get('name', '?')}' "
            f"x{t.get('sites', 1)} — "
            f"{t.get('measured_ms_share', 0)} ms/step measured, "
            f"{int(t.get('est_saved_bytes', 0)):,} bytes saved/site")
    lines.append(
        "  diagnose: python -m paddle_tpu.analysis.graph bench:gpt "
        "--select GA108 --top 5")
    lines.append(
        "  (peak-liveness estimation is rule GA108; "
        "see docs/static_analysis.md#graph-tier — or compile with "
        "to_static(analyze=True) / PADDLE_TPU_JIT_ANALYZE=1)")
    lines.append(
        "  kernel-side HBM sheets: python -m paddle_tpu.analysis.kernels "
        "paddle_tpu/ops/kernels")
    return "\n".join(lines)


def step_latency_ms(d):
    """Steady-state per-step wall latency from the bench's step breakdown
    (None when the round predates it)."""
    try:
        v = d["extra"]["step_breakdown"]["step_ms"]
        return float(v) if v else None
    except (KeyError, TypeError, ValueError):
        return None


def host_dispatch_ms(d):
    """Steady-state host dispatch cost per step from the bench's step
    breakdown (None when the round predates it). Guards the fused-optimizer
    contract: step() must stay one dispatch, not a per-param kernel chain."""
    try:
        v = d["extra"]["step_breakdown"]["host_dispatch_ms"]
        # explicit None check (not falsy): a genuine 0.0 reading must gate,
        # not silently disable the gate
        return float(v) if v is not None else None
    except (KeyError, TypeError, ValueError):
        return None


def peak_hbm_bytes(d):
    """Peak device memory from the bench's memory census (None when the
    round predates `extra.peak_hbm_bytes`)."""
    try:
        v = d["extra"]["peak_hbm_bytes"]
        return int(v) if v else None
    except (KeyError, TypeError, ValueError):
        return None


def data_wait_p50_ms(d):
    """Consumer-side DataLoader wait p50 from the bench telemetry's
    data_pipeline block (None when the round predates it or no loader ran
    in the measured window). Guards the input pipeline: a feeding path
    that starts starving the training step shows up here before the
    headline tokens/s clearly moves."""
    try:
        v = d["telemetry"]["data_pipeline"]["wait_p50_ms"]
        return float(v) if v is not None else None
    except (KeyError, TypeError, ValueError):
        return None


def _tol_pct(env_name, default):
    try:
        return float(os.environ.get(env_name, default))
    except ValueError:
        return default


def health_overhead(d):
    """Measured HealthMonitor cost as % of window wall from the bench
    telemetry block (None when the round predates training-health
    telemetry)."""
    tel = d.get("telemetry")
    if not isinstance(tel, dict):
        return None
    v = tel.get("health_overhead_pct")
    try:
        return float(v) if v is not None else None
    except (TypeError, ValueError):
        return None


def health_overhead_gate(cd):
    """Absolute soft gate on the training-health monitor's measured cost:
    the device-folded stats + one-pull-per-window design promises <1% of
    step time, and this holds the promise round over round. Ceiling via
    PERF_GATE_HEALTH_TOL_PCT (default 1); <= 0 disables; rounds without
    the field pass. Returns a list of failure messages (empty = pass)."""
    tol = _tol_pct("PERF_GATE_HEALTH_TOL_PCT", 1.0)
    if tol <= 0:
        return []
    ov = health_overhead(cd)
    if ov is None:
        return []
    if ov > tol:
        return [
            f"perf gate [REGRESSION:health-overhead] training-health "
            f"monitor cost {ov:.3f}% of window wall time (ceiling {tol:g}% "
            f"via PERF_GATE_HEALTH_TOL_PCT): the one-pull-per-window / "
            f"device-folded contract is broken — check HealthMonitor."
            f"observe_grads dispatch count and check() host work"]
    print(f"perf gate [ok:health-overhead] training-health monitor "
          f"{ov:.3f}% of window wall (ceiling {tol:g}%)")
    return []


def soft_gates(cd, bd):
    """Lower-is-better soft gates (step latency, peak HBM) of current dict
    `cd` vs baseline dict `bd`. Returns a list of failure messages (empty =
    pass); sides that lack the field are skipped, a tolerance <= 0
    disables that gate."""
    fails = []
    for name, get, env, default, unit in (
            ("step_latency", step_latency_ms, "PERF_GATE_STEP_TOL_PCT",
             10.0, "ms"),
            # host dispatch: wide default tolerance — the single-sample
            # measurement swung 4x between r04/r05 on scheduler noise alone
            # (bench now averages several enqueues, but old baselines are
            # single samples); still catches a per-param dispatch loop
            # creeping back in, which is an order-of-magnitude regression
            ("host_dispatch", host_dispatch_ms, "PERF_GATE_DISPATCH_TOL_PCT",
             150.0, "ms"),
            ("peak_hbm", peak_hbm_bytes, "PERF_GATE_HBM_TOL_PCT",
             5.0, "bytes"),
            # data-loader wait: p50 of a sub-millisecond histogram is
            # noisy between hosts, so the default tolerance is wide; it
            # still catches a prefetch pipeline that stopped hiding the
            # load (an order-of-magnitude move)
            ("data_wait_p50", data_wait_p50_ms, "PERF_GATE_DATA_WAIT_TOL_PCT",
             50.0, "ms")):
        tol = _tol_pct(env, default)
        if tol <= 0:
            continue
        cur, base = get(cd), get(bd)
        if cur is None or base is None or base <= 0:
            continue
        ceiling = base * (1 + tol / 100.0)
        delta = (cur - base) / base
        if cur > ceiling:
            msg = (
                f"perf gate [REGRESSION:{name}] current {cur:.1f} {unit} vs "
                f"baseline {base:.1f} {unit} (delta {delta:+.2%}, ceiling "
                f"{ceiling:.1f}, tol {tol:.0f}% via {env})")
            if name == "peak_hbm":
                # static-analyzer bridge: point the failure at the graph
                # tier's memory-owner estimate (same pattern as the
                # retrace gate -> TS-linter bridge)
                msg += "\n" + hbm_diagnosis(cd)
            fails.append(msg)
        else:
            print(f"perf gate [ok:{name}] current {cur:.1f} {unit} vs "
                  f"baseline {base:.1f} {unit} (delta {delta:+.2%}, "
                  f"tol {tol:.0f}%)")
    return fails


def fusion_applied_gate(cd):
    """Soft gate: the block fusion must STAY applied. The top REMAINING
    (not ``fused``) entry of ``extra.fusion_targets`` may not advertise
    more saved bytes per site than the pre-PR attention cluster
    (PERF_GATE_FUSION_MAX_MIB, default 48 — the cluster the mega-kernels
    harvested). If the attention epilogue ever un-fuses (flag regression,
    dispatch gate broken), that ~48 MiB candidate reappears at the top of
    the remaining ranking and this gate names it. <= 0 disables; rounds
    without a reconciled table pass."""
    rows = fusion_targets(cd)
    if not rows:
        return []
    ceiling_mib = _tol_pct("PERF_GATE_FUSION_MAX_MIB", 48.0)
    if ceiling_mib <= 0:
        return []
    remaining = [t for t in rows if not t.get("fused")]
    if not remaining:
        print("perf gate [ok:fusion] every reconciled candidate is "
              "harvested (all rows fused)")
        return []
    top = max(remaining, key=lambda t: int(t.get("est_saved_bytes", 0)))
    top_mib = int(top.get("est_saved_bytes", 0)) / (1 << 20)
    if top_mib > ceiling_mib:
        return [
            f"perf gate [REGRESSION:fusion] top remaining fusion target "
            f"'{top.get('name', '?')}' x{top.get('sites', 1)} advertises "
            f"{top_mib:.1f} MiB/site saved (> {ceiling_mib:g} MiB, the "
            f"pre-PR attention cluster): a harvested mega-kernel fusion "
            f"appears UNAPPLIED — check FLAGS_use_fused_blocks / "
            f"use_pallas_kernels and the block_fused_pallas dispatch "
            f"gates (tol via PERF_GATE_FUSION_MAX_MIB)"]
    print(f"perf gate [ok:fusion] top remaining target "
          f"'{top.get('name', '?')}' at {top_mib:.1f} MiB/site "
          f"(ceiling {ceiling_mib:g} MiB)")
    return []


def serve_block(d):
    """``extra.serve`` — the serving-runtime bench section (None when the
    round predates the serving engine or skipped it)."""
    blk = (d.get("extra") or {}).get("serve")
    return blk if isinstance(blk, dict) else None


def serve_subblocks(cur):
    """Every serving sub-run carrying its own zero-retrace / zero-leak
    proof: the independent-prompts block itself, the shared-prefix
    cache-on/off runs, the chunked-prefill probe's two engines, and the
    speculative A/B's spec-on/spec-off engines."""
    blocks = [("serve", cur)]
    sp = cur.get("shared_prefix") or {}
    for k in ("cache_on", "cache_off"):
        if isinstance(sp.get(k), dict):
            blocks.append((f"serve.shared_prefix.{k}", sp[k]))
    cp = cur.get("chunked_prefill") or {}
    for k in ("chunked", "monolithic"):
        if isinstance(cp.get(k), dict):
            blocks.append((f"serve.chunked_prefill.{k}", cp[k]))
    sd = cur.get("speculative") or {}
    for k in ("spec_on", "spec_off"):
        if isinstance(sd.get(k), dict):
            blocks.append((f"serve.speculative.{k}", sd[k]))
    # the tracing probe's engine runs with the tracer ON: if tracing
    # flipped a retrace / leaked a page, the hard gates catch it HERE
    if isinstance(cur.get("tracing"), dict):
        blocks.append(("serve.tracing", cur["tracing"]))
    return blocks


def shared_prefix_ttft(d):
    """p50 TTFT of the shared-prefix workload's cache-on run (None when
    the round predates the prefix cache)."""
    blk = serve_block(d)
    try:
        v = blk["shared_prefix"]["cache_on"]["ttft_ms"]["p50"]
        return float(v) if v is not None else None
    except (KeyError, TypeError, ValueError):
        return None


def serve_gates(cd, bd):
    """Serving-runtime gates. HARD (checked in EVERY serve sub-block —
    independent, shared-prefix cache-on/off, chunked/monolithic): any
    decode-program retrace after warmup (the paged-KV static-shape
    contract — requests joining/leaving/growing must never recompile the
    decode step), leaked KV pages (refcount >= 1 after drain), or LOST
    pages (the refcount-aware complement: a page in no pool state means
    the accounting dropped it). SOFT: serve tokens/s vs the baseline
    round's serve section (PERF_GATE_SERVE_TOL_PCT, default 30 —
    CPU-smoke serving numbers are thread-scheduling noisy; <= 0
    disables), and the shared-prefix cache-on p50 TTFT both within-round
    (must not exceed cache-off by more than PERF_GATE_PREFIX_TTFT_TOL_PCT,
    default 25 — the prefix cache must actually BUY latency) and against
    the baseline round's same field. Returns (hard, soft) failure
    message lists."""
    cur = serve_block(cd)
    if cur is None:
        return [], []
    hard, soft = [], []
    for name, blk in serve_subblocks(cur):
        for prog in ("decode", "verify"):
            dec = blk.get(f"{prog}_program") or {}
            retr = dec.get("retraces_after_warmup")
            if retr:
                hard.append(
                    f"perf gate [SERVE-RETRACE] {name}: {prog} program "
                    f"retraced {int(retr)}x after warmup while requests "
                    f"joined/left/grew: the paged-KV static-shape contract "
                    f"is broken (compiles={dec.get('compiles')}, see "
                    f"paddle_tpu/serving/kv_cache.py)")
        leaked = blk.get("pages_leaked")
        if leaked:
            hard.append(
                f"perf gate [SERVE-LEAK] {name}: {int(leaked)} KV "
                f"page(s) still referenced after the serve bench drained")
        lost = blk.get("pages_lost")
        if lost:
            hard.append(
                f"perf gate [SERVE-LOST] {name}: {int(lost)} KV page(s) "
                f"in no pool state (free/used/cached) — refcount "
                f"accounting dropped them")
    # shared-prefix TTFT: the cache must not cost latency on the very
    # workload it exists for
    ttft_tol = _tol_pct("PERF_GATE_PREFIX_TTFT_TOL_PCT", 25.0)
    sp = cur.get("shared_prefix") or {}
    try:
        on_p50 = float(sp["cache_on"]["ttft_ms"]["p50"])
        off_p50 = float(sp["cache_off"]["ttft_ms"]["p50"])
    except (KeyError, TypeError, ValueError):
        on_p50 = off_p50 = None
    if ttft_tol > 0 and on_p50 is not None and off_p50 and off_p50 > 0:
        ceiling = off_p50 * (1 + ttft_tol / 100.0)
        delta = (on_p50 - off_p50) / off_p50
        if on_p50 > ceiling:
            soft.append(
                f"perf gate [REGRESSION:prefix-ttft] shared-prefix p50 "
                f"TTFT {on_p50:.1f} ms with the cache ON vs {off_p50:.1f} "
                f"ms OFF (delta {delta:+.2%}, ceiling {ceiling:.1f}, tol "
                f"{ttft_tol:.0f}% via PERF_GATE_PREFIX_TTFT_TOL_PCT): "
                f"prefix caching is costing latency on its own workload")
        else:
            print(f"perf gate [ok:prefix-ttft] shared-prefix p50 TTFT "
                  f"{on_p50:.1f} ms cache-on vs {off_p50:.1f} ms "
                  f"cache-off (delta {delta:+.2%})")
    base_ttft = shared_prefix_ttft(bd) if bd else None
    cur_ttft = shared_prefix_ttft(cd)
    if ttft_tol > 0 and base_ttft and cur_ttft is not None:
        ceiling = base_ttft * (1 + ttft_tol / 100.0)
        delta = (cur_ttft - base_ttft) / base_ttft
        if cur_ttft > ceiling:
            soft.append(
                f"perf gate [REGRESSION:prefix-ttft] shared-prefix "
                f"cache-on p50 TTFT {cur_ttft:.1f} ms vs baseline round "
                f"{base_ttft:.1f} ms (delta {delta:+.2%}, ceiling "
                f"{ceiling:.1f}, tol {ttft_tol:.0f}%)")
        else:
            print(f"perf gate [ok:prefix-ttft-trend] {cur_ttft:.1f} ms "
                  f"vs baseline {base_ttft:.1f} ms (delta {delta:+.2%})")
    # speculative A/B: spec-on p50 TPOT must not exceed spec-off on the
    # same workload — speculation that costs latency is a regression of
    # the very thing it exists to buy
    spec_tol = _tol_pct("PERF_GATE_SPEC_TPOT_TOL_PCT", 25.0)
    sd = cur.get("speculative") or {}
    try:
        on_tpot = float(sd["spec_on"]["tpot_ms"]["p50"])
        off_tpot = float(sd["spec_off"]["tpot_ms"]["p50"])
    except (KeyError, TypeError, ValueError):
        on_tpot = off_tpot = None
    if spec_tol > 0 and on_tpot is not None and off_tpot and off_tpot > 0:
        ceiling = off_tpot * (1 + spec_tol / 100.0)
        delta = (on_tpot - off_tpot) / off_tpot
        if on_tpot > ceiling:
            soft.append(
                f"perf gate [REGRESSION:spec-tpot] speculative p50 TPOT "
                f"{on_tpot:.2f} ms spec-on vs {off_tpot:.2f} ms spec-off "
                f"(delta {delta:+.2%}, ceiling {ceiling:.2f}, tol "
                f"{spec_tol:.0f}% via PERF_GATE_SPEC_TPOT_TOL_PCT): "
                f"speculation is costing latency on its own workload")
        else:
            print(f"perf gate [ok:spec-tpot] p50 TPOT {on_tpot:.2f} ms "
                  f"spec-on vs {off_tpot:.2f} ms spec-off "
                  f"(delta {delta:+.2%}, tokens/step "
                  f"{sd.get('spec_on', {}).get('tokens_per_step')})")
    tol = _tol_pct("PERF_GATE_SERVE_TOL_PCT", 30.0)
    base = serve_block(bd) if bd else None
    if tol > 0 and base and base.get("tokens_per_s"):
        bv, cv = float(base["tokens_per_s"]), float(cur.get("tokens_per_s")
                                                   or 0.0)
        floor = bv * (1 - tol / 100.0)
        delta = (cv - bv) / bv
        if cv < floor:
            soft.append(
                f"perf gate [REGRESSION:serve] {cv:.1f} tokens/s vs "
                f"baseline {bv:.1f} (delta {delta:+.2%}, floor "
                f"{floor:.1f}, tol {tol:.0f}% via PERF_GATE_SERVE_TOL_PCT)")
        else:
            print(f"perf gate [ok:serve] {cv:.1f} tokens/s vs baseline "
                  f"{bv:.1f} (delta {delta:+.2%}, tol {tol:.0f}%)")
    return hard, soft


def best_of_history(pattern, metric, last_n=3):
    """Best value among the last `last_n` round files matching `pattern`
    whose metric equals `metric` (reference analog: the op-benchmark CI
    compares against a rolling recorded baseline)."""
    import glob
    import re

    def round_no(p):
        m = re.search(r"r(\d+)", p)
        return int(m.group(1)) if m else -1

    files = sorted(glob.glob(pattern), key=round_no)[-last_n:]
    best = (None, 0.0)
    for p in files:
        try:
            m, v = load_value(p)
        except Exception:
            continue
        if m == metric and v > best[1]:
            best = (p, v)
    return best


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline")
    ap.add_argument("--history", help="glob of prior BENCH_r*.json files; "
                    "baseline = best of the last 3 with the same metric")
    ap.add_argument("--current", required=True)
    ap.add_argument("--tolerance", type=float, default=0.03)
    args = ap.parse_args()
    cd = load_bench(args.current)
    cm, cv = metric_value(cd)
    # telemetry gate (observability wiring): a retrace during the measured
    # steady-state window means the number includes recompiles — fail loudly
    # even if the throughput still cleared the floor
    retraces = telemetry_retraces(cd)
    retrace_fail = bool(retraces and retraces > 0)
    if retrace_fail:
        print(f"perf gate [RETRACE] steady-state window recompiled "
              f"{retraces}x (telemetry trace_cache_retraces): the measured "
              f"number is not steady-state")
        print(retrace_diagnosis(cd))
    # continuous-sampler overhead gate: the profiler promises to back off
    # past its budget; 2x budget in a bench round means the control loop
    # is broken (or the budget knob was ignored) — fail loudly
    overhead, budget = prof_overhead(cd)
    # budget may legitimately be 0.0 (strictest contract): never let the
    # falsy zero short-circuit the gate off
    prof_fail = overhead is not None and budget is not None \
        and overhead > 2 * budget
    if prof_fail:
        print(f"perf gate [PROF-OVERHEAD] continuous sampler cost "
              f"{overhead:.3f}% of steady-state step time (budget "
              f"{budget:g}%, hard ceiling 2x): the cadence backoff "
              f"failed to hold the PADDLE_TPU_PROF_BUDGET_PCT contract")
    elif overhead is not None:
        print(f"perf gate [ok:prof_overhead] continuous sampler "
              f"{overhead:.3f}% of step time (budget {budget:g}%)")
    bd = {}
    if args.history:
        src, bv = best_of_history(args.history, cm)
        bm = cm if src else None
        if src:
            print(f"perf gate: baseline = best-of-last-3 {src} ({bv:.1f})")
            bd = load_bench(src)
    elif args.baseline:
        bd = load_bench(args.baseline)
        bm, bv = metric_value(bd)
    else:
        ap.error("need --baseline or --history")
    self_fail = retrace_fail or prof_fail
    if bv <= 0:
        print(f"perf gate: baseline has no usable value ({bm}={bv}); "
              f"{'FAIL (retrace/prof-overhead)' if self_fail else 'pass'}")
        return 1 if self_fail else 0
    if bm != cm:
        print(f"perf gate: metric changed {bm} -> {cm}; "
              f"{'FAIL (retrace/prof-overhead)' if self_fail else 'pass'} "
              "(no value comparison)")
        return 1 if self_fail else 0
    floor = bv * (1 - args.tolerance)
    delta = (cv - bv) / bv if bv else 0.0
    status = "OK" if cv >= floor else "REGRESSION"
    print(f"perf gate [{status}] {cm}: current {cv:.1f} vs baseline "
          f"{bv:.1f} (delta {delta:+.2%}, floor {floor:.1f}, "
          f"tol {args.tolerance:.0%})")
    # soft gates over the same baseline round: step latency + peak HBM
    # (only meaningful when the metric matched — same workload shape)
    soft_fails = soft_gates(cd, bd)
    # mega-kernel harvest gate: the top remaining fusion target must stay
    # below the pre-PR attention cluster (the fusion stays applied)
    soft_fails += fusion_applied_gate(cd)
    # training-health monitor: its measured cost must hold the <1%-of-
    # window contract (absolute ceiling, not baseline-relative)
    soft_fails += health_overhead_gate(cd)
    # serving runtime: hard zero-retrace/zero-leak contract + soft
    # tokens/s comparison against the same baseline round
    serve_hard, serve_soft = serve_gates(cd, bd)
    soft_fails += serve_soft
    for msg in soft_fails + serve_hard:
        print(msg)
    # trend report: purely informational (never changes the exit status) —
    # the round-over-round trajectory next to the pass/fail verdicts
    if args.history:
        try:
            try:
                from tools.perf_trend import render_trend
            except ImportError:
                from perf_trend import render_trend
            print(render_trend(args.history, current=args.current))
        except Exception as e:  # noqa: BLE001 — report step, never fatal
            print(f"perf gate: trend report unavailable ({e!r})")
    return 0 if (cv >= floor and not retrace_fail and not prof_fail
                 and not soft_fails and not serve_hard) else 1


if __name__ == "__main__":
    sys.exit(main())
