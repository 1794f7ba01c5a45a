"""Benchmark harness: train-step throughput + MFU and the serving section,
in this one process, on the one attached chip.

    python bench.py            # every section; fails unless jax sees a TPU
    python bench.py serve      # the serving section alone
    python bench.py --cpu      # harness smoke on the CPU backend (tests)

Prints ONE JSON line naming the device it ran on. There is no fallback: no
TPU (and no ``--cpu``) is an error, and a section that raises fails the run
with a non-zero exit code. A ``--cpu`` run prints ``"device": "cpu"`` and
plain ``tokens/s`` — never a per-chip unit.

Baseline discipline per BASELINE.md: primary metric is tokens/sec/chip with
MFU derived from analytic FLOPs (6N + attention correction); the north-star
target is 40% MFU, so vs_baseline = MFU / 0.40.
"""

import functools
import json
import os
import sys
import time
import traceback

# retrace counts observed inside each steady-state timing window (one entry
# per _train_throughput call); summed into the telemetry block so
# tools/perf_gate.py can fail a round whose measured window recompiled.
# _STEADY_RETRACES_BY_FN keeps the per-__qualname__ split (the retraces
# counter is labeled fn=<qualname>) so the gate's failure message can name
# the offending function and point at the trace-safety analyzer.
_STEADY_RETRACES: list = []
_STEADY_RETRACES_BY_FN: dict = {}

# HealthMonitor snapshot of the LAST _train_throughput loop (observability
# .health rides inside the measured window — the <1% overhead contract is
# only honest measured live); consumed by _attach_telemetry
_HEALTH_BLOCK: dict = {}


def _retraces_by_fn(obs):
    """{qualname: count} view of the labeled retraces counter."""
    m = obs.get_registry().get(
        "paddle_tpu_jit_trace_cache_retraces_total")
    if m is None:
        return {}
    return {labels.get("fn", "_unlabeled"): float(v)
            for labels, v in m.series()}


def _flight_overhead():
    """Micro-measure the flight recorder's per-event cost, enabled and
    disabled, on a throwaway recorder (the real tape is untouched): the
    <2%-of-step-latency / zero-when-disabled contract, verified by the
    bench itself every round."""
    from paddle_tpu.observability.flight import FlightRecorder
    n = 20000
    out = {}
    for label, on in (("enabled_ns_per_event", True),
                      ("disabled_ns_per_event", False)):
        rec = FlightRecorder(capacity=1024, enabled=on)
        t0 = time.perf_counter_ns()
        for i in range(n):
            if rec.enabled:  # the guarded hot-site pattern
                rec.record("bench_probe", i=i)
        out[label] = round((time.perf_counter_ns() - t0) / n, 1)
    return out


def _hist_quantile(name, q):
    """Quantile of an unlabelled histogram via the registry's shared
    ``Histogram.quantile`` (linear interpolation inside the owning bucket;
    overflow hits return the top finite bound — a lower bound on the true
    quantile, still gate-worthy); None when the metric is absent or has
    no observations."""
    import paddle_tpu.observability as obs
    m = obs.get_registry().get(name)
    if m is None or getattr(m, "kind", "") != "histogram":
        return None
    return m.quantile(q)


def _data_pipeline_block(obs):
    """Input-pipeline counters + consumer-side wait p50 for the telemetry
    block. ``wait_p50_ms`` is None when no DataLoader ran in the round
    (perf_gate skips the data-wait soft gate then)."""
    p50 = _hist_quantile("paddle_tpu_io_batch_wait_seconds", 0.5)
    return {
        "batches": int(obs.total("paddle_tpu_data_batches_total")),
        "epochs": int(obs.total("paddle_tpu_data_epochs_total")),
        "resume_replayed": int(obs.total(
            "paddle_tpu_data_resume_replayed_total")),
        "resume_discarded": int(obs.total(
            "paddle_tpu_data_resume_discarded_total")),
        "read_retries": int(obs.total(
            "paddle_tpu_data_read_retries_total")),
        "wait_p50_ms": None if p50 is None else round(p50 * 1000.0, 3),
    }


def _attach_telemetry(result):
    """Embed the observability snapshot in the bench JSON line — ALWAYS:
    either the full telemetry block or `"telemetry": null` plus a reason,
    so the perf trajectory is self-describing either way."""
    try:
        import paddle_tpu.observability as obs
        if not obs.enabled():
            result["telemetry"] = None
            result["telemetry_reason"] = "disabled via PADDLE_TPU_METRICS=0"
        else:
            result["telemetry"] = {
                "metrics": obs.dump(),
                "steady_state": {
                    "trace_cache_retraces": int(sum(_STEADY_RETRACES)),
                    "windows": len(_STEADY_RETRACES),
                    "retraces_by_fn": {
                        fn: int(v)
                        for fn, v in sorted(_STEADY_RETRACES_BY_FN.items())
                        if v},
                },
                # recovery counters (paddle_tpu.resilience): nonzero
                # restores/NaN events in a bench run mean the measured
                # window included recovery work — the perf number is then
                # a fault-path number, and the trajectory should say so
                "resilience": {
                    "saves_ok": int(obs.value(
                        "paddle_tpu_resilience_saves_total", status="ok")),
                    "saves_error": int(obs.value(
                        "paddle_tpu_resilience_saves_total",
                        status="error")),
                    "restores": int(obs.total(
                        "paddle_tpu_resilience_restores_total")),
                    "restore_fallbacks": int(obs.total(
                        "paddle_tpu_resilience_restore_fallbacks_total")),
                    "nan_events": int(obs.total(
                        "paddle_tpu_resilience_nan_events_total")),
                    "nan_rewinds": int(obs.total(
                        "paddle_tpu_resilience_nan_rewinds_total")),
                    "preemptions": int(obs.total(
                        "paddle_tpu_resilience_preemptions_total")),
                },
                # input pipeline: delivery counters + the consumer-side
                # wait p50 perf_gate soft-gates (a loader that starts
                # starving the step shows up here before tokens/s moves)
                "data_pipeline": _data_pipeline_block(obs),
            }
            # training-health monitor: the window stats + the measured
            # monitor cost (<1% of window wall, the acceptance contract —
            # perf_gate soft-gates health_overhead_pct on it)
            if _HEALTH_BLOCK:
                result["telemetry"]["health"] = dict(_HEALTH_BLOCK)
                result["telemetry"]["health_overhead_pct"] = round(
                    float(_HEALTH_BLOCK.get("overhead_pct", 0.0)), 4)
            # continuous profiler (observability.continuous): the measured
            # sampler cost vs its hard budget — the acceptance contract
            # (<1% of steady-state step time) rides every trajectory line,
            # and tools/perf_gate.py fails the round past 2x budget
            try:
                from paddle_tpu.observability import continuous as cont
                prof = cont.profiler_if_started()
                if prof is not None:
                    result["telemetry"]["prof_overhead_pct"] = round(
                        prof.overhead_pct, 4)
                    result["telemetry"]["prof_budget_pct"] = prof.budget_pct
                    result["telemetry"]["prof_windows"] = prof.windows
                    result["telemetry"]["prof_every"] = prof.every
            except Exception:
                pass
            # flight recorder + memory census: the black-box layer's own
            # health numbers ride the trajectory file (overhead contract:
            # <2% of step latency enabled, ~nothing disabled)
            try:
                from paddle_tpu.observability import flight, memory
                mem = memory.census(top=10)
                result["telemetry"]["flight"] = dict(
                    _flight_overhead(),
                    enabled=flight.enabled(),
                    events_recorded=len(flight.get_recorder()),
                    capacity=flight.get_recorder().capacity)
                result["telemetry"]["memory"] = mem
                # only a real allocator peak is gate-worthy: the XLA:CPU
                # fallback has no memory_stats, and end-of-run live-array
                # totals there are incidental noise
                dev_peak = int(mem.get("device", {}).get(
                    "peak_bytes_in_use", 0))
                if dev_peak:
                    result.setdefault("extra", {})["peak_hbm_bytes"] = \
                        dev_peak
            except Exception:
                pass
            result.pop("telemetry_reason", None)
    except Exception:
        result["telemetry"] = None
        result["telemetry_reason"] = \
            "observability unavailable: " + traceback.format_exc(limit=1)[:300]
    return result


def _train_throughput(model, batch, seq, steps, warmup, vocab, on_tpu,
                      lr=3e-4):
    """tokens/s + final loss for a jitted train step of `model`."""
    import numpy as np
    import paddle_tpu as paddle

    opt = paddle.optimizer.AdamW(
        lr, parameters=model.parameters(), weight_decay=0.1,
        multi_precision=True)
    if on_tpu:
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16")
    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (batch, seq + 1))
    x = paddle.to_tensor(ids[:, :-1].astype(np.int32))
    y = paddle.to_tensor(ids[:, 1:].astype(np.int32))

    # training-health telemetry rides inside the measured loop (like the
    # continuous profiler): the fold inlines into the step program, the
    # cadence check is the one host pull per window, and the snapshot's
    # overhead_pct is the <1% acceptance number perf_gate soft-gates
    from paddle_tpu.observability.health import HealthMonitor
    health = HealthMonitor(opt, check_every=5,
                           tokens_per_step=batch * seq)

    # donate param/opt-state buffers on TPU: halves the peak HBM the update
    # step holds (old + new state), buying batch/activation headroom
    @functools.partial(paddle.jit.to_static, donate_state=on_tpu)
    def train_step(x, y):
        _, loss = model(x, labels=y)
        loss.backward()
        opt.step()
        health.observe_grads()  # folded into the step program
        opt.clear_grad()
        return loss

    for _ in range(warmup):
        loss = train_step(x, y)
    float(loss)  # sync
    health.reset_window()  # drop the warmup partial window
    pulls0 = health.host_pulls
    # steady-state telemetry window: any trace-cache retrace INSIDE the
    # timed loop means the measurement included a recompile — perf_gate
    # fails the round on it (observability wiring)
    import paddle_tpu.observability as obs
    from paddle_tpu.observability import continuous as cont
    retr0 = obs.total("paddle_tpu_jit_trace_cache_retraces_total")
    by_fn0 = _retraces_by_fn(obs)
    # continuous profiler rides INSIDE the measured loop on purpose: the
    # acceptance contract is that sampling costs <1% of steady-state step
    # time, and measuring with it live is the only honest proof. Cadence 5
    # (not the 50 default) so a 20-step loop still lands ~4 windows.
    prof = cont.get_profiler()
    prof.reset(every=5)
    prof.auto_reconcile = False  # reconciled once, after the loop
    t0 = time.perf_counter()
    try:
        for i in range(steps):
            loss = train_step(x, y)
            health.observe(loss)
            health.check(i)
            cont.on_step(i)
        final = float(loss)  # device sync
        dt = time.perf_counter() - t0
    finally:
        # even on OOM-retry raises: a window left open would make every
        # later section dispatch under sampling (blocking, mismeasured)
        cont.stop()
    # reconcile NOW, while train_step (a local) is still alive — the
    # profiler only holds the program weakly; the table lands in
    # continuous.last_reconciliation() for _fusion_targets_block
    try:
        # with_unfused: the round's JSON shows the harvested delta — the
        # as-fused table (block mega-kernel candidates marked `fused`)
        # next to the composite 'before' view
        cont.fusion_targets(top=5, with_unfused=True)
    except Exception:
        print("bench: fusion_targets reconciliation failed:\n"
              + traceback.format_exc(limit=2), file=sys.stderr)
    _STEADY_RETRACES.append(
        int(obs.total("paddle_tpu_jit_trace_cache_retraces_total") - retr0))
    _HEALTH_BLOCK.clear()
    _HEALTH_BLOCK.update(health.snapshot(),
                         measured_pulls=health.host_pulls - pulls0)
    for fn, v in _retraces_by_fn(obs).items():
        d = v - by_fn0.get(fn, 0.0)
        if d > 0:
            _STEADY_RETRACES_BY_FN[fn] = \
                _STEADY_RETRACES_BY_FN.get(fn, 0.0) + d
    obs.StepTimer("bench_steady").record_window(steps, batch * seq * steps,
                                                dt)

    # step-time breakdown (BASELINE.md: compute vs host split): host time is
    # the non-blocking dispatch cost; the rest of the step is device time.
    # Averaged over several back-to-back enqueues — a single sample swung
    # 4x round-to-round (r04 3.7ms vs r05 15.5ms) purely on scheduler noise,
    # which is too loose for the perf_gate dispatch gate to bite on.
    # Single-chip, so the comm share is zero by construction.
    n_enq = 4
    t1 = time.perf_counter()
    for _ in range(n_enq):
        loss = train_step(x, y)  # enqueue only
    host_s = (time.perf_counter() - t1) / n_enq
    float(loss)  # drain
    step_s = dt / steps
    breakdown = {
        "step_ms": round(step_s * 1e3, 2),
        "host_dispatch_ms": round(host_s * 1e3, 2),
        "device_ms": round(max(step_s - host_s, 0.0) * 1e3, 2),
        "comm_ms": 0.0,
    }
    breakdown["opt_ms"] = _fused_opt_ms(model, opt)
    return batch * seq * steps / dt, final, breakdown


def _fusion_targets_block():
    """The measured mega-kernel work queue (observability.continuous):
    static GA100 candidates of every program the profiler captured in the
    LAST _train_throughput loop, joined with their measured ms/step share.
    The reconciliation itself ran inside _train_throughput (while the
    profiled StaticFunction was still alive); this reads the table. Call
    right after the bench section whose loop was profiled — a later
    section reconciles over it. Never fails the bench."""
    try:
        from paddle_tpu.observability import continuous as cont
        return cont.last_reconciliation() or []
    except Exception:
        return []


def _fusion_targets_unfused_block():
    """The composite 'before' view of the same reconciliation (candidates
    as the pure-XLA program advertises them) — embedded next to
    extra.fusion_targets so the harvested delta is visible per round."""
    try:
        from paddle_tpu.observability import continuous as cont
        return cont.last_unfused_reconciliation() or []
    except Exception:
        return []


def _fused_opt_ms(model, opt, reps=5):
    """Wall time of ONE fused optimizer dispatch (optimizer/fused.py): the
    whole multi-tensor update — every param/accumulator/master — as a
    single jitted device computation. Measured post-loop with synthetic
    zero grads (state already measured; one more update is noise): first
    step warms lazily-created state, second compiles the fused program,
    then `reps` hot dispatches are timed. Also proves the fused path live
    in every bench round: telemetry's optimizer_fused_updates_total is
    nonzero even when the train loop fused the update into the to_static
    step program."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.tensor import Tensor

    try:
        if not getattr(opt, "_fuse", False):
            return None
        params = [p for p in model.parameters() if not p.stop_gradient]
        if not params:
            return None

        def prime_grads():
            for p in params:
                p._grad = Tensor(jnp.zeros_like(p._data))

        prime_grads()
        opt.step()  # state-creating warm-up (eager per-param path)
        prime_grads()
        opt.step()  # compiles + dispatches the fused program
        if not opt._fuse or not getattr(opt._fused_impl, "dispatches", 0):
            # the engine's warn-and-fallback (failed trace/compile) doesn't
            # raise — without this check the timed reps would measure the
            # per-param fallback and report it as fused dispatch latency
            print("bench: opt_ms probe skipped: fused path fell back to "
                  "per-param (see RuntimeWarning above)", file=sys.stderr)
            opt.clear_grad()
            return None
        prime_grads()
        jax.block_until_ready([p._data for p in params])
        t0 = time.perf_counter()
        for _ in range(reps):
            opt.step()
        jax.block_until_ready([p._data for p in params])
        ms = (time.perf_counter() - t0) / reps * 1e3
        opt.clear_grad()
        return round(ms, 3)
    except Exception as e:
        # opt_ms is best-effort, but a fused dispatch failure here means the
        # path the bench claims to prove is dead — say so instead of leaving
        # an unexplained null in the JSON line
        print(f"bench: opt_ms probe failed ({type(e).__name__}: {e}); "
              f"fused={getattr(opt, '_fuse', None)}", file=sys.stderr)
        return None


def run_llama_bench(dev):
    """Llama-family single-chip bench (the north-star model family,
    BASELINE.md config #3): largest config that fits one chip comfortably."""
    import paddle_tpu as paddle
    from paddle_tpu.models import Llama, LlamaConfig

    # ~310M params: fits v5e HBM with AdamW fp32 states + bf16 compute
    cfg = LlamaConfig(vocab_size=32000, max_position_embeddings=2048,
                      hidden_size=1024, num_layers=16, num_heads=16,
                      num_kv_heads=4, intermediate_size=4096)
    batch, seq, steps, warmup = 2, 2048, 10, 2
    paddle.seed(0)
    model = Llama(cfg)
    tokens_per_s, final, breakdown = _train_throughput(
        model, batch, seq, steps, warmup, cfg.vocab_size, on_tpu=True)
    fusion_targets = _fusion_targets_block()
    fusion_targets_unfused = _fusion_targets_unfused_block()
    n_params = model.num_params()
    flops_per_token = model.flops_per_token(seq) * 3
    peak, peak_src = _peak_flops(dev)
    from paddle_tpu.observability import analytic_mfu
    mfu = analytic_mfu(tokens_per_s, flops_per_token, peak)
    return {
        "metric": "llama_310m_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_s, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 4) if peak else 0.0,
        "extra": {
            "mfu": round(mfu, 4), "loss": round(final, 3), "batch": batch,
            "seq": seq, "steps": steps, "n_params": n_params,
            "device": str(getattr(dev, "device_kind", dev.platform)),
            "dtype": "bf16", "step_breakdown": breakdown,
            "peak_flops": peak, "peak_flops_source": peak_src,
            "fusion_targets": fusion_targets,
            "fusion_targets_unfused": fusion_targets_unfused,
        },
    }


def _plan_block(model, batch, seq, measured_step_ms, dev):
    """Parallelism-planner round block (ROADMAP item 3 acceptance): what
    would paddle.planner choose for this model?

    Three records per round: (1) the chosen plan for the canonical
    8-chip topology (mesh/specs summary/schedule/recompute + predicted
    step time), (2) the rank the planner gives the repo's hand-tuned
    multichip config (dp2 x mp2 x pp2, the hybrid_parallel_train /
    MULTICHIP dryrun mesh) — a sanity dial: the planner should not bury
    the config humans converged on, and if it someday should, this row
    is the evidence, and (3) predicted-vs-measured step time for THIS
    device at the bench's real batch (single chip, so the comparison
    isolates the roofline compute model from the collective formulas).
    Never fails the bench: returns {"error": ...} on any problem."""
    try:
        from paddle_tpu.cost_model import CHIP_PRESETS
        from paddle_tpu.planner import ModelDesc, Topology, plan_search

        desc = ModelDesc.from_model(model, seq_len=seq)
        topo8 = "v5e:8"
        res = plan_search(desc=desc, topology=topo8, global_batch=32,
                          top=1)
        best = res.best
        block = {
            "topology": topo8,
            "search": {
                "n_enumerated": res.n_enumerated,
                "n_pruned": res.n_pruned,
                "n_memory_rejected": res.n_memory_rejected,
                "n_scored": res.n_scored,
                "seconds": round(res.search_seconds, 3),
            },
        }
        if best is not None:
            block["chosen"] = {
                "summary": best.summary(),
                "mesh": best.mesh,
                "micro_batches": best.schedule["micro_batches"],
                "recompute": best.recompute["enable"],
                "predicted_step_ms": round(
                    best.predicted["step_time_s"] * 1e3, 3),
                "predicted_tokens_per_s": round(
                    best.predicted["tokens_per_s"], 1),
                "fingerprint": best.fingerprint(),
            }
        hand = {"dp": 2, "mp": 2, "pp": 2}
        rank = res.rank_of(hand)
        block["hand_config"] = {
            "mesh": hand, "rank": rank,
            "of": sum(1 for s in res.scored if s.feasible)}
        # single-chip predicted vs this round's measured step: price the
        # current device's roofline (real peak if known, cpu preset
        # otherwise) at the bench's actual batch
        peak, peak_src = _peak_flops(dev)
        cpu_preset = CHIP_PRESETS["cpu"]
        topo1 = Topology(
            chips=1, slice_chips=1,
            hbm_bytes=int(cpu_preset["hbm_gb"] * (1 << 30)),
            peak_flops=peak or cpu_preset["peak_flops"],
            name=peak_src if peak else "cpu")
        res1 = plan_search(desc=desc, topology=topo1, global_batch=batch,
                           top=1)
        if res1.best is not None:
            pred_ms = res1.best.predicted["step_time_s"] * 1e3
            block["single_chip"] = {
                "predicted_step_ms": round(pred_ms, 3),
                "measured_step_ms": measured_step_ms,
                "predicted_vs_measured": round(
                    pred_ms / measured_step_ms, 4)
                if measured_step_ms else None,
                "peak_flops_source": peak_src if peak else "cpu-preset",
            }
        return block
    except Exception:
        return {"error": traceback.format_exc(limit=2)[:500]}


def _graph_analysis_block(model, batch, seq, vocab):
    """Static graph-tier analysis (paddle_tpu.analysis.graph) of the bench
    model: the top-3 fusion candidates ranked by estimated saved HBM bytes
    — ROADMAP item 2's mega-kernel target list — plus the static
    peak-liveness HBM estimate cross-validated against one measured
    attribute_memory() forward at the same shapes. Never fails the bench:
    returns {"error": ...} on any problem."""
    try:
        import jax
        import jax.numpy as jnp
        import numpy as np
        import paddle_tpu as paddle
        from paddle_tpu.analysis.graph import analyze_graph, trace_layer
        from paddle_tpu.observability.memory import attribute_memory

        x = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        y = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        report = analyze_graph(trace_layer(model, x, labels=y),
                               name="bench:gpt",
                               exclude_files=(__file__,))
        block = {
            "top_fusion_candidates": report.top_candidates(3),
            "static_peak_hbm_bytes": int(report.liveness.peak_bytes),
            "static_top_owners": [dict(o) for o in
                                  report.liveness.owners[:3]],
            "n_findings": len(report.findings),
            "n_errors": sum(1 for f in report.findings
                            if f.severity == "error"),
        }
        # measured side of the cross-validation: ONE eager no-grad forward
        # with per-module attribution (the same program the static tier
        # just analyzed — forward + loss, no backward)
        rng = np.random.default_rng(0)
        xt = paddle.to_tensor(
            rng.integers(0, vocab, (batch, seq)).astype("int32"))
        yt = paddle.to_tensor(
            rng.integers(0, vocab, (batch, seq)).astype("int32"))
        with paddle.no_grad():
            with attribute_memory(model) as attr:
                model(xt, labels=yt)
        measured = max((int(st.get("peak_bytes", 0))
                        for st in attr.peaks.values()), default=0)
        if measured:
            block["measured_peak_hbm_bytes"] = measured
            block["static_vs_measured"] = round(
                block["static_peak_hbm_bytes"] / measured, 3)
        return block
    except Exception:
        return {"error": traceback.format_exc(limit=1)[:300]}


# which kernel_ab measured row each static sheet governs: (module,
# kernel symbol, measured-ms key). The join is by identity — the sheets
# are computed at the module's pk_examples() shapes, the timings at the
# bench's A/B shapes — so read the pair as "this measured kernel, whose
# static budget/traffic model says THIS", not as a same-shape prediction.
_KERNEL_AB_JOIN = (
    ("rope_pallas", "_rope_kernel", "rope_pallas_fwdbwd_ms"),
    ("moe_gemm_pallas", "_kernel", "moe_gemm_pallas_ms"),
    ("bias_dropout_ln_pallas", "_fwd_kernel", "bias_dropout_ln_pallas_ms"),
    ("wo_matmul_pallas", "_wo_kernel", "wo_int8_decode_pallas_ms"),
    ("wo_matmul_pallas", "_wo4_kernel", "wo_int4_decode_pallas_ms"),
)


def _kernel_static_block(kernel_ab):
    """Static per-kernel RESOURCE SHEETS (``cost_model.kernel_cost`` —
    the kernel analyzer's VMEM/FLOPs/HBM accounting) joined with the
    measured ``kernel_ab`` rows per ``_KERNEL_AB_JOIN``, plus a
    graph-tier HBM cross-check on the swiglu forward example.

    Cross-check tolerance (asserted in tests/test_kernel_analysis.py):
    the sheet's hbm_bytes (distinct blocks x block bytes over the grid)
    must agree with the graph tier's input+output byte count for the
    same computation within 2x either way — the pallas pipeline re-reads
    broadcast blocks and pads tails, while the graph tier counts each
    array exactly once, so a ratio outside [0.5, 2.0] means one of the
    two static models is wrong. Never fails the bench: {"error": ...}.
    """
    try:
        import jax
        import jax.numpy as jnp
        from paddle_tpu.analysis.graph import (
            aval_bytes, build_graph, trace_callable)
        from paddle_tpu.cost_model import kernel_cost

        block = {"sheets": [], "joined": []}
        costs = {}
        for mod, kern, ms_key in _KERNEL_AB_JOIN:
            if mod not in costs:
                costs[mod] = kernel_cost("paddle_tpu.ops.kernels." + mod)
                block.setdefault("chip", costs[mod]["chip"])
                block.setdefault("vmem_budget", costs[mod]["vmem_budget"])
                block["sheets"].extend(costs[mod]["kernels"])
            sheet = next((s for s in costs[mod]["kernels"]
                          if s["kernel"] == kern), None)
            if sheet is None:
                continue
            block["joined"].append({
                "kernel": kern, "module": mod, "measured_key": ms_key,
                "measured_ms": (kernel_ab or {}).get(ms_key),
                "fits_vmem": sheet["fits_vmem"],
                "vmem_bytes": sheet["vmem_bytes"],
                "hbm_bytes": sheet["hbm_bytes"],
                "arithmetic_intensity": sheet["arithmetic_intensity"],
                # the chip roofline over the sheet's flops and bytes
                "cost_source": sheet.get("cost_source"),
                "predicted_ms": sheet.get("predicted_ms"),
            })

        from paddle_tpu.ops.kernels import swiglu_pallas as sw
        cc = kernel_cost("paddle_tpu.ops.kernels.swiglu_pallas")
        sheet = next(s for s in cc["kernels"] if s["label"] == "swiglu_fwd")
        g = jax.ShapeDtypeStruct((512, 2048), jnp.bfloat16)
        closed = trace_callable(sw.reference_swiglu, g, g)
        jx = closed.jaxpr
        io_bytes = (sum(aval_bytes(v.aval) for v in jx.invars)
                    + sum(aval_bytes(v.aval) for v in jx.outvars))
        ratio = sheet["hbm_bytes"] / max(io_bytes, 1)
        block["graph_cross_check"] = {
            "kernel": "swiglu_pallas _fwd_kernel",
            "sheet_hbm_bytes": sheet["hbm_bytes"],
            "graph_io_bytes": int(io_bytes),
            "graph_composite_bytes": int(build_graph(closed).total_bytes()),
            "ratio": round(ratio, 3),
            "tolerance": [0.5, 2.0],
            "ok": bool(0.5 <= ratio <= 2.0),
        }
        return block
    except Exception:
        return {"error": traceback.format_exc(limit=2)[:500]}


def run_gpt_bench(dev, on_tpu):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT, GPTConfig

    if on_tpu:
        cfg = GPTConfig(vocab_size=50304, max_position_embeddings=1024,
                        hidden_size=768, num_layers=12, num_heads=12)
        # b=8 exhausts HBM on a shared v5e slice (full-residual autograd);
        # b=4 fits and the MXU stays saturated at seq 1024
        batch, seq, steps, warmup = 4, 1024, 20, 3
    else:  # CPU smoke so the harness itself stays testable. Fixed work,
        # LONG steady state (VERDICT r4 weak #8: 5 steps measured dispatch
        # overhead; a -3.5%% delta sat inside the noise floor unnoticed)
        cfg = GPTConfig(vocab_size=1024, max_position_embeddings=256,
                        hidden_size=256, num_layers=4, num_heads=8)
        batch, seq, steps, warmup = 4, 256, 20, 3

    paddle.seed(0)
    model = GPT(cfg)
    flops_per_token = model.flops_per_token(seq) * 3  # fwd + bwd(2x)
    tokens_per_s, final, breakdown = _train_throughput(
        model, batch, seq, steps, warmup, cfg.vocab_size, on_tpu)
    fusion_targets = _fusion_targets_block()
    fusion_targets_unfused = _fusion_targets_unfused_block()

    peak, peak_src = _peak_flops(dev)
    from paddle_tpu.observability import analytic_mfu
    mfu = analytic_mfu(tokens_per_s, flops_per_token, peak)
    return {
        "metric": "gpt2_124m_train_tokens_per_sec_per_chip" if on_tpu
        else "gpt2_cpu_smoke_tokens_per_sec",
        "value": round(tokens_per_s, 1),
        "unit": "tokens/s/chip" if on_tpu else "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4) if peak else 0.0,
        "extra": {
            "mfu": round(mfu, 4), "loss": round(final, 3), "batch": batch,
            "seq": seq, "steps": steps,
            "device": str(getattr(dev, "device_kind", dev.platform)),
            "dtype": "bf16" if on_tpu else "f32",
            "step_breakdown": breakdown,
            "peak_flops": peak, "peak_flops_source": peak_src,
            "graph_analysis": _graph_analysis_block(
                model, batch, seq, cfg.vocab_size),
            "plan": _plan_block(model, batch, seq,
                                breakdown.get("step_ms"), dev),
            "fusion_targets": fusion_targets,
            "fusion_targets_unfused": fusion_targets_unfused,
        },
    }


def _serve_pct(xs):
    import numpy as np
    if not xs:
        return None
    return {"p50": round(float(np.percentile(xs, 50)), 2),
            "p99": round(float(np.percentile(xs, 99)), 2),
            "mean": round(float(np.mean(xs)), 2)}


def _serve_shared_prefix_block(users=8, common_len=64, suffix_len=8,
                               max_new=12):
    """Shared-system-prompt workload (ISSUE 14 acceptance): N users whose
    prompts share a long common prefix + short unique suffix, run twice
    on identical engines — prefix cache ON vs OFF. The cache-on run's
    ``prefix_hit_rate`` is the prefill-token reduction; greedy outputs
    must be token-exact across the two runs."""
    import threading

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import llama_tiny
    from paddle_tpu.serving import LLMEngine, ServingConfig

    rng = np.random.default_rng(7)
    common = [int(t) for t in rng.integers(1, 500, size=common_len)]
    prompts = [common + [int(t) for t in
                         rng.integers(1, 500, size=suffix_len)]
               for _ in range(users)]
    warm_prompts = [[int(t) for t in
                     rng.integers(1, 500, size=common_len + suffix_len)]
                    for _ in range(2)]

    def run(cache_on):
        paddle.seed(0)
        model = llama_tiny()
        eng = LLMEngine(model, ServingConfig(
            page_size=16, num_pages=129, max_batch=users,
            max_new_tokens=max_new, temperature=0.0, seed=0,
            prefix_cache=cache_on))
        # warm every steady-state signature THROUGH compilation (a
        # signature compiles on its second invocation): two distinct
        # warm prompts x two calls cover the monolithic bucket (first
        # call of each = miss), the suffix-chunk bucket a cache hit
        # dispatches (second call of each), and the decode program
        for wp in warm_prompts:
            eng.generate(wp, timeout=600)
            eng.generate(wp, timeout=600)
        warm = eng.program_stats()
        sched = eng.scheduler
        saved0, prompt0 = sched.prefix_tokens_saved, sched.prompt_tokens
        computed0 = sched.prefill_tokens_computed
        cow0 = sched.cow_copies

        results: dict = {}
        errors: list = []

        def user(uid):
            try:
                req = eng.submit(prompts[uid])
                results[uid] = (req, req.result(timeout=600))
            except Exception as e:  # noqa: BLE001 — survey, don't die
                errors.append(repr(e)[:200])

        t0 = time.perf_counter()
        user(0)          # seed the cache: first user misses, inserts
        threads = [threading.Thread(target=user, args=(u,))
                   for u in range(1, users)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0

        after = eng.program_stats()
        reqs = [results[u][0] for u in sorted(results)]
        toks = {u: results[u][1] for u in sorted(results)}
        gen = sum(len(t) for t in toks.values())
        eng.shutdown(drain=True)
        blk = {
            "requests_completed": len(results),
            "requests_failed": len(errors),
            "tokens_per_s": round(gen / wall, 1) if wall > 0 else 0.0,
            "wall_s": round(wall, 3),
            "ttft_ms": _serve_pct([r.ttft_ms for r in reqs
                                   if r.ttft_ms is not None]),
            "tpot_ms": _serve_pct([g for r in reqs for g in r.tpot_ms]),
            "e2e_ms": _serve_pct([r.e2e_ms for r in reqs
                                  if r.e2e_ms is not None]),
            "prefix_hit_rate": round(
                (sched.prefix_tokens_saved - saved0)
                / max(1, sched.prompt_tokens - prompt0), 4),
            "prefill_tokens_computed":
                sched.prefill_tokens_computed - computed0,
            "prefill_tokens_total": sched.prompt_tokens - prompt0,
            "cow_copies": sched.cow_copies - cow0,
            "pages_leaked": eng.pool.leaked(),
            "pages_lost": eng.pool.lost(),
            "decode_program": dict(
                after["decode"],
                retraces_after_warmup=after["decode"]["retraces"]
                - warm["decode"]["retraces"]),
            "errors": errors[:5],
        }
        return blk, toks

    on, toks_on = run(True)
    off, toks_off = run(False)
    return {
        "users": users, "common_len": common_len, "suffix_len": suffix_len,
        "max_new": max_new,
        "token_exact": toks_on == toks_off,
        "cache_on": on, "cache_off": off,
    }


def _serve_chunked_block(chunk=16, short_users=4, long_len=96, max_new=20):
    """Chunked-prefill probe: short requests decode while ONE long prompt
    arrives; the in-flight requests' worst inter-token gap (TPOT max /
    p99) measures how badly the arrival stalled them — monolithic
    prefill blocks a full prompt program, chunked interleaves
    ``chunk``-token pieces under the scheduler's token budget."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import llama_tiny
    from paddle_tpu.serving import LLMEngine, ServingConfig

    rng = np.random.default_rng(11)
    short_prompts = [[int(t) for t in rng.integers(1, 500, size=6)]
                     for _ in range(short_users)]
    long_prompt = [int(t) for t in rng.integers(1, 500, size=long_len)]

    def run(chunk_size):
        paddle.seed(0)
        model = llama_tiny()
        eng = LLMEngine(model, ServingConfig(
            page_size=16, num_pages=129, max_batch=short_users + 1,
            max_new_tokens=max_new, temperature=0.0, seed=0,
            prefix_cache=False, prefill_chunk=chunk_size))
        # warm both prompt shapes THROUGH compilation (second invocation
        # of a signature compiles it): decode + short bucket + the long
        # prompt's bucket/chunk signatures
        for wp in (short_prompts[0], long_prompt):
            eng.generate(wp, timeout=600)
            eng.generate(wp, timeout=600)
        warm = eng.program_stats()
        shorts = [eng.submit(p) for p in short_prompts]
        deadline = time.monotonic() + 600
        while any(len(r.tokens) < 3 for r in shorts):
            if time.monotonic() > deadline:
                eng.shutdown(drain=False)
                raise RuntimeError(
                    "chunked-prefill probe: short requests never reached "
                    "3 tokens (states: "
                    f"{[(r.state, len(r.tokens), r.error) for r in shorts]})")
            time.sleep(0.002)
        long_req = eng.submit(long_prompt)
        long_toks = long_req.result(timeout=600)
        for r in shorts:
            r.result(timeout=600)
        after = eng.program_stats()
        stall = [g for r in shorts for g in r.tpot_ms]
        chunks = eng.scheduler.chunks
        eng.shutdown(drain=True)
        return {
            "inflight_tpot_ms": dict(
                (_serve_pct(stall) or {}),
                max=round(max(stall), 2) if stall else None),
            "long_ttft_ms": round(long_req.ttft_ms, 2)
            if long_req.ttft_ms is not None else None,
            "long_generated": len(long_toks),
            "prefill_chunks": chunks,
            "pages_leaked": eng.pool.leaked(),
            "pages_lost": eng.pool.lost(),
            "decode_program": dict(
                after["decode"],
                retraces_after_warmup=after["decode"]["retraces"]
                - warm["decode"]["retraces"]),
        }

    return {"chunk": chunk, "long_prompt_len": long_len,
            "short_users": short_users,
            "chunked": run(chunk), "monolithic": run(None)}


def _serve_speculative_block(users=6, suffix_len=4, max_new=96, spec_k=6):
    """Speculative-decoding A/B (ISSUE 15 acceptance): the SAME workload
    on identical engines, spec-on (n-gram drafting + fused K+1-token
    verify program) vs spec-off (plain decode). Reports accepted
    tokens/verify-step, acceptance rate, measured tokens-per-step, and
    p50/p99 TPOT for both runs; greedy outputs must be token-exact
    across the two (the `token_exact` proof), and both engines carry
    the zero-retrace / zero-leak / zero-lost sub-block fields the perf
    gate hard-checks."""
    import threading

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import llama_tiny
    from paddle_tpu.serving import LLMEngine, ServingConfig

    rng = np.random.default_rng(17)
    base = [int(t) for t in rng.integers(1, 500, size=6)]
    # template-heavy prompts (the production shape speculation targets):
    # a repeated boilerplate block + a short unique suffix per user
    prompts = [base * 2 + [int(t) for t in
                           rng.integers(1, 500, size=suffix_len)]
               for _ in range(users)]
    warm_prompts = [base * 2 + [int(t) for t in
                                rng.integers(1, 500, size=suffix_len)]
                    for _ in range(2)]

    def run(k):
        paddle.seed(0)
        model = llama_tiny()
        eng = LLMEngine(model, ServingConfig(
            page_size=16, num_pages=129, max_batch=users,
            max_new_tokens=max_new, temperature=0.0, seed=0,
            prefix_cache=False, spec_k=k))
        # warm every steady-state signature THROUGH compilation (second
        # invocation compiles): prefill bucket, decode, and — via the
        # looping greedy streams — the verify program
        for wp in warm_prompts:
            eng.generate(wp, timeout=600)
            eng.generate(wp, timeout=600)
        warm = eng.program_stats()
        sched = eng.scheduler
        prop0, acc0 = sched.spec_proposed, sched.spec_accepted
        vsteps0, steps0 = sched.spec_steps, sched.decode_steps
        stok0, srow0 = sched.step_tokens, sched.step_rows

        results: dict = {}
        errors: list = []

        def user(uid):
            try:
                req = eng.submit(prompts[uid])
                results[uid] = (req, req.result(timeout=600))
            except Exception as e:  # noqa: BLE001 — survey, don't die
                errors.append(repr(e)[:200])

        t0 = time.perf_counter()
        threads = [threading.Thread(target=user, args=(u,))
                   for u in range(users)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0

        after = eng.program_stats()
        reqs = [results[u][0] for u in sorted(results)]
        toks = {u: results[u][1] for u in sorted(results)}
        gen = sum(len(t) for t in toks.values())
        proposed = sched.spec_proposed - prop0
        accepted = sched.spec_accepted - acc0
        vsteps = sched.spec_steps - vsteps0
        srows = sched.step_rows - srow0
        stoks = sched.step_tokens - stok0
        eng.shutdown(drain=True)
        blk = {
            "spec_k": k,
            "requests_completed": len(results),
            "requests_failed": len(errors),
            "tokens_per_s": round(gen / wall, 1) if wall > 0 else 0.0,
            "wall_s": round(wall, 3),
            "decode_steps": sched.decode_steps - steps0,
            "verify_steps": vsteps,
            "proposed_tokens": proposed,
            "accepted_tokens": accepted,
            "acceptance_rate": round(accepted / proposed, 4)
            if proposed else None,
            "accepted_tokens_per_verify_step": round(accepted / vsteps, 4)
            if vsteps else None,
            "tokens_per_step": round(stoks / srows, 4) if srows else None,
            "tpot_ms": _serve_pct([g for r in reqs for g in r.tpot_ms]),
            "e2e_ms": _serve_pct([r.e2e_ms for r in reqs
                                  if r.e2e_ms is not None]),
            "pages_leaked": eng.pool.leaked(),
            "pages_lost": eng.pool.lost(),
            "decode_program": dict(
                after["decode"],
                retraces_after_warmup=after["decode"]["retraces"]
                - warm["decode"]["retraces"]),
            "verify_program": dict(
                after["verify"],
                retraces_after_warmup=after["verify"]["retraces"]
                - warm["verify"]["retraces"]),
            "errors": errors[:5],
        }
        return blk, toks

    on, toks_on = run(spec_k)
    off, toks_off = run(0)
    return {
        "users": users, "max_new": max_new, "spec_k": spec_k,
        "token_exact": toks_on == toks_off,
        "spec_on": on, "spec_off": off,
    }


def _serve_tracing_block(users=6, max_new=12):
    """Request-tracing probe (ISSUE 16 acceptance): the serve workload
    under tracing. Proves (1) every completed request carries a root
    span with >=4 distinct child span kinds and span coverage >=90% of
    its e2e wall, (2) the live ``/requests`` and ``/trace/<id>``
    endpoints serve parser-valid JSON mid-run, (3) greedy outputs are
    token-exact tracing-on vs -off, and (4) tracing flips none of the
    zero-retrace / zero-leak / zero-lost invariants (perf_gate reads this
    block as a serve sub-block). What tracing costs is not measured here:
    a pair of runs this small tells nothing (PERF.md has the chip's)."""
    import json as _json
    import threading
    import urllib.request

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import llama_tiny
    from paddle_tpu.observability import tracing
    from paddle_tpu.observability.continuous.server import TelemetryServer
    from paddle_tpu.serving import LLMEngine, ServingConfig

    rng = np.random.default_rng(23)
    prompt_lens = [12, 28]
    prompts = [[int(t) for t in
                rng.integers(1, 500, size=prompt_lens[u % 2])]
               for u in range(users)]
    warm_prompts = [[int(t) for t in rng.integers(1, 500, size=n)]
                    for n in prompt_lens]
    tracer = tracing.get_tracer()
    was_enabled = tracer.enabled

    def run(trace_on, probe_endpoints=False):
        tracer.enabled = trace_on
        paddle.seed(0)
        model = llama_tiny()
        eng = LLMEngine(model, ServingConfig(
            page_size=16, num_pages=129, max_batch=users,
            max_new_tokens=max_new, temperature=0.0, seed=0))
        for wp in warm_prompts:
            eng.generate(wp, timeout=600)
            eng.generate(wp, timeout=600)
        warm = eng.program_stats()
        st0 = tracer.stats()
        results: dict = {}
        errors: list = []
        endpoints = None
        srv = TelemetryServer(port=0).start() if probe_endpoints else None

        def fetch(path):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}{path}", timeout=30) as r:
                return r.status, _json.loads(r.read().decode())

        def user(uid):
            try:
                req = eng.submit(prompts[uid])
                results[uid] = (req, req.result(timeout=600))
            except Exception as e:  # noqa: BLE001 — survey, don't die
                errors.append(repr(e)[:200])

        t0 = time.perf_counter()
        threads = [threading.Thread(target=user, args=(u,))
                   for u in range(users)]
        for t in threads:
            t.start()
        if probe_endpoints:
            endpoints = {"requests_ok": False, "trace_ok": False}
            try:
                # mid-run scrape: the endpoint must serve DURING a live run
                code, body = fetch("/requests")
                endpoints["requests_ok"] = (
                    code == 200 and isinstance(body.get("requests"), list))
            except Exception as e:  # noqa: BLE001
                errors.append(f"/requests probe: {e!r}"[:200])
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        st1 = tracer.stats()
        after = eng.program_stats()
        reqs = [results[u][0] for u in sorted(results)]
        if probe_endpoints:
            try:
                tid = reqs[0].trace.trace_id
                code, body = fetch(f"/trace/{tid}")
                endpoints["trace_ok"] = (code == 200 and
                                         body.get("trace_id") == tid)
            except Exception as e:  # noqa: BLE001
                errors.append(f"/trace probe: {e!r}"[:200])
            srv.close()
        eng.shutdown(drain=True)
        toks = {u: results[u][1] for u in sorted(results)}
        gen = sum(len(t) for t in toks.values())

        covs, kind_counts = [], []
        slowest = None
        for req in reqs:
            snap = tracing.get_trace(req.trace.trace_id) or {}
            rec = snap.get("record") or {}
            covs.append(float(rec.get("span_coverage") or 0.0))
            kind_counts.append(len(rec.get("span_kinds") or ()))
            if slowest is None or (rec.get("e2e_ms") or 0.0) > \
                    (slowest.get("e2e_ms") or 0.0):
                slowest = {k: rec.get(k) for k in (
                    "trace_id", "request_id", "e2e_ms", "ttft_ms",
                    "queue_ms", "prefill_ms", "decode_ms",
                    "span_coverage", "span_kinds", "spans")}
        spans = st1["spans_total"] - st0["spans_total"]
        blk = {
            "requests_completed": len(results),
            "requests_failed": len(errors),
            "tokens_per_s": round(gen / wall, 1) if wall > 0 else 0.0,
            "wall_s": round(wall, 3),
            "spans_recorded": spans,
            "coverage": {
                "mean": round(sum(covs) / len(covs), 4) if covs else None,
                "min": round(min(covs), 4) if covs else None,
                "frac_ge_90": round(
                    sum(1 for c in covs if c >= 0.9) / len(covs), 4)
                if covs else None,
            },
            "min_child_span_kinds": min(kind_counts) if kind_counts
            else None,
            "slowest_request": slowest,
            "endpoints": endpoints,
            "pages_leaked": eng.pool.leaked(),
            "pages_lost": eng.pool.lost(),
            "decode_program": dict(
                after["decode"],
                retraces_after_warmup=after["decode"]["retraces"]
                - warm["decode"]["retraces"]),
            "errors": errors[:5],
        }
        return blk, toks

    try:
        on, toks_on = run(True, probe_endpoints=True)
        _, toks_off = run(False)
    finally:
        tracer.enabled = was_enabled
    return dict(on, users=users, max_new=max_new,
                token_exact=toks_on == toks_off)


def run_serve_bench(dev=None, users=8, total_requests=16, max_new=16):
    """Serving-runtime load generator (ROADMAP item 1 acceptance): N
    concurrent synthetic users drive the continuous-batching engine over
    the paged KV cache; reports tokens/s, p50/p99 TTFT / per-token /
    end-to-end latency, mean batch occupancy — and the zero-retrace
    proof: the decode program's jit telemetry across the measured window
    (requests joining, leaving, and growing across page boundaries) must
    show ZERO retraces after warmup (tools/perf_gate.py hard-fails
    otherwise). Two more workloads ride along (ISSUE 14): the
    shared-system-prompt run proving the prefix cache's prefill-token
    reduction and TTFT win, and the chunked-prefill probe proving a
    long-prompt arrival no longer spikes in-flight TPOT."""
    import threading

    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import llama_tiny
    from paddle_tpu.serving import LLMEngine, ServingConfig

    paddle.seed(0)
    model = llama_tiny()        # vocab 512, L2 H4/KV2, hidden 64, pos 128
    cfg = ServingConfig(page_size=16, num_pages=129, max_batch=users,
                        max_new_tokens=max_new, temperature=0.0, seed=0)
    engine = LLMEngine(model, cfg)
    rng = np.random.default_rng(0)
    # two prompt-length regimes -> two prefill buckets; decode growth
    # crosses page boundaries (prompt 12 + 16 new > page_size 16)
    prompt_lens = [12, 28]

    def prompt(i):
        return list(rng.integers(1, 500,
                                 size=prompt_lens[i % len(prompt_lens)]))

    # warmup: one request per bucket compiles prefill signatures and the
    # decode program (discovery + compile); everything after is steady
    for i in range(len(prompt_lens)):
        engine.generate(prompt(i), timeout=600)
        engine.generate(prompt(i), timeout=600)
    warm = engine.program_stats()
    occ0 = engine.scheduler.occupancy_sum
    steps0 = engine.scheduler.decode_steps

    done: list = []
    errors: list = []

    def user(uid, n):
        for j in range(n):
            try:
                req = engine.submit(prompt(uid * 131 + j))
                req.result(timeout=600)
                done.append(req)
            except Exception as e:  # noqa: BLE001 — survey, don't die
                errors.append(repr(e)[:200])

    per_user = max(1, total_requests // users)
    t0 = time.perf_counter()
    threads = [threading.Thread(target=user, args=(u, per_user))
               for u in range(users)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0

    after = engine.program_stats()
    stats = engine.stats()
    engine.shutdown(drain=True)
    gen_tokens = sum(len(r.tokens) for r in done)
    ttft = [r.ttft_ms for r in done if r.ttft_ms is not None]
    e2e = [r.e2e_ms for r in done if r.e2e_ms is not None]
    tpot = [g for r in done for g in r.tpot_ms]
    steps = stats["decode_steps"] - steps0

    shared = _serve_shared_prefix_block(users=users)
    chunked = _serve_chunked_block()
    spec = _serve_speculative_block()
    tracing_blk = _serve_tracing_block()
    return {
        "users": users,
        "requests_completed": len(done),
        "requests_failed": len(errors),
        "generated_tokens": gen_tokens,
        "tokens_per_s": round(gen_tokens / wall, 1) if wall > 0 else 0.0,
        "wall_s": round(wall, 3),
        "ttft_ms": _serve_pct(ttft),
        "tpot_ms": _serve_pct(tpot),
        "e2e_ms": _serve_pct(e2e),
        "occupancy_mean": round(
            (stats["occupancy_mean"] * stats["decode_steps"] - occ0)
            / steps, 4) if steps else 0.0,
        "evictions": stats["evictions"],
        "pages_leaked": stats["pages"]["used"],
        "pages_lost": engine.pool.lost(),
        "decode_program": dict(
            after["decode"],
            retraces_after_warmup=after["decode"]["retraces"]
            - warm["decode"]["retraces"]),
        "prefill_program": dict(
            after["prefill"],
            retraces_after_warmup=after["prefill"]["retraces"]
            - warm["prefill"]["retraces"]),
        "errors": errors[:5],
        # ISSUE 14: shared-system-prompt + chunked-prefill workloads; the
        # acceptance scrapers read the top-level mirrors
        "shared_prefix": shared,
        "chunked_prefill": chunked,
        "prefix_hit_rate": shared["cache_on"]["prefix_hit_rate"],
        "cow_copies": shared["cache_on"]["cow_copies"],
        # ISSUE 15: speculative-decoding A/B + top-level mirrors
        "speculative": spec,
        "spec_acceptance_rate": spec["spec_on"]["acceptance_rate"],
        "spec_tokens_per_step": spec["spec_on"]["tokens_per_step"],
        # ISSUE 16: request-tracing probe + top-level mirrors
        "tracing": tracing_blk,
        "trace_span_coverage": tracing_blk["coverage"]["mean"],
    }


def run_flash_ab(dev):
    """A/B the Pallas flash kernels vs the XLA composite: fwd+bwd wall time
    for one attention op at Llama-bench shape (BASELINE.md asks the kernel
    either wins or documents parity)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.kernels import flash_attention as fa

    rng = np.random.default_rng(0)
    shp = (4, 2048, 16, 64)
    q, k, v, g = (jnp.asarray(rng.standard_normal(shp), jnp.bfloat16)
                  for _ in range(4))

    def timed(f, kk, vv):
        fg = jax.jit(jax.grad(
            lambda q, k, v: jnp.sum((f(q, k, v) * g).astype(jnp.float32)),
            argnums=(0, 1, 2)))
        r = fg(q, kk, vv)
        jax.block_until_ready(r)
        t0 = time.perf_counter()
        for _ in range(5):
            r = fg(q, kk, vv)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / 5 * 1e3

    pallas_ms = timed(lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
                      k, v)
    xla_ms = timed(lambda q, k, v: fa._reference_attention(q, k, v, True),
                   k, v)
    res = {"pallas_fwdbwd_ms": round(pallas_ms, 2),
           "xla_fwdbwd_ms": round(xla_ms, 2),
           "speedup": round(xla_ms / pallas_ms, 3)}

    # GQA (Llama-bench head config 16q/4kv): the kernel reads shared kv
    # heads via its index map vs the materialized-repeat composite
    try:
        kg, vg = (jnp.asarray(rng.standard_normal((4, 2048, 4, 64)),
                              jnp.bfloat16) for _ in range(2))
        gqa_pallas = timed(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True), kg, vg)
        gqa_xla = timed(
            lambda q, k, v: fa._reference_attention(q, k, v, True), kg, vg)
        res["gqa_pallas_fwdbwd_ms"] = round(gqa_pallas, 2)
        res["gqa_xla_fwdbwd_ms"] = round(gqa_xla, 2)
        res["gqa_speedup"] = round(gqa_xla / gqa_pallas, 3)
    except Exception as e:
        # the GQA signal must not vanish silently if the kernel path breaks
        res["gqa_error"] = repr(e)[:300]
    return res


def run_llama8b_layer_bench(dev, cfg=None, n_layers=2, batch=1, seq=4096,
                            steps=8, warmup=2, use_amp=True):
    """North-star arithmetic at real 8B dims (BASELINE.md config #3).

    A full Llama-8B doesn't fit one chip with AdamW states, but its MFU is
    set almost entirely by the decoder layer: run a 2-layer stack at exact
    8B dims (h=4096, 32q/8kv heads, inter=14336), measure layer MFU, and
    project the full model analytically (the lm_head matmul is assumed to
    run at the same MFU; embedding lookup is bandwidth-noise).
    """
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.models.llama import (LlamaConfig, LlamaDecoderLayer,
                                         _rope_tables)

    if cfg is None:
        cfg = LlamaConfig(vocab_size=128256, hidden_size=4096, num_layers=32,
                          num_heads=32, num_kv_heads=8,
                          intermediate_size=14336)

    paddle.seed(0)

    class LayerStack(nn.Layer):
        def __init__(self):
            super().__init__()
            self.layers = nn.LayerList(
                [LlamaDecoderLayer(cfg) for _ in range(n_layers)])

        def forward(self, x, cos, sin):
            for l in self.layers:
                x = l(x, cos, sin)
            return x

    model = LayerStack()
    opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters(),
                                 weight_decay=0.1, multi_precision=True)
    if use_amp:
        model, opt = paddle.amp.decorate(model, opt, level="O2",
                                         dtype="bfloat16")
    rng = np.random.default_rng(0)
    # unlike the full-model benches there is no int-id embedding to set the
    # activation dtype, so cast the inputs to bf16 explicitly — otherwise
    # f32 @ bf16 promotes every matmul back to f32 and halves measured MFU
    act_dtype = "bfloat16" if use_amp else "float32"
    x = paddle.to_tensor(
        rng.standard_normal((batch, seq, cfg.hidden_size)).astype(
            np.float32)).cast(act_dtype)
    cos, sin = _rope_tables(cfg, seq, dtype="float32")
    cos, sin = cos.cast(act_dtype), sin.cast(act_dtype)

    @paddle.jit.to_static
    def step(x, cos, sin):
        out = model(x, cos, sin)
        loss = (out.cast("float32") ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for _ in range(max(warmup, 1)):
        loss = step(x, cos, sin)
    float(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x, cos, sin)
    float(loss)
    dt = time.perf_counter() - t0

    params_per_layer = sum(p.size for p in model.parameters()) / n_layers
    # fwd+bwd = 3x fwd; fwd = 2*P + causal-attention 2*2*h*s/2 per token
    flops_tok_layer = 3 * (2.0 * params_per_layer
                           + 2.0 * 2.0 * cfg.hidden_size * seq / 2)
    tokens_per_s = batch * seq * steps / dt
    peak, peak_src = _peak_flops(dev)
    from paddle_tpu.observability import analytic_mfu
    layer_mfu = analytic_mfu(tokens_per_s, flops_tok_layer * n_layers, peak)
    # analytic full-8B projection: 32 layers + untied lm_head at layer MFU
    full_flops_tok = (cfg.num_layers * flops_tok_layer
                      + 3 * 2.0 * cfg.hidden_size * cfg.vocab_size)
    proj_tokens_per_s = (layer_mfu * peak / full_flops_tok) if peak else 0.0
    return {"layer_mfu_8b_dims": round(layer_mfu, 4),
            "tokens_per_sec_2layer": round(tokens_per_s, 1),
            "projected_8b_tokens_per_sec_per_chip": round(proj_tokens_per_s, 1),
            "batch": batch, "seq": seq, "n_layers_measured": n_layers,
            "params_per_layer": int(params_per_layer),
            "peak_flops": peak, "peak_flops_source": peak_src}


def run_kernel_ab(dev):
    """A/B the round-3 Pallas kernels vs their XLA composites: fused rope
    and the MoE grouped-GEMM (with realistic routing imbalance)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.kernels import moe_gemm_pallas as mg
    from paddle_tpu.ops.kernels import rope_pallas as rp

    rng = np.random.default_rng(0)
    res = {}

    def timed(f, *args):
        jf = jax.jit(f)
        jax.block_until_ready(jf(*args))
        t0 = time.perf_counter()
        for _ in range(10):
            r = jf(*args)
        jax.block_until_ready(r)
        return (time.perf_counter() - t0) / 10 * 1e3

    # rope at Llama-8B dims, fwd+bwd
    b, s, h, d = 1, 4096, 32, 128
    x = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    g = jnp.asarray(rng.standard_normal((b, s, h, d)), jnp.bfloat16)
    ang = np.outer(np.arange(s), 1.0 / (500000 ** (np.arange(0, d, 2) / d)))
    cos = jnp.asarray(np.concatenate([np.cos(ang), np.cos(ang)], -1),
                      jnp.float32)
    sin = jnp.asarray(np.concatenate([np.sin(ang), np.sin(ang)], -1),
                      jnp.float32)
    pal = timed(jax.grad(lambda a: jnp.sum(
        (rp.rope_apply(a, cos, sin, False) * g).astype(jnp.float32))), x)
    xla = timed(jax.grad(lambda a: jnp.sum(
        (rp.rope_reference(a, cos, sin) * g).astype(jnp.float32))), x)
    res["rope_pallas_fwdbwd_ms"] = round(pal, 3)
    res["rope_xla_fwdbwd_ms"] = round(xla, 3)
    res["rope_speedup"] = round(xla / pal, 3)

    # grouped-GEMM: 60 experts, capacity 128, skewed fill (half near-empty)
    e, c, hh, f = 60, 128, 2048, 1408
    counts = jnp.asarray(
        rng.choice([0, 8, 16, 128], e, p=[0.2, 0.3, 0.3, 0.2]), jnp.int32)
    mask = jnp.arange(c)[None, :, None] < counts.reshape(-1, 1, 1)
    xg = jnp.where(mask, jnp.asarray(
        rng.standard_normal((e, c, hh)), jnp.bfloat16), 0)
    w = jnp.asarray(rng.standard_normal((e, hh, f)), jnp.bfloat16)
    pal = timed(lambda a, b_: mg.grouped_matmul(a, b_, counts, False), xg, w)
    xla = timed(lambda a, b_: mg.reference_grouped_matmul(a, b_, counts),
                xg, w)
    res["moe_gemm_pallas_ms"] = round(pal, 3)
    res["moe_gemm_xla_ms"] = round(xla, 3)
    res["moe_gemm_speedup"] = round(xla / pal, 3)
    res["moe_fill_fraction"] = round(float(jnp.sum(counts)) / (e * c), 3)

    # fused bias+dropout+residual+layernorm at GPT-3-ish dims, fwd+bwd
    from paddle_tpu.ops.kernels import bias_dropout_ln_pallas as bd
    rows, hid = 8192, 4096
    xb = jnp.asarray(rng.standard_normal((rows, hid)), jnp.bfloat16)
    resid = jnp.asarray(rng.standard_normal((rows, hid)), jnp.bfloat16)
    bias = jnp.asarray(rng.standard_normal(hid), jnp.float32)
    gam = jnp.asarray(rng.standard_normal(hid), jnp.float32)
    bet = jnp.asarray(rng.standard_normal(hid), jnp.float32)
    mask2 = jnp.asarray(rng.random((rows, hid)) > 0.1, jnp.float32) / 0.9

    def bd_loss(kern):
        def f(x_, r_, g_):
            if kern:
                y, hsum = bd.bias_dropout_ln(x_, bias, r_, mask2, g_, bet,
                                             1e-5, False)
            else:
                y, hsum = bd.reference_bias_dropout_ln(x_, bias, r_, mask2,
                                                       g_, bet, 1e-5)
            return jnp.sum(y.astype(jnp.float32)) + \
                jnp.sum(hsum.astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2))

    pal = timed(bd_loss(True), xb, resid, gam)
    xla = timed(bd_loss(False), xb, resid, gam)
    res["bias_dropout_ln_pallas_ms"] = round(pal, 3)
    res["bias_dropout_ln_xla_ms"] = round(xla, 3)
    res["bias_dropout_ln_speedup"] = round(xla / pal, 3)

    # weight-only int8 matmul at decode GEMV shape (m=8) and prefill shape:
    # the decode case is weight-bandwidth-bound, where int8 HBM reads win
    from paddle_tpu.ops.kernels import wo_matmul_pallas as wm
    kk, nn_ = 4096, 11008
    wq = jnp.asarray(rng.integers(-127, 127, (kk, nn_)), jnp.int8)
    sc = jnp.asarray(rng.random(nn_) * 0.01, jnp.float32)
    wq4 = jnp.asarray(rng.integers(-127, 127, (kk, nn_ // 2)), jnp.int8)
    for label, mrows in (("decode", 8), ("prefill", 1024)):
        xa = jnp.asarray(rng.standard_normal((mrows, kk)), jnp.bfloat16)
        pal = timed(lambda a: wm.wo_int8_matmul(a, wq, sc), xa)
        xla = timed(lambda a: wm.reference_wo_int8_matmul(a, wq, sc), xa)
        res[f"wo_int8_{label}_pallas_ms"] = round(pal, 3)
        res[f"wo_int8_{label}_xla_ms"] = round(xla, 3)
        res[f"wo_int8_{label}_speedup"] = round(xla / pal, 3)
        pal4 = timed(lambda a: wm.wo_int4_matmul(a, wq4, sc), xa)
        xla4 = timed(lambda a: wm.reference_wo_int4_matmul(a, wq4, sc), xa)
        res[f"wo_int4_{label}_pallas_ms"] = round(pal4, 3)
        res[f"wo_int4_{label}_xla_ms"] = round(xla4, 3)
        res[f"wo_int4_{label}_speedup"] = round(xla4 / pal4, 3)

    # fused softmax-CE at a 50k vocab, fwd+bwd
    from paddle_tpu.ops.kernels import ce_pallas as cp
    nrows, vocab = 4096, 50304
    lg = jnp.asarray(rng.standard_normal((nrows, vocab)), jnp.bfloat16)
    lb = jnp.asarray(rng.integers(0, vocab, (nrows,)), jnp.int32)
    pal = timed(jax.grad(lambda a: jnp.sum(
        cp.c_softmax_with_cross_entropy(a, lb, 0, None, False))), lg)
    xla = timed(jax.grad(lambda a: jnp.sum(cp.reference_ce(a, lb))), lg)
    res["softmax_ce_pallas_ms"] = round(pal, 3)
    res["softmax_ce_xla_ms"] = round(xla, 3)
    res["softmax_ce_speedup"] = round(xla / pal, 3)

    # fused dropout+residual-add fwd+bwd: the in-kernel counter-hash mask
    # vs the XLA threefry composite (which materializes the mask to HBM)
    from paddle_tpu.ops.kernels import dropout_add_pallas as dak
    xr = jnp.asarray(rng.standard_normal((8192, 4096)), jnp.bfloat16)
    rr = jnp.asarray(rng.standard_normal((8192, 4096)), jnp.bfloat16)
    sd = jnp.int32(17)
    key = jax.random.PRNGKey(17)

    def _xla_da(a):
        keep = jax.random.bernoulli(key, 0.9, a.shape)
        return jnp.where(keep, a / 0.9, 0).astype(a.dtype) + rr

    pal = timed(jax.grad(lambda a: jnp.sum(
        dak.dropout_add(a, rr, sd, 0.1).astype(jnp.float32))), xr)
    xla = timed(jax.grad(lambda a: jnp.sum(_xla_da(a).astype(jnp.float32))),
                xr)
    res["dropout_add_pallas_ms"] = round(pal, 3)
    res["dropout_add_xla_ms"] = round(xla, 3)
    res["dropout_add_speedup"] = round(xla / pal, 3)

    # fused linear param-grad accumulate: in-VMEM fp32 tile accumulation
    # + aliased buffer vs XLA's GEMM-then-add (extra dW HBM round trip)
    from paddle_tpu.ops.kernels import linear_grad_add_pallas as lga
    xg = jnp.asarray(rng.standard_normal((8192, 4096)), jnp.bfloat16)
    dyg = jnp.asarray(rng.standard_normal((8192, 4096)), jnp.bfloat16)
    accg = jnp.zeros((4096, 4096), jnp.float32)
    pal = timed(lambda a: lga.linear_grad_acc(a, dyg, accg), xg)
    xla = timed(lambda a: lga.reference_grad_acc(a, dyg, accg), xg)
    res["linear_grad_acc_pallas_ms"] = round(pal, 3)
    res["linear_grad_acc_xla_ms"] = round(xla, 3)
    res["linear_grad_acc_speedup"] = round(xla / pal, 3)

    # A8W8 prefill GEMM: in-kernel per-token quant + int8 MXU vs the
    # bf16 matmul it replaces (the int8 MXU runs at twice the bf16 rate)
    from paddle_tpu.ops.kernels import a8w8_matmul_pallas as a8
    xq8 = jnp.asarray(rng.standard_normal((4096, 4096)), jnp.bfloat16)
    wq8 = jnp.asarray(rng.integers(-127, 127, (4096, 4096)), jnp.int8)
    wsq8 = jnp.asarray(rng.random(4096) * 0.01, jnp.float32)
    # baseline weight is PRE-dequantized outside the timed lambda: a real
    # bf16 deployment stores bf16 weights, so the baseline times only the
    # matmul
    wbf16 = jax.block_until_ready(
        wq8.astype(jnp.bfloat16) * wsq8.astype(jnp.bfloat16)[None, :])
    pal = timed(lambda a: a8.a8w8_matmul(a, wq8, wsq8), xq8)
    xla = timed(lambda a: a @ wbf16, xq8)
    res["a8w8_prefill_pallas_ms"] = round(pal, 3)
    res["bf16_prefill_xla_ms"] = round(xla, 3)
    res["a8w8_prefill_speedup"] = round(xla / pal, 3)

    # transformer-block mega-kernel epilogues (block_fused_pallas) vs the
    # per-op composite chains they replace, fwd+bwd at GPT-3-ish dims:
    # the three fused blocks of the fusion_targets harvest
    from paddle_tpu.ops.kernels import block_fused_pallas as bfk
    rows_e, hid_e = 8192, 4096
    xe = jnp.asarray(rng.standard_normal((rows_e, hid_e)), jnp.bfloat16)
    re_ = jnp.asarray(rng.standard_normal((rows_e, hid_e)), jnp.bfloat16)
    we = jnp.asarray(rng.standard_normal(hid_e), jnp.float32)
    bee = jnp.asarray(rng.standard_normal(hid_e), jnp.float32)
    sde = jnp.int32(23)

    def _epi_loss(fused, act, norm, p_drop, bias):
        def f(x_, r_, w_):
            if fused:
                y, hh = bfk.fused_epilogue(x_, r_, w_, bias, sde, p_drop,
                                           1e-5, act, norm, None, False)
            else:
                y, hh = bfk.reference_fused_epilogue(x_, r_, w_, bias, sde,
                                                     p_drop, 1e-5, act, norm)
            return jnp.sum(y.astype(jnp.float32)) + \
                jnp.sum(hh.astype(jnp.float32))
        return jax.grad(f, argnums=(0, 1, 2))

    # (1) attention epilogue: dropout-add + rmsnorm in one pass
    pal = timed(_epi_loss(True, None, "rms", 0.1, None), xe, re_, we)
    xla = timed(_epi_loss(False, None, "rms", 0.1, None), xe, re_, we)
    res["attn_epilogue_pallas_ms"] = round(pal, 3)
    res["attn_epilogue_xla_ms"] = round(xla, 3)
    res["attn_epilogue_speedup"] = round(xla / pal, 3)

    # (2) MLP epilogue: gelu + dropout-add + layernorm in one pass
    pal = timed(_epi_loss(True, "gelu", "layer", 0.1, bee), xe, re_, we)
    xla = timed(_epi_loss(False, "gelu", "layer", 0.1, bee), xe, re_, we)
    res["mlp_epilogue_pallas_ms"] = round(pal, 3)
    res["mlp_epilogue_xla_ms"] = round(xla, 3)
    res["mlp_epilogue_speedup"] = round(xla / pal, 3)

    # (3) serving decode epilogue at continuous-batch shape [B, 1, H]
    xd = jnp.asarray(rng.standard_normal((64, 1, hid_e)), jnp.bfloat16)
    rd = jnp.asarray(rng.standard_normal((64, 1, hid_e)), jnp.bfloat16)
    pal = timed(lambda a: bfk.decode_epilogue(a, rd, we, 1e-5, False)[0], xd)
    xla = timed(lambda a: bfk.reference_fused_epilogue(
        a, rd, we, None, 0, 0.0, 1e-5, None, "rms")[0], xd)
    res["decode_epilogue_pallas_ms"] = round(pal, 3)
    res["decode_epilogue_xla_ms"] = round(xla, 3)
    res["decode_epilogue_speedup"] = round(xla / pal, 3)

    # serving decode step through fused_multi_transformer: mmha Pallas
    # kernel vs the einsum fallback, Llama-7B-ish single layer
    from paddle_tpu.ops.kernels import _common as kcommon
    from paddle_tpu.ops.kernels import mmha_pallas as mp
    bb, hh2, dd, tt = 8, 32, 128, 2048
    q1 = jnp.asarray(rng.standard_normal((bb, 1, hh2, dd)), jnp.bfloat16)
    kbuf = jnp.asarray(rng.standard_normal((bb, hh2, tt, dd)), jnp.bfloat16)
    vbuf = jnp.asarray(rng.standard_normal((bb, hh2, tt, dd)), jnp.bfloat16)
    pos = jnp.int32(tt - 1)
    if mp.use_kernel(q1.shape, kbuf.shape, kbuf.dtype):
        pal = timed(lambda a: mp.mmha_decode(a, kbuf, vbuf, pos,
                                             interpret=kcommon
                                             .interpret_mode()), q1)
        xla = timed(lambda a: mp.reference_mmha(a, kbuf, vbuf, pos), q1)
        res["serving_mmha_decode_pallas_ms"] = round(pal, 3)
        res["serving_mmha_decode_xla_ms"] = round(xla, 3)
        res["serving_mmha_decode_speedup"] = round(xla / pal, 3)
    return res


def run_moe_bench(dev):
    """Qwen2-MoE family throughput (BASELINE.md ladder #5): activated-param
    MFU matters for MoE, so we report tokens/s plus activated fraction."""
    import paddle_tpu as paddle
    from paddle_tpu.models import Qwen2Moe, Qwen2MoeConfig

    paddle.seed(0)
    cfg = Qwen2MoeConfig(
        vocab_size=32000, max_position_embeddings=1024, hidden_size=512,
        num_layers=4, num_heads=8, num_kv_heads=4,
        moe_intermediate_size=512, shared_expert_intermediate_size=1024,
        num_experts=8, num_experts_per_tok=2)
    model = Qwen2Moe(cfg)
    batch, seq, steps, warmup = 4, 1024, 8, 2
    tokens_per_s, final, breakdown = _train_throughput(
        model, batch, seq, steps, warmup, cfg.vocab_size, on_tpu=True)
    return {"tokens_per_sec": round(tokens_per_s, 1),
            "loss": round(final, 3),
            "n_params": model.num_params(),
            "activated_params": model.num_activated_params(),
            "step_breakdown": breakdown}


def run_ernie_bench(dev):
    """ERNIE family throughput (BASELINE.md ladder #2): the native-Paddle
    flagship — dense-first + MoE-tail backbone with the router aux loss
    riding the same step."""
    import paddle_tpu as paddle
    from paddle_tpu.models import Ernie, ErnieConfig

    paddle.seed(0)
    cfg = ErnieConfig(
        vocab_size=32000, max_position_embeddings=1024, hidden_size=512,
        num_layers=4, num_heads=8, num_kv_heads=4, intermediate_size=2048,
        num_experts=4, num_experts_per_tok=2, moe_intermediate_size=512,
        shared_expert_intermediate_size=512, first_k_dense=2)
    model = Ernie(cfg)
    batch, seq, steps, warmup = 4, 1024, 8, 2
    tokens_per_s, final, breakdown = _train_throughput(
        model, batch, seq, steps, warmup, cfg.vocab_size, on_tpu=True)
    return {"tokens_per_sec": round(tokens_per_s, 1),
            "loss": round(final, 3),
            "n_params": model.num_params(),
            "step_breakdown": breakdown}


def run_dit_bench(dev):
    """DiT-S/2 training throughput (BASELINE.md ladder #4: 'trains;
    throughput reported'): images/s for the jitted DDPM train step."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import DiTPipeline, dit_s_2

    paddle.seed(0)
    pipe = DiTPipeline(dit_s_2(input_size=32, num_classes=1000))
    opt = paddle.optimizer.AdamW(1e-4, parameters=pipe.parameters())
    b = 32
    rng = np.random.default_rng(0)
    x0 = paddle.to_tensor(
        rng.standard_normal((b, 4, 32, 32)).astype(np.float32))
    y = paddle.to_tensor(rng.integers(0, 1000, b).astype(np.int64))
    noise = paddle.to_tensor(
        rng.standard_normal((b, 4, 32, 32)).astype(np.float32))
    t = paddle.to_tensor(rng.integers(0, 1000, b).astype(np.int64))

    @paddle.jit.to_static
    def step(x0, y, noise, t):
        loss = pipe(x0, y, noise, t)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for _ in range(2):
        loss = step(x0, y, noise, t)
    float(loss)
    steps = 10
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x0, y, noise, t)
    final = float(loss)
    dt = time.perf_counter() - t0
    return {"images_per_sec": round(b * steps / dt, 1),
            "loss": round(final, 4), "batch": b,
            "n_params": pipe.dit.num_params()}


def run_sd3_bench(dev):
    """SD3-class MMDiT rectified-flow training throughput (BASELINE.md
    ladder #4 'DiT / Stable-Diffusion-3'): images/s for the jitted step at
    a 1/4-width sd3-medium config that fits one chip with AdamW states."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import MMDiTConfig, SD3Pipeline

    paddle.seed(0)
    cfg = MMDiTConfig(input_size=32, patch_size=2, in_channels=16,
                      hidden_size=384, num_layers=12, num_heads=6,
                      text_dim=4096, pooled_dim=2048, max_text_len=77)
    pipe = SD3Pipeline(cfg)
    opt = paddle.optimizer.AdamW(1e-4, parameters=pipe.parameters())
    b = 16
    rng = np.random.default_rng(0)
    x0 = paddle.to_tensor(
        rng.standard_normal((b, 16, 32, 32)).astype(np.float32))
    txt = paddle.to_tensor(
        rng.standard_normal((b, 77, 4096)).astype(np.float32))
    pooled = paddle.to_tensor(
        rng.standard_normal((b, 2048)).astype(np.float32))
    noise = paddle.to_tensor(
        rng.standard_normal((b, 16, 32, 32)).astype(np.float32))
    t = paddle.to_tensor(rng.standard_normal(b).astype(np.float32))

    @paddle.jit.to_static
    def step(x0, txt, pooled, noise, t):
        loss = pipe(x0, txt, pooled, noise, t)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    for _ in range(2):
        loss = step(x0, txt, pooled, noise, t)
    float(loss)
    steps = 10
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(x0, txt, pooled, noise, t)
    final = float(loss)
    dt = time.perf_counter() - t0
    return {"images_per_sec": round(b * steps / dt, 1),
            "loss": round(final, 4), "batch": b,
            "n_params": pipe.mmdit.num_params()}


def _peak_flops(dev):
    """(bf16 peak FLOPs, source) from the device kind (spec sheets). The
    table and lookup live in paddle_tpu.observability.step_timer so training
    loops and the bench compute MFU from the same source."""
    from paddle_tpu.observability import device_peak_flops
    return device_peak_flops(dev)


# ---------------------------------------------------------------------------
# driver: one process, one device, no fallback
# ---------------------------------------------------------------------------

#: (key, section) run after the two train benches on a TPU, in this order
_TPU_SECTIONS = (
    ("serve", lambda dev: run_serve_bench()),
    ("llama8b_layer", lambda dev: run_llama8b_layer_bench(dev)),
    ("flash_ab", lambda dev: run_flash_ab(dev)),
    ("kernel_ab", lambda dev: run_kernel_ab(dev)),
    ("dit_s2", lambda dev: run_dit_bench(dev)),
    ("sd3_mmdit", lambda dev: run_sd3_bench(dev)),
    ("qwen2_moe", lambda dev: run_moe_bench(dev)),
    ("ernie", lambda dev: run_ernie_bench(dev)),
)


def _device_block(dev):
    import jax
    return {"platform": dev.platform,
            "device_kind": getattr(dev, "device_kind", ""),
            "device_count": len(jax.devices())}


def _run_tpu(dev):
    """Every section on the attached chip; a section that raises, raises."""
    gpt = run_gpt_bench(dev, True)
    result = run_llama_bench(dev)      # north-star family: primary metric
    result["extra"]["gpt2_124m_tokens_per_s"] = gpt["value"]
    result["extra"]["gpt2_124m_mfu"] = gpt["extra"]["mfu"]
    for key, fn in _TPU_SECTIONS:
        result["extra"][key] = fn(dev)
    result["extra"]["kernel_static"] = _kernel_static_block(
        result["extra"].get("kernel_ab"))
    return result


def _run_cpu(dev):
    """Harness smoke: the GPT section at toy widths plus the serving one."""
    result = run_gpt_bench(dev, False)
    result["extra"]["serve"] = run_serve_bench()
    return result


def _run_serve(dev):
    blk = run_serve_bench()
    return {"metric": "serve_tokens_per_s", "value": blk["tokens_per_s"],
            "unit": "tokens/s", "vs_baseline": 0.0, "extra": {"serve": blk}}


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("section", nargs="?", choices=("serve",),
                    help="run this section alone")
    ap.add_argument("--cpu", action="store_true",
                    help="smoke the harness on the CPU backend")
    args = ap.parse_args(argv)

    if args.cpu:
        import jax
        # a CPU process stays out of the chip runs' compile cache
        jax.config.update("jax_enable_compilation_cache", False)
        from paddle_tpu.device import force_cpu_backend
        force_cpu_backend()
    import jax
    dev = jax.devices()[0]
    if dev.platform != ("cpu" if args.cpu else "tpu"):
        print(json.dumps({"metric": "bench_failed", "value": 0.0,
                          **_device_block(dev),
                          "error": f"no TPU: jax sees {dev.platform!r} "
                                   "(pass --cpu to smoke the harness)"}))
        return 1
    run = _run_serve if args.section == "serve" else \
        _run_cpu if args.cpu else _run_tpu
    try:
        result = run(dev)
    except Exception:
        print(json.dumps({"metric": "bench_failed", "value": 0.0,
                          **_device_block(dev),
                          "error": traceback.format_exc(limit=8)}))
        return 1
    result.update(_device_block(dev))
    _attach_telemetry(result)
    if args.section is None:
        # bubble/schedule accounting for the standard pp=4, v=2, M=8 recipe
        from paddle_tpu.distributed.meta_parallel.pipeline_parallel import \
            schedule_report
        result["extra"]["pipeline_schedule"] = schedule_report(4, 2, 8)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
