"""Serving cells over `paddle_tpu.serving.LLMEngine` and a model of the
Llama layout (`models/llama.py`), under open-loop traffic.

The load generator is this process's main thread: it sleeps until each
request is due, submits it, and stamps its own clock in `on_token` (the
engine's `ttft_ms` counts from submit, not from when the request was due).
Once the window has closed it waits until every request sent has its first
token (at most `wait_first_tokens_s`), stops the engine, reads the memory
peak, frees the program and runs the plain reference over a seeded sample
of the requests the window finished, the longest among them.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from ..harness import common, traffic, weights

TRACE_AFTER_S = 6.0
TRACE_SECONDS = 4.0
SAMPLE_REQUESTS = 6


def param_count(cfg):
    """Parameters a token multiplies with: the layers' matrices and the
    output head (the embedding is a lookup)."""
    h, m, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    kv = cfg["num_key_value_heads"] * (h // cfg["num_attention_heads"])
    per_layer = 2 * h * h + 2 * h * kv + 3 * h * m
    return cfg["num_hidden_layers"] * per_layer + v * h


def build(ctx):
    """(engine, model): the program with its weights from the seed."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import Llama, LlamaConfig
    from paddle_tpu.serving import LLMEngine, ServingConfig

    cfg, seed = ctx["config"], ctx["seed"]
    eng_cfg = cfg["engine"]
    lcfg = LlamaConfig(vocab_size=cfg["vocab_size"],
                       max_position_embeddings=cfg["max_position_embeddings"],
                       hidden_size=cfg["hidden_size"],
                       num_layers=cfg["num_hidden_layers"],
                       num_heads=cfg["num_attention_heads"],
                       num_kv_heads=cfg["num_key_value_heads"],
                       intermediate_size=cfg["intermediate_size"],
                       rms_norm_eps=cfg["rms_norm_eps"],
                       rope_theta=cfg["rope_theta"],
                       initializer_range=cfg["initializer_range"],
                       tie_word_embeddings=cfg["tie_word_embeddings"])
    with paddle.LazyGuard():
        model = Llama(lcfg)
    weights.assign(model.named_parameters(),
                   weights.llama_leaves(cfg, common.seed_words(seed)))
    model.astype(eng_cfg["dtype"])
    model.eval()
    common.log(f"serve_llama: weights made at "
               f"{time.perf_counter() - ctx['t_start']:.1f}s")
    engine = LLMEngine(model, ServingConfig(
        page_size=eng_cfg["page_size"],
        num_pages=eng_cfg["pool_positions"] // eng_cfg["page_size"] + 1,
        max_batch=eng_cfg["max_batch"], max_seq_len=eng_cfg["max_seq_len"],
        prefill_buckets=tuple(eng_cfg["prefill_buckets"]),
        prefill_chunk=eng_cfg["prefill_chunk"],
        prefix_cache=eng_cfg["prefix_cache"], spec_k=eng_cfg["spec_k"],
        temperature=0.0, dtype=eng_cfg["dtype"]))
    return engine, model


def warm_up(engine, ctx, mix):
    """Every program the mix's traffic reaches, each twice (`to_static`
    runs a signature eagerly the first time and compiles it the second):
    the prefill bucket of every prompt length, and the decode step. Not the
    bucket that only a re-prefill after an eviction could reach (prompt plus
    answer so far): no cell's pool has filled (`kv_evictions` 0 in every run
    of PR 24) and a bucket costs 12 s of every run's set-up; should a later
    mix evict, `compiles_in_window.serve` and `kv_evictions` both show it."""
    rng = np.random.default_rng(ctx["seed"] ^ 0x5EED)
    vocab = ctx["config"]["vocab_size"]
    lo = engine.bucket_for(int(mix["prompt_tokens"]["min"]))
    hi = engine.bucket_for(int(mix["prompt_tokens"]["max"]))
    for b in engine.buckets:
        if not lo <= b <= hi:
            continue
        n = min(b, engine.max_seq_len - 4)
        for _ in range(2):
            ids = rng.integers(1, vocab, n).tolist()
            engine.submit(ids, max_new_tokens=4).result(timeout=900)
        common.log(f"serve_llama: bucket {b} warm at "
                   f"{time.perf_counter() - ctx['t_start']:.1f}s")


class Spans:
    """The benchmark's own spans round the engine's two device calls, on
    the host clock and as `TraceAnnotation`s in the profiler's trace."""

    def __init__(self, engine):
        import jax
        self.decode, self.prefill = [], []
        inner_decode, inner_prefill = engine.decode, engine.prefill

        def decode(tokens, positions, tables, temps):
            live = tables[:, 0] != 0
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/decode"):
                out = inner_decode(tokens, positions, tables, temps)
            self.decode.append((t, time.perf_counter(), int(live.sum()),
                                int((positions[live] + 1).sum())))
            return out

        def prefill(req):
            n = len(req.context()) - req.prefilled
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/prefill"):
                out = inner_prefill(req)
            self.prefill.append((t, time.perf_counter(), n))
            return out

        engine.decode, engine.prefill = decode, prefill


def window(engine, reqs, seconds, wait_first_s, tracer=None, lead=(),
           lead_s=0.0, at_open=None):
    """Offer `lead` (due in [0, lead_s), before the window opens: the engine
    is then as full as the rate keeps it, not empty) and then `reqs` (due in
    [0, seconds) of the window) on their schedule. Returns the records of
    all requests sent (`measured` marks those due in the window), the
    window's start, the cutoff (host clock) and the pool's fill at each
    arrival in the window."""
    records, pages = [], []
    t0 = time.perf_counter() + lead_s
    plan = [dict(r, due=r["due"] - lead_s, measured=False) for r in lead] \
        + [dict(r, measured=True) for r in reqs]
    opened = False

    def open_window():
        delay = t0 - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        if at_open is not None:
            at_open(t0)
        if tracer is not None:
            tracer.follow(t0)

    for r in plan:
        if r["measured"] and not opened:
            open_window()
            opened = True
        delay = t0 + r["due"] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        rec = {"due": r["due"], "times": [], "prompt": r["prompt"],
               "max_new": r["max_new"], "error": None,
               "measured": r["measured"]}
        rec["t_submit"] = time.perf_counter()
        try:
            rec["handle"] = engine.submit(
                r["prompt"], max_new_tokens=r["max_new"], temperature=0.0,
                on_token=lambda tok, ts=rec["times"]: ts.append(
                    time.perf_counter()))
        except Exception as e:      # noqa: BLE001 - a refusal is a failure
            rec["handle"], rec["error"] = None, repr(e)
        records.append(rec)
        if r["measured"]:
            p = engine.stats()["pages"]
            pages.append(p["used"] / p["total"])
    if not opened:
        open_window()
    rest = t0 + seconds - time.perf_counter()
    if rest > 0:
        time.sleep(rest)
    deadline = t0 + seconds + wait_first_s
    while time.perf_counter() < deadline and any(
            r["handle"] is not None and not r["times"]
            and not r["handle"].finished for r in records):
        time.sleep(0.01)
    cutoff = time.perf_counter()
    if tracer is not None:
        tracer.finish()
    return records, t0, cutoff, pages


def lead_in(mix, seed, vocab):
    """(requests, seconds) offered before the window opens, at the mix's own
    rate and lengths, from another stream of the seed."""
    lead_s = float(mix.get("lead_in_s", 0.0))
    if lead_s <= 0:
        return [], 0.0
    if "order_seed" in mix:     # its own fixed order, not the window's
        mix = dict(mix, order_seed=int(mix["order_seed"]) ^ 0x1EAD)
    return traffic.open_loop(mix, lead_s, seed ^ 0x1EAD, vocab), lead_s


def occupancy_between(s0, s1):
    """Mean share of the decode slots in use over the steps between two
    `engine.stats()` readings."""
    steps = s1["decode_steps"] - s0["decode_steps"]
    return (s1["occupancy_mean"] * s1["decode_steps"]
            - s0["occupancy_mean"] * s0["decode_steps"]) / max(steps, 1)


def latency_numbers(records, t0, seconds, cutoff):
    """TTFT and generator lateness of the requests due in the window; the
    gaps between tokens, and the tokens, that fell inside it or (for the
    window's own requests) between its close and the cutoff."""
    ttft, itl, late, in_window = [], [], [], 0
    for r in records:
        due = t0 + r["due"]
        ts = list(r["times"])
        if r["measured"]:
            late.append(1000.0 * (r["t_submit"] - due))
            ttft.append(1000.0 * ((ts[0] if ts else cutoff) - due))
        itl.extend(1000.0 * (b - a) for a, b in zip(ts, ts[1:])
                   if b >= t0 and (r["measured"] or b <= t0 + seconds))
        in_window += sum(1 for t in ts if t0 <= t <= t0 + seconds)
    return {"ttft_ms": ttft, "itl_ms": itl, "lateness_ms": late,
            "tokens_in_window": in_window}


def flops_of(records, cfg, t0, seconds):
    """Model FLOPs of the work finished inside the window: 2 x matmul
    parameters per token processed (prompt tokens of prefills whose first
    token fell in the window, and output tokens emitted in it), plus
    attention's QK and PV products over the context each token saw."""
    p2 = 2.0 * param_count(cfg)
    att = 4.0 * cfg["num_hidden_layers"] * cfg["hidden_size"]
    total = 0.0
    for r in records:
        n = len(r["prompt"])
        for j, t in enumerate(r["times"]):
            if not t0 <= t <= t0 + seconds:
                continue
            if j == 0:      # the prefill: n tokens, causal
                total += p2 * n + att * n * (n + 1) / 2
            else:           # one decode token over n + j positions
                total += p2 + att * (n + j)
    return total


def check_sample(ctx, records, quant=None):
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over a seeded sample of finished requests with the
    longest in it. With `quant`, the control: the gap of the token that the
    reference computed in that precision puts first, at the same positions."""
    import jax.numpy as jnp

    from ..reference import llama as ref
    cfg, mix = ctx["config"], ctx["traffic"]
    done = [r for r in records if r["state"] == "completed"]
    if not done:
        return {"logit_gap_max": float("inf"), "sampled_tokens": 0.0}, []
    rng = np.random.default_rng(ctx["seed"] ^ 0xC0FFEE)
    longest = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    others = [r for r in done if r is not longest]
    pick = [longest] + [others[i] for i in rng.permutation(len(others))[
        :SAMPLE_REQUESTS - 1]]
    span = ref.bucket(int(mix["output_tokens"]["max"]), 128)
    seqs = [r["prompt"] + r["tokens"][:-1] for r in pick]
    starts = [len(r["prompt"]) - 1 for r in pick]
    words = common.seed_words(ctx["seed"])
    full = ref.logits_of(cfg, words, seqs, starts, span)
    if quant:
        low = ref.logits_of(cfg, words, seqs, starts, span, quant=quant)
    worst, n_tok = 0.0, 0
    for i, r in enumerate(pick):
        n = len(r["tokens"])
        toks = jnp.zeros((span,), jnp.int32).at[:n].set(
            jnp.asarray(r["tokens"], jnp.int32))
        if quant:
            toks = jnp.argmax(low[i], axis=-1).astype(jnp.int32)
        g = np.asarray(ref.gaps(full[i], toks))[:n]
        worst = max(worst, float(g.max()))
        n_tok += n
    return {"logit_gap_max": worst, "sampled_tokens": float(n_tok)}, pick


def run(ctx):
    import paddle_tpu.observability as obs

    cfg, mix = ctx["config"], ctx["traffic"]
    engine, model = build(ctx)
    warm_up(engine, ctx, mix)
    spans = Spans(engine) if ctx["trace"] else None
    reqs = traffic.open_loop(mix, ctx["seconds"], ctx["seed"],
                             cfg["vocab_size"])

    def jit_counts():
        return (obs.total("paddle_tpu_jit_compiles_total")
                + obs.total("paddle_tpu_jit_trace_cache_retraces_total"))

    lead, lead_s = lead_in(mix, ctx["seed"], cfg["vocab_size"])
    opened = {}

    def at_open(t_open):
        opened.update(stats=engine.stats(), jit=jit_counts())
        ctx["setup_s"] = t_open - ctx["t_start"]

    records, t0, cutoff, pages = window(
        engine, reqs, ctx["seconds"], float(mix["wait_first_tokens_s"]),
        ctx["tracer"], lead, lead_s, at_open)
    s0, c0 = opened["stats"], opened["jit"]
    s1, c1 = engine.stats(), jit_counts()
    for r in records:       # what was served by the cutoff, frozen
        h = r["handle"]
        r["tokens"] = list(h.tokens) if h is not None else []
        r["queue_ms"] = h.queue_ms if h is not None else None
        r["state"] = h.state if h is not None else "refused"
        r["failed"] = r["state"] in ("failed", "rejected", "refused")
    engine.shutdown(drain=False)
    peak = common.memory_peak_bytes() if ctx["on_chip"] else 0
    lat = latency_numbers(records, t0, ctx["seconds"], cutoff)
    failed_any = sum(r["failed"] for r in records)
    failed = sum(r["failed"] and r["measured"] for r in records)
    sent = sum(r["measured"] for r in records)
    short = sum(1 for r in records if r["state"] == "completed"
                and len(r["tokens"]) != r["max_new"])
    common.log(f"serve_llama: window closed, {sent} sent in it, "
               f"{failed} failed, cutoff +{cutoff - t0 - ctx['seconds']:.2f}s")

    # -- free the program, then the reference --------------------------------
    occ = occupancy_between(s0, s1)
    for r in records:
        r["handle"] = None
    del engine, model
    if spans is not None:
        spans_decode, spans_prefill = spans.decode, spans.prefill
        del spans
    gc.collect()
    t_ref = time.perf_counter()
    numbers, _ = check_sample(ctx, records)
    common.log(f"serve_llama: reference took "
               f"{time.perf_counter() - t_ref:.1f}s")
    numbers["requests_failed"] = float(failed_any)
    numbers["length_mismatch"] = float(short)

    e2e = {"serve_tokens_per_s": lat["tokens_in_window"] / ctx["seconds"],
           "itl_p95_ms": common.percentile(lat["itl_ms"], 95)
           if lat["itl_ms"] else float("inf")}
    observed = {
        "window_s": ctx["seconds"],
        "counters": {"compiles": c1 - c0,
                     "evictions": s1["evictions"] - s0["evictions"]},
        "stats": {"batch_occupancy": occ,
                  "kv_pages_used_share": float(np.mean(pages)) if pages
                  else None},
        "spans": {"queue_wait": [r["queue_ms"] / 1000.0 for r in records
                                 if r["measured"]
                                 and r["queue_ms"] is not None],
                  "lateness": [v / 1000.0 for v in lat["lateness_ms"]],
                  "ttft": [v / 1000.0 for v in lat["ttft_ms"]],
                  "itl": [v / 1000.0 for v in lat["itl_ms"]]},
        "serve": {"weight_bytes": 2 * param_count(cfg),
                  "kv_bytes_per_position": 2 * 2 * cfg["num_hidden_layers"]
                  * cfg["num_key_value_heads"]
                  * (cfg["hidden_size"] // cfg["num_attention_heads"]),
                  "layers": cfg["num_hidden_layers"],
                  "hidden": cfg["hidden_size"]},
    }
    tr = ctx["tracer"]
    if tr is not None and tr.t_on is not None and tr.t_off is not None:
        observed["traced_s"] = tr.t_off - tr.t_on
        observed["model_flops_traced"] = flops_of(
            records, cfg, tr.t_on, tr.t_off - tr.t_on)
    if ctx["trace"]:
        observed["spans"]["decode_step"] = [b - a for a, b, *_ in spans_decode]
        observed["spans"]["prefill_per_ktoken"] = [
            1000.0 * (b - a) / n for a, b, n in spans_prefill if n > 0]
        observed["decode_calls"] = spans_decode
        observed["prefill_calls"] = spans_prefill
    return {"attempted": sent, "failed": failed, "numbers": numbers,
            "memory_peak_bytes": peak, "end_to_end": e2e,
            "observed": observed}


def limits_readings(ctx, seeds, n_control, emit):
    """For `tools/limits.py`: a window of `ctx["seconds"]` at the cell's own
    rate per seed, new weights per seed in the same engine; the control is the
    plain reference in int8 at the positions of the same prompts and tokens,
    read on the first `n_control` seeds."""
    cfg, mix = ctx["config"], ctx["traffic"]
    engine, model = build(dict(ctx, seed=seeds[0]))
    warm_up(engine, dict(ctx, seed=seeds[0]), mix)
    params = dict(model.named_parameters())
    for k, seed in enumerate(seeds):
        c = dict(ctx, seed=seed)
        if k:       # this seed's weights into the same engine, leaf by leaf
            weights.assign(params.items(), weights.llama_leaves(
                cfg, common.seed_words(seed)))
        reqs = traffic.open_loop(mix, ctx["seconds"], seed, cfg["vocab_size"])
        lead, lead_s = lead_in(mix, seed, cfg["vocab_size"])
        records, t0, cutoff, _ = window(engine, reqs, ctx["seconds"], 60.0,
                                        None, lead, lead_s)
        for r in records:       # let the longest requests finish
            if r["handle"] is not None:
                try:
                    r["handle"].result(timeout=120)
                except Exception as e:      # noqa: BLE001
                    r["error"] = repr(e)
            h = r["handle"]
            r["tokens"] = list(h.tokens) if h is not None else []
            r["state"] = h.state if h is not None else "refused"
        lat = latency_numbers(records, t0, ctx["seconds"], cutoff)
        numbers, _ = check_sample(c, records)
        held = {"requests_failed": float(sum(
                    r["state"] != "completed" for r in records)),
                "length_mismatch": float(sum(
                    len(r["tokens"]) != r["max_new"] for r in records
                    if r["state"] == "completed"))}
        numbers.update(held)
        row = {"seed": seed, "program": numbers, "sent": len(records),
               "completed": sum(r["state"] == "completed" for r in records),
               "ttft_p90_ms": common.percentile(lat["ttft_ms"], 90),
               "itl_p95_ms": common.percentile(lat["itl_ms"], 95),
               "tokens_per_s": lat["tokens_in_window"] / ctx["seconds"]}
        if k < n_control:
            row["control_int8"], _ = check_sample(c, records, quant="int8")
            row["control_int8"].update(held)
        emit(row)
    engine.shutdown(drain=False)
