"""Serving cells over `paddle_tpu.serving.LLMEngine` and a model of the AFMoE
layout (`models/afmoe.py`: Trinity), under open-loop traffic.

The load generator, the window, the lead-in and the latency numbers are
`serve_llama`'s, by import. What differs is what a layout brings: the model
and its seeded weights, the plain reference (`reference/afmoe.py`), the count
of the model's FLOPs (8 routed experts and the shared one a token, attention
over `min(context, window)` keys in a window layer) and what the work counts
under `work/` need to know of the sizes.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from ..harness import common, traffic, weights, weights_afmoe
from .serve_llama import (TRACE_AFTER_S, TRACE_SECONDS,  # noqa: F401
                          SAMPLE_REQUESTS, latency_numbers, lead_in,
                          occupancy_between, warm_up, window)


def sizes(cfg):
    """Matrix parameters by part, and the KV bytes a position takes in one
    layer: what the FLOP count and the work counts are made from."""
    h, e = cfg["hidden_size"], cfg["moe_intermediate_size"]
    q = cfg["num_attention_heads"] * cfg["head_dim"]
    kv = cfg["num_key_value_heads"] * cfg["head_dim"]
    kinds = cfg["layer_types"]
    n_dense = cfg["num_dense_layers"]
    return {
        "attention": 3 * h * q + 2 * h * kv,      # q, gate, o; k, v
        "dense_mlp": 3 * h * cfg["intermediate_size"],
        "expert": 3 * h * e,
        "shared": 3 * h * e * cfg["num_shared_experts"],
        "router": h * cfg["num_experts"],
        "head": h * cfg["vocab_size"],
        "layers": len(kinds), "dense_layers": n_dense,
        "routed_layers": len(kinds) - n_dense,
        "window_layers": sum(k == "sliding_attention" for k in kinds),
        "global_layers": sum(k == "full_attention" for k in kinds),
        "window": cfg["sliding_window"], "q_width": q, "hidden": h,
        "experts": cfg["num_experts"], "top_k": cfg["num_experts_per_tok"],
        "kv_bytes_per_position_layer": 2 * 2 * kv,
    }


def params_per_token(cfg):
    """Matrix parameters a token multiplies with: attention, the dense MLPs,
    router, the chosen experts and the shared one, and the output head."""
    s = sizes(cfg)
    return (s["layers"] * s["attention"] + s["dense_layers"] * s["dense_mlp"]
            + s["routed_layers"] * (s["router"] + s["top_k"] * s["expert"]
                                    + s["shared"]) + s["head"])


def model_of(cfg, max_positions):
    """The program's model for a configuration under the source's keys,
    lazily built (no weights yet)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.afmoe import Afmoe, AfmoeConfig
    acfg = AfmoeConfig(
        vocab_size=cfg["vocab_size"], max_position_embeddings=max_positions,
        hidden_size=cfg["hidden_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_dense_layers=cfg["num_dense_layers"],
        num_experts=cfg["num_experts"],
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        route_norm=cfg["route_norm"], route_scale=cfg["route_scale"],
        sliding_window=cfg["sliding_window"],
        layer_types=list(cfg["layer_types"]),
        rms_norm_eps=cfg["rms_norm_eps"], rope_theta=cfg["rope_theta"],
        mup_enabled=cfg["mup_enabled"],
        initializer_range=cfg["initializer_range"],
        tie_word_embeddings=cfg["tie_word_embeddings"])
    with paddle.LazyGuard():
        model = Afmoe(acfg)
    return model


def assign(model, cfg, words, dtype):
    """This seed's weights into the model, leaf by leaf (parameters and the
    routed layers' selection bias, a buffer), in `dtype`."""
    import jax.numpy as jnp
    named = dict(model.named_parameters())
    buffers = dict(model.named_buffers())
    cast = jnp.dtype(dtype)

    def pairs():
        for name, arr in weights_afmoe.leaves(cfg, words):
            arr = arr.astype(cast)
            if name in buffers:
                buffers[name]._data = arr
            else:
                yield name, arr

    weights.assign(named.items(), pairs())


def build(ctx):
    """(engine, model): the program with its weights from the seed."""
    from paddle_tpu.serving import LLMEngine, ServingConfig

    cfg, eng_cfg = ctx["config"], ctx["config"]["engine"]
    model = model_of(cfg, eng_cfg["max_seq_len"])
    assign(model, cfg, common.seed_words(ctx["seed"]), eng_cfg["dtype"])
    model.astype(eng_cfg["dtype"])
    model.eval()
    common.log(f"serve_afmoe: weights made at "
               f"{time.perf_counter() - ctx['t_start']:.1f}s")
    ps = eng_cfg["page_size"]
    engine = LLMEngine(model, ServingConfig(
        page_size=ps, num_pages=eng_cfg["pool_positions"] // ps + 1,
        max_batch=eng_cfg["max_batch"], max_seq_len=eng_cfg["max_seq_len"],
        prefill_buckets=tuple(eng_cfg["prefill_buckets"]),
        prefill_chunk=eng_cfg["prefill_chunk"],
        prefix_cache=eng_cfg["prefix_cache"], spec_k=eng_cfg["spec_k"],
        temperature=0.0, dtype=eng_cfg["dtype"]))
    return engine, model


class Spans:
    """The benchmark's own spans round the engine's two device calls
    (`serve_llama.Spans` for an engine whose decode takes the window group's
    tables too)."""

    def __init__(self, engine):
        import jax
        self.decode, self.prefill = [], []
        inner_decode, inner_prefill = engine.decode, engine.prefill

        def decode(tokens, positions, tables, temps, *window_tables):
            live = tables[:, 0] != 0
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/decode"):
                out = inner_decode(tokens, positions, tables, temps,
                                   *window_tables)
            self.decode.append((t, time.perf_counter(), int(live.sum()),
                                int((positions[live] + 1).sum()),
                                (positions[live] + 1).tolist()))
            return out

        def prefill(req):
            n = len(req.context()) - req.prefilled
            t = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench/prefill"):
                out = inner_prefill(req)
            self.prefill.append((t, time.perf_counter(), n))
            return out

        engine.decode, engine.prefill = decode, prefill


def keys_seen(n, window):
    """Keys that the n queries of a causal prefill see in all: every earlier
    position and itself, at most `window` of them (None: no bound)."""
    if window is None or n <= window:
        return n * (n + 1) / 2
    return window * (window + 1) / 2 + (n - window) * window


def flops_of(records, cfg, t0, seconds):
    """Model FLOPs of the work finished inside the window: 2 x the matrix
    parameters a token multiplies with, per token processed (prompt tokens
    of prefills whose first token fell in the window, output tokens emitted
    in it), plus attention's two products over the keys each token saw: its
    context in a global layer, the last `window` of it in a window layer."""
    s = sizes(cfg)
    p2 = 2.0 * params_per_token(cfg)
    att = 4.0 * s["q_width"]
    w = s["window"]
    total = 0.0
    for r in records:
        n = len(r["prompt"])
        for j, t in enumerate(r["times"]):
            if not t0 <= t <= t0 + seconds:
                continue
            if j == 0:      # the prefill: n tokens
                total += p2 * n + att * (
                    s["global_layers"] * keys_seen(n, None)
                    + s["window_layers"] * keys_seen(n, w))
            else:           # one decode token over n + j positions
                total += p2 + att * (s["global_layers"] * (n + j)
                                     + s["window_layers"] * min(n + j, w))
    return total


#: sampled requests that a planted fault is read on, the longest first
FAULT_REQUESTS = 3


def gap_numbers(g):
    """What is judged of a sample's per-token gaps (by how much the token
    put first lies below the reference's best): their mean and the share of
    tokens that are not the reference's first choice, which the precision
    moves and a near-tie between two experts does not, and the widest,
    which a fault at a few positions moves."""
    return {"logit_gap_mean": float(g.mean()),
            "top1_miss_share": float((g > 0).mean()),
            "logit_gap_max": float(g.max())}


def check_sample(ctx, records, variants=()):
    """`serve_llama.check_sample` with this layout's reference: by how much
    each served token's reference logit lies below the reference's best,
    over a seeded sample of finished requests with the longest in it.
    Returns (the program's numbers, {name: a variant's}, the sample). A
    variant is (name, quant, fault, requests): the reference computed with
    the control's `quant` or a planted `fault` and put in the program's
    place (its tokens are those it puts first, judged by the same sound
    pass), over the first `requests` of the sample (the longest first: it
    costs as much as the others together)."""
    import jax.numpy as jnp

    from ..reference import afmoe as ref
    cfg, mix = ctx["config"], ctx["traffic"]
    done = [r for r in records if r["state"] == "completed"]
    if not done:
        return dict(gap_numbers(np.asarray([np.inf])), sampled_tokens=0.0,
                    longest_sampled=0.0), {}, []
    rng = np.random.default_rng(ctx["seed"] ^ 0xC0FFEE)
    top_one = max(done, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    others = [r for r in done if r is not top_one]
    pick = [top_one] + [others[i] for i in
                        rng.permutation(len(others))[:SAMPLE_REQUESTS - 1]]
    span = ref.bucket(int(mix["output_tokens"]["max"]), 128)
    seqs = [r["prompt"] + r["tokens"][:-1] for r in pick]
    words = common.seed_words(ctx["seed"])
    eps = float(cfg["rms_norm_eps"])
    longest = int(cfg["engine"]["max_seq_len"])
    top, full = ref.hidden_of(cfg, words, seqs, span, longest=longest)

    def head(x, start, how):
        return ref.head(x, start, top["norm.weight"], top["lm_head.weight"],
                        span=span, eps=eps, quant=how)

    def read(hidden, how):
        """The numbers over the tokens of the sequences `hidden` holds: the
        served ones (None: all of the sample) or those that `hidden` puts
        first."""
        gap, top_len = [], 0
        for i, r in enumerate(pick[:len(hidden or pick)]):
            n, start = len(r["tokens"]), len(r["prompt"]) - 1
            logits = head(full[i], jnp.int32(start), None)
            if hidden is None:
                first = jnp.zeros((span,), jnp.int32).at[:n].set(
                    jnp.asarray(r["tokens"], jnp.int32))
            else:
                first = jnp.argmax(head(hidden[i], jnp.int32(start), how),
                                   axis=-1).astype(jnp.int32)
            gap.append(np.asarray(ref.gaps(logits, first))[:n])
            top_len = max(top_len, start + n + 1)
        gap = np.concatenate(gap)
        return dict(gap_numbers(gap), sampled_tokens=float(gap.size),
                    longest_sampled=float(top_len))

    numbers, varied = read(None, None), {}
    for name, quant, fault, requests in variants:
        low = ref.hidden_of(cfg, words, seqs[:requests], span, quant=quant,
                            fault=fault, longest=longest)[1]
        varied[name] = read(low, quant)
        del low
    return numbers, varied, pick


def run(ctx):
    import faulthandler

    import paddle_tpu.observability as obs
    faulthandler.enable()       # a crash under the program names its frame

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
    summary = engine.shutdown(drain=False)
    lost = engine.stats()["pages"]["lost"]
    peak = common.memory_peak_bytes() if ctx["on_chip"] else 0
    lat = latency_numbers(records, t0, ctx["seconds"], cutoff)
    failed_any = sum(r["failed"] for r in records)
    failed = sum(r["failed"] and r["measured"] for r in records)
    sent = sum(r["measured"] for r in records)
    short = sum(1 for r in records if r["state"] == "completed"
                and len(r["tokens"]) != r["max_new"])
    common.log(f"serve_afmoe: window closed, {sent} sent in it, "
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
    numbers, _, _ = check_sample(ctx, records)
    common.log(f"serve_afmoe: reference took "
               f"{time.perf_counter() - t_ref:.1f}s")
    numbers["requests_failed"] = float(failed_any)
    numbers["length_mismatch"] = float(short)
    numbers["pages_leaked_or_lost"] = float(summary["pages_leaked"] + lost)

    e2e = {"serve_tokens_per_s": lat["tokens_in_window"] / ctx["seconds"]}
    sz = sizes(cfg)
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
        "serve_afmoe": sz,
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
    rate per seed, new weights per seed in the same engine; then the engine
    is freed (the references need its room) and each seed's requests are put
    under the reference. The control (the plain reference in int8) is read
    on the first `n_control` seeds and each planted fault
    (`reference.afmoe.FAULTS`: the reference computed with the fault and put
    in the program's place) on the first, over the longest sampled request
    and the next `FAULT_REQUESTS - 1`, at the positions of the same prompts
    and tokens."""
    from ..reference import afmoe as ref
    cfg, mix = ctx["config"], ctx["traffic"]
    engine, model = build(dict(ctx, seed=seeds[0]))
    warm_up(engine, dict(ctx, seed=seeds[0]), mix)
    served = []
    for k, seed in enumerate(seeds):
        if k:
            assign(model, cfg, common.seed_words(seed),
                   cfg["engine"]["dtype"])
        reqs = traffic.open_loop(mix, ctx["seconds"], seed, cfg["vocab_size"])
        lead, lead_s = lead_in(mix, seed, cfg["vocab_size"])
        records, t0, cutoff, _ = window(engine, reqs, ctx["seconds"], 60.0,
                                        None, lead, lead_s)
        for r in records:       # let the longest requests finish
            h = r.pop("handle")
            if h is not None:
                try:
                    h.result(timeout=300)
                except Exception as e:      # noqa: BLE001
                    r["error"] = repr(e)
            r["tokens"] = list(h.tokens) if h is not None else []
            r["state"] = h.state if h is not None else "refused"
        lat = latency_numbers(records, t0, ctx["seconds"], cutoff)
        held = {"requests_failed": float(sum(
                    r["state"] != "completed" for r in records)),
                "length_mismatch": float(sum(
                    len(r["tokens"]) != r["max_new"] for r in records
                    if r["state"] == "completed")),
                "pages_leaked_or_lost": float(
                    engine.stats()["pages"]["lost"])}
        served.append((seed, records, held,
                       lat["tokens_in_window"] / ctx["seconds"]))
    engine.shutdown(drain=False)
    del engine, model
    gc.collect()
    for k, (seed, records, held, rate) in enumerate(served):
        variants = [("control_int8", "int8", None, SAMPLE_REQUESTS)] \
            if k < n_control else []
        if k == 0:
            variants += [("fault_" + f, None, f, FAULT_REQUESTS)
                         for f in ref.FAULTS]
        program, varied, _ = check_sample(dict(ctx, seed=seed), records,
                                          variants)
        row = {"seed": seed, "program": dict(program, **held),
               "sent": len(records), "tokens_per_s": rate,
               "completed": sum(r["state"] == "completed" for r in records)}
        row.update({name: dict(v, **held) for name, v in varied.items()})
        emit(row)
