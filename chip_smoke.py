#!/usr/bin/env python
"""Drive the two main paths once on the chip, in this one process.

    python chip_smoke.py            # one chip: phases `train` and `serve`
    python chip_smoke.py --chips 4  # four chips: phase `hybrid` only

`train`  GPT-2 124M (12 layers, hidden 768, 12 heads, vocab 50257 padded to
         50304, sequence 1024, batch 4), bf16 AMP + AdamW, through
         `paddle.jit.to_static`.
`serve`  `paddle_tpu.serving.LLMEngine` over Llama at the `llama3_8b()` widths
         in bf16, depth cut to what the chip's memory holds beside a KV pool
         of 16k positions; greedy tokens against `model.generate`.
`hybrid` `fleet` ZeRO-3 x mp (mp_degree=2, sharding_degree=2) over the same
         Llama widths on four chips, first-step loss against the dense
         single-device forward of the same weights.

One JSON line per phase, then the result line. Exit code 0 only when every
phase passed on a TPU. There is no CPU fallback: without an accelerator the
script fails. `--tiny` runs the same control flow at toy widths so that it
can be rehearsed on the CPU; it still ends `ok: false` there.
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import re
import sys
import time
import traceback

#: a greedy token may differ from the reference's only where the reference's
#: own logits call it a tie: bf16 logits near the maximum of a 128k-way
#: random-weight distribution are spaced 2^-5 apart, and two bf16 paths
#: through the same layers differ by a few of those steps
NEAR_TIE = 0.125


def emit(obj) -> None:
    print(json.dumps(obj), flush=True)


def device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def kernels_in(hlo: str) -> dict:
    """{kernel: count} of the `tpu_custom_call`s in an optimized HLO text,
    named by the innermost jitted wrapper that issued the `pallas_call`."""
    found = collections.Counter()
    for line in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="([^"]*)"', line)
        scopes = re.findall(r"jit\(([^)]+)\)", m.group(1)) if m else []
        found[scopes[-1] if scopes else "pallas_call"] += 1
    return dict(found)


def cached_hlo(static_fn) -> str:
    """Optimized HLO of every program a `to_static` function has compiled."""
    return "\n".join(static_fn.compiled_text_cached())


#: persistent compile-cache traffic of this process, by jax's own events
CACHE_EVENTS = collections.Counter()


def count_cache_events() -> None:
    import jax.monitoring

    def on_event(event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            CACHE_EVENTS[event.rsplit("/", 1)[1]] += 1

    jax.monitoring.register_event_listener(on_event)


def check(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


# -- phase: train -------------------------------------------------------------

def phase_train(tiny: bool, seed: int) -> dict:
    import functools

    import numpy as np

    import paddle_tpu as paddle
    import paddle_tpu.observability as obs
    from paddle_tpu.models import GPT, GPTConfig

    on_tpu = device_info()["platform"] == "tpu"
    if tiny:
        cfg = GPTConfig(vocab_size=1024, max_position_embeddings=128,
                        hidden_size=128, num_layers=2, num_heads=4)
        batch, seq = 2, 128
    else:
        cfg = GPTConfig(vocab_size=50304, max_position_embeddings=1024,
                        hidden_size=768, num_layers=12, num_heads=12)
        batch, seq = 4, 1024
    paddle.seed(seed)
    model = GPT(cfg)
    opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters(),
                                 weight_decay=0.1, multi_precision=True)
    model, opt = paddle.amp.decorate(model, opt, level="O2", dtype="bfloat16")
    ids = np.random.default_rng(seed).integers(0, cfg.vocab_size,
                                               (batch, seq + 1))
    x = paddle.to_tensor(ids[:, :-1].astype(np.int32))
    y = paddle.to_tensor(ids[:, 1:].astype(np.int32))

    @functools.partial(paddle.jit.to_static, donate_state=on_tpu)
    def train_step(x, y):
        _, loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    t0 = time.perf_counter()
    warm = [float(train_step(x, y)) for _ in range(3)]  # eager, compile, run
    t_warm = time.perf_counter() - t0
    retr0 = obs.total("paddle_tpu_jit_trace_cache_retraces_total")
    comp0 = obs.total("paddle_tpu_jit_compiles_total")
    t0 = time.perf_counter()
    losses = [float(train_step(x, y)) for _ in range(5)]
    t_steps = time.perf_counter() - t0
    retraces = int(obs.total("paddle_tpu_jit_trace_cache_retraces_total")
                   - retr0)
    compiles = int(obs.total("paddle_tpu_jit_compiles_total") - comp0)
    kernels = kernels_in(cached_hlo(train_step))

    out = {"phase": "train", "model": "gpt2_124m" if not tiny else "gpt2_tiny",
           "layers": cfg.num_layers, "hidden": cfg.hidden_size,
           "vocab": cfg.vocab_size, "batch": batch, "seq": seq,
           "params": int(model.num_params()),
           "warmup_losses": warm, "losses": losses,
           "retraces_after_warmup": retraces,
           "compiles_after_warmup": compiles,
           "tpu_custom_calls": kernels,
           "warmup_seconds": round(t_warm, 2),
           "steps_seconds": round(t_steps, 3)}
    check(all(np.isfinite(warm + losses)), f"non-finite loss: {warm + losses}")
    check(losses[-1] < warm[0] and losses[-1] < losses[0],
          f"loss did not fall: {warm} -> {losses}")
    check(retraces == 0 and compiles == 0,
          f"{retraces} retraces / {compiles} compiles after warm-up")
    if on_tpu:
        check(kernels, "no tpu_custom_call in the compiled train step")
    return out


# -- phase: serve -------------------------------------------------------------

#: KV pool positions the serve phase must hold beside the weights
POOL_POSITIONS = 16384


def serve_depth(cfg, page_size: int) -> int:
    """Most decoder layers the device holds in bf16 beside the embedding,
    the head and a POOL_POSITIONS KV pool, leaving a quarter of the memory
    for programs, activations and the reference's own caches."""
    import jax
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    h, m = cfg.hidden_size, cfg.intermediate_size
    kv = cfg.num_kv_heads * cfg.head_dim
    per_layer = 2 * (2 * h * h + 2 * h * kv + 3 * h * m + 2 * h)
    # the pool is rewritten functionally each step: input and output live
    pool_per_layer = 2 * 2 * (POOL_POSITIONS + page_size) * kv * 2
    fixed = 2 * (2 * cfg.vocab_size * h + h)
    return int(min(cfg.num_layers,
                   (0.75 * limit - fixed) // (per_layer + pool_per_layer)))


def phase_serve(tiny: bool, seed: int) -> dict:
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.models.llama import Llama, LlamaConfig
    from paddle_tpu.serving import LLMEngine, ServingConfig

    on_tpu = device_info()["platform"] == "tpu"
    page_size = ServingConfig.page_size
    if tiny:
        cfg = LlamaConfig(vocab_size=512, max_position_embeddings=128,
                          hidden_size=64, num_layers=2, num_heads=4,
                          num_kv_heads=2, intermediate_size=128)
        num_pages, dtype = 64, "float32"
    else:
        cfg = LlamaConfig()                      # llama3_8b() widths
        cfg.num_layers = serve_depth(cfg, page_size)
        num_pages, dtype = POOL_POSITIONS // page_size + 1, "bfloat16"
    check(cfg.num_layers >= 1, "no decoder layer fits this device")

    # `Llama(cfg)` initialises every parameter in float32, which at these
    # widths exhausts the chip before any cast: build the structure under
    # LazyGuard, then materialise and cast one parameter at a time
    paddle.seed(seed)
    with paddle.LazyGuard():
        model = Llama(cfg)
    for p in model.parameters():
        p.initialize()
        p._data = p._data.astype(dtype)
    model.astype(dtype)             # the layers' own record of their dtype
    model.eval()

    new_tokens = 12
    rng = np.random.default_rng(seed)
    lengths = (5, 7, 12, 15)       # buckets 8 and 16; all cross position 16
    prompts = [[int(t) for t in rng.integers(1, cfg.vocab_size, n)]
               for n in lengths]

    t0 = time.perf_counter()
    eng = LLMEngine(model, ServingConfig(num_pages=num_pages, dtype=dtype,
                                         max_new_tokens=new_tokens))
    try:
        reqs = [eng.submit(p) for p in prompts]
        got = [r.result(timeout=900) for r in reqs]
        t_engine = time.perf_counter() - t0
        stats = eng.stats()
        kernels = {name: kernels_in(cached_hlo(sf)) for name, sf in
                   (("decode", eng._decode_sf), ("prefill", eng._prefill_sf))}
    finally:
        eng.shutdown()
    # by group of the cache (a model with window layers has two)
    groups = eng.stats()["pages"]["groups"]
    leaked = sum(g["used"] for g in groups.values())
    lost = sum(g["lost"] for g in groups.values())

    t0 = time.perf_counter()
    ref = []
    for p in prompts:
        full = model.generate(np.asarray([p]), max_new_tokens=new_tokens)
        ref.append([int(t) for t in full[0, len(p):]])
    t_ref = time.perf_counter() - t0
    exact = sum(g == r for g, r in zip(got, ref))
    agree = [next((k for k in range(min(len(g), len(r))) if g[k] != r[k]),
                  len(g)) for g, r in zip(got, ref)]
    margins = greedy_margins(model, prompts, got)

    programs = stats["programs"]
    out = {"phase": "serve", "model": "llama3_8b widths" if not tiny
           else "llama_tiny", "depth": cfg.num_layers,
           "depth_published": LlamaConfig.num_layers,
           "hidden": cfg.hidden_size, "heads": cfg.num_heads,
           "kv_heads": cfg.num_kv_heads, "intermediate": cfg.intermediate_size,
           "vocab": cfg.vocab_size, "dtype": dtype,
           "params": int(model.num_params()),
           "pool_positions": (num_pages - 1) * page_size,
           "requests": len(prompts), "prompt_lengths": list(lengths),
           "new_tokens": new_tokens, "completed": stats["completed"],
           "token_exact_requests": exact, "tokens_agreeing": agree,
           "greedy_margins": [round(m, 4) for m in margins],
           "engine_tokens": got, "reference_tokens": ref,
           "decode_steps": stats["decode_steps"], "programs": programs,
           "pages_leaked": leaked, "pages_lost": lost,
           "page_groups": sorted(groups),
           "tpu_custom_calls": kernels,
           "engine_seconds": round(t_engine, 2),
           "reference_seconds": round(t_ref, 2)}
    if on_tpu:
        out["peak_bytes_in_use"] = \
            jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    check(all(len(g) == new_tokens for g in got) and
          stats["completed"] == len(prompts), f"not all completed: {got}")
    # token-equal to model.generate, except where bf16 leaves a tie: every
    # engine token must be (within NEAR_TIE of) the dense forward's argmax
    check(max(margins) <= NEAR_TIE and min(agree) >= 1,
          f"engine tokens {got} are not greedy (margins {margins}; "
          f"model.generate gave {ref})")
    check(programs["decode"]["compiles"] == 1 and
          programs["decode"]["retraces"] == 0,
          f"decode program compiled more than once: {programs['decode']}")
    check(programs["prefill"]["compiles"] == 2,
          f"expected two prefill buckets: {programs['prefill']}")
    check(leaked == 0 and lost == 0, f"pages leaked {leaked} lost {lost}")
    if on_tpu:
        path = programs["decode"]["path"]
        check(path.get("attention") == "paged_mmha_decode" and
              path.get("junction") == "block_decode_epilogue",
              f"decode took a composite path: {path}")
        check(len(kernels["decode"]) >= 2 and kernels["prefill"],
              f"kernels missing from the serving programs' HLO: {kernels}")
    return out


def greedy_margins(model, prompts, got):
    """How far each engine token is from greedy under the plain dense
    forward: per request, max over its tokens of (largest logit - the
    chosen token's logit) at that token's position, teacher-forced on the
    engine's own sequence. One forward for all requests, right-padded
    (causal attention keeps padding out of every real position). 0.0 means
    every token is the dense forward's argmax."""
    import numpy as np

    import paddle_tpu as paddle

    seqs = [p + g for p, g in zip(prompts, got)]
    width = -(-max(len(s) for s in seqs) // 32) * 32
    ids = np.zeros((len(seqs), width), np.int64)
    for n, seq in enumerate(seqs):
        ids[n, :len(seq)] = seq
    with paddle.no_grad():
        logits = np.asarray(
            model(paddle.to_tensor(ids)).cast("float32").numpy())
    margins = []
    for n, (p, g) in enumerate(zip(prompts, got)):
        rows = logits[n, len(p) - 1:len(p) - 1 + len(g)]
        margins.append(float(np.max(rows.max(-1) - rows[np.arange(len(g)),
                                                        g])))
    return margins


# -- phase: hybrid (four chips) -----------------------------------------------

def phase_hybrid(tiny: bool, seed: int) -> dict:
    import jax
    import numpy as np

    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet import DistributedStrategy, fleet
    from paddle_tpu.models.llama import Llama, LlamaConfig, llama_for_pipeline
    from paddle_tpu.ops.kernels import _common as kern

    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs four devices, found {len(devs)}")
    on_tpu = devs[0].platform == "tpu"
    if tiny:
        cfg = LlamaConfig(vocab_size=512, max_position_embeddings=64,
                          hidden_size=64, num_layers=2, num_heads=4,
                          num_kv_heads=2, intermediate_size=128)
        batch, seq = 2, 32
    else:
        # f32 AdamW costs ~14-16 B/parameter (weight 4, grad 4, moments 8).
        # The mp-sharded weights (most of the model) split two ways, not
        # four, so one decoder layer + embedding + head = 1.27B parameters
        # is ~10 GB of each chip's 16 GB
        cfg = LlamaConfig(num_layers=1)
        batch, seq = 2, 512
    ids = np.random.default_rng(seed).integers(0, cfg.vocab_size,
                                               (batch, seq + 1))
    x = paddle.to_tensor(ids[:, :-1].astype(np.int32))
    y = paddle.to_tensor(ids[:, 1:].astype(np.int64))

    timeline = {}

    def mark(stage):                      # GB in use on the fullest device
        if on_tpu:
            timeline[stage] = round(max(
                d.memory_stats()["bytes_in_use"] for d in devs) / 1e9, 2)
            print(f"hybrid: {stage}: {timeline[stage]} GB on the fullest "
                  "device", file=sys.stderr, flush=True)

    # the dense reference: the plain model on ONE device, before any mesh
    # exists; its weights then become the hybrid model's
    paddle.seed(seed)
    dense = Llama(cfg)
    with paddle.no_grad():
        ref_loss = float(dense(x, labels=y)[1])
    mark("dense")

    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 2, "pp_degree": 1,
                               "sharding_degree": 2, "sep_degree": 1}
    strategy.sharding_configs = {"stage": 3}
    fleet.init(is_collective=True, strategy=strategy)
    net = llama_for_pipeline(cfg, seq_len=seq, num_stages=1)
    model = fleet.distributed_model(net)
    embed, *blocks, head = net.run_function
    twins = [(embed.embed_tokens, dense.embed_tokens), (head.norm, dense.norm),
             (head.lm_head, dense.lm_head)] + \
        [(blk.block, layer) for blk, layer in zip(blocks, dense.layers)]
    for dst, src in twins:
        src_params = dict(src.named_parameters())
        for name, p in dst.named_parameters():
            p._data = jax.device_put(src_params[name]._d, p._d.sharding)
    del dense, twins, src_params, src, dst
    gc.collect()            # layers hold cycles: free the dense copy now
    mark("sharded")

    opt = fleet.distributed_optimizer(
        paddle.optimizer.AdamW(1e-4, parameters=model.parameters()))
    params = list(net.parameters())
    spread = {}
    for p in params:
        spec = tuple(a for a in (p._sharding_spec or ()) if a)
        want = 2 ** sum(a in ("mp", "sharding") for a in spec)
        have = len({s.device for s in p._data.addressable_shards
                    if s.replica_id == 0})
        spread[(spec, have)] = spread.get((spec, have), 0) + 1
        check(len(p._data.sharding.device_set) == 4 and have == want,
              f"parameter {tuple(p.shape)} with spec {spec} holds distinct "
              f"shards on {have} devices, expected {want}")
    check(any(h > 1 for _, h in spread), "no parameter is sharded")

    loss_t = net._loss_fn(model(x), y)
    loss0 = float(loss_t)
    mark("forward")
    loss_t.backward()
    mark("backward")
    opt.step()
    opt.clear_grad()
    mark("step")
    with paddle.no_grad():
        loss1 = float(net._loss_fn(model(x), y))
    for p in params:                      # the update kept every layout
        declared = jax.sharding.NamedSharding(p._d.sharding.mesh,
                                              p._sharding_spec)
        check(p._d.sharding.is_equivalent_to(declared, p._d.ndim),
              f"parameter {tuple(p.shape)} left its layout: "
              f"{p._d.sharding.spec} != {p._sharding_spec}")

    in_use = [d.memory_stats()["bytes_in_use"] for d in devs] if on_tpu \
        else None
    out = {"phase": "hybrid", "mesh": "mp2 x sharding2 (ZeRO-3)",
           "model": "llama3_8b widths" if not tiny else "llama_tiny",
           "depth": cfg.num_layers, "hidden": cfg.hidden_size,
           "vocab": cfg.vocab_size, "batch": batch, "seq": seq,
           "params": int(sum(p.size for p in params)),
           "loss": loss0, "dense_loss": ref_loss, "loss_after_step": loss1,
           "sharding": [{"spec": list(s), "devices": h, "params": n}
                        for (s, h), n in sorted(spread.items())],
           # Mosaic kernels cannot be partitioned automatically: under the
           # mesh the XLA composites run (ops.kernels._common.partitioned)
           "pallas_kernels_dispatch": kern.available(),
           "peak_gb_in_use": timeline, "bytes_in_use": in_use}
    check(np.isfinite([loss0, loss1]).all(), f"non-finite: {loss0}, {loss1}")
    check(abs(loss0 - ref_loss) <= 1e-2 * max(1.0, abs(ref_loss)),
          f"hybrid loss {loss0} != dense {ref_loss}")
    check(loss1 < loss0, f"loss did not fall after a step: {loss0} -> {loss1}")
    if on_tpu:
        check(all(b > 0 for b in in_use), f"an idle device: {in_use}")
    return out


# -- driver -------------------------------------------------------------------

def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="toy widths, to rehearse the control flow on the CPU")
    args = ap.parse_args()

    device = device_info()
    if device["platform"] != "tpu" and not args.tiny:
        emit({"ok": False, "device": device,
              "error": "no TPU attached: chip_smoke.py has no CPU path"})
        return 1
    if device["count"] != args.chips:
        emit({"ok": False, "device": device,
              "error": f"--chips {args.chips} but jax sees {device['count']}"})
        return 1

    phases = (phase_hybrid,) if args.chips == 4 else (phase_train, phase_serve)
    count_cache_events()
    ok = True
    for phase in phases:
        name = phase.__name__.removeprefix("phase_")
        t0 = time.perf_counter()
        CACHE_EVENTS.clear()
        try:
            line = dict(phase(args.tiny, args.seed), ok=True)
        except Exception as e:
            traceback.print_exc()
            line = {"phase": name, "ok": False,
                    "error": f"{type(e).__name__}: {e}"[:2000]}
            ok = False
        emit(dict(line, platform=device["platform"],
                  device_kind=device["kind"], device_count=device["count"],
                  compile_cache=dict(CACHE_EVENTS),
                  seconds=round(time.perf_counter() - t0, 2)))
    ok = ok and device["platform"] == "tpu"
    emit({"ok": ok, "device": device})
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
