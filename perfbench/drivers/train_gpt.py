"""Training cells over `paddle_tpu.models.GPT`: bf16 AMP O2, AdamW with
float32 master weights, the whole step under `paddle.jit.to_static`.

Set-up builds one compiled step with its state. `to_static` runs a new
signature eagerly once (discovery) and compiles it on the second call, so
the loader's first `WARM_STEPS` batches go to those two calls; the state is
then put back to the seed's (weights, masters, no moments, step 0) and the
same object is driven through its first three steps on the next three
batches: three calls of the compiled program, the one the window times, and
the first of them from exactly the weights the reference starts from. After
each step the gradient's norms are read from AdamW's second moment. The
window goes on from there with the same object. The plain reference follows
those three steps once the window has closed and the program's state is
freed.
"""

from __future__ import annotations

import functools
import gc
import time

import numpy as np

from ..harness import common, compare, traffic, weights

TRACE_AFTER_S = 2.0
TRACE_SECONDS = 1.5
IN_FLIGHT = 2           # steps enqueued ahead of the one waited for
MAX_STEPS = 8192        # rows the seeded stream holds, in batches
WARM_STEPS = 2          # batches spent on discovery and compilation


def flops_per_token(cfg, seq):
    """6 x matmul parameters + 12 x layers x hidden x sequence (forward and
    backward, attention counted in full, not halved for the causal mask):
    the convention of PaLM's appendix B. The tied output embedding is a
    matmul and counts; the position table is a lookup and does not."""
    h, L, v = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    matmul_params = L * 12 * h * h + v * h
    return 6.0 * matmul_params + 12.0 * L * h * seq


def run(ctx):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle
    import paddle_tpu.observability as obs
    from paddle_tpu.io import DataLoader, Dataset
    from paddle_tpu.models import GPT, GPTConfig

    cfg, mix, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    batch, seq = int(cfg["assumed"]["batch"]), int(mix["seq_len"])
    id_max = int(mix["id_max"])
    o = cfg["optimizer"]
    sizes = weights.gpt2_sizes(cfg)
    words = common.seed_words(seed)

    # -- the program, its weights from the seed ------------------------------
    gcfg = GPTConfig(vocab_size=cfg["vocab_size"],
                     max_position_embeddings=cfg["n_positions"],
                     hidden_size=cfg["n_embd"], num_layers=cfg["n_layer"],
                     num_heads=cfg["n_head"],
                     layer_norm_epsilon=cfg["layer_norm_epsilon"],
                     initializer_range=cfg["initializer_range"],
                     dropout=0.0, tie_word_embeddings=True)
    with paddle.LazyGuard():
        model = GPT(gcfg)
    named = weights.gpt2_unstack(weights.gpt2_stacked(words, **sizes),
                                 sizes["layers"])
    weights.assign(model.named_parameters(), named.items())
    del named
    common.log(f"train_gpt: weights made at {time.perf_counter() - ctx['t_start']:.1f}s")
    opt = paddle.optimizer.AdamW(o["lr"], beta1=o["beta1"], beta2=o["beta2"],
                                 epsilon=o["epsilon"],
                                 parameters=model.parameters(),
                                 weight_decay=o["weight_decay"],
                                 multi_precision=True)
    model, opt = paddle.amp.decorate(model, opt, level="O2", dtype="bfloat16")
    model.train()

    @functools.partial(paddle.jit.to_static, donate_state=ctx["on_chip"])
    def train_step(x, y):
        _, loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    class Rows(Dataset):
        def __len__(self):
            return batch * MAX_STEPS

        def __getitem__(self, i):
            row = traffic.token_row(seed, i, seq + 1, id_max)
            return row[:-1], row[1:]

    loader = DataLoader(Rows(), batch_size=batch, shuffle=False,
                        drop_last=True,
                        num_workers=int(mix.get("loader_workers", 0)))
    feed = iter(loader)
    params = dict(model.named_parameters())

    @jax.jit
    def sums(arrs):
        return [compare.slice_sums(a) for a in arrs]

    @functools.partial(jax.jit, static_argnames=("names",))
    def delta_norms(arrs, w, names):
        start = weights.gpt2_named(weights.gpt2_stacked(w, **sizes),
                                   sizes["layers"])
        return [compare.slice_norms(
            a.astype(jnp.float32) - start[n].astype(jnp.float32))
            for n, a in zip(names, arrs)]

    def by_leaf(vals):
        """{reference leaf: norms [slices] or [layers, slices]} from the
        program's per-parameter norms."""
        out = {}
        for name, v in zip(params, vals):
            v = np.asarray(v, np.float64)
            parts = name.split(".")
            if parts[0] == "blocks":
                out.setdefault(".".join(parts[2:]), {})[int(parts[1])] = v
            else:
                out[name] = v
        return {k: (np.stack([v[i] for i in range(len(v))])
                    if isinstance(v, dict) else v) for k, v in out.items()}

    # -- discovery and compilation, then back to the seed's state ------------
    for step in range(WARM_STEPS):
        x, y = next(feed)
        float(train_step(x, y))
        common.log(f"train_gpt: warm-up call {step + 1} done at "
                   f"{time.perf_counter() - ctx['t_start']:.1f}s, peak "
                   f"{common.memory_peak_bytes() / 1e9:.2f} GB")
    weights.assign(params.items(), weights.gpt2_unstack(
        weights.gpt2_stacked(words, **sizes), sizes["layers"]).items())
    for p in params.values():
        if id(p) in opt._master_weights:
            opt._master_weights[id(p)]._data = p._data.astype(jnp.float32)
        for name in ("moment1", "moment2"):
            acc = opt._accumulators[name][id(p)]
            acc._data = jnp.zeros_like(acc._data)
    opt._step_count = 0
    opt._step_tensor._data = jnp.zeros_like(opt._step_tensor._data)

    # -- the first three steps, through the window's own call and feed -------
    got = {"losses": [], "grads": []}
    moment2 = None
    for step in range(3):
        x, y = next(feed)
        got["losses"].append(float(train_step(x, y)))
        common.log(f"train_gpt: step {step + 1} loss {got['losses'][-1]:.4f} "
                   f"at {time.perf_counter() - ctx['t_start']:.1f}s, peak "
                   f"{common.memory_peak_bytes() / 1e9:.2f} GB")
        # this step's gradient as the optimizer got it, from its state
        now = by_leaf(sums([opt._accumulators["moment2"][id(p)]._data
                            for p in params.values()]))
        got["grads"].append(compare.grad_norms_from_moment2(
            moment2, now, o["beta2"]))
        moment2 = now
    now = [(opt._master_weights[id(p)] if id(p) in opt._master_weights
            else p)._data for p in params.values()]
    got["delta"] = by_leaf(delta_norms(now, words, tuple(params)))
    del now

    def jit_counts():
        return (obs.total("paddle_tpu_jit_compiles_total"),
                obs.total("paddle_tpu_jit_trace_cache_retraces_total"))

    # -- the window ----------------------------------------------------------
    tracer = ctx["tracer"]
    c0 = jit_counts()
    losses, done_t, waits = [], [], []
    t0 = time.perf_counter()
    ctx["setup_s"] = t0 - ctx["t_start"]
    while True:
        now_t = time.perf_counter()
        if now_t - t0 >= ctx["seconds"]:
            break
        if tracer is not None:
            tracer.tick(now_t - t0, lambda: losses and float(losses[-1]))
        tw = time.perf_counter()
        x, y = next(feed)
        waits.append(time.perf_counter() - tw)
        losses.append(train_step(x, y))
        if len(losses) > IN_FLIGHT:
            float(losses[-1 - IN_FLIGHT])
            done_t.append(time.perf_counter())
    last = float(losses[-1])
    t1 = time.perf_counter()
    if tracer is not None:
        tracer.finish()
    c1 = jit_counts()
    steps = len(losses)
    window = t1 - t0
    peak = common.memory_peak_bytes() if ctx["on_chip"] else 0
    window_losses = [float(v) for v in losses]

    # -- free the program, then the reference --------------------------------
    feed.close()        # ends the loader's worker processes, and waits
    loader = feed = None
    del model, opt, train_step, params, losses, x, y, sums, delta_norms
    gc.collect()
    t_ref = time.perf_counter()
    common.log(f"train_gpt: window {window:.2f}s, {steps} steps; state freed")
    ref = reference_numbers(cfg, mix, seed, batch, seq)
    numbers = compare.training_numbers(got, ref)
    common.log(f"train_gpt: reference took {time.perf_counter() - t_ref:.1f}s; "
               f"losses {got['losses']} vs {ref['losses']}; {numbers}")
    numbers["loss_window_nonfinite"] = float(
        sum(not np.isfinite(v) for v in window_losses + [last]))

    step_ms = [1000.0 * (b - a) for a, b in zip(done_t, done_t[1:])]
    return {
        "attempted": steps, "failed": 0, "numbers": numbers,
        "memory_peak_bytes": peak,
        "end_to_end": {"train_tokens_per_s": steps * batch * seq / window},
        "observed": {
            "window_s": window,
            "flops_per_step": batch * seq * flops_per_token(cfg, seq),
            "counters": {"compiles": (c1[0] - c0[0]) + (c1[1] - c0[1])},
            "spans": {"data_wait": waits, "train_step": [
                v / 1000.0 for v in step_ms]},
            "train": {"batch": batch, "seq": seq, "heads": cfg["n_head"],
                      "head_dim": cfg["n_embd"] // cfg["n_head"],
                      "layers": cfg["n_layer"]},
        },
    }


def reference_numbers(cfg, mix, seed, batch, seq, quant=None, fault=None):
    """Losses, each step's gradient norms and the parameters' change of the
    plain reference over the three batches of `seed` that follow the
    program's warm-up. `quant` is the
    control and `fault` a planted fault (`reference.gpt2.train_steps`)."""
    import jax.numpy as jnp

    from ..reference import gpt2 as ref
    o = cfg["optimizer"]
    stacked = weights.gpt2_stacked(common.seed_words(seed),
                                   **weights.gpt2_sizes(cfg))
    params0 = {k: v.astype(jnp.float32) for k, v in stacked.items()}
    del stacked
    batches = [tuple(jnp.asarray(a) for a in traffic.train_batch(
        seed, s, batch, seq, int(mix["id_max"])))
        for s in range(WARM_STEPS, WARM_STEPS + 3)]
    losses, grads, from_state, delta = ref.train_steps(
        params0, batches, heads=cfg["n_head"],
        eps=cfg["layer_norm_epsilon"], rows=2 if batch % 2 == 0 else 1,
        quant=quant, fault=fault,
        opt=dict(lr=o["lr"], b1=o["beta1"], b2=o["beta2"], eps=o["epsilon"],
                 wd=o["weight_decay"]))
    return {"losses": losses, "grads": grads, "grads_from_state": from_state,
            "delta": {k: np.asarray(v) for k, v in delta.items()}}


#: what `tools/limits.py` reads beside the program: the control (the plain
#: reference with float8_e4m3 matmuls) and the faults a training cell can have
VARIANTS = {
    "control_fp8": dict(quant="fp8"),
    "fault_half_batch": dict(fault="half_batch"),
    "fault_frozen": dict(fault="frozen"),
    "fault_sign": dict(fault="sign"),
}


def limits_readings(ctx, seeds, n_control, emit):
    """For `tools/limits.py`: the program's numbers on every seed, and on
    the first `n_control` the control's and the faults', each judged by the
    cell's limits as a run is."""
    cfg, mix = ctx["config"], ctx["traffic"]
    batch, seq = int(cfg["assumed"]["batch"]), int(mix["seq_len"])
    for k, seed in enumerate(seeds):
        c = dict(ctx, seed=seed, t_start=time.perf_counter(), tracer=None)
        out = run(c)
        row = {"seed": seed, "program": out["numbers"],
               "tokens_per_s": out["end_to_end"]["train_tokens_per_s"],
               "setup_s": c["setup_s"]}
        if k < n_control:
            ref = reference_numbers(cfg, mix, seed, batch, seq)
            for name, how in VARIANTS.items():
                row[name] = compare.training_numbers(compare.as_program(
                    reference_numbers(cfg, mix, seed, batch, seq, **how)),
                    ref)
                row[name]["loss_window_nonfinite"] = 0.0
        emit(row)
        gc.collect()
