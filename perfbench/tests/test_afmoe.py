"""The AFMoE driver at a tiny preset (`data/configs/afmoe-tiny.json`: window
8, page 4, 8 experts top-2 + 1 shared, five layers): a sound run is correct, a
run with a fault planted in the program is not, the int8 control fails, and
the work counts give hand-computed numbers. These belong beside
`test_faults.py` and `test_control.py`; they stand in a file of their own
because a PR that adds a cell may edit no file the benchmark has.
"""

import json
import os
import time
from unittest import mock

import numpy as np
import pytest

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CELL = "afmoe-tiny.chat_tiny"
BENCH = {
    "workloads": [{"name": CELL, "config": "afmoe-tiny",
                   "traffic": "chat_tiny", "chips": 1}],
    "configs": [{"name": "afmoe-tiny",
                 "file": "perfbench/tests/data/configs/afmoe-tiny.json"}],
    "end_to_end": [{"name": "serve_tokens_per_s", "unit": "tokens/s"},
                   {"name": "setup_s", "unit": "s"}],
    "per_layer": [],
}


def run(seed, seconds=2.0):
    from perfbench import run as bench_run
    return bench_run.run_cell(BENCH, CELL, seed, seconds, False,
                              require_chip=False,
                              t_start=time.perf_counter())


PATCHES = []


@pytest.fixture(autouse=True)
def _stop_patches():
    yield
    while PATCHES:
        PATCHES.pop().stop()


def _failed(result):
    return [r["name"] for r in result["compared"]
            if r["limit"] is not None and not r["value"] <= r["limit"]]


def test_sound_run_is_correct():
    result = run(2 ** 31 + 11)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    longest = next(r["value"] for r in result["compared"]
                   if r["name"] == "longest_sampled")
    assert longest > 8          # longer than the window


def _no_window(model):
    """The window layers attend without the lower bound, in prefill and in
    decode (where what they then read behind the window has been freed)."""
    from paddle_tpu.nn import functional as F
    from paddle_tpu.serving import kv_cache
    sdpa, paged = F.scaled_dot_product_attention, kv_cache.paged_attention
    for target, name, real in ((F, "scaled_dot_product_attention", sdpa),
                               (kv_cache, "paged_attention", paged)):
        patch = mock.patch.object(
            target, name, lambda *a, real=real, **kw: real(
                *a, **dict(kw, window=None)))
        patch.start()
        PATCHES.append(patch)


def _rope_on_global(model):
    model.layers[-1].self_attn.use_rope = True


def _no_bias(model):
    for layer in model.layers[1:]:
        layer.mlp.expert_bias._data = layer.mlp.expert_bias._data * 0


def _no_route_scale(model):
    for layer in model.layers[1:]:
        layer.mlp.route_scale = 1.0


def _no_shared(model):
    for layer in model.layers[1:]:
        w = layer.mlp.shared_experts.down_proj.weight
        w._data = w._data * 0


def _drop_over_2x(model):
    """The tokens past twice an expert's mean load get no expert, as a
    capacity would have it."""
    import jax
    import jax.numpy as jnp
    for layer in model.layers[1:]:
        moe = layer.mlp
        real = moe.route

        def route(tokens, rw, bias, real=real, e=moe.n_experts, k=moe.top_k):
            sel, w = real(tokens, rw, bias)
            onehot = jax.nn.one_hot(sel.reshape(-1), e, dtype=jnp.int32)
            order = (jnp.cumsum(onehot, 0) * onehot).sum(-1).reshape(
                sel.shape)
            cap = max(1, 2 * sel.shape[0] * k // e)
            return jnp.where(order <= cap, sel, e), w

        moe.route = route


@pytest.mark.parametrize("plant", [_no_window, _rope_on_global, _no_bias,
                                   _no_route_scale, _no_shared,
                                   _drop_over_2x])
def test_planted_fault_fails(plant):
    # the fault goes in once the weights are there (`build` ends in
    # `model.eval()`) and before the engine traces anything
    from paddle_tpu.models import afmoe
    made = afmoe.Afmoe.eval

    def eval_then_plant(self):
        out = made(self)
        plant(self)
        return out

    with mock.patch.object(afmoe.Afmoe, "eval", eval_then_plant):
        result = run(7)
    assert not result["correct"]
    assert set(_failed(result)) & {"logit_gap_mean", "top1_miss_share",
                                   "logit_gap_max"}, result["compared"]


def test_control_int8_fails():
    """The reference computed in int8 and put in the program's place is not
    correct by the tiny cell's limits, on every seed."""
    import jax.numpy as jnp

    from perfbench.drivers import serve_afmoe as drv
    from perfbench.harness import common, compare
    from perfbench.reference import afmoe as ref
    with open(os.path.join(DATA, "configs", "afmoe-tiny.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(DATA, "limits", CELL + ".json")) as f:
        limits = {k: v for k, v in json.load(f).items()
                  if k.startswith("logit_gap") or k == "top1_miss_share"}
    for seed in (1, 2, 3):
        rng = np.random.default_rng(seed)
        seqs = [rng.integers(1, cfg["vocab_size"], 120).tolist()
                for _ in range(3)]
        words = common.seed_words(seed)
        full = ref.logits_of(cfg, words, seqs, [0] * 3, 128)
        low = ref.logits_of(cfg, words, seqs, [0] * 3, 128, quant="int8")
        gaps = np.concatenate([np.asarray(ref.gaps(
            f, jnp.argmax(q, axis=-1).astype(jnp.int32)))[:120]
            for f, q in zip(full, low)])
        correct, rows = compare.judge(drv.gap_numbers(gaps), limits)
        assert not correct, rows


# -- the work counts against numbers worked out by hand ----------------------

SIZES = {"attention": 100, "dense_mlp": 50, "expert": 30, "shared": 30,
         "router": 8, "head": 400, "layers": 5, "dense_layers": 1,
         "routed_layers": 4, "window_layers": 4, "global_layers": 1,
         "window": 8, "q_width": 64, "hidden": 10, "experts": 8, "top_k": 2,
         "kv_bytes_per_position_layer": 128}


def _span(name, t0, t1, **counts):
    return {"name": name, "t_start": t0, "t_end": t1, "span_id": id(counts),
            "parent_id": None, "attributes": {}, "counts": counts}


@pytest.fixture
def observed(monkeypatch):
    from perfbench.work import afmoe_spans
    spans = [
        _span("serving.decode", 1.0, 1.1, rows=3, positions=100,
              positions_window=20, experts_hit=10, experts_total=32),
        _span("serving.prefill", 1.2, 1.5, tokens=40, bucket=64,
              experts_hit=30, experts_total=32),
        _span("serving.decode", 9.0, 9.1, rows=1, positions=7,
              positions_window=7, experts_hit=4, experts_total=32)]
    monkeypatch.setattr(afmoe_spans, "_buffer",
                        lambda: {"spans": spans, "dropped_until": None})
    return {"trace_interval": (0.5, 2.0), "serve_afmoe": SIZES}


def test_moe_grouped_work(observed):
    from perfbench.work import moe_grouped
    # decode: 3 rows x 2 experts x 4 layers = 24 routed rows; prefill 320
    routed = 24 + 320
    flops = 2.0 * routed * 30
    nbytes = 2.0 * (10 + 30) * 30 + 2.0 * 2.0 * routed * 10
    assert moe_grouped.count(observed) == (flops, nbytes)


def test_decode_step_bytes_and_attention_work(observed):
    from perfbench.work import decode_step_bytes_afmoe, mmha_decode_afmoe
    fixed = 2.0 * (5 * 100 + 50 + 4 * (30 + 8) + 400)
    seen = 1 * 100 + 4 * 20         # the one decode step inside the trace
    assert decode_step_bytes_afmoe.count(observed) == (
        0.0, fixed + 2.0 * 10 * 30 + 128 * seen)
    assert mmha_decode_afmoe.count(observed) == (4.0 * seen * 64, 128.0 * seen)


def test_work_counts_read_nothing_without_the_spans(observed, monkeypatch):
    from perfbench.work import (afmoe_spans, decode_step_bytes_afmoe,
                                mmha_decode_afmoe, moe_grouped)
    monkeypatch.setattr(afmoe_spans, "_buffer", lambda: None)
    assert moe_grouped.count(observed) is None
    assert decode_step_bytes_afmoe.count(observed) is None
    assert mmha_decode_afmoe.count(observed) is None


def test_flops_count_caps_the_window_layers():
    from perfbench.drivers import serve_afmoe as drv
    assert drv.keys_seen(5, 8) == 15 and drv.keys_seen(5, None) == 15
    assert drv.keys_seen(12, 8) == 36 + 4 * 8


def test_program_time_share_reads_one_program_alone():
    """The small trace's two programs share the name `jit__lambda`: cut to
    that name the share is over those programs' own operations; a name that
    nothing ran under reads nothing, and an instruction the tables differ
    about leaves the program undecided."""
    from perfbench.harness import trace
    from perfbench.readers import program_time_share
    red = trace.reduce(os.path.join(DATA, "small.xplane.pb"))
    tables = {"jit__lambda": {"dropped": 0, "variants": [
        {"fusion": "jit(_lambda)/mlp/moe_experts/dot_general"},
        {"multiply_add_fusion": "jit(_lambda)/mlp/moe_route/mul"}]}}
    obs = {"trace": red}

    def read(patterns, program="jit__lambda"):
        return program_time_share.read(obs, patterns, program, tables=tables)

    mm, _ = trace.time_by_pattern(red["ops"], [r"^fusion$"])
    inside = sum(e - s for s, e in trace.union(red["ops"]))
    assert read(["/moe_experts/"]) == pytest.approx(100.0 * mm / inside,
                                                    rel=1e-6)
    assert 0.0 < read(["/moe_route/"]) < 100.0 - read(["/moe_experts/"])
    assert read(["/moe_shared/"]) is None
    assert read(["/moe_experts/"], program="serving_decode_step") is None
    assert program_time_share.read(obs, ["/moe_experts/"], "jit__lambda",
                                   tables={}) is None
    tables["jit__lambda"]["variants"][1]["fusion"] = \
        "jit(_lambda)/attention/dot_general"
    assert read(["/moe_experts/"]) is None


@pytest.mark.parametrize("n,longest,want", [
    (100, 0, 512), (513, 0, 1024),              # no cap named: query blocks
    (1500, 18432, 2048), (2049, 18432, 4096), (9000, 18432, 16384),
    (16500, 18432, 18432), (18432, 18432, 18432)])
def test_reference_pads_to_a_few_lengths(n, longest, want):
    from perfbench.reference import afmoe as ref
    assert ref.padded_len(n, longest) == want


def test_gap_numbers_by_hand():
    from perfbench.drivers import serve_afmoe as drv
    g = np.asarray([0.0, 0.0, 0.5, 0.0, 1.5, 0.0, 0.0, 0.0, 0.0, 0.0])
    assert drv.gap_numbers(g) == {"logit_gap_mean": 0.2,
                                  "top1_miss_share": 0.2,
                                  "logit_gap_max": 1.5}
