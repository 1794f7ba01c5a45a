"""The control has to come out as not correct: the plain reference put in
the program's place and computed in the precision below the configuration's
(float8_e4m3 matmuls for bf16 training, int8 linears for bf16 serving) has to
fail at least one of the cell's numbers. Here at sizes a test run can hold;
on the chip at each cell's own size (tools/limits.py, readings in PERF.md).
"""

import json
import os

import numpy as np
import pytest

from perfbench.harness import compare

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _load(*parts):
    with open(os.path.join(DATA, *parts)) as f:
        return json.load(f)


def _training_cell():
    cfg = _load("configs", "gpt2-tiny.json")
    mix = _load("traffic", "pretrain_tiny.json")
    limits = _load("limits", "gpt2-tiny.pretrain_tiny.json")
    return cfg, mix, limits, cfg["assumed"]["batch"], mix["seq_len"]


def test_training_control_fp8_fails():
    from perfbench.drivers import train_gpt as drv
    cfg, mix, limits, batch, seq = _training_cell()
    for seed in (1, 2, 3):
        ref = drv.reference_numbers(cfg, mix, seed, batch, seq)
        low = drv.reference_numbers(cfg, mix, seed, batch, seq, quant="fp8")
        numbers = compare.training_numbers(compare.as_program(low), ref)
        numbers["loss_window_nonfinite"] = 0.0
        ok, rows = compare.judge(numbers, limits)
        failed = [n for n, v, lim in rows if lim is not None and not v <= lim]
        assert not ok and "grad1_norm_gap" in failed, rows


def test_gradient_norms_read_from_the_state_are_the_gradients():
    """The sound reference's gradient norms, worked out from its second
    moment's sums as the program's are, against those it computed."""
    from perfbench.drivers import train_gpt as drv
    cfg, mix, _, batch, seq = _training_cell()
    ref = drv.reference_numbers(cfg, mix, 4, batch, seq)
    for got, want in zip(ref["grads_from_state"], ref["grads"]):
        gap, leaf = compare.worst_norm_gap(got, want)
        assert gap < 1e-5, (gap, leaf)


@pytest.mark.parametrize("fault", ["frozen", "sign", "half_batch"])
def test_reference_with_a_fault_fails(fault):
    from perfbench.drivers import train_gpt as drv
    cfg, mix, limits, batch, seq = _training_cell()
    ref = drv.reference_numbers(cfg, mix, 5, batch, seq)
    bad = drv.reference_numbers(cfg, mix, 5, batch, seq, fault=fault)
    numbers = compare.training_numbers(compare.as_program(bad), ref)
    numbers["loss_window_nonfinite"] = 0.0
    ok, rows = compare.judge(numbers, limits)
    assert not ok, rows


def test_serving_control_int8_fails():
    import jax.numpy as jnp

    from perfbench.harness import common
    from perfbench.reference import llama as ref
    cfg = _load("configs", "llama-tiny.json")
    limit = _load("limits", "llama-tiny.chat_tiny.json")["logit_gap_max"]
    for seed in (1, 2, 3):
        rng = np.random.default_rng(seed)
        seqs = [rng.integers(1, cfg["vocab_size"], 120).tolist()
                for _ in range(6)]
        words = common.seed_words(seed)
        full = ref.logits_of(cfg, words, seqs, [0] * 6, 128)
        low = ref.logits_of(cfg, words, seqs, [0] * 6, 128, quant="int8")
        worst = max(float(np.max(np.asarray(ref.gaps(
            f, jnp.argmax(q, axis=-1).astype(jnp.int32)))))
            for f, q in zip(full, low))
        assert worst > limit, worst
