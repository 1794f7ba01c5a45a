"""`correct` has to come out false when the timed path is broken underneath.

Each test drives a whole run of a tiny cell (everything but the look for a
chip) with one fault planted in the program: a step that leaves its state
unchanged; half of the batch left out, the mean taken over the rest; a token
altered where it is produced. The training faults are planted in the compiled
step alone (`in_to_static_trace()`): the eager discovery call, which no
window times, stays sound, and the numbers still fail, since all three steps
compared are calls of the compiled program. The exchange between chips does not exist in a one-chip cell. A
sound run of the same cells comes out true.
"""

from unittest import mock

import pytest

from perfbench.tests import cells

TRAIN, SERVE = "gpt2-tiny.pretrain_tiny", "llama-tiny.chat_tiny"


def _failed(result):
    return [r["name"] for r in result["compared"]
            if r["limit"] is not None and not r["value"] <= r["limit"]]


@pytest.mark.parametrize("workload,seconds", [(TRAIN, 0.5), (SERVE, 2.0)])
def test_sound_run_is_correct(workload, seconds):
    result = cells.run(workload, 2 ** 31 + 11, seconds)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0


def test_state_left_unchanged_in_the_compiled_step_fails():
    from paddle_tpu.jit.api import in_to_static_trace
    from paddle_tpu.optimizer.optimizers import Adam
    real = Adam._append_optimize_op

    def frozen(self, p, grad):
        if not in_to_static_trace():
            return real(self, p, grad)
        master = self._get_master(p)
        held = [t for t in (p, master) if t is not None] + [
            self._accumulators[name][id(p)]
            for name in ("moment1", "moment2")]
        keep = [t._data for t in held]
        real(self, p, grad)
        for t, data in zip(held, keep):
            t._data = data

    with mock.patch.object(Adam, "_append_optimize_op", frozen):
        result = cells.run(TRAIN, 5, 0.5)
    assert not result["correct"]
    assert {"grad1_norm_gap", "grad2_norm_gap", "grad3_norm_gap",
            "delta_norm_gap"} <= set(_failed(result)), result["compared"]


def test_half_of_the_batch_left_out_of_the_compiled_step_fails():
    from paddle_tpu.jit.api import in_to_static_trace
    from paddle_tpu.models.gpt import GPT
    real = GPT.forward

    def half(self, input_ids, labels=None, **kw):
        if not in_to_static_trace():
            return real(self, input_ids, labels=labels, **kw)
        n = input_ids.shape[0] // 2
        return real(self, input_ids[:n],
                    labels=None if labels is None else labels[:n], **kw)

    with mock.patch.object(GPT, "forward", half):
        result = cells.run(TRAIN, 6, 0.5)
    assert not result["correct"]
    assert {"grad1_norm_gap", "grad2_norm_gap", "grad3_norm_gap"} \
        <= set(_failed(result)), result["compared"]


def test_altered_token_fails():
    import jax.numpy as jnp

    from paddle_tpu.serving.engine import LLMEngine

    def second_best(self, logits, temps, key, step):
        return jnp.argsort(logits, axis=-1)[..., -2].astype(jnp.int32)

    with mock.patch.object(LLMEngine, "_sample", second_best):
        result = cells.run(SERVE, 7, 2.0)
    assert not result["correct"]
    assert "logit_gap_max" in _failed(result)
