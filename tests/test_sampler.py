"""`LLMEngine._sample`: a step whose rows are all greedy draws no noise (the
draw stands behind a `lax.cond`), a step with a sampling row draws it in
float32, and the scheduler counts the sampling rows on its step span."""

import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.serving import LLMEngine, ServingConfig
from paddle_tpu.serving.speculative import scaled_filtered_logits


def _sample(logits, temps, key, step, top_k=None):
    me = types.SimpleNamespace(config=types.SimpleNamespace(top_k=top_k))
    return LLMEngine._sample(me, logits, temps, key, step)


def _key(seed):
    import jax
    return np.asarray(jax.random.PRNGKey(seed), np.uint32)


def _logits(shape, dtype, seed=0):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.standard_normal(shape) * 3.0, dtype)


@pytest.mark.parametrize("top_k", [None, 8])
@pytest.mark.parametrize("shape", [(1, 96), (5, 96)])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_greedy_step_is_the_argmax(dtype, shape, top_k):
    import jax
    import jax.numpy as jnp
    logits = _logits(shape, dtype)
    temps = jnp.zeros(shape[0], jnp.float32)
    fn = jax.jit(lambda *a: _sample(*a, top_k=top_k))
    for step in (0, 7):
        out = fn(logits, temps, _key(3), jnp.int32(step))
        assert out.dtype == jnp.int32 and out.shape == shape[:1]
        np.testing.assert_array_equal(
            np.asarray(out), np.asarray(jnp.argmax(logits, axis=-1)))


@pytest.mark.parametrize("top_k", [None, 8])
def test_mixed_step_keeps_greedy_rows_and_samples_the_others(top_k):
    import jax
    import jax.numpy as jnp
    v = 64
    logits = _logits((6, v), "float32", seed=1) / 3.0
    temps = jnp.asarray([0.0, 0.9, 0.0, 1.3, 0.0, 0.7], jnp.float32)
    greedy = np.asarray(jnp.argmax(logits, axis=-1))
    fn = jax.jit(lambda *a: _sample(*a, top_k=top_k))
    steps = np.stack([np.asarray(fn(logits, temps, _key(3), jnp.int32(s)))
                      for s in range(24)])
    keys = np.stack([np.asarray(fn(logits, temps, _key(k), jnp.int32(5)))
                     for k in range(24)])
    for outs in (steps, keys):
        assert ((0 <= outs) & (outs < v)).all()
        np.testing.assert_array_equal(outs[:, ::2],
                                      np.tile(greedy[::2], (24, 1)))
        for row in (1, 3, 5):       # the draw moves with the step / the key
            assert len(set(outs[:, row])) > 1
    if top_k is not None:           # and stays inside the row's top k
        top = np.argsort(-np.asarray(logits), axis=-1)[:, :top_k]
        for row in (1, 3, 5):
            assert set(steps[:, row]) <= set(top[row])


@pytest.mark.parametrize("temp,top_k", [(0.7, None), (1.0, 8), (0.7, 8)])
def test_sampling_row_draws_from_the_filtered_softmax_chisq(temp, top_k):
    """4000 steps of a batch of one greedy and one sampling row: the
    sampling row's tokens follow the softmax of `scaled_filtered_logits`
    (as the verify path's chi-square test holds its own draws)."""
    import jax
    import jax.numpy as jnp
    n, v = 4000, 12
    logits = _logits((2, v), "float32", seed=2) / 2.0
    temps = jnp.asarray([0.0, temp], jnp.float32)
    key = _key(7)
    draws = jax.jit(jax.vmap(
        lambda s: _sample(logits, temps, key, s, top_k=top_k)))(
            jnp.arange(n, dtype=jnp.int32))
    draws = np.asarray(draws)
    assert (draws[:, 0] == int(jnp.argmax(logits[0]))).all()
    p = np.asarray(jax.nn.softmax(
        scaled_filtered_logits(logits, temps, top_k)[1]), np.float64)
    live = p > 0
    assert live.sum() == (top_k or v)
    obs = np.bincount(draws[:, 1], minlength=v)
    assert obs[~live].sum() == 0
    chi2 = ((obs[live] - p[live] * n) ** 2 / (p[live] * n)).sum()
    assert chi2 < 36, (chi2, obs)     # df <= 11: far past alpha = 1e-3


def _eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs inside its equations."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for sub in val if isinstance(val, (tuple, list)) else (val,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


_RANDOM = {"random_bits", "threefry2x32", "random_fold_in", "random_wrap"}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("top_k", [None, 8])
def test_noise_stands_behind_a_cond_and_nothing_is_64_bits_wide(dtype, top_k):
    import jax
    import jax.numpy as jnp
    assert jax.config.jax_enable_x64     # the process the engine runs in
    closed = jax.make_jaxpr(lambda *a: _sample(*a, top_k=top_k))(
        _logits((4, 64), dtype), jnp.zeros(4, jnp.float32), _key(0),
        jnp.int32(3))
    top = [e.primitive.name for e in closed.jaxpr.eqns]
    assert top.count("cond") == 1
    assert not _RANDOM & set(top)        # no draw outside the cond
    cond = closed.jaxpr.eqns[top.index("cond")]
    greedy, noisy = ({e.primitive.name for e in _eqns(branch.jaxpr)}
                     for branch in cond.params["branches"])
    assert not _RANDOM & greedy and "random_bits" in noisy
    # a Python scalar (`-jnp.inf`, `maxval=1.0`) is a weak float64 literal
    # that its first use casts to float32; nothing else may be 64 bits wide
    for eqn in _eqns(closed.jaxpr):
        for var in list(eqn.invars) + list(eqn.outvars):
            aval = var.aval
            if getattr(aval, "dtype", None) in (jnp.float64, jnp.int64,
                                                jnp.uint64):
                assert aval.shape == () and aval.weak_type, (eqn, aval)


def test_sampling_rows_stand_on_the_decode_span():
    from paddle_tpu.models.llama import llama_tiny
    from paddle_tpu.observability import tracing
    tr = tracing.get_tracer()
    was = tr.enabled
    tr.reset()
    tr.enabled = True
    try:
        paddle.seed(5)
        model = llama_tiny(vocab_size=128, max_position_embeddings=64,
                           hidden_size=32, num_layers=1, num_heads=2,
                           num_kv_heads=1, intermediate_size=64)
        eng = LLMEngine(model, ServingConfig(
            page_size=8, num_pages=17, max_batch=2, max_new_tokens=6,
            prefix_cache=False))
        try:
            cold = eng.submit([1, 2, 3], temperature=0.0)
            warm = eng.submit([4, 5, 6, 7], temperature=0.8)
            cold, warm = cold.result(timeout=300), warm.result(timeout=300)
            alone = eng.generate([1, 2, 3], timeout=300)
        finally:
            eng.shutdown(drain=False)
        spans = [s["counts"] for s in tracing.step_spans()["spans"]
                 if s["name"] == "serving.decode"]
    finally:
        tr.enabled = was
        tr.reset()
    assert cold == alone                # the sampling row beside it or not
    assert all(0 <= t < 128 for t in warm)
    assert all(0 <= c["sampling_rows"] <= c["rows"] for c in spans)
    # a request's first token is its prefill's: one decode step each after
    assert sum(c["sampling_rows"] for c in spans) == len(warm) - 1
    assert sum(c["rows"] for c in spans) == \
        len(cold) + len(warm) + len(alone) - 3
    assert any(c["rows"] == 2 and c["sampling_rows"] == 1 for c in spans)
