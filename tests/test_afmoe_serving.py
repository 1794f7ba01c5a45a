"""AFMoE (Trinity) on the normal serving path, at a size the CPU holds:
hidden 64, 4 heads x 16 over 2 KV heads, 8 experts top-2 + 1 shared, window
8, page 4, layers dense-window, window, window, window, global. Every
comparison is against the plain reference's full forward
(`perfbench/reference/afmoe.py`: float32 at `highest`, no cache, a loop over
experts) on the same seeded weights.

Tolerances. Program and reference both compute in float32 here, so what
separates them is the order of the sums: logits of size 1 agree to 1e-6, and
`TOL` = 2e-5 leaves an order of magnitude. Any term of the equations left
out moves a logit by 0.1 or more (`test_a_term_left_out_shows`), and bf16 in
place of float32 by some 1e-2 (`test_lower_precision_shows`): both fail TOL.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.serving import LLMEngine, ServingConfig, kv_cache
from paddle_tpu.serving.model import ServingModel

from perfbench.drivers import serve_afmoe as drv
from perfbench.harness import common
from perfbench.reference import afmoe as ref

TOL = 2e-5
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(ROOT, "perfbench", "tests", "data", "configs",
                       "afmoe-tiny.json")) as f:
    CFG = json.load(f)
WINDOW, PAGE, MAX_LEN = CFG["sliding_window"], 4, 64
SEED = 2 ** 31 + 5


def build(dtype="float32", seed=SEED):
    model = drv.model_of(CFG, 128)
    drv.assign(model, CFG, common.seed_words(seed), dtype)
    model.astype(dtype)
    model.eval()
    return model


@pytest.fixture(scope="module")
def model():
    return build()


def tokens(n, seed=0):
    return np.random.default_rng(seed).integers(
        1, CFG["vocab_size"], n).tolist()


def ref_logits(ids, seed=SEED, **how):
    """[len(ids), vocab]: the reference's logits at every position."""
    out = ref.logits_of(CFG, common.seed_words(seed), [ids], [0], 128, **how)
    return np.asarray(out[0])[:len(ids)]


def test_eager_model_matches_the_reference(model):
    ids = tokens(40)
    got = model(paddle.to_tensor(np.asarray([ids], np.int32))).numpy()[0]
    assert np.abs(got - ref_logits(ids)).max() < TOL


@pytest.mark.parametrize("fault", ref.FAULTS)
def test_a_term_left_out_shows(fault):
    """Each planted fault moves the reference's own logits by far more than
    TOL: the comparison above would catch the program making it."""
    ids = tokens(40)
    assert np.abs(ref_logits(ids, fault=fault) - ref_logits(ids)).max() > 0.05


def test_lower_precision_shows():
    ids = tokens(40)
    got = build("bfloat16")(paddle.to_tensor(
        np.asarray([ids], np.int32))).numpy()[0].astype(np.float32)
    assert np.abs(got - ref_logits(ids)).max() > 50 * TOL


# -- prefill then decode through the cache ----------------------------------

class Cache:
    """A ServingModel over two small pools with the page tables a scheduler
    would keep: every row owns its pages outright, and the window group's
    entries behind the row's window are the trash page."""

    def __init__(self, model, rows):
        self.sm = ServingModel(model)
        self.max_pages = MAX_LEN // PAGE
        n = rows * self.max_pages + 1
        kv = dict(num_kv_heads=2, page_size=PAGE, head_dim=16)
        self.sm.bind_pool(kv_cache.PagePool(1, n, **kv),
                          kv_cache.PagePool(4, n, **kv))
        self.tables = 1 + np.arange(rows * self.max_pages, dtype=np.int32) \
            .reshape(rows, self.max_pages)

    def window_tables(self, lengths):
        t = self.tables.copy()
        for r, n in enumerate(lengths):
            t[r, :kv_cache.window_first_page(n, WINDOW, PAGE)] = 0
        return t

    def prefill(self, row, ids):
        toks = np.zeros((1, 32), np.int32)
        toks[0, :len(ids)] = ids
        wrow = self.window_tables([len(ids)] * (row + 1))[row]
        return self.sm.prefill_forward(
            Tensor(jnp.asarray(toks)), Tensor(jnp.int32(len(ids))),
            Tensor(jnp.asarray(self.tables[row])),
            Tensor(jnp.asarray(wrow))).numpy()[0]

    def decode(self, last, lengths, live):
        """One step: row r's token `last[r]` at position lengths[r] - 1."""
        tables = np.where(np.asarray(live)[:, None], self.tables, 0)
        wtables = np.where(np.asarray(live)[:, None],
                           self.window_tables(lengths), 0)
        pos = np.where(live, np.asarray(lengths) - 1, 0).astype(np.int32)
        return self.sm.decode_forward(
            Tensor(jnp.asarray(last, jnp.int32)), Tensor(jnp.asarray(pos)),
            Tensor(jnp.asarray(tables)), Tensor(jnp.asarray(wtables))).numpy()


@pytest.mark.parametrize("prompt", [5, WINDOW, 29],
                         ids=["shorter", "equal", "several_windows"])
def test_prefill_then_decode_logits_at_every_position(model, prompt):
    ids = tokens(prompt + 14, seed=prompt)
    want = ref_logits(ids)
    cache = Cache(model, 1)
    got = [cache.prefill(0, ids[:prompt])]
    for n in range(prompt + 1, len(ids) + 1):   # token n-1 at position n-1
        got.append(cache.decode([ids[n - 1]], [n], [True])[0])
    gaps = np.abs(np.asarray(got) - want[prompt - 1:]).max(axis=-1)
    assert gaps.max() < TOL, gaps


def test_a_batch_of_rows_of_mixed_length(model):
    prompts = [3, 12, 27]
    seqs = [tokens(p + 6, seed=10 + p) for p in prompts]
    want = [ref_logits(s) for s in seqs]
    cache = Cache(model, 4)          # the last slot stays empty
    for r, (p, s) in enumerate(zip(prompts, seqs)):
        cache.prefill(r, s[:p])
    for step in range(1, 7):
        lengths = [p + step for p in prompts] + [1]
        last = [s[n - 1] for s, n in zip(seqs, lengths)] + [0]
        got = cache.decode(last, lengths, [True, True, True, False])
        for r in range(3):
            assert np.abs(got[r] - want[r][lengths[r] - 1]).max() < TOL


# -- the routed layer --------------------------------------------------------

@pytest.mark.parametrize("interpret", [False, True],
                         ids=["composite", "kernel_interpret"])
def test_every_token_to_one_expert_nothing_dropped(model, interpret):
    """A router forced to send every token to expert 3 first: 24 tokens,
    three times what a capacity of the mean load would admit."""
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import \
        dropless_experts
    moe = model.layers[1].mlp
    bias = jnp.zeros(8, jnp.float32).at[3].set(10.0)
    x = jnp.asarray(np.random.default_rng(1).standard_normal((24, 64)),
                    jnp.float32)
    w = {"mlp." + n: p._data for n, p in moe.named_parameters()}
    w["mlp.expert_bias"] = bias
    sel, wts = moe.route(x, w["mlp.router.weight"], bias)
    got, sizes = dropless_experts(x, sel, wts, w["mlp.gate_w"],
                                  w["mlp.up_w"], w["mlp.down_w"],
                                  interpret=interpret)
    assert int(sizes[3]) == 24 and int(sizes.sum()) == 48
    want = ref._routed(x, w, jnp.int32(24),
                       ref.knobs(CFG, 1, "no_shared"),   # the routed
                       top_k=2, quant=None)              # experts alone
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < TOL


@pytest.mark.parametrize("n,k,e,tile", [(5, 2, 8, 16), (64, 8, 128, 16),
                                        (40, 2, 4, 16)])
def test_sorted_layout_puts_every_row_in_its_experts_tiles(n, k, e, tile):
    from paddle_tpu.incubate.distributed.models.moe.moe_layer import \
        sort_by_expert
    sel = jnp.asarray(np.random.default_rng(n).integers(0, e, (n, k)),
                      jnp.int32)
    src, dest, tile_expert, used, sizes = map(
        np.asarray, sort_by_expert(sel, e, tile))
    assert sizes.sum() == n * k and len(set(dest.reshape(-1))) == n * k
    assert used == sum(-(-s // tile) for s in sizes)
    for t in range(n):
        for j in range(k):
            row = dest[t, j]
            assert src[row] == t and tile_expert[row // tile] == sel[t, j]


# -- the kernels' lower bound, in interpret mode ------------------------------

def test_paged_kernel_lower_bound_against_the_composite():
    rng = np.random.default_rng(3)
    b, pages, window = 4, 40, 20
    pool = lambda: jnp.asarray(rng.standard_normal(  # noqa: E731
        (2, pages, 2, PAGE, 128)), jnp.float32)
    k, v = pool(), pool()
    q = jnp.asarray(rng.standard_normal((b, 1, 4, 128)), jnp.float32)
    tables = 1 + np.arange(b * 9, dtype=np.int32).reshape(b, 9)
    pos = np.asarray([2, 19, 31, 35], np.int32)
    for r, p in enumerate(pos):     # released behind the window
        tables[r, :kv_cache.window_first_page(p + 1, window, PAGE)] = 0
    live = jnp.asarray([True, True, True, True])
    args = (q, k, v, 1, jnp.asarray(tables), jnp.asarray(pos))
    got = kv_cache.paged_attention(*args, interpret=True, window=window,
                                   live=live)
    want = kv_cache.paged_attention(*args, interpret=False, window=window,
                                    live=live)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5
    wide = kv_cache.paged_attention(*args, interpret=False, live=live)
    assert np.abs(np.asarray(wide) - np.asarray(want))[2:].max() > 1e-3


@pytest.mark.parametrize("s,window,bq,bk", [(64, None, 16, 8), (64, 24, 16, 8),
                                            (64, 8, 32, 16), (64, 17, 16, 16)])
def test_banded_flash_forward_against_the_composite(s, window, bq, bk):
    from paddle_tpu.ops.kernels import flash_attention as fa
    from paddle_tpu.ops.kernels import flash_attention_pallas as fap
    key = jax.random.key(s)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (2, s, h, 16),
                                 jnp.float32)
               for i, h in ((1, 4), (2, 2), (3, 2)))
    got = fap.flash_attention_forward_banded(q, k, v, window=window,
                                             block_q=bq, block_k=bk,
                                             interpret=True)
    want = fa._reference_attention(q, k, v, True, window=window)
    assert np.abs(np.asarray(got) - np.asarray(want)).max() < 1e-5


# -- through the engine ------------------------------------------------------

def engine_of(model, **kw):
    cfg = dict(page_size=PAGE, num_pages=65, max_batch=4, max_seq_len=MAX_LEN,
               prefill_buckets=(16, 32, 64), prefix_cache=False)
    cfg.update(kw)
    return LLMEngine(model, ServingConfig(**cfg))


def assert_served_as_the_reference(prompt, served):
    """Every served token is the reference's first choice at its position
    (or within TOL of it)."""
    logits = ref_logits(prompt + served[:-1])[len(prompt) - 1:]
    gaps = logits.max(-1) - logits[np.arange(len(served)), served]
    assert gaps.max() < TOL, gaps


def test_window_pages_released_and_reused_none_leaked_or_lost(model):
    """Rows several windows long in a window group of 4 x 3 pages: each
    holds at most ceil(8 / 4) + 1 = 3 of them whatever its length (its whole
    context would take up to 14), so the pages that fall behind have to come
    back and be handed out again."""
    eng = engine_of(model)
    prompts = [tokens(n, seed=n) for n in (30, 9, 21, 5, 26, 17)]
    reqs = [eng.submit(p, max_new_tokens=24) for p in prompts]
    held = []
    while not all(r.finished for r in reqs):
        g = eng.stats()["pages"]["groups"]
        held.append(g["window"]["used"])
        assert g["window"]["lost"] == 0 and g["global"]["lost"] == 0
    for p, r in zip(prompts, reqs):
        assert_served_as_the_reference(p, r.result(timeout=120))
    assert max(held) <= 4 * kv_cache.window_pages(WINDOW, PAGE)
    stats = eng.stats()
    assert stats["evictions"] == 0
    assert stats["programs"]["decode"]["retraces"] == 0
    # one signature a bucket the prompts reached (32 and 16), no more
    assert stats["programs"]["prefill"]["discoveries"] == 2
    summary = eng.shutdown()
    assert summary["pages_leaked"] == 0
    assert eng.stats()["pages"]["lost"] == 0
    assert eng.stats()["pages"]["used"] == 0


def test_eviction_frees_both_groups_and_the_answer_stands(model):
    """A global group too small for four rows at their full length: the
    youngest is evicted, re-prefilled later, and still answers as the
    reference does."""
    eng = engine_of(model, num_pages=26)
    prompts = [tokens(n, seed=40 + n) for n in (20, 22, 18, 16)]
    reqs = [eng.submit(p, max_new_tokens=20) for p in prompts]
    for p, r in zip(prompts, reqs):
        assert_served_as_the_reference(p, r.result(timeout=180))
    assert eng.stats()["evictions"] > 0
    assert eng.shutdown()["pages_leaked"] == 0
    assert eng.stats()["pages"]["lost"] == 0


@pytest.mark.parametrize("asked,reason", [
    (dict(prefix_cache=True), "window pages are freed"),
    (dict(prefill_chunk=8), "chunk program"),
    (dict(spec_k=2), "verify program")])
def test_what_two_groups_cannot_do_yet_is_refused_with_the_reason(
        model, asked, reason):
    with pytest.raises(ValueError, match=reason):
        engine_of(model, **asked)


def test_step_spans_carry_the_experts_and_the_window_groups_counts(
        model, monkeypatch):
    from paddle_tpu.observability import tracing
    monkeypatch.setenv("PADDLE_TPU_TRACE", "1")
    tracing.reset() if hasattr(tracing, "reset") else None
    eng = engine_of(model)
    prompt = tokens(26, seed=2)
    eng.submit(prompt, max_new_tokens=6).result(timeout=120)
    eng.shutdown()
    spans = [s for s in tracing.step_spans()["spans"]
             if s["name"] == "serving.decode"]
    assert spans
    c = spans[-1]["counts"]
    # one live row of the four slots: 2 experts a routed layer at the most
    assert c["experts_total"] == 4 * 8 and 0 < c["experts_hit"] <= 4 * 2
    assert c["positions_window"] == WINDOW < c["positions"]
    assert c["window_pages_held"] <= kv_cache.window_pages(WINDOW, PAGE) \
        < c["window_pages_whole"]
    pre = [s for s in tracing.step_spans()["spans"]
           if s["name"] == "serving.prefill"][-1]["counts"]
    assert 0 < pre["experts_hit"] <= pre["experts_total"] == 32


def test_rows_that_are_no_tokens_reach_no_expert(model):
    moe = model.layers[2].mlp
    x = Tensor(jnp.asarray(np.random.default_rng(5).standard_normal(
        (6, 64)), jnp.float32))
    live = jnp.asarray([True, False, True, False, False, False])
    out, hit = moe.routed(x, live)
    two, _ = moe.routed(Tensor(x._data[jnp.asarray([0, 2])]))
    assert 0 < int(hit.numpy()) <= 4
    assert np.abs(out.numpy()[[0, 2]] - two.numpy()).max() < TOL
