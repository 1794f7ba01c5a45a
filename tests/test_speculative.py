"""Speculative decoding for the serving engine (ISSUE 15).

Covers the test satellites: distribution-equivalence of temperature-mode
Leviathan rejection sampling (chi-squared vs direct sampling on a tiny
vocab), greedy token-exactness spec-on == spec-off == ``model.generate``,
rollback-under-COW (a shared page in the speculative span + rejected
drafts → cow_copies bumps, the other owner's KV bytes untouched), the
verify program compiling exactly ONCE across join/leave/K-changes,
adaptive-K shrinking to 0 on an adversarial (random-token) stream,
int8 + prefix-cache + speculation composed token-exact, multi-token
accounting (tokens counted, not steps), and the perf-gate spec
directions.
"""

import importlib.util
import os

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.observability as obs
from paddle_tpu.models.llama import llama_tiny
from paddle_tpu.serving import (LLMEngine, NgramDrafter, ServingConfig,
                                SpecState, verify_tokens)
from paddle_tpu.serving.scheduler import Request


def _model(**kw):
    cfg = dict(vocab_size=128, max_position_embeddings=64, hidden_size=32,
               num_layers=1, num_heads=2, num_kv_heads=1,
               intermediate_size=64)
    cfg.update(kw)
    return llama_tiny(**cfg)


def _ngram_hits(prompt, out, k=4):
    """Drafts of the n-gram drafter whose first token the greedy stream
    `out` then confirms — how predictable a random model's stream is."""
    d, hist, n = NgramDrafter(), list(prompt), 0
    for t in out:
        p = d.propose(hist, k)
        n += bool(p) and p[0] == t
        hist.append(t)
    return n


def _engine(model=None, **kw):
    cfg = dict(page_size=8, num_pages=17, max_batch=2, max_new_tokens=6)
    cfg.update(kw)
    return LLMEngine(model or _model(), ServingConfig(**cfg))


# -- drafter + adaptive policy ------------------------------------------------

def test_ngram_drafter_prompt_lookup():
    d = NgramDrafter()
    # longest suffix n-gram, MOST RECENT earlier occurrence wins
    assert d.propose([1, 2, 3, 1, 2], 3) == [3, 1, 2]
    assert d.propose([9, 1, 2, 7, 1, 2], 2) == [7, 1]
    # no earlier occurrence of the suffix -> no draft
    assert d.propose([1, 2, 3, 4], 2) == []
    # continuation truncated by history end and by k
    assert d.propose([5, 6, 5, 6, 5], 4) == [6, 5]
    assert d.propose([5, 6, 5, 6, 5], 1) == [6]
    assert d.propose([1, 2], 0) == []
    assert d.propose([1], 3) == []
    with pytest.raises(ValueError):
        NgramDrafter(max_ngram=1, min_ngram=2)
    with pytest.raises(ValueError):
        NgramDrafter(max_ngram=3, window=3)
    # bounded lookback: a match older than `window` tokens is invisible
    small = NgramDrafter(window=6)
    hist = [7, 8, 9] + [0] * 6 + [7, 8]      # only occurrence pre-window
    assert small.propose(hist, 2) == []
    assert NgramDrafter(window=16).propose(hist, 2) == [9, 0]


def test_request_context_tail_bounded():
    """`_propose` hands a window-bounded drafter only the context tail —
    built WITHOUT materializing the full prompt+generation list."""
    req = Request([1, 2, 3, 4, 5], 8)
    req.tokens = [6, 7]
    assert req.context_tail(0) == []
    assert req.context_tail(1) == [7]
    assert req.context_tail(2) == [6, 7]
    assert req.context_tail(4) == [4, 5, 6, 7]
    assert req.context_tail(99) == req.context()


def test_spec_state_shrinks_grows_and_probes():
    st = SpecState(4)
    assert st.draft_k() == 4
    for _ in range(10):
        st.update(4, 0)                      # adversarial: all rejected
    assert st.k == 0 and st.ewma < 0.05
    # at k == 0 only the periodic probe proposes
    ks = [st.draft_k() for _ in range(st.probe_every)]
    assert ks.count(1) == 1 and set(ks) <= {0, 1}
    for _ in range(10):
        st.update(1, 1)                      # stream turned predictable
    assert st.k >= 1                         # climbed back in
    pinned = SpecState(3, adaptive=False)
    pinned.update(3, 0)
    assert pinned.draft_k() == 3             # adaptive=False pins K
    assert st.acceptance_rate() is not None


# -- acceptance math ----------------------------------------------------------

def test_verify_tokens_greedy_accepts_exact_prefix():
    import jax
    import jax.numpy as jnp
    b, s, v = 2, 4, 8
    logits = np.full((b, s, v), -5.0, np.float32)
    targets = [[2, 3, 4, 5], [1, 1, 1, 1]]
    for i in range(b):
        for j in range(s):
            logits[i, j, targets[i][j]] = 5.0
    drafts = np.array([[2, 3, 7], [1, 2, 1]], np.int32)
    dlen = np.array([3, 2], np.int32)
    out, acc = verify_tokens(
        jnp.asarray(logits), jnp.asarray(drafts), jnp.asarray(dlen),
        jnp.zeros(b, jnp.float32), jax.random.PRNGKey(0), jnp.uint32(0))
    out, acc = np.asarray(out), np.asarray(acc)
    # row 0: drafts 2,3 match, 7 != 4 -> 2 accepted + correction 4
    # row 1: draft 1 matches, 2 != 1 -> 1 accepted + correction 1
    assert list(acc) == [2, 1]
    assert list(out[0, :3]) == [2, 3, 4]
    assert list(out[1, :2]) == [1, 1]
    # draft_len = 0 row behaves exactly like a decode step (bonus only)
    out0, acc0 = verify_tokens(
        jnp.asarray(logits), jnp.asarray(drafts),
        jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.float32),
        jax.random.PRNGKey(0), jnp.uint32(0))
    assert list(np.asarray(acc0)) == [0, 0]
    assert np.asarray(out0)[0, 0] == 2 and np.asarray(out0)[1, 0] == 1


def test_temperature_rejection_sampling_distribution_chisq():
    """Acceptance satellite: the emitted-token marginal under rejection
    sampling against a deterministic draft equals the target softmax —
    chi-squared against both the analytic distribution AND a
    direct-sampling control on a tiny vocab."""
    import jax
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    n, v = 4000, 6
    lg = np.asarray(rng.standard_normal((1, 2, v)), np.float32)
    p = np.exp(lg[0, 0]) / np.exp(lg[0, 0]).sum()
    big = jnp.asarray(np.repeat(lg, n, axis=0))
    draft = 2                                 # point-mass draft proposal
    out, acc = verify_tokens(
        big, jnp.full((n, 1), draft, jnp.int32), jnp.ones(n, jnp.int32),
        jnp.ones(n, jnp.float32), jax.random.PRNGKey(7), jnp.uint32(3))
    emitted = np.asarray(out)[:, 0]
    acc_n = int(np.asarray(acc).sum())
    # both the accept and the residual-resample paths must be exercised
    assert 0 < acc_n < n
    # acceptance count is itself Binomial(n, p(draft))
    assert abs(acc_n / n - p[draft]) < 4 * np.sqrt(p[draft] / n)
    obs_counts = np.bincount(emitted, minlength=v)
    chi2 = ((obs_counts - p * n) ** 2 / (p * n)).sum()
    assert chi2 < 25, (chi2, obs_counts)      # df=5, far past alpha=1e-3
    # two-sample control vs DIRECT sampling from the target
    direct = np.asarray(jax.random.categorical(
        jax.random.PRNGKey(11), jnp.asarray(np.repeat(lg[:, 0], n, 0))))
    d_counts = np.bincount(direct, minlength=v)
    pooled = (obs_counts + d_counts) / (2 * n)
    chi2_2s = (((obs_counts - pooled * n) ** 2 / (pooled * n)).sum()
               + ((d_counts - pooled * n) ** 2 / (pooled * n)).sum())
    assert chi2_2s < 25, (chi2_2s, obs_counts, d_counts)


# -- engine end-to-end: exactness ---------------------------------------------

def test_greedy_spec_on_off_generate_token_exact():
    """THE speculative contract: greedy spec-on == spec-off ==
    model.generate, while drafts actually land."""
    # seed picked so the random model's greedy stream repeats itself
    # (jax's default RNG stream, threefry-partitionable, decides which do)
    paddle.seed(17)
    model = llama_tiny()                     # vocab 512, pos 128
    prompt = [5, 9, 11, 2, 7]
    ref = model.generate(np.asarray([prompt]), max_new_tokens=24)
    expect = [int(t) for t in ref[0, len(prompt):]]
    assert _ngram_hits(prompt, expect) >= 1, \
        "this seed's greedy stream gives the drafter nothing: pick another"
    off = _engine(model, page_size=16, num_pages=33, max_new_tokens=24,
                  spec_k=0)
    on = _engine(model, page_size=16, num_pages=33, max_new_tokens=24,
                 spec_k=4)
    try:
        got_off = off.generate(prompt, timeout=300)
        got_on = on.generate(prompt, timeout=300)
        spec = on.scheduler.spec_stats()
    finally:
        off.shutdown()
        on.shutdown()
    assert got_off == expect
    assert got_on == expect
    assert spec["accepted_tokens"] >= 1      # speculation actually engaged
    assert spec["tokens_per_step"] > 1.0
    assert on.pool.leaked() == 0 and on.pool.lost() == 0


def test_verify_program_compiles_once_across_join_leave_k_changes():
    """The verify program keeps the decode program's guarantee: static
    [max_batch, K+1] shapes, everything else values — joins, leaves,
    and per-request adaptive-K changes never retrace it."""
    paddle.seed(42)
    eng = _engine(_model(max_position_embeddings=128), max_batch=3,
                  page_size=4, num_pages=65, max_new_tokens=24, spec_k=3)
    try:
        first = eng.submit([7, 3, 7, 3])             # join (drafts fire)
        first.result(timeout=300)                     # leave
        reqs = [eng.submit([7 + i, 3, 7 + i, 3], max_new_tokens=20)
                for i in range(5)]                    # joins > slots
        for r in reqs:
            r.result(timeout=300)
        stats = eng.program_stats()
        spec = eng.scheduler.spec_stats()
    finally:
        eng.shutdown()
    assert spec["verify_steps"] >= 2         # program exercised repeatedly
    assert stats["verify"]["retraces"] == 0
    assert stats["verify"]["compiles"] == 1
    assert stats["verify"]["discoveries"] == 1
    assert stats["decode"]["retraces"] == 0
    assert eng.pool.leaked() == 0 and eng.pool.lost() == 0


def test_int8_prefix_cache_and_speculation_compose_token_exact():
    paddle.seed(43)
    model = _model(num_layers=2)
    prompt = [3, 1, 4, 3, 1, 4, 3, 1, 4, 3, 1, 4, 3, 1, 4, 3]  # 2 pages
    kw = dict(quant="weight_only_int8", page_size=8, num_pages=33,
              max_new_tokens=24, prefix_cache=True)
    off = _engine(model, spec_k=0, **kw)
    on = _engine(model, spec_k=3, **kw)
    try:
        miss_off = off.generate(prompt, timeout=300)
        hit_off = off.generate(prompt, timeout=300)
        miss_on = on.generate(prompt, timeout=300)
        hit_on = on.generate(prompt, timeout=300)     # cache hit + spec
        pstats = on.scheduler.prefix_stats()
        spec = on.scheduler.spec_stats()
    finally:
        off.shutdown()
        on.shutdown()
    assert miss_off == hit_off == miss_on == hit_on
    assert pstats["page_hits"] >= 1          # the cache engaged
    assert spec["proposed_tokens"] >= 1      # speculation engaged
    assert on._sm.quantized
    assert on.pool.leaked() == 0 and on.pool.lost() == 0


def test_spec_emission_respects_eos_mid_burst():
    paddle.seed(44)
    model = _model()
    probe = _engine(model, max_new_tokens=12, spec_k=0)
    ref = probe.generate([3, 1, 3, 1], timeout=300)
    probe.shutdown()
    eos = ref[len(ref) // 2]                 # force an early stop mid-way
    want = ref[:ref.index(eos) + 1]
    off = _engine(model, max_new_tokens=12, eos_token_id=eos, spec_k=0)
    on = _engine(model, max_new_tokens=12, eos_token_id=eos, spec_k=4)
    try:
        got_off = off.generate([3, 1, 3, 1], timeout=300)
        got_on = on.generate([3, 1, 3, 1], timeout=300)
    finally:
        off.shutdown()
        on.shutdown()
    assert got_off == want
    assert got_on == want                    # burst truncated AT the eos
    assert on.pool.leaked() == 0 and on.pool.lost() == 0


# -- rollback + COW -----------------------------------------------------------

class _WrongDrafter:
    """Proposes drafts guaranteed to be rejected: token (true + 1) mod V
    at every position, where `ref` is the request's true greedy stream."""

    def __init__(self, prompt, ref, vocab, k=2):
        self.prompt, self.ref, self.vocab, self.k = prompt, ref, vocab, k

    def propose(self, history, k):
        done = len(history) - len(self.prompt)
        if k <= 0 or done >= len(self.ref):
            return []
        nxt = self.ref[done]
        return [(nxt + 1) % self.vocab] * min(self.k, k)


def test_rollback_frees_rejected_draft_pages_and_stays_exact():
    """All-rejected drafts: the cursor advances exactly one token per
    verify step, pages allocated for the speculative span are freed
    (rollback), and the stream equals the spec-off reference."""
    paddle.seed(45)
    model = _model()
    probe = _engine(model, page_size=4, num_pages=33, max_new_tokens=10,
                    spec_k=0)
    ref = probe.generate([9, 8, 7], timeout=300)
    probe.shutdown()

    eng = _engine(model, page_size=4, num_pages=33, max_batch=2,
                  max_new_tokens=10, spec_k=3)
    sched = eng.scheduler
    sched.drafter = _WrongDrafter([9, 8, 7], ref, 128)
    req = Request([9, 8, 7], max_new_tokens=10)
    free0 = eng.pool.free_pages
    try:
        sched.submit(req)     # scheduler-level submit: stepped manually
        for _ in range(64):
            if req.finished:
                break
            sched.step()
            if req.slot is not None:
                # rollback invariant: between steps a request never
                # holds pages beyond its accepted length
                assert len(req.pages) <= \
                    eng.pool.pages_for(req.cur_len()), \
                    (len(req.pages), req.cur_len())
        assert req.state == "completed"
        assert list(req.tokens) == ref       # exact under full rejection
        assert sched.spec_rejected >= 1
        assert sched.spec_accepted == 0
        # degrade path: with the pool hogged, a draft span must NOT
        # evict anyone — _ensure_spec_pages hands back False and the
        # request decodes plainly
        req2 = Request([9, 8, 7], max_new_tokens=10)
        sched.submit(req2)
        sched._admit()
        assert req2.slot is not None
        hog = eng.pool.alloc(eng.pool.free_pages)
        assert not sched._ensure_spec_pages(req2, 3)
        assert req2.slot is not None         # still seated
        assert sched.evictions == 0
        eng.pool.free(hog)
        while not req2.finished:
            sched.step()
        assert list(req2.tokens) == ref
    finally:
        eng.shutdown(drain=False)
    assert eng.pool.free_pages == free0
    assert eng.pool.leaked() == 0 and eng.pool.lost() == 0


def test_rollback_under_cow_leaves_other_owners_kv_untouched():
    """Acceptance satellite: a SHARED page sits in the speculative span
    — the verify step must copy-on-write before writing draft KV
    (cow_copies bumps) and the original page's bytes stay identical for
    its other owner, rejected drafts rolled back."""
    import jax.numpy as jnp
    paddle.seed(46)
    model = _model()
    probe = _engine(model, page_size=4, num_pages=33, max_new_tokens=8,
                    spec_k=0)
    ref = probe.generate([6, 5, 4], timeout=300)
    probe.shutdown()

    eng = _engine(model, page_size=4, num_pages=33, max_batch=2,
                  max_new_tokens=8, spec_k=3)
    sched = eng.scheduler
    sched.drafter = _WrongDrafter([6, 5, 4], ref, 128)
    req = Request([6, 5, 4], max_new_tokens=8)
    try:
        sched.submit(req)
        sched.step()                          # prefill + first tokens
        assert req.slot is not None and len(req.tokens) >= 1
        # simulate a second owner of the page the next speculative
        # write span starts in (exactly what a prefix-cache claim of a
        # live page does)
        idx = (req.cur_len() - 1) // eng.pool.page_size
        shared = req.pages[idx]
        eng.pool.incref([shared])
        snap_k = np.asarray(eng.pool.k._data[:, shared])
        snap_v = np.asarray(eng.pool.v._data[:, shared])
        cow0 = sched.cow_copies
        sched.step()                          # verify step: COW + reject
        assert sched.cow_copies >= cow0 + 1
        assert sched.spec_rejected >= 1
        # the shared original is bit-identical: the other owner's KV
        # was never touched by the speculative writes
        np.testing.assert_array_equal(
            np.asarray(eng.pool.k._data[:, shared]), snap_k)
        np.testing.assert_array_equal(
            np.asarray(eng.pool.v._data[:, shared]), snap_v)
        assert shared not in req.pages        # remapped to a private copy
        while not req.finished:
            sched.step()
        assert list(req.tokens) == ref
        eng.pool.free([shared])               # the simulated owner leaves
    finally:
        eng.shutdown(drain=False)
    assert eng.pool.leaked() == 0 and eng.pool.lost() == 0


def test_speculation_never_evicts_other_requests_for_draft_pages():
    """Pool too tight for draft spans: speculation degrades to plain
    decode (dlen=0) instead of evicting a neighbor."""
    paddle.seed(47)
    eng = _engine(page_size=4, num_pages=9, max_batch=2,  # 8 pages total
                  max_new_tokens=8, spec_k=3)
    try:
        a = eng.submit([1, 2, 1, 2, 1])
        b = eng.submit([3, 4, 3, 4, 3])
        ra, rb = a.result(300), b.result(300)
    finally:
        eng.shutdown()
    assert len(ra) == 8 and len(rb) == 8
    assert eng.scheduler.evictions == 0
    assert eng.pool.leaked() == 0 and eng.pool.lost() == 0


class _OnlyForDrafter:
    """Drafts k (wrong) tokens for histories starting with ``first``,
    nothing for anyone else."""

    def __init__(self, first, vocab=128):
        self.first, self.vocab = first, vocab

    def propose(self, history, k):
        if not history or history[0] != self.first or k <= 0:
            return []
        return [(history[-1] + 1) % self.vocab] * k

    # window attr not required: the scheduler only calls propose()


def test_spec_growth_yields_last_page_to_plain_decode():
    """Ordering regression: a drafting row's speculative page growth
    must not consume the last free page a NON-drafting neighbor needs
    for its plain decode write — plain-decode headroom is secured for
    every row BEFORE any speculative span grows, so the draft span
    fails, rolls back, and the row decodes plainly instead of forcing
    an eviction that spec-off would never have caused.

    Layout (page_size=4, 5 allocatable pages): A(prompt 7 -> 2 pages)
    drafts 3 rejected tokens every step (span wants a 3rd page); B
    (prompt 8 -> 2 pages, never drafts) needs its 3rd page for the very
    first decode write at position 8. One free page at the first decode
    iteration: B must get it."""
    paddle.seed(53)
    eng = _engine(page_size=4, num_pages=6, max_batch=2, max_new_tokens=4,
                  spec_k=3, prefix_cache=False)
    eng.scheduler.drafter = _OnlyForDrafter(first=9)
    try:
        a = eng.submit([9, 2, 3, 4, 5, 6, 7])            # 7 -> 2 pages
        b = eng.submit([3, 2, 3, 4, 5, 6, 7, 8],         # 8 -> 2 pages
                       max_new_tokens=2)
        ra, rb = a.result(300), b.result(300)
        spec = eng.scheduler.spec_stats()
        evictions = eng.scheduler.evictions
    finally:
        eng.shutdown()
    assert len(ra) == 4 and len(rb) == 2
    assert evictions == 0                  # speculation never cost a slot
    assert spec["proposed_tokens"] > 0     # A really did keep drafting
    assert spec["accepted_tokens"] == 0    # ... and every draft rejected
    assert eng.pool.leaked() == 0 and eng.pool.lost() == 0


# -- adaptive K ---------------------------------------------------------------

def test_adaptive_k_shrinks_to_zero_on_adversarial_stream():
    """An adversarial stream (every draft wrong — the worst case of
    random-token traffic) must drive the per-request K to 0 and the
    engine back onto the plain decode program (probe steps only): the
    no-TPOT-regression guarantee. The stream stays token-exact."""
    paddle.seed(48)
    model = _model()
    probe = _engine(model, page_size=8, num_pages=33, max_new_tokens=40,
                    spec_k=0)
    ref = probe.generate([2, 4, 6], timeout=300)
    probe.shutdown()

    eng = _engine(model, page_size=8, num_pages=33, max_new_tokens=40,
                  spec_k=4)
    eng.scheduler.drafter = _WrongDrafter([2, 4, 6], ref, 128, k=4)
    try:
        req = eng.submit([2, 4, 6])
        got = req.result(timeout=300)
        spec = eng.scheduler.spec_stats()
        k_final = req.spec.k
        steps = eng.scheduler.decode_steps
    finally:
        eng.shutdown()
    assert got == ref                        # exact under full rejection
    assert k_final == 0                      # K collapsed to plain decode
    assert spec["accepted_tokens"] == 0
    # K reaches 0 within ~5 EWMA updates; afterwards only the periodic
    # 1-token probe pays a verify sweep — most steps are plain decode
    assert spec["verify_steps"] <= 10
    assert steps >= 35                       # one token per step, as plain
    assert eng.pool.leaked() == 0 and eng.pool.lost() == 0


# -- accounting ---------------------------------------------------------------

def test_multi_token_accounting_counts_tokens_not_steps():
    """Fix satellite: `paddle_tpu_serving_tokens_total{kind=generated}`
    and the TPOT samples must count ACCEPTED TOKENS, not engine
    iterations, when a verify step emits a burst."""
    paddle.seed(52)       # a seed whose greedy stream repeats (see above)
    tok0 = obs.value("paddle_tpu_serving_tokens_total", kind="generated")
    eng = _engine(_model(), page_size=8, num_pages=33, max_new_tokens=12,
                  spec_k=4)
    try:
        req = eng.submit([8, 6, 8, 6, 8])
        got = req.result(timeout=300)
        spec = eng.scheduler.spec_stats()
        steps = eng.scheduler.decode_steps
    finally:
        eng.shutdown()
    assert _ngram_hits([8, 6, 8, 6, 8], got) >= 1, \
        "this seed's greedy stream gives the drafter nothing: pick another"
    assert spec["accepted_tokens"] >= 1      # bursts actually happened
    assert steps < len(got)                  # fewer steps than tokens
    delta = obs.value("paddle_tpu_serving_tokens_total",
                      kind="generated") - tok0
    assert delta == len(got)                 # tokens counted, not steps
    assert len(req.tpot_ms) == len(got) - 1  # one amortized gap per token
    assert eng.scheduler.tokens_per_step() > 1.0


def test_spec_stats_health_and_metrics_exposition():
    paddle.seed(50)
    eng = _engine(_model(), page_size=8, num_pages=33, max_new_tokens=10,
                  spec_k=3)
    try:
        eng.generate([7, 2, 7, 2, 7], timeout=300)
        code, payload = eng.health(stall_after_s=120.0)
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert code == 200
    assert payload["spec_acceptance_rate"] is not None
    assert 0.0 <= payload["spec_acceptance_rate"] <= 1.0
    sp = stats["speculative"]
    assert sp["enabled"] and sp["spec_k"] == 3
    assert sp["proposed_tokens"] == sp["accepted_tokens"] + \
        sp["rejected_tokens"]
    assert "verify" in stats["programs"]
    from paddle_tpu.observability import render_prometheus
    from test_prometheus_format import validate_exposition
    metrics = validate_exposition(render_prometheus())
    for fam in ("paddle_tpu_serving_spec_proposed_tokens_total",
                "paddle_tpu_serving_spec_accepted_tokens_total",
                "paddle_tpu_serving_spec_acceptance_rate",
                "paddle_tpu_serving_spec_k"):
        assert fam in metrics, fam


# -- perf gate directions -----------------------------------------------------

def _perf_gate():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "tools", "perf_gate.py")
    spec = importlib.util.spec_from_file_location("perf_gate_mod3", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_perf_gate_speculative_directions():
    pg = _perf_gate()
    ok = {"decode_program": {"retraces_after_warmup": 0},
          "verify_program": {"retraces_after_warmup": 0},
          "pages_leaked": 0, "pages_lost": 0, "tokens_per_s": 50.0}
    good = dict(ok, speculative={
        "spec_on": dict(ok, tpot_ms={"p50": 4.0},
                        tokens_per_step=1.8, acceptance_rate=0.7),
        "spec_off": dict(ok, tpot_ms={"p50": 6.0})})

    def gates(serve):
        return pg.serve_gates({"extra": {"serve": serve}}, {})

    hard, soft = gates(good)
    assert hard == [] and soft == []

    import copy
    bad = copy.deepcopy(good)
    bad["speculative"]["spec_on"]["pages_leaked"] = 1
    hard, _ = gates(bad)
    assert any("SERVE-LEAK" in m and "spec_on" in m for m in hard)

    bad = copy.deepcopy(good)
    bad["speculative"]["spec_on"]["verify_program"][
        "retraces_after_warmup"] = 2
    hard, _ = gates(bad)
    assert any("SERVE-RETRACE" in m and "verify" in m for m in hard)

    bad = copy.deepcopy(good)
    bad["speculative"]["spec_on"]["pages_lost"] = 1
    hard, _ = gates(bad)
    assert any("SERVE-LOST" in m and "spec_on" in m for m in hard)

    # soft: spec-on p50 TPOT must not exceed spec-off beyond tolerance
    bad = copy.deepcopy(good)
    bad["speculative"]["spec_on"]["tpot_ms"]["p50"] = 9.0
    _, soft = gates(bad)
    assert any("spec-tpot" in m for m in soft)
