"""paddle_tpu.serving: continuous batching over a paged KV cache.

Covers the ISSUE 8 test satellites: paged-attention parity vs the
contiguous ``cached_attention`` path (composite AND interpret-mode
kernel, per-row positions), page-pool accounting (never double-frees,
leak assertion), scheduler properties (FIFO no-starvation, decode
program compiles exactly once across join/leave/grow), the
admission-control rejection path, eviction recovery, drain semantics,
quantized serving, and the HTTP mount (/generate, serving-mode /healthz,
parser-validated /metrics).
"""

import json
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import llama_tiny
from paddle_tpu.serving import (LLMEngine, PagePool, PagePoolError,
                                PagePoolExhausted, RequestRejected,
                                ServingConfig, ServingError)
from paddle_tpu.serving import kv_cache


def _model(**kw):
    cfg = dict(vocab_size=128, max_position_embeddings=64, hidden_size=32,
               num_layers=1, num_heads=2, num_kv_heads=1,
               intermediate_size=64)
    cfg.update(kw)
    return llama_tiny(**cfg)


def _engine(model=None, **kw):
    cfg = dict(page_size=8, num_pages=17, max_batch=2, max_new_tokens=6)
    cfg.update(kw)
    return LLMEngine(model or _model(), ServingConfig(**cfg))


def _pallas_interpret_ok():
    """This box's jax may predate the kernels' enable_x64 spelling; the
    mmha compat shim covers mmha, but probe once and skip kernel-parity
    tests cleanly if interpret mode itself cannot run here."""
    import jax.numpy as jnp

    from paddle_tpu.ops.kernels import mmha_pallas
    try:
        q = jnp.zeros((1, 1, 2, 8), jnp.float32)
        kb = jnp.zeros((1, 1, 8, 8), jnp.float32)
        mmha_pallas.mmha_decode(q, kb, kb, jnp.int32(0), interpret=True)
        return True
    except Exception:
        return False


# -- paged attention parity ---------------------------------------------------

def _filled_pool_and_contiguous(rng, b, h_kv, d, ps, n_pages_req, lengths):
    """Write per-row random K/V through the paged helpers AND into a
    contiguous [B, Hkv, T, D] buffer; returns (pool arrays, tables,
    contiguous k, v)."""
    import jax.numpy as jnp
    n_rows = b
    pmax = n_pages_req
    t = pmax * ps
    total_pages = 1 + n_rows * pmax
    pool_k = jnp.zeros((1, total_pages, h_kv, ps, d), jnp.float32)
    pool_v = jnp.zeros((1, total_pages, h_kv, ps, d), jnp.float32)
    kc = np.zeros((n_rows, h_kv, t, d), np.float32)
    vc = np.zeros((n_rows, h_kv, t, d), np.float32)
    tables = np.zeros((n_rows, pmax), np.int32)
    next_page = 1
    for r in range(n_rows):
        ln = lengths[r]
        npages = -(-ln // ps)
        pages = list(range(next_page, next_page + npages))
        next_page += npages
        tables[r, :npages] = pages
        kseq = rng.standard_normal((ln, h_kv, d)).astype(np.float32)
        vseq = rng.standard_normal((ln, h_kv, d)).astype(np.float32)
        kc[r, :, :ln] = kseq.transpose(1, 0, 2)
        vc[r, :, :ln] = vseq.transpose(1, 0, 2)
        row = jnp.zeros((pmax,), jnp.int32).at[:npages].set(
            jnp.asarray(pages, jnp.int32))
        # prefill-write all but the last token, token-write the last one
        # (the two write paths the runtime uses)
        pool_k = kv_cache.write_prefill(pool_k, 0, row, ln - 1,
                                        jnp.asarray(kseq[:ln - 1]), ps) \
            if ln > 1 else pool_k
        pool_v = kv_cache.write_prefill(pool_v, 0, row, ln - 1,
                                        jnp.asarray(vseq[:ln - 1]), ps) \
            if ln > 1 else pool_v
        last_page = jnp.asarray([pages[(ln - 1) // ps]], jnp.int32)
        last_slot = jnp.asarray([(ln - 1) % ps], jnp.int32)
        pool_k = kv_cache.write_token(pool_k, 0, last_page, last_slot,
                                      jnp.asarray(kseq[-1:]))
        pool_v = kv_cache.write_token(pool_v, 0, last_page, last_slot,
                                      jnp.asarray(vseq[-1:]))
    return pool_k, pool_v, jnp.asarray(tables), kc, vc


def test_write_gather_roundtrip_across_page_boundaries():
    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    ps, pmax = 8, 3
    lengths = [7, 8, 17]          # below, at, and across page boundaries
    pool_k, pool_v, tables, kc, vc = _filled_pool_and_contiguous(
        rng, 3, 2, 4, ps, pmax, lengths)
    gk = np.asarray(kv_cache.gather_layer(pool_k, 0, tables))
    for r, ln in enumerate(lengths):
        np.testing.assert_allclose(gk[r, :, :ln], kc[r, :, :ln], rtol=0,
                                   atol=0)
        # beyond ln the gather may hold trash-page junk: masked by pos,
        # never compared


def test_paged_composite_parity_vs_cached_attention():
    """Per-row paged attention == models/generation.py:cached_attention
    (scalar-pos contiguous path) row by row, lengths crossing pages."""
    import jax.numpy as jnp

    from paddle_tpu.models.generation import cached_attention
    rng = np.random.default_rng(1)
    ps, pmax, h, h_kv, d = 8, 3, 4, 2, 8
    lengths = [5, 8, 24]
    pool_k, pool_v, tables, kc, vc = _filled_pool_and_contiguous(
        rng, 3, h_kv, d, ps, pmax, lengths)
    q = rng.standard_normal((3, 1, h, d)).astype(np.float32)
    pos = np.asarray([ln - 1 for ln in lengths], np.int32)
    out = np.asarray(kv_cache.paged_attention(
        jnp.asarray(q), pool_k, pool_v, 0, tables, jnp.asarray(pos),
        interpret=False))
    for r, ln in enumerate(lengths):
        # contiguous reference: replay the SAME last-token write through
        # cached_attention, then compare its attention output
        t = pmax * ps
        kb = paddle.to_tensor(kc[r:r + 1].copy())
        vb = paddle.to_tensor(vc[r:r + 1].copy())
        k_last = kc[r, :, ln - 1][None, None]   # [1, 1, Hkv, D]
        v_last = vc[r, :, ln - 1][None, None]
        ref, _ = cached_attention(
            paddle.to_tensor(q[r:r + 1]), paddle.to_tensor(k_last),
            paddle.to_tensor(v_last), (kb, vb),
            paddle.to_tensor(np.int32(ln - 1)))
        np.testing.assert_allclose(out[r], np.asarray(ref.numpy())[0],
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.skipif(not _pallas_interpret_ok(),
                    reason="pallas interpret mode unavailable here")
def test_paged_kernel_interpret_parity_per_row_pos():
    """The mmha kernel path (interpret mode) with VECTOR positions ==
    the composite, including GQA grouping."""
    import jax.numpy as jnp
    rng = np.random.default_rng(2)
    b, h, h_kv, d, t = 3, 4, 2, 16, 64
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)).astype(np.float32))
    kb = jnp.asarray(rng.standard_normal((b, h_kv, t, d)).astype(np.float32))
    vb = jnp.asarray(rng.standard_normal((b, h_kv, t, d)).astype(np.float32))
    pos = jnp.asarray([3, 31, 62], jnp.int32)
    from paddle_tpu.ops.kernels import mmha_pallas
    out_k = mmha_pallas.mmha_decode(q, kb, vb, pos, interpret=True)
    out_c = kv_cache.reference_paged_attention(q, kb, vb, pos)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_c),
                               rtol=2e-5, atol=2e-5)


# -- page pool ----------------------------------------------------------------

def test_page_pool_accounting():
    pool = PagePool(1, 9, 1, 8, 4)
    assert pool.allocatable == 8 and pool.free_pages == 8
    pages = pool.alloc(3)
    assert len(pages) == 3 and 0 not in pages   # trash page never leaves
    assert pool.used_pages == 3
    pool.free(pages[:1])
    with pytest.raises(PagePoolError):
        pool.free(pages[:1])                     # double free
    with pytest.raises(PagePoolExhausted):
        pool.alloc(99)
    assert pool.used_pages == 2                  # failed alloc took nothing
    pool.free(pages[1:])
    assert pool.leaked() == 0
    assert pool.pages_for(17) == 3 and pool.pages_for(16) == 2


def test_page_pool_rejects_degenerate_shapes():
    with pytest.raises(ValueError):
        PagePool(1, 1, 1, 8, 4)      # no room for a non-trash page
    with pytest.raises(ValueError):
        PagePool(1, 4, 1, 0, 4)


# -- engine end-to-end --------------------------------------------------------

def test_greedy_serving_matches_generate():
    paddle.seed(11)
    model = llama_tiny()           # vocab 512, pos 128, L2 GQA
    prompt = [5, 9, 11, 2, 7]
    ref = model.generate(np.asarray([prompt]), max_new_tokens=8)
    eng = _engine(model, page_size=16, num_pages=33, max_batch=2,
                  max_new_tokens=8)
    try:
        got = eng.generate(prompt, timeout=300)
    finally:
        eng.shutdown()
    assert got == [int(t) for t in ref[0, len(prompt):]]


def test_decode_program_compiles_once_across_join_leave_grow():
    """THE paged-KV contract: requests joining, leaving, and growing
    across page boundaries never retrace the decode program."""
    import paddle_tpu.observability as obs
    paddle.seed(12)
    eng = _engine(max_batch=3, page_size=4, num_pages=33,
                  max_new_tokens=10)
    try:
        first = eng.submit([1, 2, 3, 4, 5])          # join
        first.result(timeout=300)                     # leave
        reqs = [eng.submit([7 + i, 3, 9], max_new_tokens=9)
                for i in range(5)]                    # joins > slots
        for r in reqs:
            r.result(timeout=300)                     # grow across pages
        stats = eng.program_stats()["decode"]
    finally:
        eng.shutdown()
    assert stats["retraces"] == 0
    assert stats["compiles"] == 1
    assert stats["discoveries"] == 1
    assert eng.pool.leaked() == 0


def test_fifo_admission_no_starvation():
    """max_batch=1 forces strict FIFO: completion order == submit order,
    every request completes."""
    paddle.seed(13)
    eng = _engine(max_batch=1, max_new_tokens=4)
    done = []
    try:
        reqs = [eng.submit([i + 1, i + 2],
                           on_token=None, request_id=f"r{i}")
                for i in range(5)]
        for r in reqs:
            r.result(timeout=300)
            done.append(r.request_id)
        order = sorted(reqs, key=lambda r: r.t_done)
    finally:
        eng.shutdown()
    assert [r.request_id for r in order] == [f"r{i}" for i in range(5)]
    assert all(r.state == "completed" for r in reqs)


def test_admission_rejects_impossible_requests():
    import paddle_tpu.observability as obs
    eng = _engine(page_size=8, num_pages=5, max_new_tokens=4)  # 4 pages
    before = obs.value("paddle_tpu_serving_requests_total",
                       status="rejected")
    try:
        with pytest.raises(RequestRejected):
            eng.submit(list(range(1, 30)), max_new_tokens=10)  # 5 pages
        with pytest.raises(RequestRejected):
            eng.submit([1, 2], max_new_tokens=63)   # exceeds max_seq_len
    finally:
        eng.shutdown()
    assert obs.value("paddle_tpu_serving_requests_total",
                     status="rejected") - before == 2


def test_eviction_reclaims_pages_and_recovers():
    """Two active requests outgrow the pool: the youngest is evicted
    (pages reclaimed), requeues with its prefix, and BOTH complete with
    zero leaks."""
    paddle.seed(14)
    eng = _engine(page_size=4, num_pages=7, max_batch=2, max_new_tokens=14)
    try:
        a = eng.submit([1, 2, 3, 4])
        b = eng.submit([5, 6, 7, 8])
        ra, rb = a.result(300), b.result(300)
    finally:
        eng.shutdown()
    assert len(ra) == 14 and len(rb) == 14
    assert eng.scheduler.evictions >= 1
    assert eng.pool.leaked() == 0
    assert eng.program_stats()["decode"]["retraces"] == 0


def test_eos_completes_early_and_pads_nothing():
    paddle.seed(16)
    model = _model()
    eng = _engine(model, max_new_tokens=12)
    ref = eng.generate([3, 1, 4], timeout=300)
    # stop on token #3 — or earlier when the random model's greedy stream
    # repeats a token before then (the stream depends on the RNG, the
    # contract under test does not)
    stop = max(i for i in range(3) if ref[i] not in ref[:i])
    eos = ref[stop]
    eng2 = _engine(model, max_new_tokens=12, eos_token_id=eos)
    try:
        got = eng2.generate([3, 1, 4], timeout=300)
    finally:
        eng.shutdown()
        eng2.shutdown()
    assert got == ref[:stop + 1]
    assert got[-1] == eos


def test_streaming_and_callbacks():
    paddle.seed(16)
    eng = _engine(max_new_tokens=5)
    cb_tokens = []
    try:
        streamed = list(eng.stream([2, 4, 6], timeout=300))
        req = eng.submit([2, 4, 6], on_token=cb_tokens.append)
        res = req.result(timeout=300)
    finally:
        eng.shutdown()
    assert len(streamed) == 5
    assert streamed == res == cb_tokens
    assert req.ttft_ms is not None and req.e2e_ms is not None
    assert len(req.tpot_ms) == 4        # gaps after the first token


def test_sampled_decode_temperature():
    """temperature > 0 must still terminate and produce valid ids; two
    different-seed engines may diverge (sampling actually happens)."""
    paddle.seed(17)
    model = _model(vocab_size=64)
    outs = []
    for seed in (0, 1):
        eng = _engine(model, max_new_tokens=8, temperature=0.9, seed=seed)
        try:
            outs.append(eng.generate([5, 6], timeout=300))
        finally:
            eng.shutdown()
    assert all(0 <= t < 64 for o in outs for t in o)
    assert len(outs[0]) == len(outs[1]) == 8


def test_quantized_engine_serves():
    paddle.seed(18)
    model = _model(num_layers=2)
    eng = _engine(model, quant="weight_only_int8", max_new_tokens=5)
    try:
        out = eng.generate([9, 8, 7], timeout=300)
    finally:
        eng.shutdown()
    assert len(out) == 5 and all(0 <= t < 128 for t in out)
    assert eng.pool.leaked() == 0
    assert eng._sm.quantized


def test_shutdown_drain_vs_abort():
    paddle.seed(19)
    eng = _engine(max_new_tokens=30, max_batch=2)
    a = eng.submit([1, 2])
    b = eng.submit([3, 4])
    while not a.tokens or not b.tokens:
        time.sleep(0.005)
    summary = eng.shutdown(drain=True, timeout=60)
    assert summary["pages_leaked"] == 0
    assert a.state == "completed" and b.state == "completed"

    eng2 = _engine(max_new_tokens=30, max_batch=1)
    c = eng2.submit([1, 2])
    d = eng2.submit([3, 4])          # queued behind c
    while not c.tokens:
        time.sleep(0.005)
    eng2.shutdown(drain=False)
    assert eng2.pool.leaked() == 0
    for r in (c, d):
        assert r.state in ("failed", "completed")
        if r.state == "failed":
            assert r.error
            with pytest.raises(ServingError):
                r.result(timeout=1)


def test_engine_stats_and_health():
    paddle.seed(20)
    eng = _engine(max_new_tokens=4)
    try:
        eng.generate([1, 2, 3], timeout=300)
        code, payload = eng.health(stall_after_s=120.0)
        stats = eng.stats()
    finally:
        eng.shutdown()
    assert code == 200
    assert payload["mode"] == "serving"
    assert payload["status"] in ("idle", "ok")
    assert payload["decode_steps"] == stats["decode_steps"] >= 3
    assert 0 < stats["occupancy_mean"] <= 1.0
    # staleness: fake a stuck engine with queued work
    eng._last_step_wall = time.time() - 1e4
    eng.scheduler.waiting.append(object())
    code, payload = eng.health(stall_after_s=1.0)
    eng.scheduler.waiting.clear()
    assert code == 503 and payload["status"] == "stalled"


# -- HTTP mount ---------------------------------------------------------------

@pytest.fixture
def http_engine():
    from paddle_tpu.serving import server as sserver
    paddle.seed(21)
    eng = _engine(max_new_tokens=4)
    srv = sserver.serve(eng, port=0)
    yield eng, srv.port
    srv.close()
    sserver.detach()
    eng.shutdown()


def _post(port, path, payload, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def test_http_generate_roundtrip(http_engine):
    eng, port = http_engine
    r = _post(port, "/generate", {"prompt_ids": [1, 2, 3],
                                  "max_new_tokens": 3})
    body = json.loads(r.read())
    assert r.status == 200
    assert len(body["tokens"]) == 3
    assert body["state"] == "completed"
    assert body["ttft_ms"] is not None and body["e2e_ms"] is not None


def test_http_generate_streams_ndjson(http_engine):
    eng, port = http_engine
    r = _post(port, "/generate", {"prompt_ids": [4, 5], "stream": True,
                                  "max_new_tokens": 3})
    lines = [json.loads(l) for l in r.read().splitlines()]
    assert [l["token"] for l in lines[:-1]] == lines[-1]["tokens"]
    assert lines[-1]["done"] is True


def test_http_generate_validates_and_rejects(http_engine):
    eng, port = http_engine
    import urllib.error
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(port, "/generate", {"prompt_ids": "nope"})
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        _post(port, "/generate", {"prompt_ids": [1] * 200,
                                  "max_new_tokens": 50})
    assert e.value.code == 429        # admission rejection -> back off


def test_http_healthz_serving_mode_and_metrics(http_engine):
    eng, port = http_engine
    _post(port, "/generate", {"prompt_ids": [1, 2], "max_new_tokens": 2})
    h = json.loads(urllib.request.urlopen(
        f"http://127.0.0.1:{port}/healthz", timeout=30).read())
    assert h["mode"] == "serving"
    assert h["status"] in ("idle", "ok")
    assert h["decode_steps"] >= 1
    text = urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
    from test_prometheus_format import validate_exposition
    metrics = validate_exposition(text)       # grammar-valid exposition
    serving = [m for m in metrics if m.startswith("paddle_tpu_serving_")]
    assert "paddle_tpu_serving_decode_steps_total" in serving
    assert "paddle_tpu_serving_ttft_ms" in serving
    assert "paddle_tpu_serving_kv_pages" in serving


def test_healthz_training_mode_untouched_without_engine():
    """Without an attached engine the provider must defer to the PR 7
    train-step liveness payload."""
    from paddle_tpu.observability.continuous import TelemetryServer
    from paddle_tpu.serving import server as sserver
    sserver.detach()
    import urllib.error
    srv = TelemetryServer(port=0).start()
    try:
        try:
            resp = urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/healthz", timeout=30)
        except urllib.error.HTTPError as e:
            # "stalled" comes with a 503: a file this worker ran earlier
            # stepped the process-wide profiler more than the stall
            # threshold ago (the order of the files, not this test)
            assert e.code == 503
            resp = e
        h = json.loads(resp.read())
    finally:
        srv.close()
    assert "mode" not in h               # the training payload shape
    assert h["status"] in ("idle", "ok", "stalled")
