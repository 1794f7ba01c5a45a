"""Request tracing (ISSUE 16): span lifecycle/nesting, disabled-mode
type-identity no-ops + guard cost, traceparent round-trip + malformed
rejection, exemplar-to-trace join, HTTP endpoints (404, bounded
reservoir), Chrome-trace schema, strict-RFC-8259 request log, flight
integration, and a concurrent submit/complete storm (TSAN suite).
Step spans (ISSUE 25): nesting and cause, the bounded buffer, the off
switch, the serving path's spans and counts on a tiny engine, one clock for
request and step spans, wall-clock exports."""

import json
import threading
import time
import urllib.error
import urllib.request

import pytest

from paddle_tpu.observability import flight
from paddle_tpu.observability import tracing
from paddle_tpu.observability.continuous import TelemetryServer
from paddle_tpu.observability.tracing import (
    NOOP_SPAN, NOOP_TRACE, RequestTrace, TraceContext, Tracer,
    parse_traceparent)
from paddle_tpu.serving.scheduler import Request


@pytest.fixture
def tracer():
    """The global tracer, reset and enabled for the test."""
    tr = tracing.get_tracer()
    was = tr.enabled
    tr.reset()
    tr.enabled = True
    yield tr
    tr.enabled = was
    tr.reset()


def _get(port, path):
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=10) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


# -- span lifecycle ----------------------------------------------------------

def test_span_lifecycle_and_nesting(tracer):
    tr = tracing.start_request(request_id="r1", kind="test")
    assert tr.trace_id and len(tr.trace_id) == 32
    with tr.span("prefill", tokens=8) as outer:
        with tr.span("cow", parent=outer) as inner:
            pass
    rec = tr.finish(state="completed")
    assert rec["spans"] == 2 and rec["state"] == "completed"
    snap = tracing.get_trace(tr.trace_id)
    by_name = {s["name"]: s for s in snap["spans"]}
    assert by_name["prefill"]["parent_id"] == snap["root"]["span_id"]
    assert by_name["cow"]["parent_id"] == by_name["prefill"]["span_id"]
    for s in snap["spans"]:
        assert s["t_end"] >= s["t_start"]
    # idempotent finish
    assert tr.finish() is None


def test_unfinished_child_closed_at_finish(tracer):
    tr = tracing.start_request(request_id="r2")
    tr.span("stream")              # never ended
    tr.finish(state="failed")
    snap = tracing.get_trace(tr.trace_id)
    (s,) = snap["spans"]
    assert s["attributes"]["unfinished"] is True
    assert s["t_end"] is not None


def test_span_buffer_is_bounded():
    t = Tracer(enabled=True, max_spans=4, reservoir=8, log_capacity=8)
    tr = t.start_request(request_id="r")
    for i in range(10):
        tr.add_span("decode", time.perf_counter(), time.perf_counter())
    rec = tr.finish()
    assert rec["spans"] == 4 and rec["dropped_spans"] == 6


def test_coverage_union_of_child_intervals():
    t = Tracer(enabled=True)
    tr = t.start_request()
    t0 = tr.root.t_start
    # two overlapping children covering ~half the root interval
    tr.add_span("a", t0, t0 + 0.06)
    tr.add_span("b", t0 + 0.04, t0 + 0.05)   # nested inside a
    time.sleep(0.1)
    rec = tr.finish()
    assert 0.0 < rec["span_coverage"] < 1.0


# -- disabled mode -----------------------------------------------------------

def test_disabled_mode_is_type_identity_noop():
    t = Tracer(enabled=False)
    tr = t.start_request(request_id="x")
    assert tr is NOOP_TRACE
    assert tr.span("decode") is NOOP_SPAN
    assert tr.add_span("decode", 0.0, 1.0) is NOOP_SPAN
    with tr.span("prefill") as s:
        assert s is NOOP_SPAN and s.set(a=1) is NOOP_SPAN
    assert tr.finish() is None and tr.trace_id is None
    assert t.stats()["completions"] == 0


def test_disabled_mode_guard_cost_is_measured_small():
    t = Tracer(enabled=False)
    tr = t.start_request()
    n = 20000
    t0 = time.perf_counter()
    for _ in range(n):
        tr.span("decode")
    per_call = (time.perf_counter() - t0) / n
    # a disabled span must cost nanoseconds, not microseconds; 5us is
    # an extremely generous CI bound that still catches accidental
    # allocation/locking on the disabled path
    assert per_call < 5e-6, f"disabled span() costs {per_call * 1e6:.2f}us"


# -- traceparent -------------------------------------------------------------

def test_traceparent_round_trip():
    ctx = TraceContext("ab" * 16, "cd" * 8, flags=1)
    s = ctx.to_traceparent()
    assert s == f"00-{'ab' * 16}-{'cd' * 8}-01"
    back = parse_traceparent(s)
    assert back.trace_id == ctx.trace_id
    assert back.span_id == ctx.span_id and back.flags == 1


@pytest.mark.parametrize("bad", [
    None, 42, "", "garbage", "00-abc-def-01",
    "00-" + "g" * 32 + "-" + "cd" * 8 + "-01",          # non-hex
    "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",          # zero trace id
    "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",         # zero span id
    "ff-" + "ab" * 16 + "-" + "cd" * 8 + "-01",         # forbidden version
    "00-" + "AB" * 16 + "-" + "cd" * 8 + "-01",         # uppercase hex
    "00-" + "ab" * 16 + "-" + "cd" * 8,                 # missing flags
])
def test_malformed_traceparent_rejected(bad):
    assert parse_traceparent(bad) is None


def test_malformed_traceparent_does_not_fail_the_request(tracer):
    req = Request([1, 2, 3], 4, traceparent="not-a-traceparent")
    assert req.trace is not NOOP_TRACE
    assert len(req.trace.trace_id) == 32     # fresh trace, no error
    req._finish("completed")
    assert tracing.get_trace(req.trace.trace_id) is not None


def test_inbound_traceparent_joins_the_trace(tracer):
    tp = f"00-{'ab' * 16}-{'cd' * 8}-01"
    req = Request([1, 2, 3], 4, traceparent=tp)
    assert req.trace.trace_id == "ab" * 16
    snap = req.trace.snapshot()
    assert snap["root"]["parent_id"] == "cd" * 8
    # outbound context is a child of OUR root span, same trace id
    out = parse_traceparent(req.trace.context().to_traceparent())
    assert out.trace_id == "ab" * 16
    assert out.span_id == snap["root"]["span_id"]
    req._finish("cancelled")


# -- request integration -----------------------------------------------------

def test_request_finish_carries_timing_split(tracer):
    req = Request([1, 2, 3], 4)
    req._emit(7)                   # first token: ttft + stream span open
    req._finish("completed")
    assert req.decode_ms is not None
    recs = [r for r in tracing.requests()
            if r["trace_id"] == req.trace.trace_id]
    assert len(recs) == 1
    rec = recs[0]
    for k in ("queue_ms", "prefill_ms", "decode_ms", "ttft_ms",
              "span_coverage", "span_kinds"):
        assert k in rec, k
    assert "stream" in rec["span_kinds"]


def test_burst_aggregation_one_span_per_kind_run(tracer):
    req = Request([1], 4)
    t0 = time.perf_counter()
    for _ in range(5):
        req._trace_step("decode", t0)
    req._trace_step("speculate", t0, tokens=2, proposed=3, accepted=1)
    req._trace_flush()
    req._finish("completed")
    snap = tracing.get_trace(req.trace.trace_id)
    kinds = [s["name"] for s in snap["spans"]]
    # 5 decode steps collapsed into ONE span; kind change flushed it
    assert kinds.count("decode") == 1 and kinds.count("speculate") == 1
    dec = next(s for s in snap["spans"] if s["name"] == "decode")
    assert dec["attributes"]["steps"] == 5
    rec = snap["record"]
    assert rec["spec"] == {"proposed": 3, "accepted": 1}


def test_exemplar_joins_top_bucket_to_trace(tracer):
    req = Request([1, 2], 4)
    req._emit(9)
    req._finish("completed")
    ex = tracing.exemplars()
    top = ex["paddle_tpu_serving_ttft_ms"]["top"]
    assert top["trace_id"] == req.trace.trace_id
    assert tracing.get_trace(top["trace_id"]) is not None


# -- bounded global state ----------------------------------------------------

def test_reservoir_evicts_oldest():
    t = Tracer(enabled=True, reservoir=4, log_capacity=4)
    ids = []
    for i in range(10):
        tr = t.start_request(request_id=f"r{i}")
        ids.append(tr.trace_id)
        tr.finish()
    assert t.stats()["reservoir"] <= 4
    assert t.get_trace(ids[0]) is None         # oldest evicted
    assert t.get_trace(ids[-1]) is not None    # newest kept
    assert len(t.requests()) == 4              # log ring bounded too


def test_live_table_bounded_on_leaked_requests():
    t = Tracer(enabled=True, reservoir=4, log_capacity=4)
    for i in range(t._live_capacity + 20):
        t.start_request(request_id=f"leak{i}")  # never finished
    assert t.stats()["live"] <= t._live_capacity
    assert t.stats()["dropped_live"] >= 20


def test_sampled_reservoir_keeps_every_nth():
    t = Tracer(enabled=True, reservoir=64, log_capacity=64, sample_every=3)
    kept = 0
    for i in range(9):
        tr = t.start_request()
        tr.finish()
        kept += t.get_trace(tr.trace_id) is not None
    assert kept == 3                      # 1 in 3 full span trees
    assert len(t.requests()) == 9         # but EVERY request logged


# -- HTTP endpoints ----------------------------------------------------------

def test_requests_and_trace_endpoints(tracer):
    tr = tracing.start_request(request_id="httpreq")
    tr.add_span("decode", time.time(), time.time())
    tr.finish(state="completed", queue_ms=1.5)
    srv = TelemetryServer(port=0, host="127.0.0.1").start()
    try:
        code, body = _get(srv.port, "/requests")
        assert code == 200
        payload = json.loads(body)
        assert payload["enabled"] is True
        assert any(r["trace_id"] == tr.trace_id
                   for r in payload["requests"])
        code, body = _get(srv.port, f"/trace/{tr.trace_id}")
        assert code == 200
        snap = json.loads(body)
        assert snap["trace_id"] == tr.trace_id
        assert snap["spans"][0]["name"] == "decode"
        code, body = _get(srv.port, "/trace/" + "0" * 32)
        assert code == 404 and b"unknown trace id" in body
        code, _ = _get(srv.port, "/requests?last=oops")
        assert code == 400
    finally:
        srv.close()


# -- exporters ---------------------------------------------------------------

def test_chrome_trace_schema(tracer):
    tr = tracing.start_request(request_id="ct")
    tr.add_span("prefill", time.time(), time.time() + 0.01)
    tr.finish()
    open_span = {"name": "request", "span_id": "a" * 16,
                 "parent_id": None, "t_start": time.time(), "t_end": None,
                 "trace_id": "b" * 32, "request_id": "open1"}
    ct = tracing.to_chrome_trace([tracing.get_trace(tr.trace_id)],
                                 open_spans=[open_span])
    assert isinstance(ct["traceEvents"], list)
    phs = set()
    for ev in ct["traceEvents"]:
        assert {"name", "ph", "ts", "pid", "tid", "args"} <= set(ev)
        assert isinstance(ev["ts"], float)
        phs.add(ev["ph"])
        if ev["ph"] == "X":
            assert ev["dur"] >= 0
    # closed spans render complete; the open span is KEPT as a begin
    # event (flight death-span convention), never dropped
    assert phs == {"X", "B"}
    json.dumps(ct)  # serializable


def test_request_log_is_strict_rfc8259(tracer):
    tr = tracing.start_request(request_id="nan")
    tr.add_span("decode", time.time(), time.time(),
                loss=float("nan"), lr=float("inf"))
    tr.finish(state="completed", bad=float("nan"))
    text = tracing.render_request_log()

    def boom(tok):
        raise AssertionError(f"bare {tok} token in request log")

    for line in text.strip().splitlines():
        rec = json.loads(line, parse_constant=boom)   # strict parse
        assert rec["trace_id"] == tr.trace_id
        assert rec["bad"] == "nan"


def test_flight_dump_carries_open_spans(tracer, tmp_path):
    tr = tracing.start_request(request_id="inflight")
    tr.span("prefill")
    rec = flight.FlightRecorder(capacity=8, enabled=True)
    rec.dump_dir = str(tmp_path)
    rec.record("step", step=1)
    path = rec.dump("death", step=1)
    payload = json.loads(open(path).read())
    spans = payload["tracing"]["open_spans"]
    assert any(s["request_id"] == "inflight" and s["name"] == "request"
               for s in spans)
    assert any(s["name"] == "prefill" for s in spans)
    tr.finish(state="failed")


def test_cli_renders_dump_with_open_spans(tracer, tmp_path):
    dump = {
        "tracing": {"open_spans": [], "traces": [], "requests": []},
        "extra": {"tracing_at_preempt": {"open_spans": [
            {"name": "request", "span_id": "a" * 16, "parent_id": None,
             "t_start": 123.0, "t_end": None, "trace_id": "c" * 32,
             "request_id": "rq1"}]}},
    }
    p = tmp_path / "flight_test.json"
    p.write_text(json.dumps(dump))
    out = tmp_path / "chrome.json"
    assert tracing.main([str(p), "--chrome-trace", str(out)]) == 0
    ct = json.loads(out.read_text())
    bevs = [e for e in ct["traceEvents"] if e["ph"] == "B"]
    assert bevs and bevs[0]["args"]["request_id"] == "rq1"
    assert tracing.main([str(tmp_path / "missing.json")]) == 2


# -- concurrency -------------------------------------------------------------

def test_concurrent_submit_complete_storm(tracer):
    """8 threads x 40 requests: open, span, finish, while readers
    snapshot — runs under PADDLE_TPU_TSAN=1 in the tsan_check suite."""
    n_threads, per_thread = 8, 40
    errors: list = []
    done = threading.Event()

    def worker(wid):
        try:
            for i in range(per_thread):
                tr = tracing.start_request(request_id=f"w{wid}-{i}")
                with tr.span("prefill"):
                    pass
                tr.add_span("decode", time.time(), time.time(), steps=3)
                tracing.note_exemplar("storm_ms", float(i), tr.trace_id,
                                      buckets=(10.0, 100.0))
                tr.finish(state="completed")
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    def reader():
        while not done.is_set():
            tracing.open_spans()
            tracing.requests(8)
            tracing.stats()
            tracing.exemplars()

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(n_threads)]
    r = threading.Thread(target=reader)
    r.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    done.set()
    r.join()
    assert not errors
    st = tracer.stats()
    assert st["completions"] == n_threads * per_thread
    assert st["live"] == 0
    assert st["spans_total"] == 2 * n_threads * per_thread


# -- step spans (ISSUE 25) ---------------------------------------------------

def _by_id(spans):
    return {s["span_id"]: s for s in spans}


def test_step_spans_nest_and_carry_the_causing_span(tracer):
    with tracing.span("serving.step") as outer:
        with tracing.span("serving.decode", request_id="r") as mid:
            mid.count(rows=2, positions=10)
            with tracing.span("engine.dispatch") as inner:
                pass
        with tracing.span("serving.emit"):
            pass
    got = tracing.step_spans()
    assert got["dropped"] == 0 and got["dropped_until"] is None
    by_name = {s["name"]: s for s in got["spans"]}
    assert set(by_name) == {"serving.step", "serving.decode",
                            "engine.dispatch", "serving.emit"}
    assert by_name["serving.step"]["parent_id"] is None
    assert by_name["serving.decode"]["parent_id"] == outer.span_id
    assert by_name["engine.dispatch"]["parent_id"] == mid.span_id
    assert by_name["serving.emit"]["parent_id"] == outer.span_id
    assert by_name["serving.decode"]["counts"] == {"rows": 2,
                                                   "positions": 10}
    assert by_name["serving.decode"]["attributes"] == {"request_id": "r"}
    assert inner.span_id != mid.span_id != outer.span_id
    for s in got["spans"]:      # a child lies inside its cause
        p = _by_id(got["spans"]).get(s["parent_id"])
        if p is not None:
            assert p["t_start"] <= s["t_start"] <= s["t_end"] <= p["t_end"]
    # since / until select by start, on the span clock
    t = by_name["serving.emit"]["t_start"]
    assert [s["name"] for s in tracing.step_spans(since=t)["spans"]] == \
        ["serving.emit"]
    assert "serving.emit" not in [
        s["name"] for s in tracing.step_spans(until=t - 1e-9)["spans"]]


def test_step_span_cause_is_per_thread(tracer):
    seen = {}

    def other():
        with tracing.span("io.next") as sp:
            seen["parent"] = sp.parent_id

    with tracing.span("jit.run"):
        th = threading.Thread(target=other)
        th.start()
        th.join()
        with tracing.span("engine.pull") as mine:
            pass
    assert seen["parent"] is None       # another thread's span is no cause
    assert mine.parent_id is not None


def test_program_tables_are_bounded_and_count_what_they_drop():
    t = Tracer(enabled=True)
    assert t._steps.capacity == tracing.STEP_CAPACITY
    n = tracing.PROGRAM_VARIANTS
    for i in range(n + 3):      # one name, a signature each
        t.note_program("jit_pure_arrays__f", {"fusion": f"jit(f)/s{i}/dot"})
    t.note_program("jit_pure_arrays__g", {"copy": "jit(g)/forward/copy"})
    progs = t.programs()
    f = progs["jit_pure_arrays__f"]
    assert f["dropped"] == 3 and len(f["variants"]) == n
    assert f["variants"][-1] == {"fusion": f"jit(f)/s{n + 2}/dot"}
    assert progs["jit_pure_arrays__g"] == {
        "variants": [{"copy": "jit(g)/forward/copy"}], "dropped": 0}
    t.reset()
    assert t.programs() == {}


def test_step_buffer_is_bounded_and_counts_what_it_drops():
    t = Tracer(enabled=True, step_capacity=4)
    starts = []
    for i in range(10):
        with t.span("jit.run", i=i) as sp:
            starts.append(sp.t_start)
    got = t.step_spans()
    assert [s["attributes"]["i"] for s in got["spans"]] == [6, 7, 8, 9]
    assert got["dropped"] == 6
    # the latest start among the dropped: an interval after it is whole
    assert got["dropped_until"] == starts[5]
    assert t.stats()["step_spans"] == 4
    assert t.stats()["step_spans_dropped"] == 6
    t.reset()
    assert t.step_spans() == {"spans": [], "dropped": 0,
                              "dropped_until": None}


def test_step_span_error_is_recorded_and_the_stack_unwinds(tracer):
    with pytest.raises(ValueError):
        with tracing.span("serving.step"):
            with tracing.span("serving.decode"):
                raise ValueError("boom")
    with tracing.span("serving.step") as after:
        pass
    assert after.parent_id is None
    errs = [s for s in tracing.step_spans()["spans"]
            if "error" in s["attributes"]]
    assert {s["name"] for s in errs} == {"serving.step", "serving.decode"}


def _tiny_engine(**kw):
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import llama_tiny
    from paddle_tpu.serving import LLMEngine, ServingConfig
    paddle.seed(0)
    model = llama_tiny(vocab_size=128, max_position_embeddings=64,
                       hidden_size=32, num_layers=1, num_heads=2,
                       num_kv_heads=1, intermediate_size=64)
    cfg = dict(page_size=8, num_pages=17, max_batch=2, max_new_tokens=5,
               prefix_cache=False)
    cfg.update(kw)
    return LLMEngine(model, ServingConfig(**cfg))


PROMPTS = [[3, 5, 7], list(range(1, 12)), [9] * 20, [4, 4]]


def test_off_means_noop_span_and_an_empty_buffer():
    tr = tracing.get_tracer()
    was = tr.enabled
    tr.reset()
    tr.enabled = False
    try:
        assert tracing.span("serving.step", request_id="x") is NOOP_SPAN
        with tracing.span("serving.decode") as sp:
            assert sp is NOOP_SPAN and sp.count(rows=1) is NOOP_SPAN
            assert sp.t_start is None and sp.span_id is None
        eng = _tiny_engine()
        try:
            for p in PROMPTS[:2]:
                assert len(eng.generate(p, timeout=300)) == 5
        finally:
            eng.shutdown(drain=False)
        assert tracing.step_spans() == {"spans": [], "dropped": 0,
                                        "dropped_until": None}
        assert tracing.programs() == {}
    finally:
        tr.enabled = was
        tr.reset()


@pytest.fixture
def served(tracer):
    """A tiny engine's run with tracing on: (step spans, requests)."""
    import paddle_tpu.observability as obs
    def counted():
        # the KV positions are counted by group of the cache: a model with
        # no window layers has the global group alone
        return {k: obs.value("paddle_tpu_serving_" + n, kind=k, **labels)
                for n, ks, labels in (
                    ("prefill_tokens_total", ("real", "padded"), {}),
                    ("kv_positions_total", ("live", "gathered"),
                     {"group": "global"}))
                for k in ks}

    before = counted()
    eng = _tiny_engine()
    try:
        reqs = [eng.submit(p) for p in PROMPTS]
        for r in reqs:
            r.result(timeout=300)
    finally:
        eng.shutdown(drain=False)
    after = counted()
    got = tracing.step_spans()
    assert got["dropped"] == 0
    return got["spans"], reqs, {k: after[k] - before[k] for k in after}


def test_serving_spans_count_real_padded_live_and_gathered(served):
    spans, reqs, counted = served
    pre = [s for s in spans if s["name"] == "serving.prefill"]
    assert sum(s["counts"]["tokens"] for s in pre) == \
        sum(len(p) for p in PROMPTS)
    assert {s["attributes"]["request_id"] for s in pre} == \
        {r.request_id for r in reqs}
    for s in pre:
        assert s["counts"]["bucket"] >= s["counts"]["tokens"] > 0
    dec = [s for s in spans if s["name"] == "serving.decode"]
    assert dec
    for s in dec:
        c = s["counts"]
        assert 1 <= c["rows"] <= 2
        assert c["gathered"] >= c["positions"] >= c["rows"]
        # the whole table of every slot: max_batch x max_pages x page_size
        assert c["gathered"] == 2 * 8 * 8
    # the operator's counters count the same, at the same two places
    assert counted["real"] == sum(s["counts"]["tokens"] for s in pre)
    assert counted["padded"] == sum(s["counts"]["bucket"] for s in pre)
    assert counted["live"] == sum(s["counts"]["positions"] for s in dec)
    assert counted["gathered"] == sum(s["counts"]["gathered"] for s in dec)


def test_serving_spans_follow_the_layer_boundaries(served):
    spans, _, _ = served
    by_id = _by_id(spans)

    def ancestors(s):
        while s["parent_id"] is not None and s["parent_id"] in by_id:
            s = by_id[s["parent_id"]]
            yield s["name"]

    names = {s["name"] for s in spans}
    assert {"serving.step", "serving.admit", "serving.prefill",
            "serving.decode", "serving.emit", "engine.upload",
            "engine.dispatch", "engine.pull", "jit.run"} <= names
    assert names <= set(tracing.STEP_SPANS)
    for s in spans:
        par = by_id.get(s["parent_id"])
        if s["name"].startswith("engine."):
            assert par is not None and par["name"] == "serving.decode"
            assert par["t_start"] <= s["t_start"] <= s["t_end"] \
                <= par["t_end"]
        elif s["name"] == "serving.step":
            assert par is None
        elif s["name"] in ("serving.admit", "serving.decode"):
            assert par["name"] == "serving.step"
        elif s["name"] == "serving.prefill":
            assert par["name"] == "serving.admit"
        elif s["name"] == "serving.emit":
            assert par["name"] in ("serving.step", "serving.admit")
        elif s["name"] == "jit.run":
            assert "serving.step" in set(ancestors(s))
            assert s["attributes"]["fn"].startswith("serving.")
            assert s["attributes"]["phase"] in ("eager", "compile", "run")
    # every count a span carries is one a metric reads
    for s in spans:
        assert set(s["counts"]) <= {
            "serving.prefill": {"tokens", "bucket"},
            "serving.decode": {"rows", "positions", "gathered",
                               "sampling_rows"},
        }.get(s["name"].replace("_chunk", "").replace("verify", "decode"),
              set()), s
    # upload, dispatch, pull: in that order, one of each under a decode
    for d in (s for s in spans if s["name"] == "serving.decode"):
        kids = sorted((s for s in spans if s["parent_id"] == d["span_id"]),
                      key=lambda s: s["t_start"])
        assert [k["name"] for k in kids] == [
            "engine.upload", "engine.dispatch", "engine.pull"]


def test_request_and_step_spans_share_one_clock(served):
    spans, reqs, _ = served
    by_id = _by_id(spans)
    for r in reqs:
        snap = tracing.get_trace(r.trace.trace_id)
        assert snap["clock"] == "perf_counter"
        pre = next(s for s in snap["spans"] if s["name"] == "prefill")
        step = by_id[pre["attributes"]["step_span"]]
        assert step["name"] == "serving.prefill"
        assert step["attributes"]["request_id"] == r.request_id
        # the request's prefill lies inside the step span that served it
        assert step["t_start"] <= pre["t_start"] <= pre["t_end"] \
            <= step["t_end"]
        root = snap["root"]
        assert root["t_start"] <= pre["t_start"] and \
            pre["t_end"] <= root["t_end"]
        assert abs(root["t_start"] - time.perf_counter()) < 600


def test_exported_times_are_wall_clock(served, tmp_path):
    spans, reqs, _ = served
    now = time.time()
    assert abs(tracing.to_wall(time.perf_counter()) - now) < 1.0
    snaps = [tracing.get_trace(r.trace.trace_id) for r in reqs]
    ct = tracing.to_chrome_trace(snaps, steps=spans)
    cats = {ev["cat"] for ev in ct["traceEvents"]}
    assert cats == {"request", "step"}
    for ev in ct["traceEvents"]:
        assert abs(ev["ts"] * 1e-6 - now) < 120.0, ev
    step_evs = [e for e in ct["traceEvents"] if e["cat"] == "step"]
    assert any(e["name"] == "serving.decode" and "gathered" in e["args"]
               for e in step_evs)
    # a snapshot read back from a dump is wall clock already: not shifted
    wall = tracing.flight_snapshot()["traces"]
    assert wall and all(t["clock"] == "wall" for t in wall)
    again = tracing.to_chrome_trace(wall)
    assert all(abs(ev["ts"] * 1e-6 - now) < 120.0
               for ev in again["traceEvents"])
    for rec in tracing.requests():
        assert abs(rec["t_start"] - now) < 120.0
        assert rec["t_end"] >= rec["t_start"]
    live = tracing.start_request(request_id="open-now")
    srv = TelemetryServer(port=0, host="127.0.0.1").start()
    try:
        (o,) = [s for s in tracing.open_spans()
                if s["request_id"] == "open-now"]
        assert abs(o["t_start"] - time.time()) < 1.0
        # GET /trace/<id>: the snapshot leaves the process, in wall clock
        code, body = _get(srv.port, f"/trace/{live.trace_id}")
        served_open = json.loads(body)
        assert code == 200 and served_open["clock"] == "wall"
        assert abs(served_open["root"]["t_start"] - time.time()) < 1.0
        code, body = _get(srv.port, f"/trace/{reqs[0].trace.trace_id}")
        done = json.loads(body)
        assert code == 200 and done["clock"] == "wall"
        for sp in [done["root"]] + done["spans"]:
            assert abs(sp["t_start"] - now) < 120.0
            assert sp["t_end"] >= sp["t_start"]
    finally:
        srv.close()
        live.finish(state="failed")


def test_tracer_does_not_time_itself(tracer):
    tr = tracing.start_request(request_id="c")
    tr.add_span("decode", time.perf_counter(), time.perf_counter())
    tr.finish()
    assert not {"cost_s", "span_cost_us"} & set(tracing.stats())
    assert tracing.stats()["spans_total"] == 1
