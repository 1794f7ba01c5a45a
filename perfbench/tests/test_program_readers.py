"""The two readers of what the program says about itself: `program_span` on a
hand-made buffer of step spans, `device_time_share` on the small trace
recorded on the chip with a hand-made instruction table."""

import os

import pytest

from perfbench.harness import trace
from perfbench.readers import device_time_share, program_span

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def span(i, name, t0, t1, parent=None, counts=None, **attrs):
    return {"name": name, "span_id": i, "parent_id": parent, "t_start": t0,
            "t_end": t1, "counts": counts or {}, "attributes": attrs}


def buffer():
    """Three scheduler steps at t = 10, 20, 30 (the second admits and
    prefills), after a warm-up that compiled at t = 1..2."""
    spans = [
        span(1, "jit.run", 1.0, 2.0, fn="serving.decode_step",
             phase="compile"),
        span(2, "serving.prefill", 3.0, 3.4,
             counts={"tokens": 100, "bucket": 128}),
    ]
    i = 10
    for k, (t, dur) in enumerate(((10.0, 0.080), (20.0, 0.100),
                                  (30.0, 0.090))):
        step, adm, dec = i, i + 1, i + 2
        end = t + dur + 0.010
        spans.append(span(step, "serving.step", t, end))
        spans.append(span(adm, "serving.admit", t + 0.001, t + 0.002, step))
        if k == 1:      # admits one request: a prefill of 0.2 s
            spans[-1]["t_end"] = t + 0.204
            spans.append(span(i + 9, "serving.prefill", t + 0.003, t + 0.203,
                              adm, {"tokens": 300, "bucket": 512}))
            t, end = t + 0.204, end + 0.204
            spans[-3]["t_end"] = end
        spans.append(span(dec, "serving.decode", t + 0.003, t + 0.003 + dur,
                          step, {"positions": 1000 * (k + 1),
                                 "gathered": 8000}))
        spans.append(span(i + 3, "engine.upload", t + 0.003, t + 0.0035, dec))
        spans.append(span(i + 4, "engine.dispatch", t + 0.0035, t + 0.0045,
                          dec))
        spans.append(span(i + 5, "jit.run", t + 0.0036, t + 0.0044, i + 4,
                          fn="serving.decode_step", phase="run"))
        spans.append(span(i + 6, "engine.pull", t + 0.0045, t + 0.003 + dur,
                          dec))
        spans.append(span(i + 7, "serving.emit", t + 0.004 + dur,
                          t + 0.006 + dur, step))
        i += 10
    # a step that only admitted and decoded nothing
    spans.append(span(90, "serving.step", 40.0, 40.001))
    return {"spans": spans, "dropped": 0, "dropped_until": None}


OBS = {"trace_interval": (15.0, 35.0)}


def read(**args):
    return program_span.read(OBS, buffer=buffer(), **args)


def test_median_of_durations_in_the_interval():
    # steps 2 and 3 start inside (15, 35): 100 and 90 ms
    assert read(span="serving.decode", scale=1000.0) == pytest.approx(95.0)
    # since warm-up ended (the compile at t = 2): all three
    assert read(span="serving.decode", interval="steady", scale=1000.0) == \
        pytest.approx(90.0)


def test_steady_reads_none_once_a_compile_lands_in_the_window():
    buf = buffer()
    # a new signature compiled after the trace began
    buf["spans"].append(span(91, "jit.run", 21.0, 22.0,
                             fn="serving.prefill", phase="compile"))
    assert program_span.read(OBS, span="serving.decode", interval="steady",
                             buffer=buf) is None
    assert program_span.read(OBS, span="serving.decode",
                             buffer=buf) is not None
    # or one the run counted inside its window, before the trace began
    counted = dict(OBS, counters={"compiles": 1})
    assert program_span.read(counted, span="serving.decode",
                             interval="steady", buffer=buffer()) is None
    assert program_span.read(dict(OBS, counters={"compiles": 0}),
                             span="serving.decode", interval="steady",
                             buffer=buffer()) is not None


def test_spans_of_several_names_are_one_set_of_samples():
    # the chunked path's and the speculative path's spans carry the same
    # counts as the monolithic prefill's and the decode step's
    buf = buffer()
    buf["spans"] += [
        span(92, "serving.prefill_chunk", 31.0, 31.1,
             counts={"tokens": 200, "bucket": 256}),
        span(93, "serving.step", 32.0, 32.2),
        span(94, "serving.verify", 32.01, 32.19, 93,
             {"positions": 3000, "gathered": 8000}),
        span(95, "engine.upload", 32.01, 32.013, 94),
        span(96, "engine.dispatch", 32.013, 32.014, 94)]

    def both(**args):
        return program_span.read(OBS, buffer=buf, **args)

    assert both(span=["serving.prefill", "serving.prefill_chunk"],
                ratio=["bucket", "tokens"]) == pytest.approx(768 / 500)
    assert both(span=["serving.decode", "serving.verify"],
                ratio=["gathered", "positions"]) == \
        pytest.approx(24000 / 8000)
    # upload + dispatch: 1.5, 1.5 and 4 ms
    assert both(span=["serving.decode", "serving.verify"], scale=1000.0,
                sum_of=["engine.upload", "engine.dispatch"]) == \
        pytest.approx(1.5)
    # the steps' own time: 9 and 7 ms where they decoded, 20 where verified
    assert both(span="serving.step", value="self", scale=1000.0,
                has="serving.verify") == pytest.approx(20.0)
    assert both(span="serving.step", value="self", scale=1000.0,
                has="serving.decode") == pytest.approx(8.0)
    assert both(span="serving.step", value="self", scale=1000.0,
                has=["serving.decode", "serving.verify"]) == \
        pytest.approx(9.0)


def test_ratio_of_summed_counts():
    assert read(span="serving.decode", ratio=["gathered", "positions"]) == \
        pytest.approx(16000 / 5000)
    assert read(span="serving.prefill", ratio=["bucket", "tokens"],
                interval="steady") == pytest.approx(640 / 400)
    assert read(span="serving.prefill", ratio=["bucket", "tokens"]) == \
        pytest.approx(512 / 300)
    assert read(span="serving.prefill", ratio=["bucket", "nothing"]) is None


def test_value_per_count():
    # 0.2 s over 300 tokens, as ms per 1000 tokens
    assert read(span="serving.prefill", per="tokens", scale=1e6) == \
        pytest.approx(666.666, rel=1e-4)


def test_self_time_and_sums_over_the_subtree():
    # the scheduler's own time in a step that decoded: the step less its
    # children (1 ms before admit + 1 ms between + 1 ms + 4 ms after), the
    # admit pass less its prefill (1 ms; 4 ms with the prefill inside), the
    # emit (2 ms)
    got = program_span.read(
        {"trace_interval": (25.0, 35.0)}, buffer=buffer(),
        span="serving.step", value="self", has="serving.decode",
        sum_of=["serving.step", "serving.admit", "serving.emit"],
        scale=1000.0)
    assert got == pytest.approx(1 + 1 + 1 + 4 + 1 + 2)
    # upload + dispatch under a decode; the jit.run under dispatch is not
    # counted twice
    assert read(span="serving.decode", scale=1000.0,
                sum_of=["engine.upload", "engine.dispatch"]) == \
        pytest.approx(1.5)
    assert read(span="serving.step", has="serving.prefill",
                value="duration") == pytest.approx(0.314)


def test_where_matches_attributes():
    assert read(span="jit.run", where={"fn": "decode_step$",
                                       "phase": "^run$"},
                scale=1000.0) == pytest.approx(0.8)
    assert read(span="jit.run", where={"phase": "^eager$"}) is None
    assert read(span="jit.run", where={"fn": "train_step$"}) is None


def test_none_where_there_is_nothing_to_read():
    assert read(span="serving.verify") is None
    assert program_span.read({}, span="serving.decode",
                             buffer=buffer()) is None
    assert program_span.read(OBS, span="serving.decode",
                             buffer={"spans": [], "dropped": 0,
                                     "dropped_until": None}) is None
    # the step at t = 40 decoded nothing
    assert program_span.read({"trace_interval": (39.0, 41.0)},
                             span="serving.step", has="serving.decode",
                             buffer=buffer()) is None


def test_none_where_the_buffer_dropped_spans_of_the_interval():
    buf = buffer()
    buf.update(dropped=3, dropped_until=12.0)
    assert program_span.read(OBS, span="serving.decode",
                             buffer=buf) is not None     # before it began
    buf["dropped_until"] = 16.0
    assert program_span.read(OBS, span="serving.decode", buffer=buf) is None
    assert program_span.read(OBS, span="serving.decode", interval="steady",
                             buffer=buf) is None


def test_a_program_without_step_spans_reads_none(monkeypatch):
    from paddle_tpu.observability import tracing
    monkeypatch.delattr(tracing, "step_spans")
    assert program_span.read(OBS, span="serving.decode") is None
    monkeypatch.delattr(tracing, "programs")
    assert device_time_share.read({"trace": trace.reduce(SMALL)},
                                  patterns=["."]) is None


def test_device_time_share_of_a_scope():
    red = trace.reduce(SMALL)
    assert {m[2].rsplit("(", 1)[0] for m in red["modules"]} == \
        {"jit__lambda"}
    # the two programs share a name: their tables lie side by side, and
    # `fusion` (the matmul) is named by one of them alone
    tables = {"jit__lambda": {"dropped": 0, "variants": [
        {"fusion": "jit(_lambda)/forward/dot_general",
         "copy-done": "jit(_lambda)/forward/copy"},
        {"multiply_add_fusion": "jit(_lambda)/optimizer/mul"}]}}
    obs = {"trace": red}
    fwd = device_time_share.read(obs, ["/forward/"], tables=tables)
    opt = device_time_share.read(obs, ["/optimizer/"], tables=tables)
    mm, _ = trace.time_by_pattern(red["ops"], [r"^fusion$", r"^copy-done$"])
    el, _ = trace.time_by_pattern(red["ops"], [r"^multiply_add_fusion$"])
    assert fwd == pytest.approx(100.0 * mm / red["busy_s"], rel=1e-6)
    assert opt == pytest.approx(100.0 * el / red["busy_s"], rel=1e-6)
    assert 90.0 < fwd + opt <= 100.0      # copy-start has no op_name
    assert device_time_share.read(obs, ["/backward/"], tables=tables) is None
    assert device_time_share.read(obs, ["/forward/"], tables={}) is None
    # two programs of one name that disagree about an instruction, in a way
    # that matters to the pattern: undecided, so no share and not a low one
    second = tables["jit__lambda"]["variants"][1]
    second["fusion"] = "jit(_lambda)/forward/transpose"
    assert device_time_share.read(obs, ["/forward/"], tables=tables) == \
        pytest.approx(fwd)
    second["fusion"] = "jit(_lambda)/optimizer/dot_general"
    assert device_time_share.read(obs, ["/forward/"], tables=tables) is None
    assert device_time_share.read(obs, ["/optimizer/"], tables=tables) is None
    assert device_time_share.read(obs, ["/backward/"], tables=tables) is None
    # tables of the name dropped by the tracer: those left may not cover it
    del second["fusion"]
    tables["jit__lambda"]["dropped"] = 1
    assert device_time_share.read(obs, ["/forward/"], tables=tables) is None
