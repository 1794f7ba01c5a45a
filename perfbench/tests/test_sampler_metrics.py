"""The sampler's two metrics, each read through its own file under
`metrics/`: `head_sample_device_share` from the small trace recorded on the
chip (its programs given the decode program's name and a hand-made
instruction table), `sampling_rows_share` from a traced run of the tiny Llama
cell on the CPU, whose traffic is greedy. They stand in a file of their own
because a PR may edit no file the benchmark has.
"""

import importlib
import json
import os
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
DECODE = "jit_pure_arrays__serving_decode_step"


def read(name, observed, **more):
    with open(os.path.join(HERE, "..", "metrics", name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("perfbench.readers." + spec["reader"])
    return reader.read(observed, **spec["args"], **more)


def test_head_sample_device_share_reads_the_decode_program_alone():
    from perfbench.harness import trace
    red = trace.reduce(os.path.join(HERE, "data", "small.xplane.pb"))
    red["modules"] = [(s, e, f"{DECODE}({i})")
                      for i, (s, e, _) in enumerate(red["modules"])]
    tables = {DECODE: {"dropped": 0, "variants": [{
        "fusion": f"jit({DECODE[4:]})/head_sample/dot_general",
        "multiply_add_fusion": f"jit({DECODE[4:]})/mlp/mul",
        "copy-done": f"jit({DECODE[4:]})/head_sample/cond/branch_1_fun/add"}]}}
    got = read("head_sample_device_share", {"trace": red}, tables=tables)
    mm, _ = trace.time_by_pattern(red["ops"], [r"^fusion$", r"^copy-done$"])
    inside = sum(e - s for s, e in trace.union(red["ops"]))
    assert got == pytest.approx(100.0 * mm / inside, rel=1e-6)
    assert 0.0 < got < 100.0
    # a program of another name, or no table (a parent commit): nothing
    other = {"jit_pure_arrays__serving_prefill": tables[DECODE]}
    assert read("head_sample_device_share", {"trace": red},
                tables=other) is None
    assert read("head_sample_device_share", {"trace": red},
                tables={}) is None


def span(i, name, t0, **counts):
    return {"name": name, "span_id": i, "parent_id": None, "t_start": t0,
            "t_end": t0 + 0.01, "counts": counts, "attributes": {}}


def test_sampling_rows_share_is_sampling_rows_over_rows():
    obs = {"trace_interval": (0.0, 10.0)}
    spans = [span(1, "serving.decode", 1.0, rows=3, sampling_rows=1),
             span(2, "serving.decode", 2.0, rows=4, sampling_rows=0),
             span(3, "serving.verify", 3.0, rows=1, sampling_rows=1),
             span(4, "serving.decode", 11.0, rows=9, sampling_rows=9)]
    buf = {"spans": spans, "dropped": 0, "dropped_until": None}
    assert read("sampling_rows_share", obs, buffer=buf) == \
        pytest.approx(100.0 * 2 / 8)
    # a program that does not count them (a parent commit) reads 0, as
    # greedy traffic does; one without step spans reads nothing
    for s in spans:
        del s["counts"]["sampling_rows"]
    assert read("sampling_rows_share", obs, buffer=buf) == 0.0
    assert read("sampling_rows_share", obs,
                buffer={"spans": [], "dropped": 0,
                        "dropped_until": None}) is None


@pytest.fixture
def tracer_on():
    """A traced run switches the program's tracer on through the environment,
    which a process that has made its tracer (an earlier test's untraced
    run) no longer reads: switch it on here, and back after."""
    from paddle_tpu.observability import tracing
    tr = tracing.get_tracer()
    was = tr.enabled
    tr.reset()
    tr.enabled = True
    yield
    tr.enabled = was
    tr.reset()


def test_tiny_llama_cell_reads_no_sampling_row(tracer_on):
    from perfbench import run as bench_run
    from perfbench.tests import cells
    bench = dict(cells.BENCH, per_layer=[
        {"name": "sampling_rows_share", "unit": "%"},
        {"name": "head_sample_device_share", "unit": "%"}])
    result = bench_run.run_cell(bench, "llama-tiny.chat_tiny", 2 ** 31 + 5,
                                11.0, True, require_chip=False,
                                t_start=time.perf_counter())
    assert result["correct"], result["compared"]
    assert result["metrics"]["sampling_rows_share"] == \
        {"value": 0.0, "unit": "%"}
    # no device trace off the chip: the share is left out, not reported low
    assert "head_sample_device_share" not in result["metrics"]
