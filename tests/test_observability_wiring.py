"""Integration-point telemetry tests: the jit trace cache, collectives,
the dataloader, profiler spans + chrome-trace merge, StepTimer, and the
bench/perf_gate telemetry block."""

import contextlib
import json
import os
import subprocess
import sys
import time

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.observability as obs

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def setup_function(_fn):
    obs.reset()


def test_jit_trace_cache_metrics():
    @paddle.jit.to_static
    def obs_fn(x):
        return (x * 2).sum()

    # the fn label is the wrapped callable's __qualname__ (disambiguates
    # Layer methods sharing a bare __name__)
    lbl = obs_fn.__qualname__
    a = paddle.to_tensor(np.ones((2, 2), np.float32))
    b = paddle.to_tensor(np.ones((3, 3), np.float32))
    obs_fn(a)  # discovery: miss
    obs_fn(a)  # compiled-signature hit
    assert obs.value("paddle_tpu_jit_trace_cache_misses_total", fn=lbl) == 1
    obs_fn(b)  # second shape: miss AND retrace
    assert obs.value("paddle_tpu_jit_trace_cache_misses_total", fn=lbl) == 2
    assert obs.value("paddle_tpu_jit_trace_cache_retraces_total",
                     fn=lbl) == 1
    obs_fn(b)
    obs_fn(a)
    assert obs.value("paddle_tpu_jit_trace_cache_hits_total", fn=lbl) == 3
    assert obs.value("paddle_tpu_jit_trace_cache_entries", fn=lbl) == 2
    assert obs.value("paddle_tpu_jit_compiles_total", fn=lbl) == 2
    assert obs.value("paddle_tpu_jit_trace_seconds_total", fn=lbl) > 0
    # acceptance demo: snapshot has the counters, text exposition parses
    snap = obs.dump()
    assert "paddle_tpu_jit_trace_cache_misses_total" in snap
    text = obs.serve_text()
    type_lines = [l for l in text.splitlines() if l.startswith("# TYPE ")]
    assert len(type_lines) == len(
        obs.get_registry().metrics())  # one TYPE line per metric
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        name_part, val = line.rsplit(" ", 1)
        float(val)  # every sample line ends in a parseable number


def test_comm_all_reduce_records_payload_bytes():
    from paddle_tpu.distributed.communication import all_reduce, broadcast
    from paddle_tpu.distributed.communication.group import Group

    g = Group([0, 1], name="fake_group")
    t = paddle.to_tensor(np.ones((4, 4), np.float32))
    all_reduce(t, group=g)
    assert obs.value("paddle_tpu_comm_calls_total", op="all_reduce",
                     group="fake_group") == 1
    assert obs.value("paddle_tpu_comm_payload_bytes_total", op="all_reduce",
                     group="fake_group") == 64  # 4*4 float32
    broadcast(t, src=0, group=g)
    assert obs.value("paddle_tpu_comm_calls_total", op="broadcast",
                     group="fake_group") == 1
    # group=None records under the world group
    all_reduce(t)
    assert obs.value("paddle_tpu_comm_calls_total", op="all_reduce",
                     group="world") == 1


def test_dataloader_wait_and_compute_histograms():
    class DS(paddle.io.Dataset):
        def __getitem__(self, i):
            return np.ones(2, np.float32)

        def __len__(self):
            return 8

    loader = paddle.io.DataLoader(DS(), batch_size=2, num_workers=0)
    batches = list(loader)
    assert len(batches) == 4
    wait = obs.get_registry().get("paddle_tpu_io_batch_wait_seconds").value()
    comp = obs.get_registry().get("paddle_tpu_io_compute_seconds").value()
    assert wait["count"] == 4           # one wait sample per batch
    assert comp["count"] == 3           # gaps BETWEEN batches only
    assert wait["sum"] >= 0


def test_record_event_counter_survives_window_and_trace_merges(tmp_path):
    from paddle_tpu.profiler import Profiler, RecordEvent

    # spans count even with NO active profiler (survive outside windows)
    with RecordEvent("obs_span"):
        pass
    assert obs.value("paddle_tpu_profiler_events_total",
                     name="obs_span") == 1

    prof = Profiler(timer_only=True)
    with prof:
        with RecordEvent("obs_span"):
            paddle.ones([2]).sum()
        prof.step()
    assert obs.value("paddle_tpu_profiler_events_total",
                     name="obs_span") == 2
    path = str(tmp_path / "trace.json")
    prof.export(path)
    data = json.load(open(path))
    # trace events unchanged; telemetry merged under its own key
    assert any(e["name"] == "obs_span" for e in data["traceEvents"])
    assert "paddle_tpu_profiler_events_total" in data["telemetry"]


def test_step_timer_records_latency_tokens_and_mfu():
    st = obs.StepTimer("wiring", tokens_per_step=1000,
                       flops_per_token=2.0, peak_flops=1e6)
    with st:
        time.sleep(0.01)
    assert st.last_step_s >= 0.009
    assert obs.value("paddle_tpu_step_total", name="wiring") == 1
    tps = obs.value("paddle_tpu_step_tokens_per_second", name="wiring")
    assert 0 < tps < 1000 / 0.009
    assert abs(obs.value("paddle_tpu_step_mfu_ratio", name="wiring")
               - tps * 2.0 / 1e6) < 1e-12
    # externally-timed window (the bench pattern)
    stats = st.record_window(steps=10, tokens=20000, seconds=2.0)
    assert stats["step_seconds"] == 0.2
    assert stats["tokens_per_sec"] == 10000.0
    assert obs.value("paddle_tpu_step_total", name="wiring") == 11
    st.record_transfer(4096)
    assert obs.value("paddle_tpu_step_transfer_bytes_total",
                     name="wiring") == 4096


def test_peak_flops_table_shared_with_bench():
    sys.path.insert(0, REPO)
    import bench

    class Dev:
        platform = "tpu"
        device_kind = "TPU v5e"

    flops, src = bench._peak_flops(Dev())
    assert flops == 197e12 and src.startswith("device_kind")

    class Cpu:
        platform = "cpu"
        device_kind = ""

    assert bench._peak_flops(Cpu()) == (0.0, "cpu")


def test_bench_attach_telemetry_block():
    sys.path.insert(0, REPO)
    import bench

    obs.counter("paddle_tpu_test_bench_total", "wiring-test marker").inc()
    r = bench._attach_telemetry({"metric": "m", "value": 1.0})
    assert isinstance(r["telemetry"], dict)
    assert "metrics" in r["telemetry"]
    assert "trace_cache_retraces" in r["telemetry"]["steady_state"]
    # disabled -> null with a reason
    obs.enable(False)
    try:
        r2 = bench._attach_telemetry({"metric": "m", "value": 1.0})
    finally:
        obs.enable(True)
    assert r2["telemetry"] is None
    assert "PADDLE_TPU_METRICS" in r2["telemetry_reason"]


def test_perf_gate_fails_on_steady_state_retraces(tmp_path):
    gate = os.path.join(REPO, "tools", "perf_gate.py")
    base = tmp_path / "base.json"
    cur_ok = tmp_path / "ok.json"
    cur_retrace = tmp_path / "retrace.json"
    base.write_text(json.dumps({"metric": "m", "value": 100.0}))
    cur_ok.write_text(json.dumps(
        {"metric": "m", "value": 101.0,
         "telemetry": {"metrics": {},
                       "steady_state": {"trace_cache_retraces": 0}}}))
    cur_retrace.write_text(json.dumps(
        {"metric": "m", "value": 150.0,
         "telemetry": {"metrics": {},
                       "steady_state": {"trace_cache_retraces": 3}}}))

    def run(cur):
        return subprocess.run(
            [sys.executable, gate, "--baseline", str(base),
             "--current", str(cur)], capture_output=True, text=True)

    ok = run(cur_ok)
    assert ok.returncode == 0, ok.stdout + ok.stderr
    bad = run(cur_retrace)
    assert bad.returncode == 1, bad.stdout + bad.stderr
    assert "RETRACE" in bad.stdout


# -- ISSUE 25: named programs, named scopes, jit.run and io.next spans -------

def _tiny_train_step(name="train_step"):
    from paddle_tpu.models import gpt2_tiny
    paddle.seed(0)
    model = gpt2_tiny()
    opt = paddle.optimizer.AdamW(3e-4, parameters=model.parameters())

    def step(x, y):
        _, loss = model(x, labels=y)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    step.__name__ = step.__qualname__ = name
    data = np.arange(8 * 17).reshape(8, 17) % 1024
    return (paddle.jit.to_static(step), paddle.to_tensor(data[:, :-1]),
            paddle.to_tensor(data[:, 1:]))


@contextlib.contextmanager
def _tracing_set(on):
    """The process-wide tracer, emptied and switched `on` for the block."""
    from paddle_tpu.observability import tracing
    tr = tracing.get_tracer()
    was = tr.enabled
    tr.reset()
    tr.enabled = on
    try:
        yield tracing
    finally:
        tr.enabled = was
        tr.reset()


def test_to_static_program_is_named_after_its_function():
    import jax

    @paddle.jit.to_static
    def scale(x):
        return x * 2.0 + 1.0

    x = paddle.to_tensor(np.ones((4, 4), np.float32))
    for _ in range(2):
        scale(x)
    want = "pure_arrays__test_to_static_program_is_named_after_its_" \
           "function__locals__scale"
    assert scale._program_name == want
    (jitted, cell, _), = scale._cache.values()
    lowered = jitted.lower(*cell["avals"])
    assert f"module @jit_{want} " in lowered.as_text()[:400]
    assert f"HloModule jit_{want}" in lowered.compile().as_text()[:400]
    # the prefix the accepted metric files match on is kept
    assert jitted.__wrapped__.__name__.startswith("pure_arrays__")
    assert isinstance(jitted, type(jax.jit(lambda a: a)))


def test_train_step_hlo_carries_forward_backward_optimizer():
    step, x, y = _tiny_train_step()
    for _ in range(2):
        float(step(x, y))
    (text,) = step.compiled_text_cached()
    import re
    ops = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("forward", "backward", "optimizer"):
        mine = [o for o in ops
                if re.match(r"jit\([^/]*\)/(jit\(main\)/)?%s/" % scope, o)]
        assert len(mine) > 20, (scope, len(mine))
    # each op under one of the three, named by the outermost scope
    assert not [o for o in ops if "/forward/forward/" in o]
    assert any(o.startswith("jit(pure_arrays__train_step)/") for o in ops)


def test_jit_run_spans_name_function_and_phase():
    with _tracing_set(True) as tracing:
        step, x, y = _tiny_train_step("wired_step")
        for _ in range(4):
            float(step(x, y))
        runs = [s for s in tracing.step_spans()["spans"]
                if s["name"] == "jit.run"
                and s["attributes"]["fn"] == "wired_step"]
        assert [s["attributes"]["phase"] for s in runs] == \
            ["eager", "compile", "run", "run"]
        assert runs[1]["t_end"] - runs[1]["t_start"] > \
            10 * (runs[3]["t_end"] - runs[3]["t_start"])
        # with tracing on, the compiled program hands over the table that
        # names the code behind each instruction
        entry = tracing.programs()["jit_pure_arrays__wired_step"]
        assert entry["dropped"] == 0
        table, = entry["variants"]
        scopes = {o.split("/")[1] for o in table.values() if "/" in o}
        assert {"forward", "backward", "optimizer"} <= scopes


def test_tracing_off_leaves_no_spans_and_no_program_tables():
    with _tracing_set(False) as tracing:
        step, x, y = _tiny_train_step("quiet_step")
        for _ in range(3):
            float(step(x, y))
        assert tracing.step_spans()["spans"] == []
        assert tracing.programs() == {}


def test_dataloader_next_is_a_step_span():
    from paddle_tpu.io import DataLoader, Dataset

    class Rows(Dataset):
        def __len__(self):
            return 12

        def __getitem__(self, i):
            return np.full((3,), i, np.float32)

    with _tracing_set(True) as tracing:
        n = sum(1 for _ in DataLoader(Rows(), batch_size=4))
        spans = [s for s in tracing.step_spans()["spans"]
                 if s["name"] == "io.next"]
        # one per batch, and the one that found the end
        assert n == 3 and len(spans) == 4


def test_serving_programs_carry_their_scopes():
    from paddle_tpu.models.llama import llama_tiny
    from paddle_tpu.serving import LLMEngine, ServingConfig
    with _tracing_set(True) as tracing:
        paddle.seed(0)
        model = llama_tiny(vocab_size=128, max_position_embeddings=64,
                           hidden_size=32, num_layers=1, num_heads=2,
                           num_kv_heads=1, intermediate_size=64)
        eng = LLMEngine(model, ServingConfig(
            page_size=8, num_pages=17, max_batch=2, max_new_tokens=4,
            prefix_cache=False))
        try:
            for _ in range(2):
                eng.generate([1, 2, 3, 4, 5], timeout=300)
        finally:
            eng.shutdown(drain=False)
        progs = tracing.programs()
        decode, = progs["jit_pure_arrays__serving_decode_step"]["variants"]
        prefill, = progs["jit_pure_arrays__serving_prefill"]["variants"]

        def scopes(table):
            return {p for o in table.values() for p in o.split("/")[1:-1]}

        assert {"kv_gather", "kv_write", "attention", "mlp",
                "head_sample"} <= scopes(decode)
        assert {"kv_write", "attention", "mlp", "head_sample"} \
            <= scopes(prefill)
        assert "kv_gather" not in scopes(prefill)
        assert eng.gathered_positions("decode") == 2 * 8 * 8
