"""Nothing on the main paths falls back in silence: an unknown chip, a
failed kernel-example trace, a missing TPU or a second process on a chip
host is an error, and a serving program says which path it took."""

import json
import os
import sys

import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.ops.kernels import _common as kern

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


class _Dev:
    def __init__(self, kind, platform="tpu"):
        self.device_kind, self.platform = kind, platform


def test_unknown_tpu_kind_has_no_default_peak():
    from paddle_tpu.observability import device_peak_flops
    assert device_peak_flops(_Dev("TPU v5 lite"))[0] == 197e12
    assert device_peak_flops(_Dev("cpu", "cpu")) == (0.0, "cpu")
    with pytest.raises(KeyError, match="TPU v9x"):
        device_peak_flops(_Dev("TPU v9x"))


def test_chip_name_reads_the_device_and_refuses_unknown(monkeypatch):
    from paddle_tpu.cost_model import collective
    monkeypatch.delenv("PADDLE_TPU_CHIP", raising=False)
    assert collective.chip_name() == "cpu"            # the attached device
    assert collective.chip_name("v5e") == "v5e"       # an explicit name
    for kind, preset in (("TPU v5 lite", "v5e"), ("TPU v5p", "v5p"),
                         ("TPU v6 lite", "v6e"), ("TPU v4", "v4")):
        monkeypatch.setattr(jax, "devices", lambda k=kind: [_Dev(k)])
        assert collective.chip_name() == preset
        assert collective.chip_vmem_bytes() == \
            collective.CHIP_PRESETS[preset]["vmem_bytes"]
    monkeypatch.setattr(jax, "devices", lambda: [_Dev("TPU v9x")])
    with pytest.raises(KeyError, match="TPU v9x"):
        collective.chip_vmem_bytes()
    with pytest.raises(KeyError):
        collective.roofline_ms(1.0, 1.0, "v9x")


def test_kernel_availability_does_not_swallow_backend_errors(monkeypatch):
    def boom():
        raise RuntimeError("backend init failed")
    monkeypatch.setattr(jax, "devices", boom)
    kern._on_tpu.cache_clear()
    try:
        with pytest.raises(RuntimeError, match="backend init failed"):
            kern.available()
    finally:
        monkeypatch.undo()
        kern._on_tpu.cache_clear()
    assert kern.available() is False                  # CPU: composites


def test_kernels_stand_down_under_a_multi_device_mesh(monkeypatch):
    """Mosaic kernels cannot be partitioned automatically: on a TPU they
    dispatch on one device, and under a mesh the composites run."""
    from paddle_tpu.distributed.fleet import DistributedStrategy, fleet
    from paddle_tpu.distributed.topology import reset_topology_state
    monkeypatch.setattr(kern, "_on_tpu", lambda: True)
    assert kern.available() and not kern.partitioned()
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
                               "sharding_degree": 2, "sep_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    try:
        assert kern.partitioned() and not kern.available()
    finally:
        reset_topology_state()
    assert kern.available()


def test_failed_example_trace_is_an_error_finding(tmp_path):
    from paddle_tpu.analysis.diagnostics import ERROR
    from paddle_tpu.analysis.kernels import collect, has_errors
    mod = tmp_path / "broken_pallas.py"
    mod.write_text(
        "import jax\n"
        "from jax.experimental import pallas as pl\n"
        "def f(x):\n"
        "    raise TypeError('api moved')\n"
        "    return pl.pallas_call(None)(x)\n"
        "def pk_examples():\n"
        "    return [('broken', f, (jax.ShapeDtypeStruct((8, 128), 'float32'),), {})]\n")
    findings, sheets = collect([str(mod)])
    assert sheets == []
    failed = [f for f in findings if f.rule_id == "PK209"
              and "example trace failed" in f.message]
    assert failed and all(f.severity == ERROR for f in failed)
    assert has_errors(findings)       # zero kernels modelled is not "clean"


def test_every_kernel_module_is_modelled():
    from paddle_tpu.analysis.kernels.model import extract_module
    kdir = os.path.join(REPO, "paddle_tpu", "ops", "kernels")
    mods = sorted(f for f in os.listdir(kdir) if f.endswith("_pallas.py"))
    assert len(mods) == 15
    for f in mods:
        models, notes = extract_module(os.path.join(kdir, f))
        assert models and not [n for n in notes if n.failed], (f, notes)


def test_flash_gate_refuses_blocks_off_the_sublane_tile():
    from paddle_tpu.ops.kernels import flash_attention as fa
    kern.force_dispatch(True)
    try:
        assert fa._pallas_ok(jnp.zeros((1, 16, 4, 64)))
        assert fa._pallas_ok(jnp.zeros((1, 1024, 4, 64)))
        assert not fa._pallas_ok(jnp.zeros((1, 7, 4, 64)))     # 7-row block
        assert not fa._pallas_ok(jnp.zeros((1, 300, 4, 64)))   # 300 % 256
    finally:
        kern.force_dispatch(False)


def test_launcher_refuses_several_children_on_a_chip_host(monkeypatch):
    import importlib
    launch = importlib.import_module("paddle_tpu.distributed.launch.main")
    monkeypatch.setattr(launch, "_local_tpu_chips", lambda: 4)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="one process per host"):
        launch.launch(["--nproc_per_node", "2", "train.py"])


def test_bench_without_a_tpu_is_an_error_not_a_cpu_number(capsys):
    import bench
    assert bench.main([]) == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["metric"] == "bench_failed" and line["platform"] == "cpu"
    assert "no TPU" in line["error"] and "unit" not in line


def test_bench_section_that_raises_fails_the_run(monkeypatch, capsys):
    import bench

    def boom(*a, **k):
        raise RuntimeError("section blew up")
    monkeypatch.setattr(bench, "run_serve_bench", boom)
    assert bench.main(["serve", "--cpu"]) == 1
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "section blew up" in line["error"]


def test_serving_programs_report_the_path_they_took():
    from paddle_tpu.models.llama import llama_tiny
    from paddle_tpu.serving import LLMEngine, ServingConfig
    paddle.seed(3)
    eng = LLMEngine(llama_tiny(), ServingConfig(max_new_tokens=3))
    try:
        eng.generate([1, 2, 3, 4, 5], timeout=300)
        stats = eng.program_stats()
    finally:
        eng.shutdown()
    assert stats["decode"]["path"] == {"attention": "composite",
                                       "junction": "composite"}
    assert stats["prefill"]["path"] == {"junction": "composite"}
    assert stats["verify"]["path"] == {}              # never traced


def test_to_static_state_is_what_outlives_the_step():
    """Activations of the eager discovery call are not program state, and
    state the step only reads is not copied out of the compiled program."""
    import numpy as np
    from paddle_tpu.models import gpt2_tiny
    paddle.seed(0)
    model = gpt2_tiny()
    opt = paddle.optimizer.AdamW(1e-3, parameters=model.parameters())
    x = paddle.to_tensor(np.zeros((2, 32), np.int32))

    @paddle.jit.to_static
    def step(x):
        _, loss = model(x, labels=x)
        loss.backward()
        opt.step()
        opt.clear_grad()
        return loss

    @paddle.jit.to_static
    def infer(x):
        with paddle.no_grad():
            return model(x)

    for _ in range(2):
        step(x), infer(x)
    n_params = sum(p.size for p in model.parameters())
    (state,) = step._state_by_key.values()
    held = sum(t.size for t in state if t.dtype == paddle.float32)
    assert held <= 3 * n_params + 64        # weights + two Adam moments
    (_, cell, inf_state), = infer._cache.values()
    assert len(inf_state) >= len(list(model.parameters()))
    assert cell["written"] == []            # weights are read, not returned
