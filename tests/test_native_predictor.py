"""Native PJRT serving engine tests (reference analog: the fake-device
plugin test in paddle/phi/backends/custom/fake_cpu_device.h +
test/custom_runtime — the device ABI is exercised end to end in CI with a
fake plugin; real hardware swaps in without code changes)."""

import os
import shutil

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import inference
from paddle_tpu.inference import native

g_pp = shutil.which("g++")
pytestmark = pytest.mark.skipif(g_pp is None, reason="no C++ toolchain")

_CSRC = os.path.join(os.path.dirname(__file__), "..", "paddle_tpu", "csrc")


@pytest.fixture(scope="module")
def fake_plugin(tmp_path_factory):
    from paddle_tpu.utils.cpp_extension import _build_so
    cflags = []
    for inc in native._engine_include_dirs():
        cflags += ["-I", inc]
    return _build_so(
        "fake_pjrt", [os.path.abspath(os.path.join(_CSRC,
                                                   "fake_pjrt_plugin.cc"))],
        cflags, [], str(tmp_path_factory.mktemp("fake_plugin")), True)


class _TwoLinear(paddle.nn.Layer):
    def __init__(self):
        super().__init__()
        self.fc1 = paddle.nn.Linear(8, 16)
        self.fc2 = paddle.nn.Linear(16, 4)

    def forward(self, x):
        return self.fc2(paddle.nn.functional.relu(self.fc1(x)))


@pytest.fixture(scope="module")
def exported(tmp_path_factory):
    model = _TwoLinear()
    path = str(tmp_path_factory.mktemp("native") / "model")
    out = inference.export_native(
        model, path,
        [paddle.static.InputSpec([2, 8], "float32", name="x")])
    return model, out


def test_container_roundtrip(exported):
    model, path = exported
    c = native.read_container(path)
    # 4 params (2 weights + 2 biases) + 1 input, in flattened (sorted) order
    kinds = [a[0] for a in c.args]
    assert kinds == [0, 0, 0, 0, 1]
    assert c.args[-1][4] == "x"
    assert c.args[-1][2] == (2, 8)
    assert len(c.outs) == 1
    assert c.outs[0][1] == (2, 4)
    assert b"module" in c.mlir[:4096]
    assert len(c.copts) > 0  # serialized CompileOptionsProto
    total = sum(a[3] for a in c.args if a[0] == 0)
    assert len(c.weights) == total


def test_tpu_lowered_program(exported):
    """The container's module is lowered for the TPU target (the native
    engine's deployment platform), not the host CPU."""
    _, path = exported
    c = native.read_container(path)
    assert b"stablehlo" in c.mlir or b"mhlo" in c.mlir


def test_fake_plugin_roundtrip(exported, fake_plugin, tmp_path):
    """Full ABI pass through the C++ engine against the fake plugin: dlopen,
    version check, client+device discovery, compile, h2d, execute, d2h. The
    fake executes identity, so output0 must be byte-exact input0 (the first
    flattened param)."""
    model, path = exported
    pred = inference.NativePredictor(
        path, plugin_path=fake_plugin,
        build_directory=str(tmp_path / "engine"))
    assert pred.platform == "fake"
    assert pred.get_input_names() == ["x"]
    x = np.random.default_rng(0).normal(size=(2, 8)).astype(np.float32)
    out, = pred.run([x])
    first_param_name = sorted(model.state_dict().keys())[0]
    first_param = np.asarray(model.state_dict()[first_param_name].numpy())
    np.testing.assert_array_equal(out, first_param)


def test_create_predictor_native_path(exported, fake_plugin):
    _, path = exported
    cfg = inference.Config(path[:-len(".ptpu")])
    cfg.enable_native_engine(plugin_path=fake_plugin)
    pred = inference.create_predictor(cfg)
    assert isinstance(pred, inference.NativePredictor)


def test_static_shape_contract(exported, fake_plugin):
    _, path = exported
    pred = inference.NativePredictor(path, plugin_path=fake_plugin)
    with pytest.raises(ValueError, match="static-shape"):
        pred.run([np.zeros((3, 8), np.float32)])


def test_bad_plugin_errors(exported, tmp_path):
    _, path = exported
    with pytest.raises(RuntimeError, match="dlopen|GetPjrtApi"):
        inference.NativePredictor(path,
                                  plugin_path=str(tmp_path / "absent.so"))


def test_dynamic_spec_rejected(tmp_path):
    model = _TwoLinear()
    with pytest.raises(ValueError, match="static"):
        inference.export_native(
            model, str(tmp_path / "m"),
            [paddle.static.InputSpec([-1, 8], "float32", name="x")])


@pytest.mark.skipif(native.default_plugin_path() is None,
                    reason="no libtpu plugin in image")
def test_libtpu_numeric_parity(exported, tmp_path):
    """Real-hardware path: compile + execute through libtpu and compare with
    the host forward. Requires an attached TPU (guarded by env)."""
    if os.environ.get("PTPU_RUN_TPU_NATIVE") != "1":
        pytest.skip("set PTPU_RUN_TPU_NATIVE=1 on a TPU host")
    model, path = exported
    pred = inference.NativePredictor(path)
    x = np.random.default_rng(0).normal(size=(2, 8)).astype(np.float32)
    out, = pred.run([x])
    ref = model(paddle.to_tensor(x)).numpy()
    np.testing.assert_allclose(out, np.asarray(ref), rtol=2e-2, atol=2e-2)
