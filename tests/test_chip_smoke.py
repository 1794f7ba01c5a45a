"""chip_smoke.py rehearsed on the CPU: same control flow at toy widths,
and no fallback — without a TPU it never reports success."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")
sys.path.insert(0, REPO)


def _run(args, cwd=REPO, script=SMOKE, n_devices=1, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}")
    out = subprocess.run([sys.executable, script, *args], cwd=cwd, env=env,
                         capture_output=True, text=True, timeout=timeout)
    lines = [json.loads(l) for l in out.stdout.splitlines()
             if l.startswith("{")]
    return out, lines


def test_no_chip_fails_at_once_without_running_a_phase():
    out, lines = _run([])
    assert out.returncode != 0
    assert len(lines) == 1 and lines[0]["ok"] is False
    assert lines[0]["device"]["platform"] == "cpu"
    assert "no TPU" in lines[0]["error"]


def test_alone_in_a_directory_it_fails(tmp_path):
    import shutil
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "chip_smoke.py", "--tiny"],
                         cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok": true' not in out.stdout


def test_tiny_rehearsal_runs_both_phases_and_still_reports_no_chip():
    out, lines = _run(["--tiny"])
    assert out.returncode != 0, out.stdout
    *phases, last = lines
    assert last == {"ok": False, "device": {"platform": "cpu", "kind": "cpu",
                                            "count": 1}}
    assert [p["phase"] for p in phases] == ["train", "serve"]
    assert all(p["ok"] for p in phases), out.stdout + out.stderr[-3000:]
    train, serve = phases
    assert all(p["platform"] == "cpu" and p["device_count"] == 1
               for p in phases)
    assert train["retraces_after_warmup"] == 0
    assert train["losses"][-1] < train["warmup_losses"][0]
    assert serve["token_exact_requests"] == serve["requests"] == 4
    assert max(serve["greedy_margins"]) < 1e-3
    assert serve["programs"]["decode"]["compiles"] == 1
    assert serve["programs"]["decode"]["path"]["attention"] == "composite"
    assert serve["pages_leaked"] == serve["pages_lost"] == 0


def test_tiny_hybrid_rehearsal_on_four_virtual_devices():
    out, lines = _run(["--tiny", "--chips", "4"], n_devices=4)
    assert out.returncode != 0, out.stdout
    hybrid, last = lines
    assert last["ok"] is False and last["device"]["count"] == 4
    assert hybrid["phase"] == "hybrid" and hybrid["ok"], \
        out.stdout + out.stderr[-3000:]
    assert abs(hybrid["loss"] - hybrid["dense_loss"]) < 1e-2
    assert any(s["devices"] > 1 for s in hybrid["sharding"])


def test_chips_option_must_match_the_devices_jax_sees():
    out, lines = _run(["--tiny", "--chips", "4"], n_devices=1)
    assert out.returncode != 0 and lines[-1]["ok"] is False
    assert "--chips 4" in lines[-1]["error"]


def test_kernels_in_names_custom_calls_by_their_wrapper():
    import chip_smoke
    hlo = "\n".join([
        '%a = f32[8] custom-call(%x), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(pure_arrays)/attention/kv_gather/jit(paged_mmha_decode)/pallas_call"}',
        '%b = f32[8] custom-call(%x), custom_call_target="tpu_custom_call", '
        'metadata={op_name="jit(pure_arrays)/attention/kv_gather/jit(paged_mmha_decode)/pallas_call"}',
        '%c = f32[8] custom-call(%x), custom_call_target="Sharding"',
    ])
    assert chip_smoke.kernels_in(hlo) == {"paged_mmha_decode": 2}


class _FixedLogits:
    """A 'model' whose logits are the same at every position."""

    def __init__(self, logits):
        self._logits = np.asarray(logits, np.float32)

    def __call__(self, ids):
        import paddle_tpu as paddle
        b, n = ids.shape
        return paddle.to_tensor(np.tile(self._logits, (b, n, 1)))


def test_greedy_margins_measure_distance_from_the_dense_argmax():
    import chip_smoke
    model = _FixedLogits([5.0, 4.95, 1.0])
    margins = chip_smoke.greedy_margins(
        model, [[1, 2], [2]], [[0, 0, 0], [0, 1, 2]])
    assert margins[0] == 0.0                      # always the argmax
    assert margins[1] == pytest.approx(4.0)       # token 2 is 4.0 away
    assert chip_smoke.greedy_margins(model, [[1]], [[1, 0]])[0] == \
        pytest.approx(0.05, abs=1e-5)             # a tie within NEAR_TIE
    assert 0.05 < chip_smoke.NEAR_TIE < 4.0
