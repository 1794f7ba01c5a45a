"""Bench harness stays runnable: tiny-dims smoke of the 8B-layer
microbench, the perf gate's history discipline, and the one-process driver
(no chip and no ``--cpu`` is an error, never a CPU number)."""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))


def test_llama8b_layer_microbench_tiny_dims():
    import bench
    from paddle_tpu.device import force_cpu_backend
    from paddle_tpu.models.llama import LlamaConfig

    dev = force_cpu_backend().devices("cpu")[0]
    cfg = LlamaConfig(vocab_size=512, hidden_size=64, num_layers=4,
                      num_heads=4, num_kv_heads=2, intermediate_size=128)
    r = bench.run_llama8b_layer_bench(dev, cfg=cfg, n_layers=2, batch=2,
                                      seq=64, steps=2, warmup=1,
                                      use_amp=False)
    assert r["tokens_per_sec_2layer"] > 0
    assert r["n_layers_measured"] == 2
    # attn (q+k+v+o) + mlp (gate+up+down) + 2 rmsnorm weights
    h, kv, m = 64, 2 * 16, 128
    expect = (h * h + 2 * h * kv + h * h) + 3 * h * m + 2 * h
    assert r["params_per_layer"] == expect
    # cpu → no peak flops → mfu stays 0 rather than garbage
    assert r["layer_mfu_8b_dims"] == 0.0


def test_perf_gate_best_of_last3_history(tmp_path):
    """r5 gate discipline (VERDICT r4 #10): baseline = best of the last 3
    rounds, 3% tolerance, signed delta printed."""
    import json
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    gate = os.path.join(root, "tools", "perf_gate.py")
    vals = {1: 1000.0, 2: 1573.0, 3: 1400.0, 4: 1500.0}
    for r, v in vals.items():
        with open(tmp_path / f"BENCH_r{r:02d}.json", "w") as f:
            json.dump({"metric": "toks", "value": v}, f)
    cur = tmp_path / "cur.json"
    # best of last 3 (r2..r4) = 1573; 1540 is -2.1% -> OK at 3%
    with open(cur, "w") as f:
        json.dump({"metric": "toks", "value": 1540.0}, f)
    out = subprocess.run(
        [sys.executable, gate, "--history",
         str(tmp_path / "BENCH_r*.json"), "--current", str(cur)],
        capture_output=True, text=True)
    assert out.returncode == 0, out.stdout
    assert "best-of-last-3" in out.stdout and "r02" in out.stdout
    assert "delta -2.10%" in out.stdout, out.stdout
    # 1518 is -3.5% below the best -> REGRESSION (the r4 case, now loud)
    with open(cur, "w") as f:
        json.dump({"metric": "toks", "value": 1518.0}, f)
    out = subprocess.run(
        [sys.executable, gate, "--history",
         str(tmp_path / "BENCH_r*.json"), "--current", str(cur)],
        capture_output=True, text=True)
    assert out.returncode == 1
    assert "REGRESSION" in out.stdout
