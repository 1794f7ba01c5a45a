#!/usr/bin/env python3
"""Read, on the chip and at a cell's own size, the numbers its limits are
set from: the program's (the lower reading is their largest over the seeds)
and the control's and the planted faults' (the upper reading is their
smallest). One process for all seeds of a cell.

    python3 perfbench/tools/limits.py <workload> <seconds> <n_control> <seed> [<seed> ...]

The control and the faults are read on the first `n_control` seeds by the
cell's driver (`limits_readings` there says what they are), and every
reading is put through `compare.judge` with the cell's limits, as a run's
are. Prints one JSON line per seed, the verdicts and a summary; also written
to chiprun_out/limits.<workload>.jsonl.
"""

import importlib
import json
import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def report(rows, limits):
    """Every reading through `compare.judge` with the cell's own limits, as
    a run's are (the program has to pass on every seed, each control and
    fault to fail on every seed it was read on), then each number's lower
    reading beside the least that each control and fault gave."""
    from perfbench.harness import compare
    variants = sorted({k for r in rows for k in r
                       if k.startswith(("control_", "fault_"))})
    for r in rows:
        for key in ["program"] + [v for v in variants if v in r]:
            ok, judged = compare.judge(r[key], limits)
            failed = [n for n, val, lim in judged
                      if lim is not None and not val <= lim]
            print(f"judge seed {r['seed']} {key}: correct={ok}"
                  + (f" failed={failed}" if failed else ""))
    names = [n for n, v in rows[0]["program"].items()
             if isinstance(v, float)]
    for n in names:
        lower = max(r["program"][n] for r in rows)
        uppers = {key: min(r[key][n] for r in rows if key in r)
                  for key in variants if n in next(
                      r[key] for r in rows if key in r)}
        print(f"summary {n}: lower (program, max over {len(rows)} seeds) "
              f"{lower:.6g}; " + "; ".join(
                  f"{k} (min) {v:.6g}" for k, v in uppers.items()))


def main():
    workload, seconds, n_control = (sys.argv[1], float(sys.argv[2]),
                                    int(sys.argv[3]))
    seeds = [int(s) for s in sys.argv[4:]]
    from perfbench.harness import common
    device = common.start_program(1)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg = common.load_json("configs", cell["config"] + ".json")
    mix = common.load_json("traffic", cell["traffic"] + ".json")
    ctx = {"config": cfg, "traffic": mix, "seconds": seconds,
           "t_start": T0, "on_chip": True, "trace": False, "tracer": None,
           "device": device, "workload": workload}
    out = os.path.join(ROOT, "chiprun_out", f"limits.{workload}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    rows = []
    with open(out, "a") as f:
        def emit(row):
            rows.append(row)
            slim = {k: v for k, v in row.items()
                    if k != "reference_grad_norms"}
            print(json.dumps(slim), flush=True)
            f.write(json.dumps(row) + "\n")
            f.flush()
        driver = importlib.import_module("perfbench.drivers." + cfg["driver"])
        driver.limits_readings(ctx, seeds, n_control, emit)
    report(rows, common.load_json("limits", workload + ".json"))
    print("memory_peak_bytes", common.memory_peak_bytes(), "seconds",
          round(time.perf_counter() - T0, 1))


if __name__ == "__main__":
    main()
