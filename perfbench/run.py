#!/usr/bin/env python3
"""Run one cell of BENCHMARK.json and print its result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

A cell is a configuration (`configs/<config>.json`, which names its driver)
under a traffic mix (`traffic/<mix>.json`), judged by `limits/<cell>.json`.
With `--trace 0` the line carries the cell's end-to-end metrics; with
`--trace 1` its per-layer metrics, each read by the reader that
`metrics/<name>.json` names. No chip is an error: nothing falls back to the
CPU. See README.md beside this file.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _applies(metric: dict, workload: str) -> bool:
    return "workloads" not in metric or workload in metric["workloads"]


def _json(path):
    with open(path) as f:
        return json.load(f)


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, require_chip: bool = True,
             t_start: float | None = None) -> dict:
    """Drive one run of one cell and return the result line as a dict.
    `require_chip=False` is for the CPU tests of the harness alone."""
    cell = next((w for w in bench["workloads"] if w["name"] == workload), None)
    if cell is None:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json")
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    conf_path = os.path.join(ROOT, conf["file"])
    base = os.path.dirname(os.path.dirname(conf_path))
    config = _json(conf_path)
    mix = _json(os.path.join(base, "traffic", cell["traffic"] + ".json"))
    limits = _json(os.path.join(base, "limits", workload + ".json"))

    from perfbench.harness import common, compare, tracing
    device = common.start_program(
        int(cell["chips"]) if require_chip else None, trace)
    on_chip = device["platform"] == "tpu"
    driver = importlib.import_module("perfbench.drivers." + config["driver"])
    tracer = None
    if trace:
        tracer = tracing.WindowTracer(driver.TRACE_AFTER_S,
                                      driver.TRACE_SECONDS)
    ctx = {"config": config, "traffic": mix, "seed": int(seed),
           "seconds": float(seconds), "trace": bool(trace), "tracer": tracer,
           "t_start": T_START if t_start is None else t_start,
           "on_chip": on_chip, "device": device, "workload": workload}
    out = driver.run(ctx)

    correct, rows = compare.judge(out["numbers"], limits)
    device = dict(device, memory_peak_bytes=int(out["memory_peak_bytes"]))
    observed = dict(out["observed"], config=config, traffic=mix,
                    peaks=common.peaks(device["kind"]) if on_chip else None,
                    end_to_end=out["end_to_end"], numbers=out["numbers"])
    metrics, line = {}, {}
    if not trace:
        values = dict(out["end_to_end"], setup_s=ctx["setup_s"])
        for m in bench["end_to_end"]:
            if _applies(m, workload):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        red = tracer.reduce()
        observed["trace"] = red
        observed["trace_interval"] = (tracer.t_on, tracer.t_off)
        if red and red["busy_s"]:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            line["breakdown"] = red["breakdown"]
        for m in bench["per_layer"]:
            if not _applies(m, workload):
                continue
            spec = _json(os.path.join(HERE, "metrics", m["name"] + ".json"))
            reader = importlib.import_module(
                "perfbench.readers." + spec["reader"])
            value = reader.read(observed, **spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": int(out["attempted"]),
              "failed": int(out["failed"]), "metrics": metrics,
              "device": device}
    result.update(line)
    result["workload"] = workload
    result["seed"] = int(seed)
    result["compared"] = [{"name": n, "value": v, "limit": lim}
                          for n, v, lim in rows]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    from perfbench.harness.common import NoChip
    try:
        result = run_cell(bench, args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 3
    for row in result["compared"]:
        print(f"compared {row['name']} = {row['value']!r} "
              f"(limit {row['limit']!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
