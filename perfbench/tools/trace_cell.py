#!/usr/bin/env python3
"""Run one cell with --trace 1, keep its raw .xplane.pb under
chiprun_out/ and print a description of it: the look at a trace by hand
that comes before any reader is written against it.

    python3 perfbench/tools/trace_cell.py <workload> <seed> <seconds> [batch]

`batch` tries another `assumed.batch` than the configuration's file gives
(for sizing a training cell; the file is what a benchmark run uses).
"""

import json
import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main():
    workload, seed, seconds = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    from perfbench import run
    from perfbench.harness import trace, tracing
    dest = os.path.join(ROOT, "chiprun_out", f"{workload}.xplane.pb")
    os.makedirs(os.path.dirname(dest), exist_ok=True)
    plain = tracing.WindowTracer.reduce

    def reduce_and_keep(self):
        src = trace.find_xplane(self.dir)
        with open(dest.replace(".xplane.pb", ".trace.txt"), "w") as f:
            f.write("\n".join(line[:300] for line in
                              trace.describe(src).split("\n")))
        if os.path.getsize(src) < 20 << 20:
            self.keep(dest)
        return plain(self)

    tracing.WindowTracer.reduce = reduce_and_keep
    if len(sys.argv) > 4:
        read = run._json

        def read_with_batch(path):
            d = read(path)
            if "assumed" in d and "batch" in d["assumed"]:
                d["assumed"]["batch"] = int(sys.argv[4])
            return d

        run._json = read_with_batch
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    result = run.run_cell(bench, workload, seed, seconds, True, t_start=T0)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
