#!/usr/bin/env python3
"""Run one cell with --trace 1, keep its raw .xplane.pb, and say what the
program was doing while the device idled: every idle gap between device
operations is cut at the boundaries of the program's own spans (the
`paddle_tpu/<name>` annotations `paddle_tpu.observability.tracing` puts on the
profiler's host plane) and each piece goes to the innermost span over it.

    python3 perfbench/tools/gaps_by_span.py <workload> <seed> <seconds>

Prints the cell's result line, and writes to `chiprun_out/<workload>.gaps.txt`
(and to stderr): idle seconds by innermost span; how many runs of each
program lie inside a span of each name (the spans are on the profiler's own
clock: no offset is estimated); device time by the scope that made each
operation (the first `jax.named_scope` under the program, from the program's
instruction tables); and the cell's step spans inside the traced interval as
`chiprun_out/<workload>.spans.json`. `harness/trace.py` keeps only `bench/`
and `PjitFunction(` host events, so this reads the trace itself.
"""

import json
import os
import re
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

SPAN_PREFIX = "paddle_tpu/"


def host_spans(path, prefixes=(SPAN_PREFIX,)):
    """[(start_s, end_s, name)] of the host plane's events whose name starts
    with one of `prefixes`, sorted by start."""
    from jax.profiler import ProfileData

    from perfbench.harness import trace
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != trace.HOST_PLANE:
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(tuple(prefixes)):
                    s = ev.start_ns * 1e-9
                    out.append((s, s + ev.duration_ns * 1e-9,
                                ev.name.split("#", 1)[0]))
    return sorted(out)


def gaps_by_innermost(ops, spans):
    """{name: idle seconds}: each gap of the busy union cut at the spans'
    boundaries, each piece given to the shortest span that covers it
    (`(no span)` where none does). A span that began before the gap did is
    marked `[device done]` (the device has finished, the host is still in
    it); one that ends after the gap does `[device not started]` (the host
    has moved on, the device has not begun)."""
    from perfbench.harness import trace
    busy = trace.union(ops)
    total = {}
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        if s1 <= e0:
            continue
        over = [sp for sp in spans if sp[0] < s1 and sp[1] > e0]
        cuts = sorted({e0, s1} | {t for sp in over for t in sp[:2]
                                  if e0 < t < s1})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            cover = [sp for sp in over if sp[0] <= mid < sp[1]]
            name = "(no span)"
            if cover:
                sp = min(cover, key=lambda sp: sp[1] - sp[0])
                name = sp[2] + (" [device done]" if sp[0] < e0 else
                                " [device not started]" if sp[1] > s1
                                else "")
            total[name] = total.get(name, 0.0) + (b - a)
    return total


def runs_inside(modules, spans):
    """{(program, span name): [runs inside such a span, runs]}: a program's
    run (an `XLA Modules` event) is inside a span when the span's interval
    holds all of it."""
    out = {}
    names = sorted({sp[2] for sp in spans})
    for s, e, label in modules:
        prog = re.sub(r"\(\d+\)$", "", label.split(" | ")[0])
        for name in names:
            row = out.setdefault((prog, name), [0, 0])
            row[1] += 1
            if any(sp[2] == name and sp[0] <= s and e <= sp[1]
                   for sp in spans):
                row[0] += 1
    return out


def time_by_scope(red, tables):
    """{(program, scope): seconds} over the leaf operations: the scope is
    the first path component of the `op_name` under the program's own
    `jit(...)`; `(compiler)` for operations without an `op_name`,
    `(undecided)` where the tables of the program's name give its
    instruction several scopes or some of them were dropped."""
    from perfbench.readers import device_time_share

    def scope_of(op):
        parts = [p for p in op.split("/")
                 if not re.fullmatch(r"jit\([^)]*\)", p)]
        return parts[0] if len(parts) > 1 else "(no scope)"

    out = {}
    for s, e, prog, ops in device_time_share.op_names(red, tables):
        prog = prog or "(no program)"
        scopes = None if ops is None else {scope_of(op) for op in ops}
        scope = "(undecided)" if scopes is None or len(scopes) > 1 \
            else next(iter(scopes), "(compiler)")
        out[(prog, scope)] = out.get((prog, scope), 0.0) + (e - s)
    return out


def report(path, red, interval):
    from paddle_tpu.observability import tracing
    spans = host_spans(path)
    lines = [f"trace {os.path.basename(path)}: busy {red['busy_s']:.4f}s of "
             f"{red['window_s']:.4f}s, {len(spans)} program spans on the "
             f"host plane"]
    idle = gaps_by_innermost(red["ops"], spans)
    all_idle = sum(idle.values()) or 1.0
    lines.append("idle seconds by innermost program span:")
    for name, sec in sorted(idle.items(), key=lambda kv: -kv[1]):
        lines.append(f"  {sec:9.5f}s {100 * sec / all_idle:5.1f}%  {name}")
    lines.append("runs of each program inside a span of each name:")
    for (prog, name), (n_in, n) in sorted(runs_inside(
            red["modules"], spans).items()):
        if n_in and prog.startswith("jit_pure_arrays"):
            lines.append(f"  {n_in:5d} of {n:5d}  {prog}  in  {name}")
    tables = tracing.programs()
    lines.append(f"device seconds by program and scope "
                 f"({len(tables)} instruction tables):")
    for (prog, scope), sec in sorted(time_by_scope(red, tables).items(),
                                     key=lambda kv: -kv[1])[:40]:
        lines.append(f"  {sec:9.5f}s {100 * sec / red['busy_s']:5.1f}%  "
                     f"{prog}  {scope}")
    steps = tracing.step_spans(*interval)
    lines.append(f"step spans in the traced interval: {len(steps['spans'])}"
                 f", dropped since start {steps['dropped']}")
    return "\n".join(lines), steps


def main():
    workload, seed, seconds = sys.argv[1], int(sys.argv[2]), float(sys.argv[3])
    from perfbench import run
    from perfbench.harness import trace, tracing
    out = os.path.join(ROOT, "chiprun_out", workload)
    os.makedirs(os.path.dirname(out), exist_ok=True)
    plain = tracing.WindowTracer.reduce

    def reduce_and_report(self):
        src = trace.find_xplane(self.dir)
        if os.path.getsize(src) < 24 << 20:
            self.keep(out + ".xplane.pb")
        red = trace.reduce(src)
        if red["busy_s"]:
            text, steps = report(src, red, (self.t_on, self.t_off))
            with open(out + ".gaps.txt", "w") as f:
                f.write(text + "\n")
            with open(out + ".spans.json", "w") as f:
                json.dump(steps, f)
            print(text, file=sys.stderr, flush=True)
        return plain(self)

    tracing.WindowTracer.reduce = reduce_and_report
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    result = run.run_cell(bench, workload, seed, seconds, True, t_start=T0)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
