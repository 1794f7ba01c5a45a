#!/usr/bin/env python3
"""Find the knee of a serving mix once, on the chip: a few fixed rates, one
window each, in one process (one engine, one set of weights).

    python3 perfbench/tools/sweep.py <config> <mix> <seed> <seconds> <rate> [<rate> ...]

Rates go up; each is offered for the mix's `lead_in_s` and then for a window
of `seconds`, with no pause between rates, so every window opens on an engine
as full as the traffic before it left it. All requests run to their end
before any window is judged. The first rate should be far below capacity:
its readings are the unloaded ones from which the limits are fixed (TTFT
limit = TTFT_X x the unloaded median TTFT; gap limit = GAP_X x the unloaded
median of the requests' mean gaps); `SWEEP_LIMITS=<ttft_ms>,<gap_ms>` in the
environment gives limits fixed by an earlier sweep. A request meets the limits when its first token came within the TTFT
limit of when it was due and the mean gap between its tokens is within the
gap limit; one that failed or never finished misses. The knee is the highest
rate at which 90 % of the requests sent meet both and the queue is empty at
the window's close. Prints a CSV table (also written to
chiprun_out/<mix>.sweep.csv); copy it beside the mix's file.
"""

import os
import sys
import time

T0 = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

TTFT_X = 10.0
GAP_X = 2.0


def main():
    config, mix_name, seed, seconds = (sys.argv[1], sys.argv[2],
                                       int(sys.argv[3]), float(sys.argv[4]))
    rates = [float(r) for r in sys.argv[5:]]
    from perfbench.drivers import serve_llama as drv
    from perfbench.harness import common, traffic
    device = common.start_program(1)
    cfg = common.load_json("configs", config + ".json")
    mix = common.load_json("traffic", mix_name + ".json")
    ctx = {"config": cfg, "traffic": mix, "seed": seed, "t_start": T0,
           "on_chip": True, "trace": False}
    engine, _model = drv.build(ctx)
    drv.warm_up(engine, ctx, mix)
    common.log(f"sweep: set-up {time.perf_counter() - T0:.1f}s on {device}")
    limits = None
    if os.environ.get("SWEEP_LIMITS"):
        limits = tuple(float(v) for v in os.environ["SWEEP_LIMITS"].split(","))
    windows = []
    for i, rate in enumerate(rates):
        at = dict(mix, rate_per_s=rate)
        reqs = traffic.open_loop(at, seconds, seed + i, cfg["vocab_size"])
        lead, lead_s = drv.lead_in(at, seed + i, cfg["vocab_size"])
        opened = {}
        records, t0, cutoff, _ = drv.window(
            engine, reqs, seconds, 0.0, None, lead, lead_s,
            lambda t: opened.update(stats=engine.stats()))
        windows.append((rate, records, t0, cutoff, opened["stats"],
                        engine.stats()))
        common.log(f"sweep: rate {rate} offered, backlog "
                   f"{windows[-1][5]['queue_depth']}")
    for _, records, *_ in windows:      # every request runs to its end
        for r in records:
            if r["handle"] is not None:
                try:
                    r["handle"].result(timeout=300)
                except Exception as e:      # noqa: BLE001
                    r["error"] = repr(e)
    rows = []
    for rate, records, t0, cutoff, s0, s1 in windows:
        lat = drv.latency_numbers(records, t0, seconds, time.perf_counter())
        per_req = []
        for r in records:
            if not r["measured"]:
                continue
            ts = r["times"]
            done = r["handle"] is not None and \
                r["handle"].state == "completed"
            ttft = 1000.0 * (ts[0] - t0 - r["due"]) if ts else float("inf")
            gap = 1000.0 * (ts[-1] - ts[0]) / (len(ts) - 1) \
                if len(ts) > 1 else 0.0
            per_req.append((done, ttft, gap))
        if limits is None:
            limits = (TTFT_X * common.median([p[1] for p in per_req]),
                      GAP_X * common.median([p[2] for p in per_req
                                             if p[2] > 0]))
        met = sum(1 for d, t, g in per_req
                  if d and t <= limits[0] and g <= limits[1])
        rows.append({
            "rate_per_s": rate, "sent": len(per_req),
            "met_both_share": met / len(per_req),
            "backlog_at_close": s1["queue_depth"],
            "ttft_p50_ms": common.percentile(lat["ttft_ms"], 50),
            "ttft_p90_ms": common.percentile(lat["ttft_ms"], 90),
            "itl_p50_ms": common.percentile(lat["itl_ms"], 50),
            "itl_p95_ms": common.percentile(lat["itl_ms"], 95),
            "tokens_per_s_in_window": lat["tokens_in_window"] / seconds,
            "batch_occupancy": drv.occupancy_between(s0, s1),
            "evictions": s1["evictions"] - s0["evictions"],
            "gen_lateness_p95_ms": common.percentile(lat["lateness_ms"], 95),
            "ttft_limit_ms": limits[0], "gap_limit_ms": limits[1]})
        common.log(f"sweep: {rows[-1]}")
    engine.shutdown(drain=False)
    cols = list(rows[0])
    text = ",".join(cols) + "\n" + "\n".join(
        ",".join(f"{r[c]:.6g}" for c in cols) for r in rows) + "\n"
    out = os.path.join(ROOT, "chiprun_out", mix_name + ".sweep.csv")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        f.write(text)
    print(text)
    print("memory_peak_bytes", common.memory_peak_bytes())


if __name__ == "__main__":
    main()
