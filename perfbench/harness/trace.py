"""From a profiler trace (.xplane.pb) to numbers: the busy union of device
operations, the idle share, device time by name pattern, and the longest idle
gaps with what the host was doing in them.

Reads with `jax.profiler.ProfileData` alone. Times are seconds; an event is
(start, end, label). A device plane is one whose name starts with
`/device:TPU:`; its leaf operations are the line `XLA Ops`, its programs the
line `XLA Modules`. Host spans are the events of the `/host:CPU` plane whose
name starts with one of `HOST_PREFIXES` (the benchmark's own
`TraceAnnotation`s) or is a jit dispatch (`PjitFunction(...)`).
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
HOST_PREFIXES = ("bench/", "PjitFunction(")
#: stats of a device event that say which program and source op it is
LABEL_STATS = ("hlo_module", "tf_op", "long_name", "hlo_op", "name_scope")


def short_name(name: str) -> str:
    """A device event is named by its whole HLO instruction
    (`%fusion.12 = bf16[...] fusion(...)`): keep the instruction's name."""
    return name.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return files[-1]


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except Exception:       # noqa: BLE001 - a plane without readable stats
        return {}


def load(path: str) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "host": [...]}
    with events as (start_s, end_s, label), sorted by start."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = {"devices": {}, "host": []}
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                for ev in line.events:
                    st = _stats(ev)
                    label = " | ".join([short_name(ev.name)] + [
                        str(st[k]) for k in LABEL_STATS if k in st])
                    s = ev.start_ns * 1e-9
                    dev[key].append((s, s + ev.duration_ns * 1e-9, label))
            dev["ops"].sort()
            dev["modules"].sort()
            out["devices"][plane.name] = dev
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIXES):
                        s = ev.start_ns * 1e-9
                        out["host"].append(
                            (s, s + ev.duration_ns * 1e-9, ev.name))
    out["host"].sort()
    return out


def union(intervals):
    """Merged, sorted, non-overlapping (start, end) of (start, end, ...)."""
    merged = []
    for iv in sorted((i[0], i[1]) for i in intervals):
        if merged and iv[0] <= merged[-1][1]:
            if iv[1] > merged[-1][1]:
                merged[-1] = (merged[-1][0], iv[1])
        else:
            merged.append(iv)
    return merged


def busy_seconds(ops) -> float:
    return sum(e - s for s, e in union(ops))


def window_of(ops):
    """(start, end) of the traced device activity."""
    if not ops:
        return None
    return min(o[0] for o in ops), max(o[1] for o in ops)


def time_by_pattern(events, patterns) -> tuple[float, int]:
    """(seconds, count) of the events whose label matches any regex. Summed,
    not merged: one core runs its leaf operations one after another."""
    regs = [re.compile(p) for p in patterns]
    sec, n = 0.0, 0
    for s, e, label in events:
        if any(r.search(label) for r in regs):
            sec += e - s
            n += 1
    return sec, n


def containing(outer, ops, pattern):
    """The events of `outer` in whose interval an op matching `pattern`
    starts."""
    import bisect
    reg = re.compile(pattern)
    starts = sorted(s for s, _, label in ops if reg.search(label))
    out = []
    for ev in outer:
        i = bisect.bisect_left(starts, ev[0])
        if i < len(starts) and starts[i] <= ev[1]:
            out.append(ev)
    return out


def top_ops(ops, k=10):
    """[[name, seconds], ...]: the leaf operations that took most time,
    grouped by label with trailing instruction numbers dropped."""
    total = {}
    for s, e, label in ops:
        name = re.sub(r"[._\d]+$", "", label.split(" | ")[0]) or label
        total[name] = total.get(name, 0.0) + (e - s)
    return [[n, t] for n, t in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:k]]


def idle_gaps(ops, host, k=10, clock_offset=0.0):
    """[[what, seconds], ...]: the longest gaps between device operations,
    each named by the host span that covers most of it (`unattributed` where
    none does). Gaps of one name are summed; the list is by total time."""
    busy = union(ops)
    total = {}
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        if s1 <= e0:
            continue
        best, cover = "unattributed", 0.0
        for hs, he, name in host:
            hs, he = hs + clock_offset, he + clock_offset
            if he <= e0:
                continue
            if hs >= s1:
                break
            c = min(he, s1) - max(hs, e0)
            if c > cover:
                best, cover = name, c
        total[best] = total.get(best, 0.0) + (s1 - e0)
    return [[n, t] for n, t in sorted(total.items(),
                                      key=lambda kv: -kv[1])[:k]]


def reduce(path: str) -> dict:
    """The numbers every traced run reports, averaged over the chips used."""
    data = load(path)
    devs = [d for d in data["devices"].values() if d["ops"]]
    if not devs:
        return {"busy_s": None, "window_s": None, "devices": data["devices"],
                "host": data["host"], "ops": [], "modules": []}
    busy = sum(busy_seconds(d["ops"]) for d in devs) / len(devs)
    wins = [window_of(d["ops"]) for d in devs]
    window = max(w[1] for w in wins) - min(w[0] for w in wins)
    first = devs[0]
    return {"busy_s": busy, "window_s": window, "devices": data["devices"],
            "host": data["host"], "ops": first["ops"],
            "modules": first["modules"],
            "breakdown": {"device_ops": top_ops(first["ops"]),
                          "idle_gaps": idle_gaps(first["ops"], data["host"])}}


def describe(path: str, n=40) -> str:
    """A look at a trace by hand: planes, lines, the first events with
    their stats, and the heaviest leaf operations."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        out.append(f"PLANE {plane.name}")
        for line in plane.lines:
            evs = list(line.events)
            out.append(f"  LINE {line.name}: {len(evs)} events")
            for ev in evs[:4]:
                out.append(f"      {ev.name} start={ev.start_ns} "
                           f"dur={ev.duration_ns} stats={_stats(ev)}")
    red = reduce(path)
    out.append(f"busy_s={red['busy_s']} window_s={red['window_s']}")
    for name, sec in top_ops(red["ops"], n):
        out.append(f"  {sec:10.6f}s {name}")
    out.append("modules:")
    for name, sec in top_ops(red["modules"], 10):
        out.append(f"  {sec:10.6f}s {name}")
    out.append(f"host spans: {len(red['host'])}; first {red['host'][:5]}")
    return "\n".join(out)
