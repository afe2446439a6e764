"""Reduce a profiler trace to device busy and idle time, time per device
program, and idle gaps attributed to the harness's host spans.

``load_xplane`` turns the profiler's ``.xplane.pb`` into plain lists;
``reduce_events`` does the arithmetic on those lists only, so it can be
checked on a hand-made event list.  Times are nanoseconds on the trace's
one clock (host spans and device events share it).
"""

from __future__ import annotations

import re
from pathlib import Path

# device lines: each op as it ran, and each whole program (XLA module)
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."          # the harness's own TraceAnnotation spans
WINDOW_SPAN = "bench.window"
NO_SPAN = "host (no bench span)"
# the device GET: the fused every-shard lookup and its filter probe
GET_PROGRAMS = ("_local_get_all_shards", "_shard_filter_probe")
TOP = 10


def load_xplane(path: Path) -> dict:
    """{"devices": {plane: {"ops": [...], "modules": [...]}}, "host":
    [...]} with each event a (name, start_ns, end_ns) tuple."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(path))
    devices: dict = {}
    host: list = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            d = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key is None:
                    continue
                d[key].extend((ev.name, ev.start_ns, ev.start_ns
                               + ev.duration_ns) for ev in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((ev.name, ev.start_ns, ev.start_ns
                             + ev.duration_ns) for ev in line.events
                            if ev.name.startswith(HOST_PREFIX))
    return {"devices": devices, "host": host}


def merge(intervals) -> list:
    """Union of (start, end) intervals as sorted disjoint intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def clip(intervals, t0: float, t1: float) -> list:
    return [(max(s, t0), min(e, t1)) for s, e in intervals
            if e > t0 and s < t1]


def gaps(busy: list, t0: float, t1: float) -> list:
    """Complement of sorted disjoint ``busy`` within [t0, t1]."""
    out, cur = [], t0
    for s, e in busy:
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def innermost(spans) -> list:
    """Properly nested spans of one thread -> disjoint (start, end, name)
    segments, each named by the innermost span open over it."""
    bounds = []
    for name, s, e in spans:
        bounds.append((s, 1, -e, name))
        bounds.append((e, 0, 0, name))
    bounds.sort()
    segs, stack, prev = [], [], None
    for t, is_start, _, name in bounds:
        if prev is not None and t > prev:
            segs.append((prev, t, stack[-1] if stack else NO_SPAN))
        if is_start:
            stack.append(name)
        elif name in stack:
            # remove the innermost open span of that name
            del stack[len(stack) - 1 - stack[::-1].index(name)]
        prev = t
    return segs


def attribute(idle: list, segs: list) -> dict:
    """Nanoseconds of the sorted disjoint ``idle`` intervals by the name of
    the sorted disjoint (start, end, name) ``segs`` they overlap."""
    out: dict = {}
    j = 0
    for s, e in idle:
        while j < len(segs) and segs[j][1] <= s:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < e:
            a, b, name = segs[k]
            ov = min(b, e) - max(a, s)
            if ov > 0:
                out[name] = out.get(name, 0) + ov
            k += 1
    return out


def program_name(name: str) -> str:
    """An XLA module's program name without its ``jit_`` prefix and the
    ``(id)`` suffix the runtime appends."""
    name = re.sub(r"\(\d+\)$", "", name)
    return name[4:] if name.startswith("jit_") else name


def op_name(name: str) -> str:
    """An op event's HLO instruction name: the text before `` = `` of the
    instruction the profiler records, without its ``%``."""
    return name.split(" = ", 1)[0].lstrip("%")


def clip_events(events, t0: float, t1: float) -> list:
    return sorted((n, max(s, t0), min(e, t1)) for n, s, e in events
                  if e > t0 and s < t1)


def label_ops(ops: list, modules: list) -> list:
    """Name each op ``<program>:<op>`` by the program running over its
    start (both lists sorted by start; programs do not overlap)."""
    mods = sorted(modules, key=lambda m: m[1])
    out, j = [], 0
    for n, s, e in sorted(ops, key=lambda o: o[1]):
        while j < len(mods) and mods[j][2] <= s:
            j += 1
        prog = (program_name(mods[j][0])
                if j < len(mods) and mods[j][1] <= s else "?")
        out.append((f"{prog}:{op_name(n)}", s, e))
    return out


def reduce_events(ev: dict) -> dict:
    """The reduction: traced window from the ``bench.window`` span; busy as
    the union of device ops in it, averaged over devices; seconds per
    device program; the costliest ops; idle time by host span."""
    win = [(s, e) for n, s, e in ev["host"] if n == WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    t0, t1 = win[0]
    if not ev["devices"]:
        raise ValueError("no device plane in the trace")
    busy_ns, programs, ops, first_busy = [], {}, {}, None
    for plane in sorted(ev["devices"]):
        d = ev["devices"][plane]
        busy = merge(clip([(s, e) for _, s, e in d["ops"]], t0, t1))
        busy_ns.append(sum(e - s for s, e in busy))
        if first_busy is None:
            first_busy = busy
        for n, s, e in d["modules"]:
            for a, b in clip([(s, e)], t0, t1):
                p = program_name(n)
                c = programs.setdefault(p, [0.0, 0])
                c[0] += b - a
                c[1] += 1
        for n, a, b in label_ops(clip_events(d["ops"], t0, t1),
                                 clip_events(d["modules"], t0, t1)):
            ops[n] = ops.get(n, 0) + (b - a)
    idle = gaps(first_busy, t0, t1)
    # the window itself stands in for "no harness span open"
    by_span = attribute(idle, innermost(
        [(NO_SPAN, t0, t1)]
        + [h for h in ev["host"] if h[0] != WINDOW_SPAN]))
    window_s = (t1 - t0) * 1e-9
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    top_idle = sorted(by_span.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "window_s": window_s,
        "busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
        "programs": {p: {"seconds": c[0] * 1e-9, "calls": c[1]}
                     for p, c in programs.items()},
        "breakdown": {
            "device_ops": [[n, v * 1e-9] for n, v in top_ops],
            "idle_gaps": [[n, v * 1e-9] for n, v in top_idle],
        },
    }


def get_device_s(summary: dict) -> float:
    """Device seconds of the GET programs in the traced window."""
    return sum(v["seconds"] for p, v in summary["programs"].items()
               if any(g in p for g in GET_PROGRAMS))


def summarize(xplane: Path, work: dict | None) -> dict:
    out = reduce_events(load_xplane(xplane))
    out["get_work"] = work
    return out
