"""Reduction of a profiler trace to what the per-layer metrics read.

Two steps, so the arithmetic can be tested without a chip:
`load_xplane` turns the profiler's `.xplane.pb` into plain lists
(`jax.profiler.ProfileData`), and `reduce` turns those into busy and idle
seconds, device seconds per XLA module and per op, the collectives' share
and the longest idle gaps. `reduce` is pure Python.

Device names are the ones XLA prints. An idle gap is split among the
engine phases (`omnia.engine.*`, `omnia_tpu/engine/phases.py`) whose self
time on the engine thread covers it (`phase_shares`, the one attribution:
`harness/spans.py` sums the same shares into its table), and named by the
phase with the largest share, else by this benchmark's own spans that
overlap it, else "engine loop, not attributed"; then the module that ended it.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
HOST_PREFIX = ("bench.", "omnia.")
ENGINE_STEP, ENGINE_IDLE_SLEEP = "omnia.engine.step", "omnia.engine.idle_sleep"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|all-to-all|reduce-scatter|collective-permute|"
    r"collective-broadcast)")
# Control-flow containers hold their bodies' ops as separate events on the
# same line; counting both would count the body twice.
# A module shorter than this is host bookkeeping around a placement (a
# scatter into a per-slot vector); a gap is named by the next real step.
BOOKKEEPING_NS = 20_000
CONTAINER = re.compile(r"^(while|conditional|call)(\.\d+)?$")


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"the profiler wrote no .xplane.pb under {trace_dir}")
    return paths[-1]


def short_name(name: str) -> str:
    """An op event is named by its whole HLO line, `%fusion.5 = bf16[...]
    fusion(...)`; the part before ` = `, without the `%`, is its name."""
    return name.split(" = ", 1)[0].lstrip("%")


def load_xplane(path: str) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns], ...]}]}]} for the device planes, plus the host lines that
    hold this benchmark's own annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device:
                evs = [[short_name(e.name), float(e.start_ns), float(e.duration_ns)]
                       for e in line.events]
            else:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns)]
                       for e in line.events if e.name.startswith(HOST_PREFIX)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def describe(trace: dict, top: int = 12) -> list:
    """One trace by hand: planes, lines, event counts and the names that
    took most time on each line."""
    out = []
    for plane in trace["planes"]:
        for line in plane["lines"]:
            totals: dict = {}
            for name, _s, d in line["events"]:
                totals[name] = totals.get(name, 0.0) + d
            ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
            out.append({
                "plane": plane["name"], "line": line["name"],
                "events": len(line["events"]),
                "top": [[n, round(d / 1e9, 6)] for n, d in ranked],
            })
    return out


def sample(trace: dict, seconds: float) -> dict:
    """The first `seconds` of every line after the first device event:
    small enough to keep beside the tests as a recorded trace."""
    starts = [e[1] for p in trace["planes"] if DEVICE_PLANE.match(p["name"])
              for line in p["lines"] for e in line["events"]]
    lo = min(starts)
    hi = lo + seconds * 1e9
    planes = []
    for p in trace["planes"]:
        lines = [{"name": line["name"],
                  "events": [e for e in line["events"] if lo <= e[1] and e[1] + e[2] <= hi]}
                 for line in p["lines"]]
        planes.append({"name": p["name"], "lines": [ln for ln in lines if ln["events"]]})
    return {"planes": planes}


def _union(intervals: list) -> list:
    """Sorted, merged [start, end] intervals."""
    merged: list = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def innermost_segments(events: list) -> list:
    """One thread's nested spans as a flat, sorted list of [start, end,
    name]: at every instant the innermost open span. What is left of a span
    after its children are cut out is its self time. Events are [name,
    start_ns, duration_ns, ...]."""
    out: list = []
    stack: list = []  # [name, end, cursor]

    def close(upto: float) -> None:
        while stack and stack[-1][1] <= upto:
            name, end, cursor = stack.pop()
            if end > cursor:
                out.append([cursor, end, name])
            if stack:
                stack[-1][2] = max(stack[-1][2], end)

    for name, start, dur, *_x in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack and start > stack[-1][2]:
            out.append([stack[-1][2], start, stack[-1][0]])
        if stack:
            stack[-1][2] = max(stack[-1][2], start)
        stack.append([name, start + dur, start])
    close(float("inf"))
    out.sort()
    return out


def is_engine_thread(events: list) -> bool:
    """Whether one host line's events are an engine thread's: its phases can
    own an idle gap."""
    names = {e[0] for e in events}
    return ENGINE_STEP in names or ENGINE_IDLE_SLEEP in names


def segment_table(segments: list) -> tuple:
    """(segments sorted, their starts, the longest one's length): what
    `phase_shares` bisects. Segments are [start, end, name]."""
    segments = sorted(segments)
    return (segments, [s for s, _e, _n in segments],
            max((e - s for s, e, _n in segments), default=0.0))


def phase_shares(segments: list, starts: list, longest: float, lo: float, hi: float) -> dict:
    """{name: ns} of [lo, hi] under each of the sorted [start, end, name]
    segments: who owns how much of one idle gap."""
    out: dict = {}
    i = bisect.bisect_left(starts, lo - longest)
    while i < len(segments) and segments[i][0] < hi:
        s, e, name = segments[i]
        overlap = min(e, hi) - max(s, lo)
        if overlap > 0:
            out[name] = out.get(name, 0.0) + overlap
        i += 1
    return out


def module_base(name: str) -> str:
    """`jit_decode_chunk(1234)` -> `jit_decode_chunk`."""
    return name.split("(", 1)[0]


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def reduce(trace: dict) -> dict:
    """All seconds are means over the device planes (the chips used)."""
    devices = [p for p in trace["planes"] if DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("the trace holds no /device:TPU:N plane")
    host_lines = [line["events"] for p in trace["planes"]
                  if not DEVICE_PLANE.match(p["name"]) for line in p["lines"]]
    engine = segment_table([seg for events in host_lines if is_engine_thread(events)
                            for seg in innermost_segments(events)])
    # Every other host span kept, e.g. `bench.submit`.
    others = segment_table([[s, s + d, n] for events in host_lines
                            if not is_engine_thread(events) for n, s, d in events])
    n_dev = len(devices)
    busy = window = 0.0
    modules: dict = {}        # base name -> {"count", "seconds", "by_id": {full: [count, s]}}
    ops: dict = {}            # op name -> seconds
    ops_in_module: dict = {}  # module base -> {op name -> [count, seconds]}
    gaps: dict = {}           # label -> seconds
    longest_gap = 0.0
    for plane in devices:
        mods = sorted(_line(plane, MODULE_LINE), key=lambda e: e[1])
        op_events = _line(plane, OPS_LINE) or mods
        leaf = [e for e in op_events if not CONTAINER.match(e[0])]
        spans = _union([[s, s + d] for _n, s, d in leaf if d > 0])
        if not spans:
            continue
        lo, hi = spans[0][0], spans[-1][1]
        window += (hi - lo) / 1e9
        busy += sum(e - s for s, e in spans) / 1e9
        for name, _s, d in mods:
            m = modules.setdefault(module_base(name),
                                   {"count": 0, "seconds": 0.0, "by_id": {}})
            m["count"] += 1 / n_dev
            m["seconds"] += d / 1e9 / n_dev
            one = m["by_id"].setdefault(name, [0.0, 0.0])
            one[0] += 1 / n_dev
            one[1] += d / 1e9 / n_dev
        starts = [s for _n, s, _d in mods]
        for name, s, d in leaf:
            ops[name] = ops.get(name, 0.0) + d / 1e9 / n_dev
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s < mods[i][1] + mods[i][2]:
                per = ops_in_module.setdefault(module_base(mods[i][0]), {})
                one = per.setdefault(name, [0.0, 0.0])
                one[0] += 1 / n_dev
                one[1] += d / 1e9 / n_dev
        for (_s0, e0), (s1, _e1) in zip(spans, spans[1:]):
            gap = (s1 - e0) / 1e9
            if gap <= 0:
                continue
            longest_gap = max(longest_gap, gap)
            i = bisect.bisect_left(starts, s1 - 1)
            while i < len(mods) and mods[i][2] < BOOKKEEPING_NS:
                i += 1  # scatters and converts of a few microseconds
            nxt = module_base(mods[i][0]) if i < len(mods) else "end of trace"
            phases = phase_shares(*engine, e0, s1)
            if phases:
                what = max(phases.items(), key=lambda kv: (kv[1], kv[0]))[0]
            else:
                what = ("+".join(sorted(phase_shares(*others, e0, s1)))
                        or "engine loop, not attributed")
            label = f"{what}; before {nxt}"
            gaps[label] = gaps.get(label, 0.0) + gap / n_dev
    if window <= 0:
        raise ValueError("no device operation ran inside the traced window")

    def seconds_matching(table: dict, pattern) -> float:
        return sum(v[1] for k, v in table.items() if pattern.match(k))

    return {
        "devices": n_dev,
        "window_s": window / n_dev,
        "busy_s": busy / n_dev,
        "modules": modules,
        "ops": ops,
        "ops_in_module": ops_in_module,
        "collective_s_in_module": {
            m: seconds_matching(t, COLLECTIVE) for m, t in ops_in_module.items()},
        "idle_gaps": gaps,
        "longest_gap_s": longest_gap,
    }


def breakdown(reduced: dict, top: int = 10) -> dict:
    ops = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(reduced["idle_gaps"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps]}
