#!/usr/bin/env python3
"""Who owns an idle gap, and which scope owns a device second.

The program writes its engine-loop phases into the profiler's own trace
(`omnia_tpu/engine/phases.py`: `omnia.engine.*` spans on the engine
thread, nested under `omnia.engine.step`) and puts named scopes on the ops
of its step programs (the model module, `engine/programs.py`). Two steps,
as in `trace.py`: `load` turns the `.xplane.pb` into plain lists, keeping
the host spans named `omnia.*` with their attributes and each device op
with its scope, and `reduce` (pure Python) gives

- self time per phase (a span's duration less its children's);
- for every device idle gap, the seconds of it that fall under each
  phase's self time on the engine thread, else `unattributed`;
- device seconds per scope inside each XLA module.

A trace of a program without the spans or the scopes reduces to empty
tables, and every reader then returns None.

By hand, for a directory that `run.py --trace 1` left behind (the scopes
are those of the cell the directory is named after, else the default set):

    python3 benchmark/harness/spans.py .bench_trace/<cell>
"""

from __future__ import annotations

import bisect
import os
import sys

if __package__ in (None, ""):  # run as a script: this directory is sys.path[0]
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from harness import trace as tr
from harness.manifest import Cell, program_scopes

HOST_PREFIX = "omnia."
STEP = tr.ENGINE_STEP
UNATTRIBUTED = "unattributed"
# The stat that holds a device op's HLO op_name, the path of jit names and
# named scopes it was traced under. The profiler keeps it once an op, on the
# event's metadata, which `ProfileData` does not show: `op_names` reads it.
OP_NAME_STAT = "tf_op"
SCOPES = frozenset({
    "embed", "layers", "lm_head", "sample", "finish_mask", "insert",
    "attn.qkv", "attn.rope", "kv.update", "attn.decode", "attn.prefill",
    "attn.out", "mlp", "moe.route", "moe.experts",
})
# An op directly under a scope that wraps a `lax.scan` (`layers`, and what a
# configuration's `program.scopes` calls "scan") is one that the scan emitted
# itself: its slice of the layer's operands out of the stacked arrays and the
# write-back of the layer's results. No named scope can be put around those.
SCANS = frozenset({"layers"})
SCAN_IO = "layers.scan_io"
UNSCOPED = "unscoped"
# An op without an op name is one the compiler put in itself. Its copies
# (`copy.190`: layout changes, and what copy insertion adds so that a donated
# buffer can be written in place) are told apart from the rest by opcode.
XLA_COPY = "xla.copy"


def scopes_of(model: dict | None = None) -> tuple:
    """(names, scans): the program's scopes as the cells of the configuration
    whose file `model` is read them: the default set and what the file's
    `program.scopes` adds to it; `scans` are those that wrap a `lax.scan`."""
    own = program_scopes(model or {})
    return SCOPES | set(own), SCANS | {n for n, kind in own.items() if kind == "scan"}


def scope_of(op_name: str, scopes=SCOPES, scans=SCANS) -> str:
    """`jit(decode_chunk)/while/body/closed_call/layers/while/body/
    closed_call/kv.update/scatter` -> `kv.update`: the innermost of the
    program's scopes on the path; `<name>.scan_io` where that wraps a scan."""
    for part in reversed(op_name.rstrip(":").split("/")):
        if part in scopes:
            return part + ".scan_io" if part in scans else part
    return UNSCOPED


def _varint(buf, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: an int for a varint,
    a memoryview for a length-delimited field; fixed-width ones skipped."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            value, i = None, i + (8 if wire == 1 else 4)
        else:
            raise ValueError(f"wire type {wire} in an xplane")
        yield key >> 3, value


def _map_values(entries):
    for entry in entries:
        for field, value in _fields(entry):
            if field == 2:
                yield value


def op_names(xplane_path: str) -> dict:
    """{plane name: {event name: op_name}} from the `.xplane.pb` itself.
    An XPlane keeps what all events of one op share (`XEventMetadata`: the
    name, which is the HLO line, and stats such as `tf_op`) in a table of
    its own; only that table is decoded here, the lines are skipped.
    (xplane.proto: XSpace.planes=1; XPlane.name=2, event_metadata=4,
    stat_metadata=5; XEventMetadata.name=2, stats=5; XStat.metadata_id=1,
    str_value=5, ref_value=7; XStatMetadata.id=1, name=2.)"""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: dict = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stats = "", [], []
        for f, value in _fields(plane):
            if f == 2:
                name = bytes(value).decode()
            elif f == 4:
                events.append(value)
            elif f == 5:
                stats.append(value)
        if not tr.DEVICE_PLANE.match(name):
            continue
        stat_names = {}
        for meta in _map_values(stats):
            fields = dict(_fields(meta))
            stat_names[fields.get(1, 0)] = bytes(fields.get(2, b"")).decode()
        per = out.setdefault(name, {})
        for meta in _map_values(events):
            event_name, op_name = "", None
            for f, value in _fields(meta):
                if f == 2:
                    event_name = bytes(value).decode()
                elif f == 5:
                    stat = dict(_fields(value))
                    if stat_names.get(stat.get(1)) == OP_NAME_STAT:
                        op_name = (bytes(stat[5]).decode() if 5 in stat
                                   else stat_names.get(stat.get(7), ""))
            if op_name:
                per[event_name] = op_name
    return out


def load(trace_dir: str, model: dict | None = None) -> dict:
    """`trace.load_xplane`'s scheme with a fourth element on each event:
    {"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns, extra], ...]}]}]}. `extra` is the span's attributes on a
    host line (only `omnia.*` spans are kept) and the op's scope on a
    device line (None on the modules' line), read with the scopes of the
    configuration whose file `model` is (`scopes_of`)."""
    from jax.profiler import ProfileData

    known = scopes_of(model)
    path = tr.find_xplane(trace_dir)
    names = op_names(path)
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = bool(tr.DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name == tr.OPS_LINE:
                scopes = {n: scope_of(o, *known) for n, o in names.get(plane.name, {}).items()}
                evs = []
                for e in line.events:
                    short = tr.short_name(e.name)
                    scope = scopes.get(e.name, UNSCOPED)
                    if scope == UNSCOPED and short.split(".")[0] == "copy":
                        scope = XLA_COPY
                    evs.append([short, float(e.start_ns), float(e.duration_ns), scope])
            elif device:
                evs = [[tr.short_name(e.name), float(e.start_ns), float(e.duration_ns), None]
                       for e in line.events]
            else:
                evs = [[e.name, float(e.start_ns), float(e.duration_ns), dict(e.stats)]
                       for e in line.events if e.name.startswith(HOST_PREFIX)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def reduce(raw: dict) -> dict:
    """Seconds are means over the device planes, as in `trace.reduce`."""
    devices = [p for p in raw["planes"] if tr.DEVICE_PLANE.match(p["name"])]
    if not devices:
        raise ValueError("the trace holds no /device:TPU:N plane")
    n_dev = len(devices)

    phases: dict = {}    # name -> {"count", "seconds", "self_s"}
    segments: list = []  # the engine threads' innermost spans
    decode_steps = 0
    step_clock = None    # (profiler ns, mono_ns) of one step span
    for plane in raw["planes"]:
        if tr.DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            flat = tr.innermost_segments(line["events"])
            for name, start, dur, attrs in line["events"]:
                p = phases.setdefault(name, {"count": 0, "seconds": 0.0, "self_s": 0.0})
                p["count"] += 1
                p["seconds"] += dur / 1e9
                if name == "omnia.engine.decode_dispatch":
                    decode_steps += int(attrs.get("chunk", 0))
                if name == STEP and step_clock is None and "mono_ns" in attrs:
                    step_clock = (start, int(attrs["mono_ns"]))
            for s, e, name in flat:
                phases[name]["self_s"] += (e - s) / 1e9
            if tr.is_engine_thread(line["events"]):
                segments += flat
    engine = tr.segment_table(segments)

    idle_by_phase: dict = {}
    idle_s = 0.0
    scopes: dict = {}  # module base -> {scope: seconds}
    for plane in devices:
        mods = sorted(tr._line(plane, tr.MODULE_LINE), key=lambda e: e[1])
        ops = tr._line(plane, tr.OPS_LINE) or mods
        leaf = [e for e in ops if not tr.CONTAINER.match(e[0]) and e[2] > 0]
        busy = tr._union([[e[1], e[1] + e[2]] for e in leaf])
        for (_s0, g0), (g1, _e1) in zip(busy, busy[1:]):
            if g1 <= g0:
                continue
            idle_s += (g1 - g0) / 1e9 / n_dev
            shares = tr.phase_shares(*engine, g0, g1)
            for name, ns in shares.items():
                idle_by_phase[name] = idle_by_phase.get(name, 0.0) + ns / 1e9 / n_dev
            rest = (g1 - g0) - sum(shares.values())
            if rest > 0:
                idle_by_phase[UNATTRIBUTED] = (
                    idle_by_phase.get(UNATTRIBUTED, 0.0) + rest / 1e9 / n_dev)
        mod_starts = [m[1] for m in mods]
        for _name, start, dur, scope in leaf:
            i = bisect.bisect_right(mod_starts, start) - 1
            if i < 0 or start >= mods[i][1] + mods[i][2]:
                continue
            per = scopes.setdefault(tr.module_base(mods[i][0]), {})
            key = scope or UNSCOPED
            per[key] = per.get(key, 0.0) + dur / 1e9 / n_dev

    return {
        "devices": n_dev,
        "phases": phases,
        "has_engine_spans": bool(segments),
        "decode_steps": decode_steps,
        "idle_s": idle_s,
        "idle_by_phase": idle_by_phase,
        "scopes": scopes,
        "step_clock": step_clock,
    }


def reduced(ctx: dict):
    """What the readers share: the reduction of the traced run's own trace
    directory, made once a run; None where the run was not traced."""
    if "spans" not in ctx:
        traced = ctx.get("traced")
        ctx["spans"] = reduce(load(traced["dir"], ctx.get("model"))) if traced else None
    return ctx["spans"]


def idle_ms_per_step(ctx: dict, *phase_names: str):
    """Device idle seconds under those phases' self time, per decode step
    the engine dispatched while the trace ran (`chunk` of its
    `omnia.engine.decode_dispatch` spans)."""
    sp = reduced(ctx)
    if not sp or not sp["has_engine_spans"] or not sp["decode_steps"]:
        return None
    idle = sum(sp["idle_by_phase"].get(n, 0.0) for n in phase_names)
    return idle / sp["decode_steps"] * 1e3


def scope_share(ctx: dict, module: str, *scope_names: str):
    """Share (%) of one module's scoped-or-not device seconds under those
    scopes; None where the module ran no op that carries one of them."""
    sp = reduced(ctx)
    per = sp["scopes"].get(module) if sp else None
    if not per:
        return None
    mine = sum(per.get(s, 0.0) for s in scope_names)
    return 100.0 * mine / sum(per.values()) if mine else None


def clock_offset_s(red: dict):
    """Seconds to add to a flight event's `mono` to land on the profiler's
    time axis: `flight.to_chrome_trace(events, profiler_offset_s=...)`."""
    if not red["step_clock"]:
        return None
    prof_ns, mono_ns = red["step_clock"]
    return (prof_ns - mono_ns) / 1e9


def tables(red: dict) -> str:
    lines = []
    idle = red["idle_s"]
    lines.append(f"device idle {idle:.4f} s over {red['devices']} device plane(s); "
                 f"{red['decode_steps']} decode steps dispatched in the trace")
    lines.append(f"{'idle gap owner (self time)':44s} {'s':>9s} {'%':>6s}")
    for name, s in sorted(red["idle_by_phase"].items(), key=lambda kv: -kv[1]):
        lines.append(f"{name:44s} {s:9.4f} {100 * s / idle if idle else 0:6.1f}")
    lines.append("")
    lines.append(f"{'phase':44s} {'count':>7s} {'total s':>9s} {'self s':>9s}")
    for name, p in sorted(red["phases"].items(), key=lambda kv: -kv[1]["self_s"]):
        lines.append(f"{name:44s} {p['count']:7d} {p['seconds']:9.4f} {p['self_s']:9.4f}")
    for module, per in sorted(red["scopes"].items(), key=lambda kv: -sum(kv[1].values())):
        total = sum(per.values())
        lines.append("")
        lines.append(f"{module}: {total:.4f} device s")
        for scope, s in sorted(per.items(), key=lambda kv: -kv[1]):
            lines.append(f"  {scope:42s} {s:9.4f} {100 * s / total:6.1f}")
    off = clock_offset_s(red)
    if off is not None:
        lines.append("")
        lines.append(f"flight clock -> profiler clock: add {off:.6f} s to `mono`")
    return "\n".join(lines)


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__)
        return 2
    try:
        model = Cell(os.path.basename(os.path.normpath(argv[0]))).model
    except KeyError:  # not a cell's directory: the default scopes
        model = None
    print(tables(reduce(load(argv[0], model))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
