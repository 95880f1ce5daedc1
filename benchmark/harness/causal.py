#!/usr/bin/env python3
"""Which device program a read-back waited for, and how long after it ended.

`harness/spans.py` lays the engine thread's phases against the device's idle
gaps, but a `.chunk_sync` span there does not say which program it waited
for, so a transfer that took long cannot be told from a host that arrived
late. Since PR 37 the program's spans name their own causes
(`omnia_tpu/engine/phases.py` call sites): a `.decode_dispatch` carries
`seq` and the `.chunk_sync` and `.emit` that read that chunk back carry the
same; a `.prefill_dispatch` carries `request_id`, a `seq` of its own series
and `last` (its placement's last piece), and the first token's `.chunk_sync`
(`chunk=0`) and `.emit` carry `request_id`. `join` lays each series of
dispatch spans, in order, on the module events of its programs, in order:

- the k-th dispatch span in the trace owns the k-th module event, after a
  shift at the head: module events whose dispatch came before the host's
  trace began are dropped, or dispatch spans whose module ran before the
  device's trace began. The smallest shift is taken under which every
  module starts after its dispatch span starts and ends before that
  dispatch was read back; module events left over at the end must start
  after the last dispatch span (the device's trace outlasts the host's).
  No such shift is no join: `join` returns `(None, why)`.
- the two clocks are laid on each other anew by every profiler session, to
  within a millisecond or two (two traces of one cell in one process read
  lags 1.3 ms apart, their enqueue -> start moving the other way). Where
  the series do not line up as recorded, the smallest offset of the
  device's clock (steps of 0.25 ms, at most 3 ms: under a decode step)
  under which both do is applied to every module event, and `clock` says
  which; `clock` also gives the window of offsets the trace allows, for the
  level of a lag is known no better than that window is wide.
- for every dispatch: enqueue -> device start, device seconds, and the
  **read-back lag** = end of the `.chunk_sync` of that `seq` (or
  `request_id`) - max(end of the module, start of that `.chunk_sync`): how
  long finished tokens took to reach a host that was already asking.
- for a first token also the **read lag** = end of its `.chunk_sync` - end
  of its last prefill module: how long a finished first token waited, the
  transfer and the deferral behind the next decode dispatch included.

A trace of a program without the attributes (the parent of PR 37) joins
nothing, and every reader returns None.

By hand, one row a placement and one a decode chunk:

    python3 benchmark/harness/causal.py .bench_trace/<cell>
"""

from __future__ import annotations

import os
import sys

if __package__ in (None, ""):  # run as a script: this directory is sys.path[0]
    sys.path[0] = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

from harness import spans
from harness import trace as tr
from harness.layer_common import DECODE_MODULE, PREFILL_MODULES
from harness.manifest import Cell

E = "omnia.engine."
# The programs a `.prefill_dispatch` span calls: a fresh prompt's, and the
# pieces of a chunked extend (the last one samples).
PREFILL_SERIES = PREFILL_MODULES + ("jit_extend", "jit_extend_nosample")
# Module events at the trace's head whose dispatch came before it: at most
# the pipeline's depth and the step behind a prefill; beyond this, no join.
MAX_AHEAD = 8
# A chunk cannot be read before its module ended. The two clocks are laid
# on each other by the profiler to within microseconds; a read-back that
# ends this long before a module ends did not wait for that module.
EARLY_NS = 1e6
# Offsets of the device's clock tried, in order, where the series do not
# line up as recorded: the smallest first, and none as long as a decode step,
# past which a chunk could be taken for its neighbour.
OFFSETS_NS = sorted((k * 0.25e6 for k in range(-12, 13)), key=lambda x: (abs(x), -x))


def _series(dispatches: list, modules: list, read_by: list, what: str, slack=EARLY_NS):
    """([module or None for each dispatch span], shift, module events past
    the last dispatch span), or a str saying why the series does not line
    up. `shift` > 0 module events are dropped at the head (their dispatch
    came before the host's trace began), or `-shift` dispatch spans are
    (the device's trace began after the host's); the smallest shift is
    taken under which every module starts after the span it is paired
    with starts, ends before that dispatch was read back (`read_by`: the
    end of its read-back's span, or None where none is in the trace), and
    the module events left over start after the last dispatch span (the
    device's trace ended after the host's). A read-back may end `slack`
    before its module does. Events are [name, start_ns, duration_ns, ...],
    in order."""
    n = len(dispatches)
    last = dispatches[-1][1] if dispatches else float("-inf")
    for shift in sorted(range(-min(MAX_AHEAD, n), min(MAX_AHEAD, len(modules)) + 1),
                        key=lambda x: (abs(x), -x)):
        spans, by = dispatches[max(-shift, 0):], read_by[max(-shift, 0):]
        rest = modules[max(shift, 0):]
        if (all(m[1] >= d[1] and (t is None or m[1] + m[2] <= t + slack)
                for d, m, t in zip(spans, rest, by))
                and all(m[1] >= last for m in rest[len(spans):])):
            paired = [None] * (n - len(spans)) + rest[:len(spans)]
            return paired + [None] * (n - len(paired)), shift, max(len(rest) - len(spans), 0)
    pairs = list(zip(dispatches, modules, read_by))
    early = [(d[1] - m[1]) / 1e6 for d, m, _t in pairs if m[1] < d[1]]
    late = [(m[1] + m[2] - t) / 1e6 for _d, m, t in pairs
            if t is not None and m[1] + m[2] > t + slack]
    return (f"{what}: no {MAX_AHEAD} or fewer module events or dispatch spans dropped at the "
            f"head make every module start after its dispatch span and end before its "
            f"read-back ({n} dispatch spans, {len(modules)} module events): something "
            f"without a span ran the program, or the two clocks disagree (paired as they "
            f"stand, {len(early)} modules start before their dispatch span, by up to "
            f"{max(early, default=0.0):.3f} ms, and {len(late)} end after their read-back, "
            f"by up to {max(late, default=0.0):.3f} ms)")


def _lag_ms(sync, module):
    """Read-back lag: the sync's end less the later of the module's end and
    the sync's start."""
    return (sync[1] + sync[2] - max(module[1] + module[2], sync[1])) / 1e6


def join(raw: dict):
    """(joined, None) or (None, why). `raw` is `spans.load`'s. `joined`:
    {"chunks": [row a decode dispatch], "placements": [row a first token
    read], "clock": {the offset applied to the device's clock and the
    window the trace allows}, "unjoined": {...counts at the trace's
    edges}}; times in ms, `at_ms` from the trace's first engine span."""
    devices = sorted((p for p in raw["planes"] if tr.DEVICE_PLANE.match(p["name"])),
                     key=lambda p: p["name"])
    if not devices:
        return None, "the trace holds no /device:TPU:N plane"
    mods = sorted(tr._line(devices[0], tr.MODULE_LINE), key=lambda e: e[1])
    engine = [e for p in raw["planes"] if not tr.DEVICE_PLANE.match(p["name"])
              for line in p["lines"] if tr.is_engine_thread(line["events"])
              for e in line["events"]]
    engine.sort(key=lambda e: e[1])

    def named(name):
        return [e for e in engine if e[0] == E + name]

    dispatches = named("decode_dispatch")
    if not engine or not any("seq" in d[3] for d in dispatches):
        return None, "no .decode_dispatch span carries a seq: a program before PR 37, or no session"
    t0 = engine[0][1]
    syncs = named("chunk_sync")
    sync_of = {s[3]["seq"]: s for s in syncs if "seq" in s[3]}
    first_read = {s[3]["request_id"]: s for s in syncs
                  if s[3].get("chunk") == 0 and "request_id" in s[3]}

    def end_of(sync):
        return None if sync is None else sync[1] + sync[2]

    pieces = named("prefill_dispatch")
    decode_by = [end_of(sync_of.get(d[3].get("seq"))) for d in dispatches]
    prefill_by = [end_of(first_read.get(p[3].get("request_id"))) for p in pieces]
    why = None
    for offset in OFFSETS_NS:
        # As recorded a read-back may end EARLY_NS before its module; a
        # clock that is moved is moved until none does.
        moved = [[m[0], m[1] + offset, *m[2:]] for m in mods] if offset else mods
        slack = 0.0 if offset else EARLY_NS
        decode = _series(dispatches,
                         [m for m in moved if tr.module_base(m[0]) == DECODE_MODULE],
                         decode_by, "decode", slack)
        prefill = _series(pieces,
                          [m for m in moved if tr.module_base(m[0]) in PREFILL_SERIES],
                          prefill_by, "prefill", slack)
        said = [x for x in (decode, prefill) if isinstance(x, str)]
        if not said:
            break
        why = why or said[0]  # what the clocks as recorded did not allow
    else:
        return None, why + f"; nor under any offset of the device's clock up to {abs(OFFSETS_NS[-1]) / 1e6:g} ms"
    emit_of = {e[3]["seq"]: e for e in named("emit") if "seq" in e[3]}
    in_trace = {d[3]["seq"] for d in dispatches}
    chunks = []
    for d, m in zip(dispatches, decode[0]):
        sync = sync_of.get(d[3]["seq"])
        row = {"seq": d[3]["seq"], "chunk": d[3].get("chunk"), "at_ms": (d[1] - t0) / 1e6,
               "enqueue_to_start_ms": None, "device_ms": None, "readback_lag_ms": None,
               "sync_ms": sync[2] / 1e6 if sync else None,
               "emit_ms": emit_of[d[3]["seq"]][2] / 1e6 if d[3]["seq"] in emit_of else None}
        if m is not None:
            row["enqueue_to_start_ms"] = (m[1] - d[1]) / 1e6
            row["device_ms"] = m[2] / 1e6
            if sync is not None:
                row["readback_lag_ms"] = _lag_ms(sync, m)
        chunks.append(row)

    by_request: dict = {}  # request_id -> [(piece span, its module or None)]
    for p, m in zip(pieces, prefill[0]):
        by_request.setdefault(p[3].get("request_id"), []).append((p, m))
    first_emits = {e[3]["request_id"]: e for e in named("emit") if "request_id" in e[3]}
    claims = {c[3]["request_id"]: c for c in named("claim") if "request_id" in c[3]}
    placements = []
    unread = 0
    for rid, sync in first_read.items():
        mine = by_request.get(rid, [])
        if not mine or not mine[-1][0][3].get("last") or any(m is None for _p, m in mine):
            unread += 1  # placed before the trace began, or a module after it
            continue
        m = mine[-1][1]
        row = {"request_id": rid, "at_ms": (mine[0][0][1] - t0) / 1e6, "pieces": len(mine),
               "take": sum(int(p[3].get("take", 0)) for p, _m in mine),
               "enqueue_to_start_ms": (mine[0][1][1] - mine[0][0][1]) / 1e6,
               "device_ms": sum(x[2] for _p, x in mine) / 1e6,
               "readback_lag_ms": _lag_ms(sync, m),
               "read_lag_ms": (sync[1] + sync[2] - m[1] - m[2]) / 1e6}
        # The flight recorder's stages, where it was on: a placement's whole
        # story from the trace directory alone.
        for span, keys in ((claims.get(rid), ("slot_wait_ms", "loop_wait_ms", "flush_ms")),
                           (first_emits.get(rid), ("place_ms", "prefill_ms", "read_blocked_ms"))):
            for key in keys:
                if span is not None and key in span[3]:
                    row[key] = float(span[3][key])
        placements.append(row)

    # Causality bounds the clocks' disagreement from both sides: no module
    # starts before its dispatch span does, none ends after its read-back.
    starts = [r["enqueue_to_start_ms"] for r in chunks + placements
              if r["enqueue_to_start_ms"] is not None]
    lags = [r["readback_lag_ms"] for r in chunks + placements
            if r["readback_lag_ms"] is not None]
    return {
        "chunks": chunks, "placements": placements,
        "clock": {
            # The offset applied to the device's clock (0: as recorded), and
            # the offsets the joined trace would still allow: a lag's level
            # is known no better than this window is wide.
            "offset_ms": offset / 1e6,
            "window_ms": [-min(starts, default=0.0), min(lags, default=0.0)],
        },
        "unjoined": {
            # Edges of the trace. A shift is module events dropped at the
            # head (dispatched before the host's trace began: at most the
            # pipeline's depth + 1) or, below zero, dispatch spans dropped
            # there (the device's trace began later).
            "decode_shift": decode[1], "prefill_shift": prefill[1],
            "modules_past_the_last_dispatch_span": decode[2] + prefill[2],
            "syncs_of_dispatches_before_the_trace": sum(
                1 for s in sync_of if s not in in_trace),
            "dispatches_whose_module_is_past_the_trace": sum(
                1 for r in chunks if r["device_ms"] is None),
            "dispatches_not_read_in_the_trace": sum(
                1 for r in chunks if r["device_ms"] is not None and r["sync_ms"] is None),
            "first_tokens_with_a_piece_outside_the_trace": unread,
        },
    }, None


def joined(ctx: dict):
    """What the readers share: the join of the traced run's own trace
    directory, made once a run; None where the run was not traced or the
    trace does not join (the reason goes to standard error)."""
    if "causal" not in ctx:
        traced = ctx.get("traced")
        ctx["causal"] = None
        if traced and traced.get("dir"):
            ctx["causal"], why = join(spans.load(traced["dir"], ctx.get("model")))
            if why:
                print(f"[bench] causal join: {why}", file=sys.stderr, flush=True)
    return ctx["causal"]


def _mean(values):
    values = [v for v in values if v is not None]
    return sum(values) / len(values) if values else None


def readback_lag_ms(ctx):
    """Mean read-back lag over the decode chunks read in the trace."""
    j = joined(ctx)
    return _mean(r["readback_lag_ms"] for r in j["chunks"]) if j else None


def read_lag_ms(ctx):
    """Mean read lag over the placements whose first token was read in the trace."""
    j = joined(ctx)
    return _mean(r["read_lag_ms"] for r in j["placements"]) if j else None


def tables(j: dict) -> str:
    def cell(v, width=9):
        return f"{'-':>{width}s}" if v is None else f"{v:{width}.3f}"

    lines = [f"{len(j['placements'])} placements and {len(j['chunks'])} decode dispatches "
             f"joined to their device programs; at the trace's edges: "
             + ", ".join(f"{k.replace('_', ' ')} {v}" for k, v in j["unjoined"].items())]
    lo, hi = j["clock"]["window_ms"]
    moved = j["clock"]["offset_ms"]
    lines.append((f"device clock moved by {moved:g} ms to line the series up" if moved
                  else "device clock as recorded")
                 + f"; the trace would allow {lo:.3f} to {hi:.3f} ms more (no module starts "
                 f"before its dispatch span, none ends after its read-back): a lag's level is "
                 f"known to within that window, its spread over the trace exactly")
    stage_keys = ("slot_wait_ms", "loop_wait_ms", "flush_ms", "place_ms", "prefill_ms",
                  "read_blocked_ms")
    lines.append("")
    lines.append(f"{'placement (ms)':24s} {'at':>9s} {'take':>6s} {'enq->dev':>9s} "
                 f"{'device':>9s} {'rb lag':>9s} {'read lag':>9s} "
                 + " ".join(f"{k[:-3]:>12s}" for k in stage_keys))
    for r in j["placements"]:
        lines.append(f"{r['request_id'][-24:]:24s} {cell(r['at_ms'])} {r['take']:6d} "
                     f"{cell(r['enqueue_to_start_ms'])} {cell(r['device_ms'])} "
                     f"{cell(r['readback_lag_ms'])} {cell(r['read_lag_ms'])} "
                     + " ".join(cell(r.get(k), 12) for k in stage_keys))
    lines.append("")
    lines.append(f"{'decode dispatch (ms)':24s} {'at':>9s} {'steps':>6s} {'enq->dev':>9s} "
                 f"{'device':>9s} {'rb lag':>9s} {'sync':>9s} {'emit':>9s}")
    for r in j["chunks"]:
        lines.append(f"{'seq ' + str(r['seq']):24s} {cell(r['at_ms'])} {r['chunk'] or 0:6d} "
                     f"{cell(r['enqueue_to_start_ms'])} {cell(r['device_ms'])} "
                     f"{cell(r['readback_lag_ms'])} {cell(r['sync_ms'])} {cell(r['emit_ms'])}")
    lines.append("")
    chunk_lags = [r["readback_lag_ms"] for r in j["chunks"] if r["readback_lag_ms"] is not None]
    lines.append(f"mean read-back lag of the decode chunks "
                 f"{cell(_mean(chunk_lags))} ms (least {cell(min(chunk_lags, default=None))}, "
                 f"most {cell(max(chunk_lags, default=None))}); of the first "
                 f"tokens {cell(_mean(r['readback_lag_ms'] for r in j['placements']))} ms; "
                 f"mean read lag of the first tokens "
                 f"{cell(_mean(r['read_lag_ms'] for r in j['placements']))} ms")
    # Free of the clocks' offset: what the host saw of a placement beyond
    # its device time, and the lags' spread above the trace's own least.
    around = [r["enqueue_to_start_ms"] + r["readback_lag_ms"] for r in j["placements"]]
    lines.append(f"whatever the clocks' offset: a placement's enq->dev + rb lag "
                 f"{cell(_mean(around))} ms (least {cell(min(around, default=None))}, most "
                 f"{cell(max(around, default=None))}); a decode chunk's rb lag above the least "
                 f"{cell(_mean(chunk_lags) - min(chunk_lags) if chunk_lags else None)} ms")
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
    j, why = join(spans.load(argv[0], model))
    if j is None:
        print(f"no join: {why}")
        return 1
    print(tables(j))
    return 0


if __name__ == "__main__":
    sys.exit(main())
