#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips. It builds the engine from the
cell's files, checks the forward against the plain reference, warms the
cell's shapes, ramps, measures for --seconds and prints one JSON object as
its last line. No TPU, or fewer chips than the cell asks for: exit 2 and no
result. --rehearse-cpu runs the same control flow at tiny widths on the
CPU for the sandbox rehearsals, says so, prints no result and exits 3.
See benchmark/README.md.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

T_PROCESS = time.monotonic()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (BENCH_DIR, ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(*a) -> None:
    print("[bench]", *a, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true")
    ap.add_argument("--rate", type=float, default=None,
                    help="sweep only: offered rate in place of the cell's")
    ap.add_argument("--stall-dump", type=float, default=0.0,
                    help="by hand: dump every thread's stack to stderr when the "
                         "process stands still for this many seconds")
    ap.add_argument("--dump-trace", default=None,
                    help="write the reduced trace and its description here")
    args = ap.parse_args(argv)

    from harness.manifest import Cell, load_generator, reference_sizes, served_by

    cell = Cell(args.workload)
    if args.rehearse_cpu:
        cell.rehearse()  # rehearsal/<cell>.json, where a cell's slots x rows need it
    if args.rate is not None:
        cell.traffic["rate_rps"] = args.rate

    # Jax and the program are imported only now, so that a wrong workload
    # name fails before anything touches a chip.
    import jax
    from omnia_tpu.engine.coldstart import ColdStartTracker
    from omnia_tpu.engine.engine import InferenceEngine
    from omnia_tpu.engine.types import resolve_dtype
    from omnia_tpu.utils.compile_cache import enable_compilation_cache

    from harness import correct, roofline
    from harness import metrics as mt
    from harness import trace as tr
    from harness.compiles import CompileCounter
    from harness.load import DRIVERS, Heartbeat, prime
    from harness.weights import seeded_params

    devices = jax.devices()
    platform = devices[0].platform
    if args.rehearse_cpu:
        log(f"REHEARSAL on {platform} x{len(devices)}: tiny widths, no result")
    elif platform != "tpu" or len(devices) < cell.chips:
        log(f"cell {cell.name} needs {cell.chips} TPU chip(s); JAX reports "
            f"{platform} x{len(devices)}. No result.")
        return 2
    devices = devices[:cell.chips]
    kind = devices[0].device_kind

    peaks = None if args.rehearse_cpu else roofline.peaks(kind)  # unknown kind raises

    # Where JAX_COMPILATION_CACHE_DIR says, else <checkout>/.jax_cache: the
    # program's own rule, switched on here so that the weights program and
    # the reference check are cached too (the engine calls it again).
    enable_compilation_cache()
    compiles = CompileCounter(jax)

    mc = cell.model_config(rehearse=args.rehearse_cpu)
    ecfg = cell.engine_config(flight_events=(1 << 16) if args.trace else 0)
    coldstart = ColdStartTracker()
    coldstart.begin_phase("backend_init")
    t = time.monotonic()
    mesh_devices = devices if cell.chips > 1 else None
    params = seeded_params(mc, ecfg, mesh_devices, args.seed, resolve_dtype(ecfg.dtype),
                           model_module=cell.model_module)
    engine = InferenceEngine(mc, ecfg, params=params, seed=args.seed & 0x7FFFFFFF,
                             devices=mesh_devices, coldstart=coldstart)
    if served_by(engine) != cell.model_module:
        # `correct` would judge one module's forward while the window times another's.
        raise SystemExit(
            f"configuration {cell.spec['config']} names the model module "
            f"{cell.model_module!r} (program.module in its file), and the engine "
            f"dispatches to {served_by(engine)!r}: the key follows the program, never leads it")
    jax.block_until_ready(engine.params)
    build_s = time.monotonic() - t
    log(f"engine built in {build_s:.1f} s on {platform} {kind!r} x{len(devices)}")

    t = time.monotonic()
    ref = correct.check(engine, mc, reference_sizes(mc, cell.config_as_run(args.rehearse_cpu)),
                        args.seed, reference=cell.reference, model_module=cell.model_module)
    reference_s = time.monotonic() - t
    log(f"reference check in {reference_s:.1f} s: {json.dumps(ref)}")

    t = time.monotonic()
    engine.warmup(sessions=bool(cell.traffic.get("sessions", False)))
    warmup_s = time.monotonic() - t
    req0, hit0 = compiles.snapshot()
    log(f"warmup in {warmup_s:.1f} s; set-up asked the compile cache for {req0} "
        f"programs: {req0 - hit0} compiled, {hit0} from cache")

    sched = load_generator(cell.traffic["generator"])(cell.traffic, args.seed, args.seconds)
    # A request that has not ended 2 x seconds after it was due has failed.
    driver = DRIVERS[sched["loop"]](engine, sched, args.seed, mc.vocab_size,
                                    event_timeout_s=2 * args.seconds)
    w0, w1 = sched["window"]
    engine.start()
    t = time.monotonic()
    primed = prime(engine, ecfg, mc.vocab_size, args.seed, compiles)
    log(f"primed the request path in {time.monotonic() - t:.1f} s; programs asked "
        f"of the compiler in each pass: {primed}")
    req_r0, _ = compiles.snapshot()
    heartbeat = Heartbeat(dump_after_s=args.stall_dump)
    heartbeat.start()
    t0 = time.monotonic()
    driver.start(t0)

    def sleep_until(offset: float) -> None:
        time.sleep(max(t0 + offset - time.monotonic(), 0.0))

    sleep_until(w0)
    setup_s = time.monotonic() - T_PROCESS
    c_window0 = dict(engine.metrics)
    req_w0, _ = compiles.snapshot()
    traced = None
    if args.trace:
        span = min(4.0, max(args.seconds / 3, 0.5))
        trace_dir = os.path.join(ROOT, ".bench_trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        sleep_until(w0 + (args.seconds - span) / 2)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        ta, c_trace0 = time.monotonic(), dict(engine.metrics)
        time.sleep(span)
        tb, c_trace1 = time.monotonic(), dict(engine.metrics)
        jax.profiler.stop_trace()
        traced = {"t": (ta, tb), "dir": trace_dir,
                  "counters": _delta(c_trace0, c_trace1)}
    sleep_until(w1)
    c_window1 = dict(engine.metrics)
    queue_at_end = engine.queue_depth()
    req_w1, _ = compiles.snapshot()
    driver.wait(deadline=t0 + w1 + min(2 * args.seconds, 120.0))
    driver.stop()
    heartbeat.stop()
    log(f"host stalls: the heartbeat's worst beat was {heartbeat.worst[0] * 1e3:.0f} ms "
        f"late, {heartbeat.worst[1] - t0:.1f} s into the run (window {w0:g}-{w1:g} s)")
    flight = {}
    if engine._flight is not None:
        for ev in engine._flight.events("terminal"):
            flight[ev.request_id] = ev.attrs.get("breakdown", {})
    engine.stop()

    measured = driver.measured()
    compiled_in_window = req_w1 - req_w0
    failed = [r for r in measured if not r.ok]
    correct_ = bool(ref["ok"]) and not failed and compiled_in_window == 0 and bool(measured)
    log(f"window {args.seconds:g} s: {len(measured)} requests, {len(failed)} failed, "
        f"{compiled_in_window} programs asked of the compiler inside the window, "
        f"{req_w0 - req_r0} in the ramp before it")
    for r in failed[:5]:
        log(f"  failed: #{r.index} finish={r.finish} tokens={r.tokens}/{r.max_tokens} "
            f"error={r.error}")

    ctx = {
        "seconds": args.seconds, "records": measured, "all_records": driver.records,
        "chips": cell.chips, "model": cell.model, "peaks": peaks,
        "engine": {"num_slots": ecfg.num_slots},
        "counters_window": _delta(c_window0, c_window1),
        "setup": {"setup_s": setup_s, "build_s": build_s, "reference_s": reference_s,
                  "warmup_s": warmup_s, "phases": coldstart.phase_seconds(),
                  "programs_asked": req0, "programs_from_cache": hit0},
        "flight": flight, "trace": None, "traced": traced,
    }
    log("lengths: " + json.dumps(mt.describe_lengths(measured)))
    if sched["loop"] == "open":
        log("generator lateness ms: " + json.dumps(mt.lateness_histogram(measured)))
        log("backlog: " + json.dumps({**mt.backlog(measured), "queue_at_window_end": queue_at_end,
                                      "offered_rps": cell.traffic["rate_rps"]}))

    device = {
        "platform": platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": max(
            int((d.memory_stats() or {}).get("peak_bytes_in_use", 0)) for d in devices),
    }
    result = {"correct": correct_, "attempted": len(measured), "failed": len(failed),
              "metrics": {}, "device": device}
    if args.trace:
        raw = tr.load_xplane(tr.find_xplane(traced["dir"]))
        if args.rehearse_cpu:
            log("REHEARSAL: a CPU trace has no device plane; nothing to reduce")
            return 3
        reduced = tr.reduce(raw)
        ctx["trace"] = reduced
        if args.dump_trace:
            os.makedirs(args.dump_trace, exist_ok=True)
            with open(os.path.join(args.dump_trace, f"{cell.name}.describe.json"), "w") as f:
                json.dump(tr.describe(raw, top=40), f, indent=1)
            with open(os.path.join(args.dump_trace, f"{cell.name}.sample.json"), "w") as f:
                json.dump(tr.sample(raw, seconds=0.25), f)
        device["busy_s"], device["window_s"] = reduced["busy_s"], reduced["window_s"]
        result["breakdown"] = tr.breakdown(reduced)
        for name, mod in cell.layer_metrics:
            value = mod.read(ctx)
            if value is not None:
                result["metrics"][name] = {"value": value, "unit": mod.UNIT}
    else:
        for m in cell.end_to_end:
            value = mt.END_TO_END[m["name"]](ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    # Each number `correct` compared, beside its limit: last on stderr, and
    # last in the line.
    result["compared"] = {**correct.compared(ref), "failed": [len(failed), 0],
                          "compiled_in_window": [compiled_in_window, 0]}
    for name, (value, limit) in result["compared"].items():
        print(f"[bench] compared {name}: {value!r} (limit {limit!r})", file=sys.stderr, flush=True)
    if args.rehearse_cpu:
        log("REHEARSAL line (not a result): " + json.dumps(result))
        return 3
    print(json.dumps(result), flush=True)
    return 0


def _delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in b
            if isinstance(b[k], (int, float)) and not isinstance(b[k], bool)}


if __name__ == "__main__":
    sys.exit(main())
