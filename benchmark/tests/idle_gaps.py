"""Where a cell's device idle goes: traced windows with the program's
tracer bound, their idle gaps put down to the program's spans, in one
process on the chip.

    python3 benchmark/tests/idle_gaps.py --workload <cell> --seed <n> \\
        --seconds 51

Set-up and warm-up as the harness makes them, then two traced windows
one after the other: ``registry``, with a registry bound alone as the
benchmark's traced run binds it, and ``tracer``, with an ``obs.Tracer``
beside it and the window opened through ``obs.profile.anchor``, so
that the program's spans land on the profiler's clock
(``trace/attribute.py``). Prints one JSON line per window and writes
them to ``chiprun_out/idle_gaps_<cell>.jsonl``. The benchmark's own runs
never run this: it is what the traced run reads once the harness binds
a tracer (PERF.md, Open questions).
"""

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: per-layer metrics read in each window, the four of
#: ``trace/attribute.py`` last
METRICS = ("dispatch.outside_engine_share", "engine.host_share",
           "device.idle_share", "dispatch.split_share",
           "dispatch.prepare_share", "dispatch.certify_share",
           "device.idle_attributed_share")
#: the spans whose shares, with the engines' phase time, name the window
NAMED = ("independent.split", "independent.prepare",
         "independent.finish", "analysis.certify")


def window(cell, pool, order, first, seconds, with_tracer):
    """One traced window; returns its record."""
    from benchmark import harness
    from benchmark.trace import attribute
    from benchmark.trace import profile as profile_trace
    from jepsen_tpu import obs
    from jepsen_tpu.obs import profile as obs_profile
    reg = obs.Registry()
    tr = obs.Tracer() if with_tracer else None
    outer = obs_profile.anchor if with_tracer else profile_trace.annotation
    trace_dir = os.path.join(ROOT, "benchmark", ".trace")
    checks = []
    profile_trace.start(trace_dir)
    try:
        with obs.sink_scope(tr, reg), outer(profile_trace.WINDOW):
            w0 = time.monotonic()
            i = first
            while True:
                j = order[i % len(order)]
                c = harness._one_check(cell, pool[j], j,
                                       profile_trace.annotation)
                checks.append(c)
                i += 1
                if c.t1 - w0 >= seconds:
                    break
    finally:
        profile_trace.stop()
    device, host = profile_trace.load_events(trace_dir)
    shutil.rmtree(trace_dir, ignore_errors=True)
    program = None
    if tr is not None:
        starts = [s for n, s, _ in host if n == profile_trace.WINDOW]
        if starts:
            program = attribute.program_spans(obs_profile.on_profile_clock(
                tr.events(), profile_trace.WINDOW, starts[0]))
    red = attribute.reduce_events(device, host, program)
    run = harness.Run(window_s=checks[-1].t1 - w0, checks=checks,
                      phase_s=harness._phase_seconds(reg), trace=red)
    metrics = {}
    for name in METRICS:
        v = harness.load_module(
            os.path.join(ROOT, "benchmark", "metrics", name + ".py"),
            "bench_metric_" + name.replace(".", "_")).read(run)
        if v is not None:
            metrics[name] = v
    by_label = {}
    for label, s in (red.gaps if red is not None else ()):
        by_label[label] = by_label.get(label, 0.0) + s
    rec = {"cell": cell.name, "mode": "tracer" if with_tracer
           else "registry",
           "checks_s": [c.t1 - c.t0 for c in checks],
           "s_per_check": run.window_s / len(checks),
           "failed": sum(1 for c in checks if c.failed),
           "window_s": run.window_s, "metrics": metrics,
           "phase_s": sum(run.phase_s.values()),
           "idle_by_label": sorted(by_label.items(),
                                   key=lambda kv: -kv[1])[:15]}
    if red is not None:
        rec["device"] = {"busy_s": red.busy_s, "window_s": red.window_s}
        rec["breakdown"] = red.breakdown()
    if isinstance(red, attribute.Attributed):
        rec["program_s"] = dict(sorted(red.program_s.items(),
                                       key=lambda kv: -kv[1])[:20])
        named = sum(red.program_s.get(n, 0.0) for n in NAMED)
        rec["unnamed_share"] = (red.window_s - named
                                - rec["phase_s"]) / red.window_s
        rec["outside_program_spans_share"] = 1 - red.named_s / red.window_s
        rec["tracer_events"] = len(tr.events())
    return rec, len(checks)


def readings(bench, name, seed, seconds, out=None):
    from benchmark import harness
    from benchmark.trace import compiles as compile_events
    from benchmark.trace import profile as profile_trace
    cell = harness.load_cell(ROOT, bench, name)
    listener = compile_events.Listener()
    listener.install()
    devs = harness.require_chips(cell.chips)
    cell.check = cell.entry.make_check(cell.config, cell.traffic)
    pool = []
    for i in range(int(cell.traffic["pool"])):
        keys = harness.make_keys(cell.traffic, cell.config, i)
        pool.append(harness.Item(keys, cell.entry.prepare(
            keys, cell.traffic, cell.config, harness.arrivals(seed, i)),
            harness.count_invocations(keys)))
    order = harness.cycle_order(len(pool), seed)
    warm = cell.traffic.get("warmup", {})
    lo, hi = int(warm.get("min", 1)), int(warm.get("max", 4))
    n = 0
    for n in range(hi):
        before = listener.count()
        j = order[n % len(order)]
        harness._one_check(cell, pool[j], j, profile_trace.no_annotation)
        if n + 1 >= lo and listener.count() == before:
            break
    setup_s = time.monotonic() - T_START
    recs = []
    first = n + 1
    for with_tracer in (False, True):
        before = listener.count()
        rec, done = window(cell, pool, order, first, seconds, with_tracer)
        first += done
        rec.update(seed=seed, setup_s=setup_s,
                   compiles=listener.count() - before,
                   device_kind=harness.device_info(devs)["kind"])
        print(json.dumps(rec), flush=True)
        if out is not None:
            out.write(json.dumps(rec) + "\n")
            out.flush()
        recs.append(rec)
    return recs


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=51.0)
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    from jepsen_tpu import jaxcache
    jaxcache.enable()
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"idle_gaps_{args.workload}.jsonl"), "a") as out:
        readings(bench, args.workload, args.seed, args.seconds, out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
