"""Benchmark: the BASELINE.json config ladder for the device WGL engine.

Rungs (BASELINE.md north-star table):
  0. max single-key history length decidable in 60 s (primary metric),
     measured to the engine's limit by exponential growth + bisection,
     per model, including a raw-search FIFO row (round-4 rework)
  1. single ~200-op cas-register histories     (CPU-parity baseline)
  2. 32-key batched per-key checks, one chip   (jepsen.independent style)
  2b. 256-key batch -- the throughput headline since round 3
  2c. 1024-key batch + the keys-vs-throughput curve (headline is the
      best of 2b/2c)
  3. mutex, high contention
  4/4b. FIFO queue, info-free (aspect fast path)
  4c. 10k-op FIFO with info dequeues (exact aspect, round-3 extension)
  4d. 2k-op info FIFO through the RAW search engine (witness-order hint)
  5. 10k-op / 64-process cas-register with many info ops
     (the stretch goal: decided on device where the CPU oracle gives up)
  6. linear engine home turf: 50k-op 2-process crash-free history where
     the CPU event sweep beats the device search (the racer is real)
  7. streaming-monitor detection latency on an injected violation
  8. fleet compile-ledger reuse: the same 2x2 matrix run twice in two
     SEPARATE scheduler processes; the warm process must report
     persistent-ledger hits > 0, with cold-vs-warm wall clock recorded
  9. search-plan reduction: the same quiescent 4-key register history
     checked with the searchplan analyzer on and off; the detail
     records segment count, config-count estimate vs actual, wall
     clock for both paths, and the planner's own cost fraction
  11. obs overhead: the same fixed-op run with the tracer + crash-safe
      telemetry journals ON vs obs OFF entirely; the fleet telemetry
      plane must cost < 5% of clean-run wall clock
  12. introspection overhead: the same fixed device WGL search with
      the search-progress telemetry (per-dispatch progress-tensor
      device reads, heartbeats, padding accounting, journal flushes)
      ON vs obs OFF entirely — interleaved OFF/ON pairs, min-of-N
      quiet-floor estimator (rung 11's methodology); must stay < 5%,
      with explored-configs and device duty cycle in the detail so
      the optimization arc restarts from a measured baseline

The baseline is the sequential CPU WGL oracle (our knossos stand-in,
checker/wgl.py) with a 60 s / config-capped budget per history.

Prints TWO JSON lines: the full detail blob first, then a SHORT
headline-only line {"metric", "value", "unit", "vs_baseline",
"headline_rung"} LAST -- the driver's tail capture must always catch a
parseable headline (BENCH_r04's detail-first single line pushed the
headline out of the captured tail, VERDICT r4 weak #1). Since round 3
the headline value is the 256/1024-key batch rate (rounds 1-2 reported
the 32-key rung 2 rate, still present in the detail for a
like-for-like trend; vs_baseline divides by the single-thread CPU
oracle rate measured on the 32-key subset). The batch rungs are timed
as median-of-3 (a single-shot point once read 2,622 ops/s against a
stable ~8k, VERDICT r4 weak #2); per-run times ship in the detail.
"""

import json
import random
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 1)[0])

ORACLE_BUDGET_S = 60.0


def timed3(fn):
    """Median-of-3 timed runs. Returns (median_s, sorted runs, last
    result). A median over three warm runs keeps one stall from
    poisoning a reported rate."""
    runs = []
    res = None
    for _ in range(3):
        t0 = time.monotonic()
        res = fn()
        runs.append(round(time.monotonic() - t0, 3))
    return sorted(runs)[1], sorted(runs), res


def _oracle_worker(spec_name, hist, q):
    import sys as _s
    _s.path.insert(0, __file__.rsplit("/", 1)[0])
    from jepsen_tpu.checker import wgl
    from jepsen_tpu.models import model_spec
    spec = model_spec(spec_name)
    e, st = spec.encode(hist)
    t0 = time.monotonic()
    r = wgl.check_encoded(spec, e, st, max_configs=50_000_000)
    q.put({"valid": r["valid"], "s": time.monotonic() - t0})


class OracleRace:
    """CPU oracle in a killable subprocess (a timed-out thread would keep
    burning CPU under the device benches)."""

    def __init__(self, spec_name, hist):
        import multiprocessing as mp
        self.ctx = mp.get_context("spawn")
        self.q = self.ctx.Queue()
        self.p = self.ctx.Process(target=_oracle_worker,
                                  args=(spec_name, hist, self.q),
                                  daemon=True)
        self.t0 = time.monotonic()
        self.p.start()

    def result(self, budget_s=ORACLE_BUDGET_S):
        left = max(0.0, budget_s - (time.monotonic() - self.t0))
        self.p.join(timeout=left)
        out = {"valid": "unknown", "error": "timeout",
               "s": min(budget_s, time.monotonic() - self.t0)}
        try:
            # a process that exited cleanly has a result, but it may still
            # be in the queue's pipe buffer right after join(): block
            # briefly rather than misreport a near-deadline finish as a
            # timeout
            if self.p.exitcode == 0:
                got = self.q.get(timeout=5)
            else:
                got = self.q.get_nowait()
            out.update(got)
            out.pop("error", None)
        except Exception:  # noqa: BLE001 - empty queue = still running
            pass
        if self.p.is_alive():
            self.p.terminate()
        return out


def _monitor_rung(n_ops=512, violate_at=256, chunk=64):
    """Streaming-monitor detection metrics (jepsen_tpu.monitor): feed a
    synthetic cas-register stream with a violation injected half way
    (a read of a never-written value -- definitively invalid) through
    a standalone Monitor on the device engine, and report

      time_to_first_verdict_s  wall from monitor start to its first
                               definite chunk verdict (compile + first
                               search; the cold-start cost)
      detection_latency_s      wall from the violating op landing to
                               the violation being proven
      abort_latency_s          wall from the violating op landing to
                               the abort latch actually flipping

    Self-contained and never fatal: a monitor regression must show up
    as numbers (or an error field), not break the throughput bench."""
    try:
        from jepsen_tpu import monitor as jmon
        from jepsen_tpu import robust
        from jepsen_tpu.models import model_spec
        spec = model_spec("cas-register")
        latch = robust.ChainedLatch()
        mon = jmon.Monitor(spec, latch, chunk=chunk,
                           engine="jax-wgl").start()
        t_violation = None
        val = 0
        for i in range(n_ops):
            if i == violate_at:
                ops = [{"type": "invoke", "process": 0, "f": "read",
                        "value": None},
                       {"type": "ok", "process": 0, "f": "read",
                        "value": 10**6}]
            elif i % 2 == 0:
                val = i + 1
                ops = [{"type": "invoke", "process": 0, "f": "write",
                        "value": val},
                       {"type": "ok", "process": 0, "f": "write",
                        "value": val}]
            else:
                ops = [{"type": "invoke", "process": 0, "f": "read",
                        "value": None},
                       {"type": "ok", "process": 0, "f": "read",
                        "value": val}]
            for op in ops:
                mon.offer(op)
            if i == violate_at:
                t_violation = time.monotonic()
            if latch.is_set():
                break
        detected = latch.wait(120)
        t_abort = time.monotonic()
        mon.stop()
        s = mon.summary()
        return {
            "detected": bool(detected),
            "verdict": s.get("verdict"),
            "chunk": chunk,
            "ops_consumed": s.get("ops_consumed"),
            "checks": s.get("checks"),
            "time_to_first_verdict_s": s.get("time_to_first_verdict_s"),
            "detection_latency_s": s.get("detection_latency_s"),
            "abort_latency_s": (round(t_abort - t_violation, 4)
                                if detected and t_violation is not None
                                else None),
            "detected_at_index": s.get("detected_at_index"),
        }
    except Exception as exc:  # noqa: BLE001 - numbers, not crashes
        return {"error": repr(exc)}


def _stream_monitor_rung(n_streams=100, rounds=24, chunk=8,
                         violate_every=10):
    """Device-resident frontier monitoring at fleet width (rung 16,
    checker/streamlin + monitor/wgl_stream): drive ``n_streams``
    concurrent monitored cas-register streams, every ``violate_every``-th
    one carrying an injected stale read at the half-way round, in two
    modes --

      off  the pre-streamlin behavior: per-chunk FLAT re-search of the
           whole materialized prefix (mengine.check_prefix, jax-wgl)
      on   StreamCheck frontiers with the service Coalescer up, so
           strangers' frontier folds share padded (model, bucket)
           device batches

    and report sustained monitored-ops/s per mode, detection latency
    p50/p99 across the violating streams (violating op offered ->
    check proves False), the device duty cycle from the
    ``wgl.device_busy_s`` counter over each mode's wall (the PR 13
    metrics plane), per-chunk fold cost from the stream counters (the
    observable O(window) claim), and the coalescer's batch/segment/
    owners evidence (acceptance: batches > 0 with owners >= 2).
    Self-contained and never fatal."""
    import threading as _threading

    try:
        from jepsen_tpu import obs
        from jepsen_tpu.fleet import service
        from jepsen_tpu.models import model_spec
        from jepsen_tpu.monitor import engine as _mengine
        from jepsen_tpu.monitor.stream import StreamEncoder
        from jepsen_tpu.monitor.wgl_stream import StreamCheck

        spec = model_spec("cas-register")

        def reg_busy():
            reg = obs.registry()
            if reg is None:
                return 0.0
            return sum(v for k, v in
                       reg.snapshot()["counters"].items()
                       if k.startswith("wgl.device_busy_s"))

        def stream_ops(s, bad_round):
            ops, val = [], None
            for j in range(rounds):
                val = j + 1
                ops.append(({"type": "invoke", "process": 0,
                             "f": "write", "value": val}, None))
                ops.append(({"type": "ok", "process": 0,
                             "f": "write", "value": val}, None))
                rv = 10**6 if j == bad_round else val
                ops.append(({"type": "invoke", "process": 0,
                             "f": "read", "value": None}, None))
                ops.append(({"type": "ok", "process": 0,
                             "f": "read", "value": rv},
                            "violate" if j == bad_round else None))
            return ops

        def drive(mode):
            done = [0] * n_streams
            detect = {}
            streams_sc = []
            lock = _threading.Lock()

            def one(s):
                bad = rounds // 2 if s % violate_every == 0 else None
                if mode == "on":
                    sc = StreamCheck(spec, owner=f"bench-{s}")
                    with lock:
                        streams_sc.append(sc)
                else:
                    sc = StreamEncoder(spec)
                t_bad = None
                n = 0
                for i, (op, mark) in enumerate(stream_ops(s, bad)):
                    offered = sc.offer(op, i)
                    done[s] += 1
                    if mark == "violate":
                        t_bad = time.monotonic()
                    if offered:
                        n += 1
                        if n % chunk == 0 or mark == "violate":
                            if mode == "on":
                                r = sc.check()
                            else:
                                e, st = sc.materialize()
                                r = _mengine.check_prefix(
                                    spec, e, st, engine="jax-wgl")
                            if r["valid"] is False:
                                if t_bad is not None:
                                    with lock:
                                        detect[s] = (time.monotonic()
                                                     - t_bad)
                                return

            busy0 = reg_busy()
            t0 = time.monotonic()
            ths = [_threading.Thread(target=one, args=(s,))
                   for s in range(n_streams)]
            for t in ths:
                t.start()
            for t in ths:
                t.join()
            wall = time.monotonic() - t0
            lat = sorted(detect.values())
            out = {
                "wall_s": round(wall, 2),
                "ops": sum(done),
                "ops_per_s": round(sum(done) / wall, 1) if wall else None,
                "streams": n_streams,
                "violating": n_streams // violate_every
                + (1 if n_streams % violate_every else 0),
                "detected": len(lat),
                "detect_p50_ms": round(lat[len(lat) // 2] * 1e3, 1)
                if lat else None,
                "detect_p99_ms": round(
                    lat[min(len(lat) - 1,
                            int(len(lat) * 0.99))] * 1e3, 1)
                if lat else None,
                "device_busy_s": round(reg_busy() - busy0, 3),
                "duty_cycle": round((reg_busy() - busy0) / wall, 4)
                if wall else None,
            }
            if mode == "on":
                folds = sum(sc.seal_folds + sc.probe_folds
                            for sc in streams_sc)
                cells = sum(sc.fold_cells for sc in streams_sc)
                out.update({
                    "folds": folds,
                    "cells_per_fold": round(cells / folds, 1)
                    if folds else None,
                    "coalesced_folds": sum(sc.coalesced_folds
                                           for sc in streams_sc),
                    "solo_folds": sum(sc.solo_folds
                                      for sc in streams_sc),
                    "flat_fallbacks": sum(sc.flat_checks
                                          for sc in streams_sc),
                    "frontier_peak": max((sc.frontier_peak
                                          for sc in streams_sc),
                                         default=None),
                    # widest batch any fold rode: each stream is its
                    # own owner with one in-flight fold, so a batch of
                    # K members is K distinct owners sharing a dispatch
                    "batch_peak": max((sc.batch_peak
                                       for sc in streams_sc),
                                      default=1),
                    "device_fold_s": round(sum(sc.device_s
                                               for sc in streams_sc),
                                           3),
                })
            return out

        # OFF first (no coalescer), then ON with the batcher up
        service.configure_coalesce(enabled=False)
        off = drive("off")
        service.configure_coalesce(enabled=True, window_ms=25)
        try:
            on = drive("on")
            st = service.coalescer().stats()
            on["batches"] = st["batches"]
            on["segments"] = st["segments"]
            reg = obs.registry()
            owners_max = None
            if reg is not None:
                h = reg.snapshot().get("histograms", {}).get(
                    "service.coalesce.owners")
                if h:
                    owners_max = h.get("max")
            # registry histogram when a metrics plane is up; the
            # stream-side batch_peak is the registry-free evidence
            # (each stream = one owner with one in-flight fold)
            on["owners_max"] = owners_max or on.get("batch_peak")
        finally:
            service.configure_coalesce(enabled=False)
        return {
            "chunk": chunk, "rounds": rounds,
            "off": off, "on": on,
            "speedup": round(on["ops_per_s"] / off["ops_per_s"], 2)
            if off.get("ops_per_s") and on.get("ops_per_s") else None,
            "goal_met": bool(
                on.get("detected") == on.get("violating")
                and off.get("detected") == off.get("violating")
                and (on.get("batches") or 0) > 0
                and (on.get("owners_max") or 0) >= 2),
        }
    except Exception as exc:  # noqa: BLE001 - numbers, not crashes
        return {"error": repr(exc)[:300]}


def _fleet_reuse_rung(time_limit_s=3, budget_s=600):
    """Cross-PROCESS compile reuse (jepsen_tpu.fleet.ledger): run the
    SAME 2x2 register matrix twice in two separate scheduler
    processes sharing one store, and report

      cold / warm           per-process wall clock, exit code, and the
                            campaign report's compile-cache delta
      cross_process_reuse   True iff the second process reported
                            ledger hits > 0 (shapes the first process
                            compiled counted as hits, not re-misses)

    The subprocesses are pinned to CPU: the bench process holds the
    accelerator, and the ledger's claim is platform-independent.
    Self-contained and never fatal: a regression must show up as
    numbers (or an error field), not break the throughput bench."""
    import os
    import subprocess
    import tempfile
    try:
        # NB not __file__.rsplit("/", 1): invoked as `python bench.py`
        # __file__ is relative and has no slash to split on, and the
        # subprocess (unlike this process) can't lean on cwd
        repo = os.path.dirname(os.path.abspath(__file__))
        workdir = tempfile.mkdtemp(prefix="jepsen-fleet-reuse-")
        env = {**os.environ, "PYTHONPATH": repo, "JAX_PLATFORMS": "cpu"}
        out = {"matrix": "workload=register x seeds=2",
               "time_limit_s": time_limit_s}
        for phase in ("cold", "warm"):
            t0 = time.monotonic()
            p = subprocess.run(
                [sys.executable, "-m", "jepsen_tpu", "campaign",
                 "--no-ssh", "--time-limit", str(time_limit_s),
                 "--axis", "workload=register", "--seeds", "2",
                 "--parallel", "2", "--campaign-id", f"reuse-{phase}"],
                cwd=workdir, capture_output=True, text=True,
                timeout=budget_s, env=env)
            wall = round(time.monotonic() - t0, 1)
            rep_path = os.path.join(workdir, "store", "campaigns",
                                    f"reuse-{phase}", "report.json")
            with open(rep_path) as f:
                rep = json.load(f)
            cc = rep.get("compile_cache") or {}
            out[phase] = {"wall_s": wall, "exit": p.returncode,
                          "hits": cc.get("hits"),
                          "misses": cc.get("misses"),
                          "ledger": cc.get("ledger")}
        out["cross_process_reuse"] = bool(
            (out["warm"].get("hits") or 0) > 0)
        out["warm_speedup"] = round(
            out["cold"]["wall_s"] / out["warm"]["wall_s"], 2) \
            if out["warm"]["wall_s"] else None
        return out
    except Exception as exc:  # noqa: BLE001 - numbers, not crashes
        return {"error": repr(exc)[:300]}


def _fleet_survival_rung(time_limit_s=2, budget_s=900):
    """Fleet survivability (jepsen_tpu.fleet sync/chaos): the same
    2-seed register matrix dispatched to 2 loopback workers with an
    ISOLATED worker store (artifact sync on), three ways:

      clean        no faults: baseline fleet wall clock
      chaos        --chaos-profile soak:7 (exit-255s, a hang, a
                   kill -9, a partial download, torn ledger tail):
                   wall clock + lease/steal/sync counts -- the price
                   of surviving, and proof every recovery path ran
      warm         the clean matrix again in a FRESH process sharing
                   the same store: with the persistent jax
                   compilation cache enabled, the restart should stop
                   paying the XLA compiles the first run did

    chaos_overhead_x is chaos wall / clean wall; warm reports the
    ledger's cold/warm wall split and the jax cache population.
    Self-contained and never fatal: a survivability regression must
    show up as numbers (or an error field), not break the bench."""
    import os
    import subprocess
    import tempfile
    try:
        repo = os.path.dirname(os.path.abspath(__file__))
        workdir = tempfile.mkdtemp(prefix="jepsen-fleet-survival-")
        env = {**os.environ, "PYTHONPATH": repo, "JAX_PLATFORMS": "cpu"}
        out = {"matrix": "workload=register x seeds=2",
               "time_limit_s": time_limit_s}
        # NB the warm phase REUSES the clean phase's worker store: the
        # persistent jax compilation cache + compile ledger live
        # there, and surviving a process restart is their whole claim
        wstores = {"clean": "wstore-clean", "chaos": "wstore-chaos",
                   "warm": "wstore-clean"}
        for phase, extra in (("clean", []),
                             ("chaos", ["--chaos-profile", "soak:7"]),
                             ("warm", [])):
            t0 = time.monotonic()
            p = subprocess.run(
                [sys.executable, "-m", "jepsen_tpu", "campaign",
                 "--no-ssh", "--time-limit", str(time_limit_s),
                 "--axis", "workload=register", "--seeds", "2",
                 "--parallel", "2", "--workers", "local,local",
                 "--lease", "300", "--max-leases", "5",
                 "--sync-timeout", "60",
                 "--worker-store",
                 os.path.join(workdir, wstores[phase]),
                 "--campaign-id", f"survival-{phase}", *extra],
                cwd=workdir, capture_output=True, text=True,
                timeout=budget_s, env=env)
            wall = round(time.monotonic() - t0, 1)
            cdir = os.path.join(workdir, "store", "campaigns",
                                f"survival-{phase}")
            recs = []
            with open(os.path.join(cdir, "cells.jsonl")) as f:
                for ln in f:
                    try:
                        recs.append(json.loads(ln))
                    except ValueError:
                        pass
            ev = [r for r in recs if r.get("event")]
            outcomes = [r for r in recs if not r.get("event")]
            out[phase] = {
                "wall_s": wall, "exit": p.returncode,
                "cells": len(outcomes),
                "ok": sum(1 for r in outcomes
                          if r.get("outcome") is True),
                "leases": sum(1 for e in ev
                              if e["event"] == "lease"),
                "steals": sum(1 for e in ev
                              if e["event"] == "lease-failed"),
                "syncs_ok": sum(1 for e in ev
                                if e["event"] == "artifact-sync"
                                and e.get("status") == "ok"),
                "syncs_failed": sum(1 for e in ev
                                    if e["event"] == "artifact-sync"
                                    and e.get("status") == "failed"),
                "mirrored": sum(1 for r in outcomes
                                if r.get("synced") is True
                                and os.path.isdir(str(r.get("path")))),
            }
        if out["clean"]["wall_s"]:
            out["chaos_overhead_x"] = round(
                out["chaos"]["wall_s"] / out["clean"]["wall_s"], 2)
        from jepsen_tpu.fleet import ledger as fledger
        led = fledger.Ledger(os.path.join(workdir, "store",
                                          "compile_ledger"))
        st = led.stats()
        from jepsen_tpu import jaxcache
        jax_cache = jaxcache.enable()
        out["warm_restart"] = {
            "cold_wall_s": st.get("cold_wall_s"),
            "warm_wall_s": st.get("warm_wall_s"),
            "warm_vs_clean_x": round(
                out["warm"]["wall_s"] / out["clean"]["wall_s"], 2)
            if out["clean"]["wall_s"] else None,
            "jax_cache_files": sum(
                len(files) for _, _, files in os.walk(jax_cache)),
        }
        return out
    except Exception as exc:  # noqa: BLE001 - numbers, not crashes
        return {"error": repr(exc)[:300]}


def _ha_takeover_rung(time_limit_s=2, budget_s=900):
    """Coordinator failover (jepsen_tpu.fleet.ha): the rung-10 2-seed
    register matrix on 2 loopback workers, two ways:

      clean        coordinator HA on (lease 3 s), no faults: the
                   lease-renewal plane's price on the fleet wall
      kill         the ``coordinator-kill`` chaos fault SIGKILLs the
                   active coordinator right after a seeded lease
                   grant; a standby process tails the journal, fences
                   the corpse, and finishes the campaign

    Reported: detection+takeover latency (SIGKILL, stamped by the
    chaos die-once marker, to the standby's first post-takeover
    coordinator-lease grant), cells re-leased vs lost after the kill,
    and the kill-soak wall vs the clean HA wall. Self-contained and
    never fatal: a failover regression must show up as numbers (or an
    error field), not break the bench."""
    import os
    import subprocess
    import tempfile
    try:
        repo = os.path.dirname(os.path.abspath(__file__))
        workdir = tempfile.mkdtemp(prefix="jepsen-ha-takeover-")
        env = {**os.environ, "PYTHONPATH": repo, "JAX_PLATFORMS": "cpu"}
        out = {"matrix": "workload=register x seeds=2",
               "time_limit_s": time_limit_s, "coordinator_lease_s": 3}
        base = [sys.executable, "-m", "jepsen_tpu", "campaign",
                "--no-ssh", "--time-limit", str(time_limit_s),
                "--axis", "workload=register", "--seeds", "2",
                "--parallel", "2", "--workers", "local,local",
                "--lease", "300", "--max-leases", "5",
                "--coordinator-lease-s", "3", "--takeover-grace-s", "2"]

        def read_journal(cid):
            recs = []
            path = os.path.join(workdir, "store", "campaigns", cid,
                                "cells.jsonl")
            with open(path) as f:
                for ln in f:
                    try:
                        recs.append(json.loads(ln))
                    except ValueError:
                        pass
            return recs

        # clean: HA on, nobody dies -- the renewal plane's price
        t0 = time.monotonic()
        p = subprocess.run(base + ["--campaign-id", "ha-clean"],
                           cwd=workdir, capture_output=True, text=True,
                           timeout=budget_s, env=env)
        clean_wall = round(time.monotonic() - t0, 1)
        recs = read_journal("ha-clean")
        out["clean"] = {
            "wall_s": clean_wall, "exit": p.returncode,
            "ok": sum(1 for r in recs if not r.get("event")
                      and r.get("outcome") is True),
            "renewals": sum(1 for r in recs
                            if r.get("event") == "coordinator-lease"),
        }

        # kill: chaos SIGKILLs the coordinator; a standby takes over
        t0 = time.monotonic()
        coord = subprocess.Popen(
            base + ["--chaos-profile", "coordinator-kill:7",
                    "--campaign-id", "ha-kill"],
            cwd=workdir, stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL, env=env)
        cdir = os.path.join(workdir, "store", "campaigns", "ha-kill")
        deadline = time.monotonic() + 60
        while not os.path.exists(os.path.join(cdir, "campaign.json")) \
                and time.monotonic() < deadline:
            time.sleep(0.2)
        standby = subprocess.run(
            base + ["--standby", "--campaign-id", "ha-kill"],
            cwd=workdir, capture_output=True, text=True,
            timeout=budget_s, env=env)
        coord.wait(timeout=budget_s)
        kill_wall = round(time.monotonic() - t0, 1)

        from jepsen_tpu.analysis.fleetmodel import parse_t
        recs = read_journal("ha-kill")
        takeover_i, takeover = next(
            ((i, r) for i, r in enumerate(recs)
             if r.get("event") == "coordinator-takeover"), (None, None))
        # the chaos die-once marker is written (flush+fsync)
        # immediately before the SIGKILL: its mtime IS the kill stamp
        marker = os.path.join(cdir, "chaos-coordinator-kill")
        kill_t = os.path.getmtime(marker) if os.path.exists(marker) \
            else None
        first_grant_t = next(
            (parse_t(r.get("t")) for r in recs[takeover_i or 0:]
             if r.get("event") == "coordinator-lease"
             and r.get("epoch") == (takeover or {}).get("epoch")), None)
        outcomes = [r for r in recs if not r.get("event")]
        terminal = {str(r.get("cell")) for r in outcomes
                    if r.get("outcome") != "aborted"}
        releases = sum(1 for i, r in enumerate(recs)
                       if r.get("event") == "lease"
                       and takeover_i is not None and i > takeover_i)
        out["kill"] = {
            "wall_s": kill_wall,
            "coordinator_exit": coord.returncode,   # -9: chaos landed
            "standby_exit": standby.returncode,
            "takeover": takeover is not None,
            "takeover_epoch": (takeover or {}).get("epoch"),
            "detect_takeover_s": round(
                parse_t(takeover.get("t")) - kill_t, 1)
            if takeover is not None and kill_t
            and parse_t(takeover.get("t")) else None,
            "kill_to_first_grant_s": round(first_grant_t - kill_t, 1)
            if first_grant_t and kill_t else None,
            "cells_releases_after_takeover": releases,
            "cells_lost": 2 - len(terminal),
            "kill_vs_clean_x": round(kill_wall / clean_wall, 2)
            if clean_wall else None,
        }
        return out
    except Exception as exc:  # noqa: BLE001 - numbers, not crashes
        return {"error": repr(exc)[:300]}


def _searchplan_rung(keys=4, bursts=6):
    """Search-plan reduction (jepsen_tpu.analysis.searchplan): the
    same quiescent multi-key cas-register batch checked with planning
    on and off, reporting

      segments                sub-searches the planner produced
      est_configs             planner's estimate, planned vs unplanned
      configs_explored        ACTUAL configs, planned vs unplanned
      wall_s                  device wall, planned vs unplanned
      planner_s / frac        the analyzer's own cost and its share of
                              the planned path's end-to-end time

    Each key is `bursts` concurrent write||write bursts separated by
    sealed quiescent writes, with one crashed (:info) read per burst
    and a STALE final read: the history is invalid, so both paths run
    a full exhaustion proof — and the flat one must carry every
    subset of the forever-open crashed reads (they are optional to
    linearize at every config, ~2^bursts distinct configs), while the
    planner elides them as search-dead and proves each tiny segment
    in isolation. The stale value is one actually written earlier, so
    the state-abstraction fast path can't shortcut either side.
    Self-contained and never fatal: a planner regression must show up
    as numbers (or an error field), not break the throughput bench."""
    try:
        from jepsen_tpu.analysis import searchplan
        from jepsen_tpu.models import model_spec
        from jepsen_tpu.parallel import check_batch_encoded
        spec = model_spec("cas-register")

        def key_hist():
            evs = []
            i = 0

            def ev(t, p, f, v):
                nonlocal i
                evs.append({"type": t, "process": p, "f": f,
                            "value": v, "index": i})
                i += 1

            for j in range(bursts):
                x = j * 10
                ev("invoke", 0, "write", x)
                ev("invoke", 1, "write", x + 1)
                ev("ok", 0, "write", x)
                ev("ok", 1, "write", x + 1)
                ev("invoke", 100 + j, "read", None)  # client times out:
                ev("info", 100 + j, "read", None)    # open forever
                ev("invoke", 0, "write", x + 5)   # sealing quiescent
                ev("ok", 0, "write", x + 5)       # write closes burst
            ev("invoke", 2, "read", None)
            ev("ok", 2, "read", 0)                # stale read: invalid
            return evs

        hists = [key_hist() for _ in range(keys)]
        out = {"keys": keys, "ops_per_key": len(hists[0]) // 2}

        # unplanned: today's default per-key batch
        pairs_off = [spec.encode(hv) for hv in hists]
        t0 = time.monotonic()
        r_off = check_batch_encoded(spec, pairs_off)
        out["wall_s_unplanned"] = round(time.monotonic() - t0, 3)

        # planned: segment each key at sealed quiescent cuts, one batch
        t0 = time.monotonic()
        all_segs = []
        spans = []
        est_planned = 0
        for hv in hists:
            segs, _info = searchplan.segment_events(spec, hv,
                                                    min_segment=1)
            spans.append((len(all_segs), len(segs)))
            all_segs += segs
            est_planned += sum(s.est_configs for s in segs)
        planner_s = time.monotonic() - t0
        pairs_on = [spec.encode(s.events) for s in all_segs]
        t0 = time.monotonic()
        r_on = check_batch_encoded(spec, pairs_on)
        wall_on = time.monotonic() - t0
        out.update({
            "segments": len(all_segs),
            "est_configs": {
                "planned": est_planned,
                "unplanned": sum(searchplan.estimate_configs(hv)
                                 for hv in hists)},
            "configs_explored": {
                "planned": sum(int(r.get("configs_explored") or 0)
                               for r in r_on),
                "unplanned": sum(int(r.get("configs_explored") or 0)
                                 for r in r_off)},
            "wall_s_planned": round(wall_on, 3),
            "planner_s": round(planner_s, 4),
            "planner_frac": round(planner_s / max(1e-9,
                                                  planner_s + wall_on),
                                  4),
            "verdicts_equal": (
                [r.get("valid") for r in r_off]
                == [searchplan.merge_segment_results(
                    r_on[s:s + c]).get("valid")
                    for s, c in spans]),
        })
        out["reduction"] = round(
            out["configs_explored"]["unplanned"]
            / max(1, out["configs_explored"]["planned"]), 2)
        return out
    except Exception as exc:  # noqa: BLE001 - numbers, not crashes
        return {"error": repr(exc)[:300]}


#: simulated per-op client latency for the obs-overhead rung, seconds.
#: 0.5 ms is CONSERVATIVE: the reference framework's ops cross SSH to
#: real database processes (network RTT alone is 0.1-1 ms; device-model
#: ops are far slower), so a clean-run denominator built from 0.5 ms
#: ops overstates the telemetry plane's relative cost, never hides it.
OBS_RUNG_OP_S = 0.0005


def _obs_overhead_rung(n_ops=4000, concurrency=8, pairs=6):
    """Telemetry-plane overhead (jepsen_tpu.obs): the same fixed-op
    run with obs OFF vs obs ON — where ON means the full fleet plane:
    per-op trace spans, metrics, AND the incremental crash-safe
    journals at the shipped default flush cadence. The client costs
    OBS_RUNG_OP_S per op (see above: conservative vs any real op) at a
    realistic concurrency (real campaigns run 5-64 workers; at
    concurrency 2 the interpreter loop is artificially
    dispatch-latency-bound and every microsecond of main-loop work
    triples through a GIL convoy), which makes ``overhead_frac`` the
    plane's share of a representative clean-run wall clock. One extra
    OFF/ON pair runs with the noop client — ops that cost literally
    nothing — and is reported as the ``stress_*`` detail: the
    instrumentation's worst case against a degenerate denominator,
    tracked but not the goal.

    Methodology: OFF/ON runs strictly interleaved, overhead computed
    from the per-variant MINIMUM. The shared CI/dev boxes this runs on
    show hypervisor-steal noise far larger than the effect (identical
    runs vary by 2-3x minutes apart); under additive load noise the
    minimum is the standard quiet-floor estimator, and interleaving
    keeps a slow stretch from landing entirely on one variant.
    Goal: overhead < 5%."""
    import tempfile

    try:
        from jepsen_tpu import checker as cc
        from jepsen_tpu import client as jclient
        from jepsen_tpu import core, store
        from jepsen_tpu import generator as gen
        from jepsen_tpu.os import noop as os_noop

        class _DelayClient(jclient.Client):
            def invoke(self, test, op):
                time.sleep(OBS_RUNG_OP_S)
                out = dict(op)
                out["type"] = "ok"
                return out

            def reusable(self, test):
                return True

        def build(obs_on, delay):
            return {
                "name": "bench-obs-overhead",
                "nodes": ["n1"], "concurrency": concurrency,
                "ssh": {"dummy?": True}, "os": os_noop,
                "client": _DelayClient() if delay else jclient.noop,
                "checker": cc.unbridled_optimism(),
                "generator": gen.clients(gen.limit(
                    n_ops, gen.repeat({"f": "read"}))),
                # default telemetry-flush-ms (500): the rung measures
                # the plane as shipped, journals included
                "obs?": obs_on,
            }

        def run_one(obs_on, delay=True):
            t0 = time.perf_counter()
            t = core.run(core.prepare_test(build(obs_on, delay)))
            assert t["results"]["valid"] is True
            return time.perf_counter() - t0, t

        saved = store.base_dir
        off_runs, on_runs = [], []
        with tempfile.TemporaryDirectory() as tmp:
            store.base_dir = tmp
            try:
                run_one(False)          # warm both code paths once
                run_one(True)
                for _ in range(pairs):
                    off_runs.append(run_one(False)[0])
                    s, t_on = run_one(True)
                    on_runs.append(s)
                stress_off = run_one(False, delay=False)[0]
                stress_on = run_one(True, delay=False)[0]
                trace_p = store.path(t_on, "trace.jsonl")
                trace_events = sum(1 for _ in open(trace_p)) \
                    if trace_p and __import__("os").path.exists(
                        trace_p) else None
            finally:
                store.base_dir = saved
        off_s, on_s = min(off_runs), min(on_runs)
        overhead = (on_s - off_s) / off_s if off_s > 0 else None
        return {
            "n_ops": n_ops, "pairs": pairs,
            "op_cost_s": OBS_RUNG_OP_S,
            "off_s": round(off_s, 4),
            "off_runs": [round(x, 3) for x in off_runs],
            "on_s": round(on_s, 4),
            "on_runs": [round(x, 3) for x in on_runs],
            "trace_events": trace_events,
            "overhead_frac": (round(overhead, 4)
                              if overhead is not None else None),
            "stress_off_s": round(stress_off, 4),
            "stress_on_s": round(stress_on, 4),
            "stress_overhead_frac": round(
                (stress_on - stress_off) / stress_off, 4)
            if stress_off > 0 else None,
            "goal": "< 0.05",
            "goal_met": (overhead is not None and overhead < 0.05),
        }
    except Exception as exc:  # noqa: BLE001 - numbers, not crashes
        return {"error": repr(exc)}


def _introspection_overhead_rung(pairs=5, n_ops=2000):
    """Device-search introspection overhead (rung 12): the same
    fixed cas-register device search with the progress telemetry —
    per-dispatch progress-tensor reads (explored / frontier / depth
    ride ONE batched device_get), heartbeat trace events, padding
    accounting, and the crash-safe journal flushes — fully ON
    (tracer + registry + journals, the plane as shipped) vs obs OFF
    entirely. OFF/ON runs strictly interleaved with the overhead
    computed from per-variant MINIMA (rung 11's quiet-floor
    estimator: hypervisor-steal noise on shared boxes is 2-3x the
    effect). The detail records the search's explored configs and
    its device duty cycle (wgl.device_busy_s / measured wall) so the
    perf trajectory restarts from a measured baseline. Goal: < 5%."""
    import os
    import tempfile

    try:
        from jepsen_tpu import obs
        from jepsen_tpu.checker import jax_wgl
        from jepsen_tpu.models import cas_register_spec
        from jepsen_tpu.simulate import random_history

        hist = random_history(random.Random(1212), "cas-register",
                              n_procs=16, n_ops=n_ops, crash_p=0.02)
        e, st = cas_register_spec.encode(hist)
        # compile outside the timed pairs; 1-iteration dispatch cap so
        # every run pays one heartbeat-bearing dispatch PER ITERATION
        # instead of finishing inside one chunk (the overhead under
        # test is per-dispatch — this is its worst case)
        kw = {"timeout_s": 120.0, "chunk_iters": 1}
        jax_wgl.check_encoded(cas_register_spec, e, st, max_configs=1)

        def run_off():
            # mask the bench's own global registry: OFF means the
            # engines resolve NO sinks at capture
            with obs.bind(None, None):
                t0 = time.perf_counter()
                r = jax_wgl.check_encoded(cas_register_spec, e, st,
                                          **kw)
                return time.perf_counter() - t0, r, None

        def run_on():
            with tempfile.TemporaryDirectory() as tmp:
                tr, reg = obs.Tracer(), obs.Registry()
                tr.attach_journal(os.path.join(
                    tmp, "trace.jsonl.journal"))
                reg.attach_journal(os.path.join(
                    tmp, "metrics.json.journal"))
                with obs.bind(tr, reg):
                    t0 = time.perf_counter()
                    r = jax_wgl.check_encoded(cas_register_spec, e,
                                              st, **kw)
                    dt = time.perf_counter() - t0
                tr.close_journal()
                reg.close_journal()
                return dt, r, reg

        off_runs, on_all = [], []
        run_off()            # warm both code paths once, untimed
        run_on()
        for _ in range(pairs):
            s_off, r_off, _ = run_off()
            off_runs.append(s_off)
            on_all.append(run_on())
        off_s = min(off_runs)
        # the min-wall ON run is the quiet-floor sample; its OWN
        # registry supplies the busy wall so the duty cycle pairs
        # numerator and denominator from the same run
        on_s, best_on, best_reg = min(on_all, key=lambda t: t[0])
        on_runs = [t[0] for t in on_all]
        overhead = (on_s - off_s) / off_s if off_s > 0 else None
        busy = float(best_reg.counter_value(
            "wgl.device_busy_s", engine="jax-wgl")) \
            if best_reg is not None else None
        # chunk wall + per-phase breakdown from the SAME best run's
        # registry: busy is now the device-compute bracket, so the
        # chunk_s sum supplies the old full-dispatch-wall context and
        # phase_s says where the difference went
        intro = {}
        if best_reg is not None:
            try:
                from jepsen_tpu.obs.merge import introspection_summary
                intro = introspection_summary(best_reg.snapshot())
            except Exception:  # noqa: BLE001
                intro = {}
        return {
            "n_ops": n_ops, "ops": len(e), "pairs": pairs,
            "valid": best_on.get("valid") if best_on else None,
            "explored_configs": best_on.get("configs_explored")
            if best_on else None,
            "chunks": int(best_reg.counter_value(
                "wgl.chunks", engine="jax-wgl"))
            if best_reg is not None else None,
            "device_busy_s": round(busy, 4)
            if busy is not None else None,
            "duty_cycle": round(busy / on_s, 4)
            if busy is not None and on_s > 0 else None,
            "chunk_s": intro.get("chunk_s"),
            "phase_s": intro.get("phase_s"),
            "off_s": round(off_s, 4),
            "off_runs": [round(x, 3) for x in off_runs],
            "on_s": round(on_s, 4),
            "on_runs": [round(x, 3) for x in on_runs],
            "overhead_frac": (round(overhead, 4)
                              if overhead is not None else None),
            "goal": "< 0.05",
            "goal_met": (overhead is not None and overhead < 0.05),
        }
    except Exception as exc:  # noqa: BLE001 - numbers, not crashes
        return {"error": repr(exc)}


def _service_throughput_rung(clients=8, per_client=3, bursts=10):
    """Batched multi-tenant checking (rung 13): N concurrent clients
    driving ONE live server over loopback, the same mixed
    valid/invalid submissions checked with coalescing OFF then ON
    (the per-request "coalesce" payload knob against a
    coalescing-enabled server, so transport, admission, and engine
    stay constant across modes — only the batching differs).

    Each mode's fan-out runs twice and the SECOND pass is the timed
    one: the solo path and every pow-2 batch width the coalescer
    closes compile on the first pass, so the timed numbers compare
    steady-state dispatch, not compiles. Reports checks/s, p50/p99
    verdict latency, batches/segments/occupancy from the service
    coalesce counters, the device duty cycle (wgl.device_busy_s over
    the mode wall, the PR 13 metrics plane), and verdict equality
    across modes. Self-contained and never fatal."""
    import json as _json
    import threading
    import urllib.request

    try:
        from jepsen_tpu import obs, web
        from jepsen_tpu.fleet import service

        service.reset()
        # every loopback client shares one caller id (no tokens):
        # budgets must admit the whole fan-out without shedding
        service.configure(budgets={"concurrent-checks": 4 * clients,
                                   "queue-depth": 8 * clients})
        server = web.serve({"ip": "127.0.0.1", "port": 0})
        port = server.server_address[1]
        url = f"http://127.0.0.1:{port}/api/check"

        def hist(bad):
            ev = []

            def e(t, p, f, v):
                ev.append({"type": t, "process": p, "f": f,
                           "value": v})

            for j in range(bursts):
                x = j * 10
                e("invoke", 0, "write", x)
                e("invoke", 1, "write", x + 1)
                e("ok", 0, "write", x)
                e("ok", 1, "write", x + 1)
                e("invoke", 0, "write", x + 5)
                e("ok", 0, "write", x + 5)
            e("invoke", 2, "read", None)
            # the stale read targets a genuinely-written value, so
            # invalidity needs the real search, not the abstraction
            e("ok", 2, "read", 0 if bad else (bursts - 1) * 10 + 5)
            return ev

        # shape-identical across clients (one compile bucket, the
        # cross-tenant ledger-hit case); every 4th client submits a
        # violation so batches mix valid and invalid
        hists = [[hist(bad=(c % 4 == 3)) for _ in range(per_client)]
                 for c in range(clients)]

        def post(h, coalesce):
            body = _json.dumps({"history": h, "model": "cas-register",
                                "coalesce": coalesce,
                                "timeout-s": 120}).encode()
            req = urllib.request.Request(
                url, data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.monotonic()
            with urllib.request.urlopen(req, timeout=600) as r:
                got = _json.loads(r.read())
            return time.monotonic() - t0, got["valid"]

        def reg_busy():
            reg = obs.registry()
            if reg is None:
                return 0.0
            return sum(v for k, v in
                       reg.snapshot()["counters"].items()
                       if k.startswith("wgl.device_busy_s"))

        def reg_chunk():
            # full dispatch-chunk wall (the wgl.chunk_s histogram):
            # busy above is the device-compute bracket, chunk is the
            # old whole-chunk meaning, busy <= chunk always
            reg = obs.registry()
            if reg is None:
                return 0.0
            return sum(float((h or {}).get("sum") or 0.0)
                       for k, h in
                       reg.snapshot()["histograms"].items()
                       if k.startswith("wgl.chunk_s"))

        def fan_out(flag):
            lat = [[None] * per_client for _ in range(clients)]
            vrd = [[None] * per_client for _ in range(clients)]
            errors = []

            def one_client(c):
                for i in range(per_client):
                    try:
                        lat[c][i], vrd[c][i] = post(hists[c][i], flag)
                    except Exception as exc:  # noqa: BLE001
                        errors.append(repr(exc)[:120])

            threads = [threading.Thread(target=one_client, args=(c,))
                       for c in range(clients)]
            t0 = time.monotonic()
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            return (time.monotonic() - t0, lat, vrd, errors)

        out = {"clients": clients, "per_client": per_client,
               "ops_per_check": 6 * bursts + 1}
        verdicts = {}
        for mode, flag in (("off", False), ("on", True)):
            fan_out(flag)                     # warm pass: compiles
            st0 = service.coalescer().stats()
            busy0 = reg_busy()
            chunk0 = reg_chunk()
            wall, lat, vrd, errors = fan_out(flag)
            st1 = service.coalescer().stats()
            busy = reg_busy() - busy0
            chunk = reg_chunk() - chunk0
            flat = sorted(x for row in lat for x in row
                          if x is not None)
            n = len(flat)
            verdicts[mode] = [v for row in vrd for v in row]
            out[mode] = {
                "wall_s": round(wall, 3),
                "checks_per_s": round(n / wall, 2) if wall else None,
                "p50_ms": round(flat[n // 2] * 1000, 1) if n else None,
                "p99_ms": round(flat[min(n - 1, int(0.99 * n))]
                                * 1000, 1) if n else None,
                "errors": errors[:5],
                "batches": st1["batches"] - st0["batches"],
                "segments": st1["segments"] - st0["segments"],
                "device_busy_s": round(busy, 3),
                "chunk_s": round(chunk, 3),
                "duty_cycle": round(busy / wall, 4) if wall else None,
            }
        st = service.coalescer().stats()
        out["occupancy"] = st["occupancy"]
        out["verdicts_identical"] = verdicts["on"] == verdicts["off"]
        out["violations_detected"] = sum(
            1 for v in verdicts["on"] if v is False)
        if out["off"]["checks_per_s"] and out["on"]["checks_per_s"]:
            out["coalesce_speedup_x"] = round(
                out["on"]["checks_per_s"]
                / out["off"]["checks_per_s"], 2)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/api/metrics",
                timeout=30) as r:
            text = r.read().decode()
        out["metrics_exposed"] = (
            "jepsen_service_coalesce_batches" in text
            and "jepsen_service_coalesce_occupancy" in text
            and "jepsen_admission_shed_total" in text)
        server.shutdown()
        service.reset()
        return out
    except Exception as exc:  # noqa: BLE001 - numbers, not crashes
        return {"error": repr(exc)[:300]}


def _txn_scale_rung(n_txns=16384, appends_per_txn=7, chunk=1024,
                    budget_s=900):
    """Transactional cycle checking at scale (rung 15): a serial
    list-append history of >= 1e5 micro-ops checked two ways -- one
    offline ``cycle/`` analysis of the whole history (the
    cycle-checked txns/s headline) and the family="txn" monitor core
    driven chunk by chunk (per-chunk latency plus the squaring-pass
    ledger against the from-scratch closure every chunk would
    otherwise pay -- the incrementality contract, measured).

    This is the scale/family the WGL engine is refused at outright:
    multi-key txn micro-ops have no sequential model, so the rung
    records the model registry's refusal verbatim instead of timing a
    search that cannot exist. The duty cycle comes from the
    ``txn.closure_busy_s`` counter the closure kernels bracket (the
    same metrics plane as ``wgl.device_busy_s``), over each mode's
    wall. Self-contained and never fatal."""
    import numpy as _np

    try:
        from jepsen_tpu import cycle, obs
        from jepsen_tpu.monitor import engine as mengine
        from jepsen_tpu.monitor.txn import TxnCheck

        # serial multi-key history: each txn reads its key's committed
        # prefix THEN appends (the read stays cross-txn: observing your
        # own in-txn appends is legal but exercises nothing), keys
        # retire after txns_per_key txns so reads stay short
        txns_per_key = 8
        events = []
        t = 0
        for i in range(n_txns):
            k = f"k{i // txns_per_key}"
            base = (i % txns_per_key) * appends_per_txn
            mops = ([["r", k, None]]
                    + [["append", k, base + j + 1]
                       for j in range(appends_per_txn)])
            done = [list(m) for m in mops]
            done[0] = ["r", k, list(range(1, base + 1))]
            events.append({"type": "invoke", "f": "txn",
                           "process": i % 8, "time": t, "value": mops})
            events.append({"type": "ok", "f": "txn",
                           "process": i % 8, "time": t + 1,
                           "value": done})
            t += 2
        micro_ops = n_txns * (appends_per_txn + 1)
        out = {"txns": n_txns, "micro_ops": micro_ops,
               "events": len(events), "chunk": chunk}

        # the WGL side of the fork in the road: no sequential model
        # exists for multi-key txn micro-ops, so the linearizability
        # path refuses at the registry, before any search
        try:
            from jepsen_tpu.models import model_spec
            model_spec("txn-append")
            out["wgl_refusal"] = None
        except KeyError as exc:
            out["wgl_refusal"] = str(exc)[:160]

        def busy():
            reg = obs.registry()
            if reg is None:
                return 0.0
            return sum(v for key, v in
                       reg.snapshot()["counters"].items()
                       if key.startswith("txn.closure_busy_s"))

        # OFFLINE: one full analysis -- the txns/s headline
        b0, p0 = busy(), cycle.closure_passes()
        t0 = time.monotonic()
        res = mengine.check_txn_prefix(events, "append")
        off_wall = time.monotonic() - t0
        off_busy = busy() - b0
        out["offline"] = {
            "valid": res.get("valid"),
            "wall_s": round(off_wall, 3),
            "txns_per_s": round(n_txns / off_wall, 1)
            if off_wall else None,
            "micro_ops_per_s": round(micro_ops / off_wall, 1)
            if off_wall else None,
            "closure_passes": cycle.closure_passes() - p0,
            "device_busy_s": round(off_busy, 3),
            "duty_cycle": round(off_busy / off_wall, 4)
            if off_wall else None,
        }

        # STREAMING: the monitor core, chunk txns at a time, frontier
        # resident across chunks
        core = TxnCheck(workload="append")
        lat = []
        b0, p0 = busy(), cycle.closure_passes()
        t0 = time.monotonic()
        exhausted = False
        for start in range(0, len(events), 2 * chunk):
            for ev in events[start:start + 2 * chunk]:
                core.offer(ev)
            c0 = time.monotonic()
            r = core.check()
            lat.append(time.monotonic() - c0)
            if r.get("valid") is not True:
                out["streaming_error"] = {
                    "valid": r.get("valid"),
                    "anomaly_types": r.get("anomaly_types")}
                break
            if time.monotonic() - t0 > budget_s:
                exhausted = True
                break
        inc_wall = time.monotonic() - t0
        inc_passes = cycle.closure_passes() - p0
        inc_busy = busy() - b0
        lat_s = sorted(lat)
        out["streaming"] = {
            "chunks": len(lat),
            "txns_checked": core.n_txns,
            "wall_s": round(inc_wall, 3),
            "budget_exhausted": exhausted,
            "chunk_p50_ms": round(lat_s[len(lat_s) // 2] * 1e3, 1)
            if lat_s else None,
            "chunk_max_ms": round(lat_s[-1] * 1e3, 1)
            if lat_s else None,
            "closure_passes": inc_passes,
            "closure_rebuilds": core.frontier.rebuilds,
            "device_busy_s": round(inc_busy, 3),
            "duty_cycle": round(inc_busy / inc_wall, 4)
            if inc_wall else None,
        }

        # the counterfactual: one from-scratch closure at the final
        # padded size, timed once -- what EVERY chunk would pay
        # without the resident frontier
        n_pad = max(64, int(core.frontier.n_pad))
        scratch_steps = max(1, int(_np.ceil(_np.log2(max(2, n_pad)))))
        adj = core.frontier._adj[:core.frontier.n, :core.frontier.n]
        p1 = cycle.closure_passes()
        s0 = time.monotonic()
        cycle.transitive_closure(adj)
        scratch_wall = time.monotonic() - s0
        out["scratch"] = {
            "n_pad": n_pad,
            "closure_s": round(scratch_wall, 3),
            "closure_passes": cycle.closure_passes() - p1,
            "per_chunk_passes_if_rebuilt": scratch_steps,
            "total_passes_if_rebuilt": scratch_steps * len(lat),
        }
        if inc_passes:
            out["passes_saved_x"] = round(
                scratch_steps * len(lat) / inc_passes, 2)
        out["goal"] = ("valid at >= 1e5 micro-ops; incremental passes "
                       "< per-chunk from-scratch total")
        out["goal_met"] = bool(
            not exhausted
            and out["offline"]["valid"] is True
            and "streaming_error" not in out
            and inc_passes < scratch_steps * max(1, len(lat)))
        return out
    except Exception as exc:  # noqa: BLE001 - numbers, not crashes
        return {"error": repr(exc)[:300]}


def _error_headline(msg):
    """The zero-value headline shape every bench failure path emits
    (one definition so error lines can't drift from success lines)."""
    return json.dumps({"metric": "ops verified/sec (cas-register)",
                       "value": 0.0, "unit": "ops/s",
                       "vs_baseline": 0.0, "error": msg})


def main():
    # persistent compile cache: the kernel's shape buckets are designed
    # for reuse across processes and runs
    from jepsen_tpu import jaxcache
    jaxcache.enable()
    import jax
    if jax.devices()[0].platform != "tpu":
        # a measurement that finds no chip fails; it never falls back
        print(_error_headline(f"no TPU: {jax.devices()}"))
        sys.exit(1)
    # bind a metrics registry for the whole bench: the engines'
    # search-telemetry heartbeats (chunk latencies, states explored,
    # dedup-table load) accumulate in it and ship inside the headline
    # detail blob, so every reported rate carries its own evidence
    from jepsen_tpu import obs
    _obs_reg = obs.Registry()
    with obs.bind(None, _obs_reg):
        _bench_body(_obs_reg)


def _bench_body(_obs_reg):
    from jepsen_tpu.checker import jax_wgl, wgl
    from jepsen_tpu.models import (cas_register_spec, fifo_queue_spec,
                                   mutex_spec)
    from jepsen_tpu.parallel import check_batch_encoded
    from jepsen_tpu.simulate import corrupt, random_history

    rungs = {}
    rng = random.Random(45100)

    # -- rungs 1 + 2: cas-register, single + batched ---------------------
    # (drawn FIRST from the seeded rng: the same histories as round 1's
    # bench, so the headline rate is comparable across rounds)
    spec = cas_register_spec
    n_keys, ops_per_key = 32, 200
    hists = []
    for k in range(n_keys):
        hist = random_history(rng, "cas-register", n_procs=8,
                              n_ops=ops_per_key, crash_p=0.02)
        if k % 8 == 7:
            hist = corrupt(rng, hist)
        hists.append(hist)
    hist3 = random_history(rng, "mutex", n_procs=64, n_ops=10_000,
                           crash_p=0.02)
    hist4 = random_history(rng, "fifo-queue", n_procs=6, n_ops=150,
                           crash_p=0.02)
    hist5 = random_history(rng, "cas-register", n_procs=64, n_ops=10_000,
                           crash_p=0.05)
    pairs = [spec.encode(hist) for hist in hists]
    total_ops = sum(len(e) for e, _ in pairs)

    t0 = time.monotonic()
    base_results = [wgl.check_encoded(spec, e, st, max_configs=2_000_000)
                    for e, st in pairs]
    cpu_s = time.monotonic() - t0

    # rung 1: one history at a time on device (warm, after compile)
    e1, st1 = pairs[0]
    jax_wgl.check_encoded(spec, e1, st1)
    t0 = time.monotonic()
    r1 = jax_wgl.check_encoded(spec, e1, st1)
    rung1_s = time.monotonic() - t0
    rungs["1-cas-single"] = {
        "ops": len(e1), "device_s": round(rung1_s, 3),
        "valid": r1["valid"],
    }

    # rung 2: the whole key batch in one device program (kept at 32 keys
    # for round-over-round comparability; the oracle agreement check
    # anchors correctness)
    # TWO warmups: compaction points are timing-dependent, so one run
    # does not visit every (batch-width, frontier-width) kernel variant
    # -- a first timed run once paid 22 s of mid-run compiles that a
    # second warm run avoided entirely (4.3 s)
    check_batch_encoded(spec, pairs)
    check_batch_encoded(spec, pairs)
    dev_s, runs2, dev_results = timed3(
        lambda: check_batch_encoded(spec, pairs))
    agree = sum(1 for a, b in zip(base_results, dev_results)
                if a["valid"] == b["valid"])
    dev_rate = total_ops / dev_s
    cpu_rate = total_ops / cpu_s
    rungs["2-cas-multikey"] = {
        "keys": n_keys, "total_ops": total_ops,
        "device_s": round(dev_s, 3), "device_s_runs": runs2,
        "cpu_oracle_s": round(cpu_s, 3),
        "device_rate": round(dev_rate, 1),
        "cpu_rate": round(cpu_rate, 1),
        "verdicts_agree": f"{agree}/{n_keys}",
    }

    # rung 2b (the HEADLINE since round 3): 256 keys, same per-key
    # shape. The key axis is nearly free on device -- that is the point
    # of the batched kernel -- so the throughput headline uses the wide
    # batch; vs_baseline divides by the single-thread CPU oracle rate
    # measured on the 32-key subset above (same workload distribution;
    # a full 256-key oracle run would blow the bench budget).
    rng2 = random.Random(20260730)
    hists2b = list(hists)
    for k in range(len(hists), 256):
        h = random_history(rng2, "cas-register", n_procs=8,
                           n_ops=ops_per_key, crash_p=0.02)
        if k % 8 == 7:
            h = corrupt(rng2, h)
        hists2b.append(h)
    pairs2b = [spec.encode(h) for h in hists2b]
    total2b = sum(len(e) for e, _ in pairs2b)
    check_batch_encoded(spec, pairs2b)        # compile warmups (x2:
    check_batch_encoded(spec, pairs2b)        # see rung 2)
    dev2b_s, runs2b, res2b = timed3(
        lambda: check_batch_encoded(spec, pairs2b))
    rate2b = total2b / dev2b_s
    rungs["2b-cas-256key"] = {
        "keys": 256, "total_ops": total2b,
        "device_s": round(dev2b_s, 3), "device_s_runs": runs2b,
        "device_rate": round(rate2b, 1),
        "invalid_keys": sum(1 for r in res2b if r["valid"] is False),
        "unknown_keys": sum(1 for r in res2b
                            if r["valid"] == "unknown"),
    }

    # rung 2c: K=1024 and the keys-vs-throughput CURVE (VERDICT r3 next
    # #7: the claimed "throughput via the key axis" trade reported as a
    # measured curve, not a point). Same per-key workload distribution
    # as 2/2b; the 32- and 256-key points reuse those rungs' runs.
    hists2c = list(hists2b)
    for k in range(len(hists2c), 1024):
        h = random_history(rng2, "cas-register", n_procs=8,
                           n_ops=ops_per_key, crash_p=0.02)
        if k % 8 == 7:
            h = corrupt(rng2, h)
        hists2c.append(h)
    pairs2c = [spec.encode(h) for h in hists2c]
    total2c = sum(len(e) for e, _ in pairs2c)
    check_batch_encoded(spec, pairs2c)        # compile warmups (x2:
    check_batch_encoded(spec, pairs2c)        # see rung 2)
    dev2c_s, runs2c, res2c = timed3(
        lambda: check_batch_encoded(spec, pairs2c))
    rate2c = total2c / dev2c_s
    rungs["2c-cas-1024key"] = {
        "keys": 1024, "total_ops": total2c,
        "device_s": round(dev2c_s, 3), "device_s_runs": runs2c,
        "device_rate": round(rate2c, 1),
        "invalid_keys": sum(1 for r in res2c if r["valid"] is False),
        "unknown_keys": sum(1 for r in res2c
                            if r["valid"] == "unknown"),
        "curve_ops_per_s": {"32": round(dev_rate, 1),
                            "256": round(rate2b, 1),
                            "1024": round(rate2c, 1)},
    }

    # -- rung 3: mutex, high contention ----------------------------------
    e3, st3 = mutex_spec.encode(hist3)
    jax_wgl.check_encoded(mutex_spec, e3, st3, timeout_s=120)  # warm
    t0 = time.monotonic()
    r3 = jax_wgl.check_encoded(mutex_spec, e3, st3, timeout_s=60)
    d3 = time.monotonic() - t0
    rungs["3-mutex"] = {
        "ops": len(e3), "procs": 64,
        "device_s": round(d3, 1), "device_valid": r3["valid"],
        "device_iterations": r3.get("iterations"),
    }

    # -- rung 4: FIFO queue ----------------------------------------------
    e4, st4 = fifo_queue_spec.encode(hist4)
    t0 = time.monotonic()
    r4 = jax_wgl.check_encoded(fifo_queue_spec, e4, st4, timeout_s=60)
    d4 = time.monotonic() - t0
    rungs["4-fifo-queue"] = {
        "ops": len(e4), "procs": 6,
        "device_s": round(d4, 1), "device_valid": r4["valid"],
        "engine": r4.get("engine"),
    }

    # rung 4b: info-free FIFO at 25x the old search's reach -- decided
    # by the exact aspect (bad-pattern) fast path
    hist4b = random_history(rng, "fifo-queue", n_procs=16, n_ops=5000,
                            crash_p=0.0)
    e4b, st4b = fifo_queue_spec.encode(hist4b)
    t0 = time.monotonic()
    r4b = jax_wgl.check_encoded(fifo_queue_spec, e4b, st4b)
    rungs["4b-fifo-aspect-5k"] = {
        "ops": len(e4b), "procs": 16,
        "device_s": round(time.monotonic() - t0, 2),
        "device_valid": r4b["valid"], "engine": r4b.get("engine"),
    }

    # rung 4c: 10k-op FIFO with ~500 crashed ops INCLUDING info
    # dequeues, decided exactly by the round-3 closure+matching aspect
    # (round 2 punted all info-dequeue histories to the search, which
    # capped out near 200 ops)
    hist4c = random_history(rng, "fifo-queue", n_procs=64, n_ops=10_000,
                            crash_p=0.05)
    e4c, st4c = fifo_queue_spec.encode(hist4c)
    t0 = time.monotonic()
    r4c = jax_wgl.check_encoded(fifo_queue_spec, e4c, st4c)
    rungs["4c-fifo-info-10k"] = {
        "ops": len(e4c), "procs": 64,
        "infos": int((~e4c.is_ok).sum()),
        "info_dequeues": sum(1 for o in hist4c if o["type"] == "info"
                             and o["f"] == "dequeue"),
        "device_s": round(time.monotonic() - t0, 2),
        "device_valid": r4c["valid"], "engine": r4c.get("engine"),
    }

    # rung 4d: the SEARCH engine itself (fast path disabled) on a
    # 2k-op info-dequeue-bearing FIFO history: the witness-order hint +
    # junk-enqueue prune let the greedy rollout walk an explicit
    # linearization, so the B&B decides in a handful of iterations
    # where round 2's kernel capped out near 200 ops
    import dataclasses
    forced = dataclasses.replace(fifo_queue_spec, fast_check=None)
    hist4d = random_history(rng, "fifo-queue", n_procs=16, n_ops=2000,
                            crash_p=0.05)
    e4d, st4d = forced.encode(hist4d)
    jax_wgl.check_encoded(forced, e4d, st4d, timeout_s=120)  # warm compile
    t0 = time.monotonic()
    r4d = jax_wgl.check_encoded(forced, e4d, st4d, timeout_s=60)
    d4d = time.monotonic() - t0
    assert r4d.get("engine") == "jax-wgl", r4d
    rungs["4d-fifo-info-search-2k"] = {
        "ops": len(e4d), "procs": 16,
        "infos": int((~e4d.is_ok).sum()),
        "device_s": round(d4d, 2), "device_valid": r4d["valid"],
        "engine": r4d.get("engine"),
        "device_iterations": r4d.get("iterations"),
        "search_goal_met": bool(r4d["valid"] in (True, False)
                                and d4d < 60),
    }

    # -- rung 5: the stretch goal ----------------------------------------
    # warm the compile first: the goal gates on wall clock, and a
    # cold compile (observed 60+ s once) is not the search's time
    e5, st5 = cas_register_spec.encode(hist5)
    jax_wgl.check_encoded(cas_register_spec, e5, st5, timeout_s=120)
    t0 = time.monotonic()
    r5 = jax_wgl.check_encoded(cas_register_spec, e5, st5, timeout_s=120)
    d5 = time.monotonic() - t0
    rungs["5-cas-10k-64proc"] = {
        "ops": len(e5), "procs": 64,
        "infos": int((~e5.is_ok).sum()),
        "device_s": round(d5, 1), "device_valid": r5["valid"],
        "device_iterations": r5.get("iterations"),
    }

    # -- rung 6: the linear engine's home turf ---------------------------
    # knossos's competition races linear and wgl as co-equal engines
    # (reference checker.clj:199-202). On long LOW-concurrency
    # crash-free histories the event sweep's config set stays tiny and
    # the CPU linear engine beats the device search outright (which
    # pays W*n tensor work per iteration); this rung proves the racer
    # genuinely wins somewhere (VERDICT r3 weak #5).
    from jepsen_tpu.checker import linear
    hist6 = random_history(random.Random(606), "cas-register",
                           n_procs=2, n_ops=50_000, crash_p=0.0)
    e6, st6 = cas_register_spec.encode(hist6)
    t0 = time.monotonic()
    r6l = linear.check_encoded(cas_register_spec, e6, st6,
                               max_configs=200_000)
    d6l = time.monotonic() - t0
    jax_wgl.check_encoded(cas_register_spec, e6, st6, max_configs=1)
    t0 = time.monotonic()
    r6d = jax_wgl.check_encoded(cas_register_spec, e6, st6,
                                timeout_s=90)
    d6d = time.monotonic() - t0
    rungs["6-linear-home-turf"] = {
        "ops": len(e6), "procs": 2, "crash_p": 0.0,
        "linear_s": round(d6l, 2), "linear_valid": r6l["valid"],
        "device_s": round(d6d, 2), "device_valid": r6d["valid"],
        "linear_wins": bool(r6l["valid"] in (True, False)
                            and (d6l < d6d
                                 or r6d["valid"] not in (True, False))),
    }

    # -- rung 0: the BASELINE primary metric -----------------------------
    # max single-key history length decidable in 60 s, measured to the
    # engine's ACTUAL limit: exponential growth until a size fails the
    # budget, then bisection to tighten the decided/undecided bracket.
    # (Round 3 walked a hardcoded ladder whose top rung decided in
    # 7.6 s, so the reported "max" was the ladder's end, not the
    # engine's limit -- VERDICT r3 weak #1.) Each shape bucket is
    # compile-warmed with a 1-iteration probe before its first timed
    # run so growth gates on search time, not compile stalls; the
    # engine's adaptive dispatch quantum enforces the wall budget.
    import dataclasses
    fifo_search = dataclasses.replace(fifo_queue_spec, fast_check=None)
    BUDGET_S = 60.0
    # per-row cap on total probe time. 600 s leaves room for one
    # mispredicted dispatch (observed: a single 256k-request dispatch
    # running 418 s against a 60 s budget) plus the retry + bisection
    # probes that rescue the bracket afterwards
    ROW_WALL_S = 600.0
    rows0 = (
        # (row key, model name, spec, procs, crash_p, start, cap)
        ("cas-register", "cas-register", cas_register_spec, 64, 0.05,
         16_000, 1_024_000),
        ("mutex", "mutex", mutex_spec, 64, 0.05, 8_000, 1_024_000),
        # the aspect row's old 1.6M cap was the reported max (3.4 s
        # decided -- the cap, not the engine, bound; VERDICT r4 #4).
        # Measured scaling: the aspect check runs ~2.2 s per 1M ops
        # (60 s budget would bind near ~26M), but host-side Python
        # history generation + encode costs ~30 s per 1M ops, so the
        # per-row wall binds first around 12.8M -- the honest,
        # recorded failure mode (gen_s per probe documents it)
        ("fifo-queue-aspect", "fifo-queue", fifo_queue_spec, 64, 0.05,
         200_000, 25_600_000),
        # the raw SEARCH engine on info-dequeue-bearing FIFO histories
        # (aspect disabled, like rung 4d): the honest search-path row
        ("fifo-queue-search", "fifo-queue", fifo_search, 16, 0.05,
         2_000, 256_000),
    )
    maxlen = {}
    for mi, (row, mname, mspec, procs, crash_p, start, cap) in \
            enumerate(rows0):

        def attempt(n_ops, _mi=mi, _mname=mname, _mspec=mspec,
                    _procs=procs, _crash=crash_p):
            # one deterministic sub-seed per (row, size): growth and
            # bisection probes never shift each other's histories, and
            # rows stay independent across rounds
            seed = 77000 + _mi * 1_000_003 + n_ops
            tg = time.monotonic()
            h0 = random_history(random.Random(seed), _mname,
                                n_procs=_procs, n_ops=n_ops,
                                crash_p=_crash)
            e0, st0 = _mspec.encode(h0)
            # history generation + encode is host-side Python and grows
            # linearly; at the aspect row's tens-of-millions-of-ops
            # scale it becomes the binding constraint, so it is
            # recorded separately from the (budgeted) check time
            gen_s = round(time.monotonic() - tg, 1)
            try:
                # 1-iteration probe: compiles the bucket's kernels
                jax_wgl.check_encoded(_mspec, e0, st0, max_configs=1)
                t0 = time.monotonic()
                r0 = jax_wgl.check_encoded(_mspec, e0, st0,
                                           timeout_s=BUDGET_S)
                dt0 = time.monotonic() - t0
            except Exception as exc:  # noqa: BLE001 - e.g. device OOM
                return {"n_ops": n_ops, "ops": len(e0), "s": None,
                        "gen_s": gen_s, "ok": False,
                        "error": repr(exc)[:200]}
            return {"n_ops": n_ops, "ops": len(e0),
                    "s": round(dt0, 1), "gen_s": gen_s,
                    "ok": bool(r0["valid"] in (True, False)
                               and dt0 <= BUDGET_S),
                    "engine": r0.get("engine", "jax-wgl"),
                    "table_load": r0.get("table_load"),
                    "table_insert_failures":
                        r0.get("table_insert_failures"),
                    "error": r0.get("error")}

        t_row = time.monotonic()

        def attempt_fair(n_ops):
            """One retry when a not-ok probe either errored outright
            (s None) or grossly overshot the budget (>1.5x) -- the
            adaptive quantum calibrates from measured per-iteration
            wall, so a mid-probe stall can burn the window without giving the search a
            fair 60 s; deciding on retry proves 60 s decidability
            honestly. Skipped once the row wall is spent (a retry
            would double the overrun)."""
            a = attempt(n_ops)
            # deterministic resource failures are not flaky: retrying
            # an OOM-sized probe would just OOM again and eat the row
            # wall the bisection needs
            oom = any(t in (a.get("error") or "")
                      for t in ("RESOURCE_EXHAUSTED", "Out of memory",
                                "out of memory"))
            flaky = a["s"] is None or a["s"] > BUDGET_S * 1.5
            if (not a["ok"] and flaky and not oom
                    and time.monotonic() - t_row < ROW_WALL_S):
                a = attempt(n_ops)
            return a
        good, bad = None, None
        n = start
        while n <= cap and time.monotonic() - t_row < ROW_WALL_S:
            a = attempt_fair(n)
            if a["ok"]:
                good, n = a, n * 2
            else:
                bad = a
                break
        # bisect the [good, bad] bracket until it's tight (<15%); the
        # bracket>2000 guard keeps mid strictly inside the bracket at
        # the 1000-op probe granularity (otherwise the clamp can pin
        # mid to good's own size and the loop would spin re-running
        # the identical probe until the row wall)
        while (good is not None and bad is not None
               and bad["n_ops"] - good["n_ops"] > 2000
               and bad["n_ops"] > good["n_ops"] * 1.15
               and time.monotonic() - t_row < ROW_WALL_S):
            mid = round((good["n_ops"] + bad["n_ops"]) / 2, -3)
            mid = int(min(max(mid, good["n_ops"] + 1000),
                          bad["n_ops"] - 1000))
            a = attempt_fair(mid)
            if a["ok"]:
                good = a
            else:
                bad = a
        entry = None
        if good is not None:
            entry = {"ops": good["ops"], "requested": good["n_ops"],
                     "s": good["s"], "gen_s": good["gen_s"],
                     "engine": good["engine"]}
            if good.get("table_load") is not None:
                entry["table_load"] = good["table_load"]
                entry["table_insert_failures"] = \
                    good["table_insert_failures"]
            if bad is not None:
                entry["first_fail"] = {
                    "requested": bad["n_ops"], "ops": bad["ops"],
                    "s": bad["s"], "gen_s": bad.get("gen_s"),
                    "error": bad["error"]}
            elif good["n_ops"] * 2 > cap:
                entry["cap_reached"] = cap
            else:
                # the per-row wall bound before the 60 s check budget
                # did; gen_s in the probes shows whether host-side
                # history generation (not the engine) ate the wall
                entry["row_budget_exhausted"] = True
        elif bad is not None:
            entry = {"ops": 0, "first_fail": {
                "requested": bad["n_ops"], "ops": bad["ops"],
                "s": bad["s"], "gen_s": bad.get("gen_s"),
                "error": bad["error"]}}
        maxlen[row] = entry
    rungs["0-maxlen-60s"] = maxlen

    # streaming-monitor rung: the BENCH trajectory's headline for the
    # online path is detection latency, not throughput -- how long
    # after a violating op lands does the monitor's latch flip. Runs
    # after the timed device rungs (its chunk checks share the chip)
    rungs["7-monitor-detection"] = _monitor_rung()

    # fleet rung: cold-vs-warm wall clock of the same matrix in two
    # SEPARATE scheduler processes; warm must report ledger hits > 0
    # (runs on CPU in subprocesses -- see the rung's docstring)
    rungs["8-fleet-reuse"] = _fleet_reuse_rung()

    # search-plan rung: quiescent-cut slicing must beat the flat batch
    # on explored configs, with the planner itself in the noise
    rungs["9-searchplan"] = _searchplan_rung()

    # fleet-survival rung: the chaos soak's wall-clock price vs the
    # clean fleet, plus the warm-restart win from the persistent jax
    # compilation cache (CPU subprocesses; see the rung's docstring)
    rungs["10-fleet-survival"] = _fleet_survival_rung()

    # obs-overhead rung: the fleet telemetry plane (tracer + metrics +
    # crash-safe journals) must stay under 5% of clean-run wall clock
    # on the interpreter hot path (pure host work; chip not involved)
    rungs["11-obs-overhead"] = _obs_overhead_rung()

    # introspection-overhead rung: the search-progress telemetry
    # (progress-tensor device reads + heartbeats + padding accounting
    # + journal flushes) must stay under 5% of the same search with
    # obs off, and the detail re-baselines explored-configs and the
    # device duty cycle for the optimization arc
    rungs["12-introspection-overhead"] = _introspection_overhead_rung()

    # service-throughput rung: the cross-tenant coalescer must turn
    # queued /api/check wait into device occupancy — coalescing ON
    # strictly beats OFF on checks/s at concurrency >= 8 with
    # per-submission verdicts identical to the solo path
    rungs["13-service-throughput"] = _service_throughput_rung()

    # ha-takeover rung: kill the fleet coordinator mid-campaign and
    # measure how fast a standby fences it and finishes the work —
    # detection+takeover latency, re-leased vs lost cells, and the
    # kill-soak wall against the clean HA wall (rung 10's matrix)
    rungs["14-ha-takeover"] = _ha_takeover_rung()

    # txn-scale rung: the transactional family at the scale WGL is
    # refused at — cycle-checked txns/s over >= 1e5 micro-ops offline,
    # then the streaming monitor core over the same history: per-chunk
    # latency and the squaring-pass ledger vs the from-scratch closure
    # every chunk would otherwise pay, duty cycle from the
    # closure-busy counter
    rungs["15-txn-scale"] = _txn_scale_rung()

    # stream-monitor rung: 100 concurrent monitored streams, flat
    # re-search vs device-resident frontiers riding coalesced batches
    # — monitored-ops/s, detection p50/p99, duty cycle, and the
    # owners >= 2 batch-sharing evidence
    rungs["16-stream-monitor"] = _stream_monitor_rung()

    # CPU oracles race in parallel subprocesses AFTER all device
    # measurements (their CPU load would pollute the device numbers);
    # total added wall time <= one 60 s budget
    oracles = {"3": OracleRace("mutex", hist3),
               "4": OracleRace("fifo-queue", hist4),
               "4c": OracleRace("fifo-queue", hist4c),
               "4d": OracleRace("fifo-queue", hist4d),
               "5": OracleRace("cas-register", hist5)}
    for key, rung in (("3", "3-mutex"), ("4", "4-fifo-queue"),
                      ("4c", "4c-fifo-info-10k"),
                      ("4d", "4d-fifo-info-search-2k"),
                      ("5", "5-cas-10k-64proc")):
        o = oracles[key].result()
        rungs[rung]["cpu_s"] = round(o["s"], 1)
        rungs[rung]["cpu_valid"] = o["valid"]
    rungs["5-cas-10k-64proc"]["goal_met"] = bool(
        r5["valid"] in (True, False) and d5 < 60
        and rungs["5-cas-10k-64proc"]["cpu_valid"] == "unknown")

    if agree != n_keys:
        print(_error_headline(f"verdict mismatch: {agree}/{n_keys}"))
        return

    headline_rung, headline = max(
        (("2b-cas-256key", rate2b), ("2c-cas-1024key", rate2c)),
        key=lambda kv: kv[1])
    head = {
        "metric": "ops verified/sec (cas-register)",
        "value": round(headline, 1),
        "unit": "ops/s",
        "vs_baseline": round(headline / cpu_rate, 3),
        "headline_rung": headline_rung,
    }
    # environment: every detail blob says WHERE it was measured, so
    # a cross-host comparison can refuse instead of flagging hardware
    # differences as regressions
    env = None
    try:
        import os
        import socket
        devs = jax.devices()
        env = {"hostname": socket.gethostname(),
               "jax_platforms": os.environ.get("JAX_PLATFORMS"),
               "jax": jax.__version__, "platform": devs[0].platform,
               "device_count": len(devs)}
    except Exception:  # noqa: BLE001 - the headline must print
        pass
    # detail first, short headline-only line LAST: the driver captures
    # the output's tail, and the detail blob once pushed the headline
    # fields out of it (BENCH_r04 "parsed": null)
    print(json.dumps({**head, "detail": rungs,
                      "environment": env,
                      # whole-bench scope: includes the compile
                      # warm-up dispatches the timed rungs exclude, so
                      # chunk_s tails here overstate the measured runs
                      "metrics_scope": "whole-bench-incl-warmups",
                      "metrics": _obs_reg.snapshot()}))
    print(json.dumps(head))


if __name__ == "__main__":
    main()
