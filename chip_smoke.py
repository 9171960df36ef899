#!/usr/bin/env python3
"""Bring the linearizability checker's main path up on the chip.

Runs, in ONE process (a chip belongs to one process; the only children
are jax-free CPU-oracle workers), the entry points a user calls at the
sizes Jepsen users produce:

  a. harness end to end: ``core.run(demo.demo_test(...))``, the
     register workload (5 nodes, per-key-limit 20, 10 s), clean and
     with ``--bug dirty-read``;
  b. keyed batch (BASELINE config 2): 256 cas keys x 200 ops through
     ``independent`` -> ``keyshard.check_batch_encoded``, every key's
     verdict against ``checker/wgl.py`` on the CPU;
  c. one long key (BASELINE config 5): a 10k-op, 64-process cas
     history and its corrupted twin through ``jax_wgl.check_encoded``,
     which must run the compiled fused Pallas rollout;
  d. service: ``web.serve`` with coalescing, one good and one bad
     history posted to ``/api/check``;
  e. the device-resident ``streamlin`` monitor on the demo harness.

Each phase prints one JSON line (verdicts, wall and compile seconds).
Any failure ends the script non-zero. The last line is exactly
``{"ok": true, "device": {...}}``. It refuses to run anywhere but a TPU.

``--chips 4`` runs only the mesh phase: phase b's batch sharded over a
4-chip mesh and phase c's valid history through the sharded
single-key search, both against the one-device engine.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import logging
import multiprocessing
import os
import random
import sys
import threading
import time
import traceback
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))

#: shapes (PERF.md "Cells"); profile_kernel's seed
SEED = 45100
#: the reference's register defaults, but 10 s and not 30 s: at 30 s a
#: cold phase a took 784 s on the chip (448 s compiling, PERF.md), too
#: close with the other phases to the 1200 s a run may take
REGISTER = {"nodes": [f"n{i}" for i in range(1, 6)], "per-key-limit": 20,
            "time-limit": 10}
BATCH = {"keys": 256, "ops": 200, "procs": 8, "crash_p": 0.02,
         "corrupt_every": 8}
LONG = {"ops": 10_000, "procs": 64, "crash_p": 0.05, "corrupt_seed": 1}
STREAM_TIME_LIMIT = 90

_COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/jaxpr_trace_duration")
_compile = {"s": 0.0, "n": 0}
_compile_lock = threading.Lock()


def _on_duration(event, secs, **_):
    if event in _COMPILE_EVENTS:
        with _compile_lock:
            _compile["s"] += secs
            _compile["n"] += event == _COMPILE_EVENTS[0]


def _compiled():
    with _compile_lock:
        return _compile["s"], _compile["n"]


def _phase(name, fn):
    """Run one phase; print its JSON line with wall and compile time."""
    c0, n0 = _compiled()
    t0 = time.monotonic()
    out = fn()
    c1, n1 = _compiled()
    line = {"phase": name, "wall_s": time.monotonic() - t0,
            "compile_s": c1 - c0, "compiles": n1 - n0, **out}
    print(json.dumps(line, default=str), flush=True)
    return line


def _check(cond, what):
    if not cond:
        raise AssertionError(what)


class _Fallbacks(logging.Handler):
    """Collects the checker's "falling back" warnings: a batched or
    planned device path that failed and handed its keys to another."""

    def __init__(self):
        super().__init__(logging.WARNING)
        self.seen = []

    def emit(self, record):
        msg = record.getMessage()
        if "falling back" in msg:
            self.seen.append(msg)


@contextlib.contextmanager
def _no_fallback(what):
    handler = _Fallbacks()
    log = logging.getLogger("jepsen_tpu")
    log.addHandler(handler)
    try:
        yield
    finally:
        log.removeHandler(handler)
    _check(not handler.seen, f"{what}: {handler.seen}")


# -- the CPU oracle: spawn children that never import jax -------------------

def _oracle_init():
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, ROOT)


def _oracle(hist):
    from jepsen_tpu.checker import wgl
    from jepsen_tpu.models import cas_register_spec
    e, st = cas_register_spec.encode(hist)
    return wgl.check_encoded(cas_register_spec, e, st)["valid"], \
        "jax" in sys.modules


def _cpu_oracle(hists):
    ctx = multiprocessing.get_context("spawn")
    with ctx.Pool(min(len(hists), os.cpu_count() or 1),
                  initializer=_oracle_init) as pool:
        got = pool.map(_oracle, hists)
    _check(not any(j for _, j in got), "an oracle child imported jax")
    return [v for v, _ in got]


# -- inputs -----------------------------------------------------------------

def batch_histories(seed=SEED, **shape):
    """Phase b's keys (tools/profile_kernel.py --rung 2)."""
    from jepsen_tpu.simulate import corrupt, random_history
    shape = {**BATCH, **shape}
    rng = random.Random(seed)
    out = []
    for k in range(shape["keys"]):
        h = random_history(rng, "cas-register", n_procs=shape["procs"],
                           n_ops=shape["ops"], crash_p=shape["crash_p"])
        out.append(corrupt(rng, h) if k % shape["corrupt_every"]
                   == shape["corrupt_every"] - 1 else h)
    return out


def keyed_history(hists, procs):
    """Phase b's keys as one ``jepsen.independent`` history: [k v]
    values, disjoint processes per key."""
    from jepsen_tpu import independent
    out = []
    for k, hist in enumerate(hists):
        for op in hist:
            op = dict(op)
            op["process"] = op["process"] + k * procs
            op["value"] = independent.tuple_(k, op.get("value"))
            op["index"] = len(out)
            out.append(op)
    return out


def long_histories(seed=SEED, **shape):
    """Phase c's history (tools/profile_kernel.py --rung 5) and its
    ``simulate.corrupt`` twin. With ~500 crashed ops pending forever, a
    flipped read is almost always still linearizable (a crashed write
    explains it): of seeds 1-39 none gave a twin the search refutes
    (PERF.md). Seed 1's twin reads a value impossible from every
    state, so it is invalid for certain."""
    from jepsen_tpu.simulate import corrupt, random_history
    shape = {**LONG, **shape}
    hist = random_history(random.Random(seed), "cas-register",
                          n_procs=shape["procs"], n_ops=shape["ops"],
                          crash_p=shape["crash_p"])
    return hist, corrupt(random.Random(shape["corrupt_seed"]), hist)


# -- phases -----------------------------------------------------------------

def _lin_engines(results):
    engines = {}
    for r in results["workload"]["results"].values():
        eng = r["linearizable"].get("engine")
        engines[eng] = engines.get(eng, 0) + 1
    return engines


def phase_harness(register=REGISTER):
    """a. ``python -m jepsen_tpu test --workload register --no-ssh``,
    clean and with the dirty-read bug, in this process."""
    from jepsen_tpu import core, demo
    out = {}
    for bug, want in ((None, True), ("dirty-read", False)):
        opts = {"workload": "register", "no-ssh": True,
                "algorithm": "jax-wgl", **register}
        if bug:
            opts["bug"] = bug
        with _no_fallback(f"harness {bug or 'clean'}"):
            res = core.run(demo.demo_test(opts))["results"]
        engines = _lin_engines(res)
        tag = bug or "clean"
        out[tag] = {"valid": res["valid"],
                    "keys": len(res["workload"]["results"]),
                    "engines": engines}
        _check(res["valid"] is want, f"harness {tag}: valid "
               f"{res['valid']!r}, want {want}")
        # the device engine decided every key: jax-wgl searched, or its
        # own host pre-check (``aspect``) decided before the search
        _check(engines.get("jax-wgl", 0) > 0
               and set(engines) <= {"jax-wgl", "aspect"},
               f"harness {tag}: engines {engines}")
    return out


def phase_batch(hists=None):
    """b. the keyed batch through ``independent`` on the device, every
    key against the CPU oracle."""
    from jepsen_tpu import independent
    from jepsen_tpu.checker import checkers
    hists = hists if hists is not None else batch_histories()
    keyed = keyed_history(hists, BATCH["procs"])
    lin = checkers.linearizable({"model": "cas-register",
                                 "algorithm": "jax-wgl"})
    t0 = time.monotonic()
    with _no_fallback("batch"):
        res = independent.checker(lin).check({}, keyed)
    device_s = time.monotonic() - t0
    subs = [independent.subhistory(k, keyed) for k in range(len(hists))]
    t0 = time.monotonic()
    want = _cpu_oracle(subs)
    oracle_s = time.monotonic() - t0
    got = [res["results"][k]["valid"] for k in range(len(hists))]
    bad = [k for k in range(len(hists)) if got[k] != want[k]]
    _check(not bad, f"batch: {len(bad)} keys disagree with the oracle, "
           f"first {bad[:8]}")
    # every key the device search refuted carries a witness that the
    # CPU model replays (keys the host pre-check refuted carry none)
    searched = [k for k in range(len(hists)) if got[k] is False
                and res["results"][k].get("engine") == "jax-wgl"]
    for k in searched:
        _certified(subs[k], res["results"][k], f"batch key {k}")
    _check(searched, "batch: the device search refuted no key")
    return {"keys": len(hists), "ops": sum(len(h) for h in hists),
            "valid_keys": got.count(True), "invalid_keys": got.count(False),
            "device_refuted_certified": len(searched),
            "agree": len(hists) - len(bad), "check_s": device_s,
            "oracle_s": oracle_s}


def _certified(hist, result, what):
    """The refutation's witness replays through the CPU model."""
    from jepsen_tpu.analysis import certify
    from jepsen_tpu.models import cas_register_spec as spec
    cert, diags = certify.certify_with_diagnostics(spec, hist, result,
                                                   differential=False)
    replayed = [c for c in cert["checks"] if c.get("status") == "replayed"]
    errs = [d.code for d in diags if d.severity == "error"]
    _check(replayed and not errs,
           f"{what}: certify {errs}, checks {cert['checks']}")


def phase_long(hists=None, rollout="pallas"):
    """c. one long key, valid then corrupted, through the single-key
    engine; the valid one twice (the second run is warm). The device
    search decides the valid history on the rollout; the corrupted
    twin's impossible read is refuted by the engine's host pre-check
    (see ``long_histories``)."""
    from jepsen_tpu.checker import jax_wgl
    from jepsen_tpu.models import cas_register_spec as spec
    good, bad = hists if hists is not None else long_histories()
    out = {}
    for tag, hist, want in (("cold", good, True), ("warm", good, True),
                            ("corrupt", bad, False)):
        e, st = spec.encode(hist)
        t0 = time.monotonic()
        r = jax_wgl.check_encoded(spec, e, st)
        out[tag] = {"valid": r["valid"], "s": time.monotonic() - t0,
                    "rows": len(e), "engine": r.get("engine"),
                    "rollout": r.get("rollout"),
                    "iterations": r.get("iterations"),
                    "configs": r.get("configs_explored")}
        _check(r["valid"] is want, f"long {tag}: {r.get('valid')!r} "
               f"({r.get('error')}), want {want}")
        if want:
            _check(r.get("engine") == "jax-wgl"
                   and r.get("rollout") == rollout,
                   f"long {tag}: {r.get('engine')} rollout "
                   f"{r.get('rollout')!r}, want {rollout}")
    return out


def _service_history(bad):
    """/api/check input from the service-batch-smoke CI job: write
    bursts and a final read no host fast path decides."""
    ev = []
    for j in range(3):
        x = j * 10
        ev += [{"type": "invoke", "process": 0, "f": "write", "value": x},
               {"type": "invoke", "process": 1, "f": "write",
                "value": x + 1},
               {"type": "ok", "process": 0, "f": "write", "value": x},
               {"type": "ok", "process": 1, "f": "write", "value": x + 1},
               {"type": "invoke", "process": 0, "f": "write",
                "value": x + 5},
               {"type": "ok", "process": 0, "f": "write", "value": x + 5}]
    ev += [{"type": "invoke", "process": 2, "f": "read", "value": None},
           {"type": "ok", "process": 2, "f": "read",
            "value": 0 if bad else 25}]
    return ev


def phase_service():
    """d. web.serve in-process, coalescing on; two tenants post at
    once (tenants make the batch's ``owners`` count meaningful)."""
    from jepsen_tpu import web
    from jepsen_tpu.fleet import service
    service.configure(tokens={"tok-0": "tenant-0", "tok-1": "tenant-1"})
    server = web.serve({"ip": "127.0.0.1", "port": 0, "coalesce?": True,
                        "coalesce-window-ms": 150})
    port = server.server_address[1]
    try:
        def post(i, body):
            req = urllib.request.Request(
                f"http://127.0.0.1:{port}/api/check",
                data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json",
                         "Authorization": f"Bearer tok-{i}"})
            with urllib.request.urlopen(req, timeout=600) as r:
                return json.loads(r.read())

        got = [None, None]

        def one(i):
            got[i] = post(i, {"history": _service_history(bad=i == 1),
                           "model": "cas-register", "searchplan": False})

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        _check(got[0] is not None and got[0]["valid"] is True,
               f"service good: {got[0]}")
        _check(got[1] is not None and got[1]["valid"] is False,
               f"service bad: {got[1]}")
        with urllib.request.urlopen(urllib.request.Request(
                f"http://127.0.0.1:{port}/api/metrics",
                headers={"Authorization": "Bearer tok-0"}),
                timeout=60) as r:
            text = r.read().decode()
        batches = [ln for ln in text.splitlines()
                   if ln.startswith("jepsen_service_coalesce_batches")
                   and 'model="cas-register"' in ln]
        _check(any(float(ln.rsplit(" ", 1)[1]) > 0 for ln in batches),
               f"service: no coalesced jax-wgl batch in {batches}")
        return {"good": got[0]["valid"], "bad": got[1]["valid"],
                "coalesced": [g.get("coalesced") for g in got],
                "batches": batches}
    finally:
        server.shutdown()
        server.server_close()


def phase_stream(time_limit=STREAM_TIME_LIMIT):
    """e. the streamlin monitor drive (.claude/skills/verify/SKILL.md)."""
    from jepsen_tpu import core, demo
    test = demo.demo_test({"workload": "register", "no-ssh": True,
                           "nodes": ["n1", "n2", "n3"],
                           "bug": "dirty-read", "time-limit": time_limit,
                           "monitor": {"chunk": 8,
                                       "engine": "streamlin"}})
    res = core.run(test)["results"]
    mon = res["monitor"]
    st = mon["stream"]
    _check(mon["verdict"] is False, f"stream verdict {mon['verdict']}")
    _check(res["abort-reason"] == "monitor-violation"
           and res["valid"] is False,
           f"stream: {res.get('abort-reason')} / {res['valid']}")
    _check(st["flat_checks"] == 0 and "fallback" not in st,
           f"stream fell back: {st}")
    _check(st["confirm_mismatches"] == 0, f"stream mismatch: {st}")
    return {"verdict": mon["verdict"], "valid": res["valid"],
            "detected_at_index": mon.get("detected_at_index"),
            "stream": {k: st[k] for k in sorted(st)
                       if isinstance(st[k], (int, float, str))}}


def _sample_devices(devs, stop, seen):
    """Mesh phase observer: which devices hold shards of live mesh
    arrays, and each device's bytes in use, while the engines run."""
    import jax
    from jax.sharding import NamedSharding
    while not stop.wait(0.05):
        for a in jax.live_arrays():
            if isinstance(a.sharding, NamedSharding) \
                    and len(a.sharding.device_set) == len(devs):
                seen["shards"].update(s.device.id
                                      for s in a.addressable_shards)
        for d in devs:
            b = (d.memory_stats() or {}).get("bytes_in_use", 0)
            seen["bytes"][d.id] = max(seen["bytes"].get(d.id, 0), b)


def phase_mesh(devs):
    """--chips 4: phase b over a keys mesh and phase c's valid history
    through the sharded single-key search, each against the one-device
    engine in this process."""
    from jepsen_tpu.checker import jax_wgl
    from jepsen_tpu.models import cas_register_spec as spec
    from jepsen_tpu.parallel import check_batch_encoded, \
        check_encoded_sharded, keys_mesh
    mesh = keys_mesh(len(devs))
    pairs = [spec.encode(h) for h in batch_histories()]
    # the valid long history: its corrupted twin never reaches the
    # search (the host pre-check refutes it), so it has no shards
    good, _bad = long_histories()
    e, st = spec.encode(good)
    stop, seen = threading.Event(), {"shards": set(), "bytes": {}}
    sampler = threading.Thread(target=_sample_devices,
                               args=(devs, stop, seen), daemon=True)
    sampler.start()
    try:
        t0 = time.monotonic()
        batch_mesh = [r["valid"] for r in
                      check_batch_encoded(spec, pairs, mesh=mesh)]
        batch_mesh_s = time.monotonic() - t0
        t0 = time.monotonic()
        sharded = check_encoded_sharded(spec, e, st, mesh)
        sharded_s = time.monotonic() - t0
    finally:
        stop.set()
        sampler.join()
    t0 = time.monotonic()
    batch_one = [r["valid"] for r in check_batch_encoded(spec, pairs)]
    batch_one_s = time.monotonic() - t0
    t0 = time.monotonic()
    single = jax_wgl.check_encoded(spec, e, st)
    single_s = time.monotonic() - t0
    _check(batch_mesh == batch_one, "mesh batch verdicts differ from "
           "the one-device engine")
    _check(sharded["valid"] == single["valid"],
           f"sharded {sharded['valid']} != one-device {single['valid']}")
    ids = sorted(d.id for d in devs)
    _check(sorted(seen["shards"]) == ids,
           f"mesh shards on devices {sorted(seen['shards'])}, want {ids}")
    _check(all(seen["bytes"].get(i, 0) > 0 for i in ids),
           f"bytes in use per device {seen['bytes']}")
    busy = sum(1 for x in sharded["shard_explored"] if x > 0)
    _check(busy >= 2, f"sharded search: shard_explored "
           f"{sharded['shard_explored']}")
    return {"batch": {"keys": len(pairs), "agree": True,
                      "invalid_keys": batch_mesh.count(False),
                      "mesh_s": batch_mesh_s, "one_device_s": batch_one_s},
            "sharded": {"valid": sharded["valid"],
                        "one_device": single["valid"],
                        "shard_explored": sharded["shard_explored"],
                        "mesh_s": sharded_s, "one_device_s": single_s},
            "shard_devices": sorted(seen["shards"]),
            "bytes_in_use_max": seen["bytes"]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh phase on four chips")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "jepsen_tpu")):
        print(f"chip_smoke: no jepsen_tpu package beside {__file__}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    from jepsen_tpu import jaxcache
    jaxcache.enable()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax.devices() = {devs})",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devs)} devices",
              file=sys.stderr)
        return 1
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    if args.chips == 4:
        _phase("mesh", lambda: phase_mesh(devs[:4]))
    else:
        _phase("a-harness", phase_harness)
        _phase("b-batch", phase_batch)
        _phase("c-long", phase_long)
        _phase("d-service", phase_service)
        _phase("e-stream", phase_stream)
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    code = 1
    try:
        code = main()
    except BaseException:  # noqa: BLE001 - report, then exit non-zero
        traceback.print_exc()
    sys.stdout.flush()
    sys.stderr.flush()
    # skip interpreter teardown: daemon threads of the harness and the
    # service may still hold device work (cli.hard_main)
    os._exit(code)
