"""Per-dispatch phase attribution (obs.phases) and the idle-bubble
ledger (obs.bubbles).

The invariants pinned here are the ones the whole "where does the
wall go?" plane rests on:

* phase spans from one session are exactly contiguous and
  non-overlapping (the cursor design), so the bubble fold can treat
  every gap as real;
* ``compile`` is emitted ONLY for the device lap right after a
  compile-ledger miss;
* ``wgl.device_busy_s`` is the device-compute bracket when phases
  are measured and never exceeds the full dispatch-chunk wall
  (``wgl.chunk_s``) — the busy-honesty fix;
* bubble re-folds are byte-identical and the attribution math adds
  up.
"""

import time

import pytest

from jepsen_tpu import obs
from jepsen_tpu.models import cas_register_spec
from jepsen_tpu.obs import bubbles
from jepsen_tpu.obs import phases as obs_phases
from jepsen_tpu.obs import search as obs_search
from jepsen_tpu.simulate import random_history


# ---------------------------------------------------------------------------
# PhaseSession unit behavior

def _phase_events(tr):
    return [e for e in tr.events()
            if e.get("ph") == "X" and e.get("cat") == "phase"]


def test_session_spans_contiguous_and_nonoverlapping():
    tr, reg = obs.Tracer(), obs.Registry()
    with obs.bind(tr, reg):
        ph = obs_phases.capture("unit")
        assert ph.enabled
        for phase in ("encode", "plan", "h2d", "device", "d2h",
                      "host"):
            time.sleep(0.002)
            ph.lap(phase)
    evs = sorted(_phase_events(tr), key=lambda e: e["ts"])
    assert [e["name"] for e in evs] == [
        f"wgl.phase.{p}" for p in ("encode", "plan", "h2d", "device",
                                   "d2h", "host")]
    for a, b in zip(evs, evs[1:]):
        # one cursor, one clock offset: exactly contiguous (float-us
        # rounding only)
        assert abs((a["ts"] + a["dur"]) - b["ts"]) < 1.0, (a, b)
    # both sink legs agree: counter seconds == span seconds
    for e in evs:
        phase = e["name"][len("wgl.phase."):]
        c = reg.counter_value("wgl.phase_s", phase=phase,
                              engine="unit")
        assert c == pytest.approx(e["dur"] / 1e6, rel=1e-6)
        assert ph.totals[phase] == pytest.approx(e["dur"] / 1e6,
                                                 rel=1e-6)


def test_compile_phase_only_after_ledger_miss():
    tr, reg = obs.Tracer(), obs.Registry()
    with obs.bind(tr, reg):
        ph = obs_phases.capture("unit")
        ph.note_compile(True)          # miss arms the next device lap
        ph.lap("device")
        ph.lap("device")               # disarmed: plain device again
        ph.note_compile(False)         # a hit arms nothing
        ph.lap("device")
    names = [e["name"] for e in sorted(_phase_events(tr),
                                       key=lambda e: e["ts"])]
    assert names == ["wgl.phase.compile", "wgl.phase.device",
                     "wgl.phase.device"]


def test_disabled_session_times_but_emits_nothing():
    # nothing bound: lap still returns the measured wall (callers
    # reuse the number for heartbeats) but no sink sees anything
    ph = obs_phases.capture("unit")
    assert not ph.enabled
    time.sleep(0.002)
    assert ph.lap("device") > 0.0
    assert ph.totals == {}

    # bound, but the run said phases? False: same contract
    tr, reg = obs.Tracer(), obs.Registry()
    with obs.bind(tr, reg), obs.sink_scope(tr, reg,
                                           {"phases?": False}):
        ph2 = obs_phases.capture("unit")
        assert not ph2.enabled
        assert ph2.lap("device") >= 0.0
        obs_phases.note_wait("unit", 0.1)
    assert _phase_events(tr) == []
    assert reg.snapshot()["counters"] == {}


def test_note_wait_emits_one_span_and_counter():
    tr, reg = obs.Tracer(), obs.Registry()
    with obs.bind(tr, reg):
        obs_phases.note_wait("unit", 0.25, owner="t1")
    evs = _phase_events(tr)
    assert len(evs) == 1 and evs[0]["name"] == "wgl.phase.wait"
    assert evs[0]["dur"] == pytest.approx(0.25e6, rel=1e-6)
    assert evs[0]["args"]["owner"] == "t1"
    assert reg.counter_value("wgl.phase_s", phase="wait",
                             engine="unit") == pytest.approx(0.25)
    # garbage wall is dropped, not crashed on
    with obs.bind(tr, reg):
        obs_phases.note_wait("unit", None)
        obs_phases.note_wait("unit", -3.0)
    assert reg.counter_value("wgl.phase_s", phase="wait",
                             engine="unit") == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# busy honesty: device_s vs chunk_s

def test_heartbeat_device_s_repoints_busy():
    reg = obs.Registry()
    with obs.bind(None, reg):
        so = obs_search.capture()
        # phases measured: busy is the device-compute bracket
        so.heartbeat("jax-wgl", iteration=1, chunk_s=1.0,
                     device_s=0.2)
        # phases off (no device_s): busy falls back to the chunk wall
        so.heartbeat("jax-wgl", iteration=2, chunk_s=0.5)
    busy = reg.counter_value("wgl.device_busy_s", engine="jax-wgl")
    assert busy == pytest.approx(0.7)
    h = reg.snapshot()["histograms"]["wgl.chunk_s{engine=jax-wgl}"]
    assert h["count"] == 2 and h["sum"] == pytest.approx(1.5)
    assert busy <= h["sum"]


def _engine_invariants(tr, reg, engine):
    evs = _phase_events(tr)
    evs = [e for e in evs if e["args"].get("engine") == engine]
    assert evs, f"no phase spans for {engine}"
    phases = {e["name"][len("wgl.phase."):] for e in evs}
    assert phases <= set(obs_phases.PHASES), phases
    assert {"encode", "device", "d2h", "host"} <= phases, phases
    # non-overlap per (pid, tid) lane
    lanes = {}
    for e in evs:
        lanes.setdefault((e.get("pid"), e.get("tid")),
                         []).append(e)
    for lane in lanes.values():
        lane.sort(key=lambda e: e["ts"])
        for a, b in zip(lane, lane[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1.0, (a, b)
    # the satellite pin: busy (device bracket, compile included) can
    # never exceed the full chunk wall
    busy = reg.counter_value("wgl.device_busy_s", engine=engine)
    h = reg.snapshot()["histograms"].get(
        "wgl.chunk_s{engine=%s}" % engine, {})
    assert busy > 0 and h.get("count", 0) >= 1
    assert busy <= h["sum"] * 1.001 + 1e-6, (busy, h["sum"])
    # device+compile span wall is what the busy counter summed
    span_dev = sum(e["dur"] / 1e6 for e in evs
                   if e["name"] in ("wgl.phase.device",
                                    "wgl.phase.compile"))
    assert busy == pytest.approx(span_dev, rel=0.02, abs=0.005)


def test_single_key_engine_emits_phase_plane():
    from jepsen_tpu.checker import jax_wgl
    hist = random_history(__import__("random").Random(7),
                          "cas-register", n_procs=4, n_ops=60,
                          crash_p=0.0)
    e, st = cas_register_spec.encode(hist)
    tr, reg = obs.Tracer(), obs.Registry()
    with obs.bind(tr, reg):
        r = jax_wgl.check_encoded(cas_register_spec, e, st,
                                  chunk_iters=32)
    assert r["valid"] is True
    _engine_invariants(tr, reg, "jax-wgl")
    # second identical search: the compile ledger is now hot for this
    # shape, so no lap may be attributed to compile
    tr2, reg2 = obs.Tracer(), obs.Registry()
    with obs.bind(tr2, reg2):
        jax_wgl.check_encoded(cas_register_spec, e, st,
                              chunk_iters=32)
    assert not [ev for ev in _phase_events(tr2)
                if ev["name"] == "wgl.phase.compile"]


def test_batch_engine_emits_phase_plane():
    from jepsen_tpu.parallel import keyshard
    rng = __import__("random").Random(11)
    pairs = [cas_register_spec.encode(
        random_history(rng, "cas-register", n_procs=4, n_ops=50,
                       crash_p=0.0)) for _ in range(3)]
    tr, reg = obs.Tracer(), obs.Registry()
    with obs.bind(tr, reg):
        rs = keyshard.check_batch_encoded(cas_register_spec, pairs,
                                          chunk_iters=32)
    assert [r["valid"] for r in rs] == [True] * 3
    _engine_invariants(tr, reg, "jax-wgl-batch")


# ---------------------------------------------------------------------------
# bubble ledger

def _span(pid, ts_us, dur_us, phase, engine="e"):
    return {"ph": "X", "cat": "phase", "name": f"wgl.phase.{phase}",
            "pid": pid, "tid": 1, "ts": float(ts_us),
            "dur": float(dur_us), "args": {"engine": engine}}


def test_bubble_fold_attribution_math():
    events = [
        # episode 1: 0.4 s extent, 0.2 s device, idle fully named
        _span(1, 0, 100_000, "encode"),
        _span(1, 100_000, 200_000, "device"),
        _span(1, 300_000, 50_000, "d2h"),
        _span(1, 350_000, 50_000, "host"),
        # >1 s quiet, then episode 2 with an unbracketed 0.1 s gap
        _span(1, 2_000_000, 100_000, "device"),
        _span(1, 2_200_000, 100_000, "host"),
    ]
    led = bubbles.fold_events(events)
    assert led["lanes"] == 1 and led["episodes"] == 2
    assert led["device_s"] == pytest.approx(0.3)
    # ep1 idle 0.2 attributed 0.2; ep2 extent 0.3, idle 0.2,
    # attributed 0.1, residual 0.1 (the unbracketed gap)
    assert led["idle_s"] == pytest.approx(0.4)
    assert led["attributed_s"] == pytest.approx(0.3)
    assert led["residual_s"] == pytest.approx(0.1)
    assert led["attribution_frac"] == pytest.approx(0.75)
    # the quiet stretch is reported but OUTSIDE the denominator
    assert led["inter_episode_s"] == pytest.approx(1.6)
    assert led["phases"]["host"] == pytest.approx(0.15)
    assert led["engines"]["e"]["device_s"] == pytest.approx(0.3)


def test_bubble_fold_byte_deterministic(tmp_path):
    events = [_span(1, i * 1000, 900, p)
              for i, p in enumerate(("encode", "device", "d2h",
                                     "host") * 5)]
    led1 = bubbles.fold_events(events)
    led2 = bubbles.fold_events(list(reversed(events)))
    assert bubbles.dumps(led1) == bubbles.dumps(led2)
    out = bubbles.write_ledger(led1, str(tmp_path / "b.json"))
    with open(out) as f:
        assert f.read() == bubbles.dumps(led1)
    # "path" never reaches the canonical bytes
    led1["path"] = "somewhere"
    assert bubbles.dumps(led1) == bubbles.dumps(led2)
    # no phase spans -> empty ledger, not a crash
    assert bubbles.fold_events([])["episodes"] == 0


def test_bubble_fold_ignores_non_phase_events():
    events = [
        _span(1, 0, 100_000, "device"),
        {"ph": "X", "cat": "search", "name": "wgl.phase.device",
         "pid": 1, "tid": 1, "ts": 0.0, "dur": 9e9, "args": {}},
        {"ph": "M", "name": "process_name", "pid": 1, "args": {}},
    ]
    led = bubbles.fold_events(events)
    assert led["device_s"] == pytest.approx(0.1)
    assert led["episodes"] == 1


# ---------------------------------------------------------------------------
# PL022

def test_pl022_lint_trend():
    from jepsen_tpu.analysis import planlint

    codes = planlint.lint_phases

    assert codes({}) == []
    # phases off while a consumer needs the spans
    errs = codes({"phases?": False, "profile?": True,
                  "bubbles?": True})
    assert len(errs) == 2
    assert all(d.code == "PL022" and d.severity == "error"
               for d in errs)
    assert codes({"phases?": True, "profile?": True}) == []
    # and lint_plan carries the pass (the fleet/campaign wiring)
    t = {"name": "x", "phases?": False, "profile?": True}
    assert any(d.code == "PL022" for d in planlint.lint_plan(t))


# ---------------------------------------------------------------------------
# fold surfaces

def test_introspection_summary_folds_phases_and_chunk():
    from jepsen_tpu.obs.merge import introspection_summary
    reg = obs.Registry()
    reg.inc("wgl.device_busy_s", 2.0, engine="jax-wgl")
    reg.inc("wgl.phase_s", 2.0, phase="device", engine="jax-wgl")
    reg.inc("wgl.phase_s", 0.5, phase="h2d", engine="jax-wgl")
    reg.observe("wgl.chunk_s", 3.0, engine="jax-wgl")
    out = introspection_summary(reg.snapshot())
    assert out["device_busy_s"]["jax-wgl"] == pytest.approx(2.0)
    assert out["chunk_s"]["jax-wgl"] == pytest.approx(3.0)
    assert out["phase_s"]["jax-wgl"] == {"device": 2.0, "h2d": 0.5}
    assert out["device_busy_s"]["jax-wgl"] <= out["chunk_s"]["jax-wgl"]
