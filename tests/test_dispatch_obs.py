"""The checker-dispatch layer's spans and the clock they share with the
device trace:

* every obs facade function resolves its sinks by one rule,
  ``obs.current_sinks()``, so a ``sink_scope`` steers spans, counters
  and histograms alike;
* ``independent.*`` spans name the key split, the per-key encode and
  the finish, once per check, on the batched and the fallback path;
* certification's replays are named (``certify.*``) while the replayed
  engines' own counters stay silent;
* ``obs.profile.anchor`` maps the tracer's spans onto the profiler's
  clock to well under a millisecond;
* a keyed batch over a chip mesh names its compactions
  (``keyshard.compact``) and reports each chip's share of the search
  (``shard_explored`` on ``wgl.done``, ``wgl.shard_explored_total``);
  a one-device batch reports neither.
"""

import glob
import os
import time

import pytest

from jepsen_tpu import checker as cc
from jepsen_tpu import history as h
from jepsen_tpu import independent, obs
from jepsen_tpu.checker import checkers as ck
from jepsen_tpu.checker.core import certify_verdict
from jepsen_tpu.obs import profile as obs_profile

T = independent.tuple_


def _spans(tr, cat=None):
    return [e for e in tr.events() if e.get("ph") == "X"
            and (cat is None or e.get("cat") == cat)]


def _names(tr, cat=None):
    return [e["name"] for e in _spans(tr, cat)]


# ---------------------------------------------------------------------------
# one rule for sinks


def _emit_all():
    with obs.span("probe.span", cat="probe"):
        pass
    obs.instant("probe.instant")
    obs.inc("probe.count")
    obs.observe("probe.hist", 0.5)
    return obs.enabled()


@pytest.mark.parametrize("how", ["bind", "run_scope", "sink_scope",
                                 "silenced"])
def test_facade_resolves_through_current_sinks(how):
    outer = (obs.Tracer(), obs.Registry())
    inner = (obs.Tracer(), obs.Registry())
    test = {}
    scopes = {"bind": lambda: obs.bind(*inner),
              "run_scope": lambda: obs.run_scope(test),
              "sink_scope": lambda: obs.sink_scope(*inner),
              "silenced": lambda: obs.sink_scope(None, None)}
    with obs.bind(*outer), scopes[how]():
        on = _emit_all()
    if how == "run_scope":
        inner = (test["obs"]["tracer"], test["obs"]["registry"])
    want = None if how == "silenced" else inner
    assert on is (want is not None)
    for pair in (outer, inner):
        tr, reg = pair
        hit = pair is want
        assert ("probe.span" in _names(tr)) is hit
        assert ("probe.instant" in [e["name"] for e in tr.events()]) \
            is hit
        assert reg.counter_value("probe.count") == (1 if hit else 0)
        assert ("probe.hist" in reg.snapshot()["histograms"]) is hit


# ---------------------------------------------------------------------------
# certification: named replays, silent replayed engines


def _invalid_history():
    ev = []
    for f, v in (("write", 1), ("write", 2), ("read", 1), ("read", 2)):
        ev.append({"index": len(ev), "type": "invoke", "process": 0,
                   "f": f, "value": None if f == "read" else v})
        ev.append({"index": len(ev), "type": "ok", "process": 0,
                   "f": f, "value": v})
    return h.ensure_indexed(ev)


def test_certify_spans_land_under_sink_scope():
    lin = ck.linearizable({"model": "cas-register",
                           "algorithm": "jax-wgl"})
    hist = _invalid_history()
    result = lin.check({"certify?": False}, hist, {})
    assert result["valid"] is False and result["engine"] == "jax-wgl"
    tr, reg = obs.Tracer(), obs.Registry()
    test = {}
    with obs.sink_scope(tr, reg):
        certify_verdict(lin, test, hist, result)
    assert test["certificate"]["verdict"] is False
    names = _names(tr)
    assert names.count("analysis.certify") == 1
    cross = [e for e in _spans(tr) if e["name"] == "certify.cross_check"]
    assert len(cross) == 1
    assert cross[0]["args"]["engine"] == "linear"
    assert cross[0]["args"]["parent"] == "analysis.certify"
    diff = [e for e in _spans(tr) if e["name"] == "certify.differential"]
    assert sorted(e["args"]["engine"] for e in diff) == [
        "jax-wgl", "linear", "wgl"]
    assert all(e["args"]["rows"] > 0 for e in diff)
    # the analyzer's own metrics reach the scoped registry now ...
    snap = reg.snapshot()
    assert reg.counter_value("analysis.certify.runs", verdict="False") == 1
    assert any(k.startswith("analysis.run_s") for k in snap["histograms"])
    # ... while the replayed engines' stay out of the run's accounting
    assert not [k for k in snap["counters"] if k.startswith("wgl.")]
    assert not _spans(tr, cat="phase")


# ---------------------------------------------------------------------------
# independent: split / prepare / finish


def _keyed_history(keys, bad_keys=()):
    hist = []
    for i, k in enumerate(keys):
        p = i % 3
        hist += [
            h.invoke_op(p, "write", T(k, 1)),
            h.ok_op(p, "write", T(k, 1)),
            h.invoke_op(p, "read", T(k, None)),
            h.ok_op(p, "read", T(k, 99 if k in bad_keys else 1)),
        ]
    return hist


def _dispatch_spans(tr):
    return {e["name"]: e for e in _spans(tr, cat="dispatch")}


def test_independent_spans_on_the_batched_path():
    c = independent.checker(ck.linearizable({"model": "cas-register",
                                             "algorithm": "jax-wgl"}))
    tr, reg = obs.Tracer(), obs.Registry()
    with obs.bind(tr, reg):
        r = cc.check(c, {}, _keyed_history(["a", "b", "c"],
                                           bad_keys={"b"}))
    assert r["valid"] is False and r["failures"] == ["b"]
    assert sorted(_names(tr, "dispatch")) == [
        "independent.finish", "independent.prepare", "independent.split"]
    sp = _dispatch_spans(tr)
    assert sp["independent.split"]["args"]["keys"] == 3
    assert sp["independent.split"]["args"]["events"] == 12
    assert sp["independent.split"]["args"]["unkeyed"] == 0
    assert sp["independent.prepare"]["args"]["keys"] == 3
    assert sp["independent.prepare"]["args"]["pairs"] >= 3
    assert sp["independent.finish"]["args"]["keys"] == 3
    # one after another on the caller's thread, the device batch
    # between prepare and finish
    order = sorted(sp.values(), key=lambda e: e["ts"])
    assert [e["name"] for e in order] == [
        "independent.split", "independent.prepare", "independent.finish"]
    for a, b in zip(order, order[1:]):
        assert a["ts"] + a["dur"] <= b["ts"] + 1.0
    assert len({e["tid"] for e in order}) == 1
    assert _names(tr).count("analysis.certify") == 1


def test_independent_spans_on_the_fallback_path():
    c = independent.checker(ck.linearizable({"model": "cas-register",
                                             "algorithm": "wgl"}))
    tr, reg = obs.Tracer(), obs.Registry()
    with obs.bind(tr, reg):
        r = cc.check(c, {}, [h.op("info", "nemesis", "kill", None)]
                     + _keyed_history(["a", "b"], bad_keys={"a"}))
    assert r["valid"] is False
    assert _names(tr, "dispatch") == ["independent.split"]
    args = _dispatch_spans(tr)["independent.split"]["args"]
    assert args["keys"] == 2
    assert args["events"] == 9
    assert args["unkeyed"] == 1
    assert _names(tr).count("analysis.certify") == 1


# ---------------------------------------------------------------------------
# one clock: the anchor


def _profiled(tmp_path, body):
    import jax
    pdir = str(tmp_path / "prof")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(pdir, profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(pdir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    host = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    host.setdefault(e.name, (e.start_ns, e.duration_ns))
    return host


def test_anchor_maps_tracer_spans_onto_the_profiler_clock(tmp_path):
    import jax
    if not obs_profile.available():
        pytest.skip("jax.profiler unavailable in this environment")
    tr = obs.Tracer()

    def body():
        with obs.bind(tr, None), obs_profile.anchor("test.window"):
            time.sleep(0.02)
            with jax.profiler.TraceAnnotation("test.block"), \
                    obs.span("test.block", cat="dispatch"):
                time.sleep(0.05)
            time.sleep(0.01)

    host = _profiled(tmp_path, body)
    assert obs_profile.on_profile_clock(tr.events(), "missing", 0) is None
    mapped = obs_profile.on_profile_clock(
        tr.events(), "test.window", host["test.window"][0])
    got = {n: (s, d) for n, _cat, s, d in mapped}
    want_s, want_d = host["test.block"]
    s, d = got["test.block"]
    assert abs(s - want_s) < 1e6
    assert abs(d - want_d) < 1e6
    assert got["test.window"][0] == pytest.approx(host["test.window"][0],
                                                  abs=1.0)


def test_profile_scope_opens_the_anchor(tmp_path):
    if not obs_profile.available():
        pytest.skip("jax.profiler unavailable in this environment")
    pdir = str(tmp_path / "prof" / "profile")
    tr = obs.Tracer()
    with obs.bind(tr, None):
        with obs_profile.scope({"profile?": True, "profile-dir": pdir}):
            with obs.span("analyze"):
                pass
    anchors = [e for e in _spans(tr, "anchor")
               if e["name"] == obs_profile.ANCHOR]
    assert len(anchors) == 1
    inner = [e for e in _spans(tr) if e["name"] == "analyze"]
    assert inner[0]["args"]["parent"] == obs_profile.ANCHOR


# ---------------------------------------------------------------------------
# the keyed batch over a chip mesh


@pytest.mark.parametrize("chips", [4, 1])
def test_keyed_mesh_batch_spans_and_chip_counters(chips):
    import jax
    from test_keyshard_chips import check, keyed_cas_history, keyed_checker
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    tr, reg = obs.Tracer(), obs.Registry()
    with obs.sink_scope(tr, reg):
        res = check(keyed_checker(chips=chips), keyed_cas_history()[1])
    assert any(r.get("compactions") for r in res["results"].values())
    compacts = [e for e in _spans(tr, "search")
                if e["name"] == "keyshard.compact"]
    done = [e["args"] for e in tr.events()
            if e["name"] == "wgl.done.jax-wgl-batch"]
    per_chip = {dict(labels)["shard"]: v for (name, labels), v
                in reg.series()["counters"].items()
                if name == "wgl.shard_explored_total"}
    assert len(done) == 1
    if chips == 1:
        assert compacts == [] and per_chip == {}
        assert "shard_explored" not in done[0]
        return
    for e in compacts:
        a = e["args"]
        assert a["chips"] == 4
        assert a["keys_before"] > a["keys_after"] >= 4
        assert 0 <= a["moved"] <= a["keys_after"]
    assert compacts and any(e["args"]["moved"] for e in compacts)
    split = done[0]["shard_explored"]
    assert len(split) == 4 and sum(split) == done[0]["configs_explored"]
    assert done[0]["shard_max_share"] == round(max(split) / sum(split), 4)
    assert per_chip == {str(i): n for i, n in enumerate(split)}
