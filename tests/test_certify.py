"""Proof-carrying verdicts (jepsen_tpu/analysis/certify.py): the
normalized witness schema pinned across engines, every seeded
mutation class caught by its VC code, the bounded cross-check and
differential harness, the checker/monitor/service/campaign wiring,
byte-deterministic certificate.json, planlint PL023, and — the
acceptance property — certification NEVER flips a verdict."""

import copy
import json
import os

import numpy as np
import pytest

import jax

from jepsen_tpu import core as jcore
from jepsen_tpu import history as h
from jepsen_tpu import store
from jepsen_tpu.analysis import certify, planlint
from jepsen_tpu.checker import core as ccore
from jepsen_tpu.checker import jax_wgl, linear, wgl, witness
from jepsen_tpu.checker.checkers import Linearizable
from jepsen_tpu.models import base as mbase

SPEC = mbase.model_spec("register")


@pytest.fixture(autouse=True)
def store_tmpdir(tmp_path, monkeypatch):
    monkeypatch.setattr(store, "base_dir", str(tmp_path / "store"))


# ---------------------------------------------------------------------------
# history builders


def _pairs(ops):
    """Sequential invoke/ok pairs: [(f, value), ...]."""
    ev, idx = [], 0
    for f, v in ops:
        ev.append({"index": idx, "type": "invoke", "process": 0,
                   "f": f, "value": None if f == "read" else v})
        idx += 1
        ev.append({"index": idx, "type": "ok", "process": 0,
                   "f": f, "value": v})
        idx += 1
    return ev


def valid_concurrent():
    """w1 || r=1: linearizable, undecidable without a real order."""
    return [
        {"index": 0, "type": "invoke", "process": 0, "f": "write",
         "value": 1},
        {"index": 1, "type": "invoke", "process": 1, "f": "read",
         "value": None},
        {"index": 2, "type": "ok", "process": 0, "f": "write",
         "value": 1},
        {"index": 3, "type": "ok", "process": 1, "f": "read",
         "value": 1},
    ]


def invalid_sequential():
    """w1; w2; r=1; r=2 sequentially: every read value was genuinely
    written (the state-abstraction fast path can't decide), but no
    total order satisfies both reads -> the real search runs and
    decides False."""
    return _pairs([("write", 1), ("write", 2), ("read", 1),
                   ("read", 2)])


def _certify(result, hist, test=None, samples=0, **kw):
    lin = Linearizable(SPEC)
    client = lin.prepare_history(h.client_ops(h.ensure_indexed(hist)))
    return certify.certify_with_diagnostics(
        SPEC, client, result, test=test, samples=samples, **kw)


def _codes(diags):
    return sorted({d.code for d in diags})


def _linear_result(hist, test=None):
    t = dict(test or {})
    return Linearizable(SPEC, algorithm="linear").check(
        t, h.ensure_indexed(hist), {}), t


# ---------------------------------------------------------------------------
# the normalized witness schema, pinned across engines


def test_witness_schema_linear_invalid():
    r, _ = _linear_result(invalid_sequential())
    assert r["valid"] is False
    w = r["witness"]
    assert w["schema"] == witness.WITNESS_SCHEMA == 1
    assert w["engine"] == "linear"
    assert w["verdict"] is False
    assert w["rows"] == 4 and w["n_ok"] == 4
    assert w["segment"] is None
    assert sorted(w["order"]) == sorted(w["linearized_rows"])


def test_witness_schema_jax_wgl_both_verdicts():
    """The device engine emits the same schema on BOTH verdicts (the
    valid path decodes the winning TOPK slot into a full witness)."""
    for hist, want in ((valid_concurrent(), True),
                       (invalid_sequential(), False)):
        e, st = SPEC.encode(h.ensure_indexed(hist))
        r = jax_wgl.check_encoded(SPEC, e, st)
        assert r["valid"] is want
        w = r["witness"]
        assert w["schema"] == 1 and w["engine"] == "jax-wgl"
        assert w["verdict"] is want
        assert w["rows"] == len(e) and w["n_ok"] == int(e.n_ok)
        if want:
            # a valid witness linearizes every ok row, replayably
            assert sorted(w["linearized_rows"]) == list(range(len(e)))
            assert sorted(w["order"]) == sorted(w["linearized_rows"])


def test_witness_schema_wgl_oracle():
    """The CPU WGL oracle attaches the same schema on False (no
    engine tag: it is the oracle, not a device engine)."""
    e, st = SPEC.encode(h.ensure_indexed(invalid_sequential()))
    r = wgl.check_encoded(SPEC, e, st)
    assert r["valid"] is False
    assert r["witness"]["schema"] == 1
    assert r["witness"]["verdict"] is False


def test_witness_schema_searchshard():
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip(f"need 2 devices, have {len(devs)}")
    from jax.sharding import Mesh
    from jepsen_tpu.parallel import check_encoded_sharded
    mesh = Mesh(np.array(devs[:2]), ("search",))
    e, st = SPEC.encode(h.ensure_indexed(invalid_sequential()))
    try:
        r = check_encoded_sharded(SPEC, e, st, mesh)
    except TypeError as exc:  # old jax lacks shard_map check_vma
        pytest.skip(f"sharded engine unavailable: {exc}")
    assert r["valid"] is False
    w = r["witness"]
    assert w["schema"] == 1 and w["engine"] == "jax-wgl-sharded"
    assert w["verdict"] is False


def test_clean_verdicts_certify_clean():
    """Soundness, direction one: untampered runs produce ZERO
    diagnostics (valid replays; invalid cross-checks confirmed)."""
    for hist in (valid_concurrent(), invalid_sequential()):
        e, st = SPEC.encode(h.ensure_indexed(hist))
        r = jax_wgl.check_encoded(SPEC, e, st)
        cert, diags = _certify(r, hist, samples=1)
        assert diags == [], [d.message for d in diags]
        names = {c["name"]: c for c in cert["checks"]}
        assert names["witness"]["status"] == "replayed"
        if r["valid"] is False:
            assert names["cross-check"]["status"] == "confirmed"


# ---------------------------------------------------------------------------
# mutation detection: every seeded tamper class raises its VC code


def test_vc001_illegal_transition():
    """Tampering the order to read-before-write keeps precedence legal
    but makes the model reject the read from the init state."""
    hist = valid_concurrent()
    e, st = SPEC.encode(h.ensure_indexed(hist))
    r = jax_wgl.check_encoded(SPEC, e, st)
    assert r["valid"] is True and r["witness"]["order"] == [0, 1]
    r["witness"]["order"] = [1, 0]
    _, diags = _certify(r, hist)
    assert _codes(diags) == ["VC001"]


def test_vc002_real_time_violation():
    """Swapping two SEQUENTIAL writes (both always legal) violates
    only real-time precedence."""
    hist = _pairs([("write", 1), ("write", 2), ("read", 2)])
    r, _ = _linear_result(hist)
    assert r["valid"] is True
    e, st = _encoded(hist)
    w = witness.build(SPEC, e, "linear", True, np.ones(3, bool), st)
    w["order"] = [1, 0, 2]
    r2 = dict(r, witness=w)
    _, diags = _certify(r2, hist)
    assert "VC002" in _codes(diags)


def _encoded(hist):
    e, st = SPEC.encode(h.ensure_indexed(hist))
    return e, st


def test_vc003_incomplete_valid_witness():
    hist = valid_concurrent()
    e, st = _encoded(hist)
    r = jax_wgl.check_encoded(SPEC, e, st)
    w = r["witness"]
    w["linearized_rows"] = [0]
    w["order"] = [0]
    _, diags = _certify(r, hist)
    assert "VC003" in _codes(diags)


def test_vc004_flipped_verdict():
    r, _ = _linear_result(invalid_sequential())
    r["witness"]["verdict"] = True
    _, diags = _certify(r, invalid_sequential())
    assert "VC004" in _codes(diags)


def test_vc005_malformed_witness():
    base, _ = _linear_result(invalid_sequential())
    for tamper in (
        lambda w: w.update(rows=99),
        lambda w: w.update(schema=2),
        lambda w: w.update(n_ok=1),
        lambda w: w.update(linearized_rows=[0, 0]),
        lambda w: w.update(order=[0, 0]),
        lambda w: w.update(linearized_rows=[0, 77]),
    ):
        r = copy.deepcopy(base)
        tamper(r["witness"])
        _, diags = _certify(r, invalid_sequential())
        assert "VC005" in _codes(diags), tamper


def test_vc006_device_verdict_without_witness():
    e, st = _encoded(invalid_sequential())
    r = jax_wgl.check_encoded(SPEC, e, st)
    r.pop("witness")
    _, diags = _certify(r, invalid_sequential())
    assert any(d.code == "VC006" and d.severity == "info"
               for d in diags)
    # CPU engines legitimately carry no witness: note, not finding
    r2 = {"valid": False, "engine": "linear"}
    _, d2 = _certify(r2, invalid_sequential())
    assert "VC006" not in _codes(d2)


def test_vc008_cross_check_refutes():
    """A valid history recorded as False is refuted by the
    independent engine."""
    hist = valid_concurrent()
    _, diags = _certify({"valid": False, "engine": "jax-wgl"}, hist)
    assert "VC008" in _codes(diags)


def test_vc009_budget_exhausted_is_info_not_fatal():
    r, _ = _linear_result(invalid_sequential())
    _, diags = _certify(r, invalid_sequential(), budget=1)
    vc9 = [d for d in diags if d.code == "VC009"]
    assert vc9 and all(d.severity == "info" for d in vc9)
    assert not [d for d in diags if d.severity == "error"]


def test_contended_invalid_key_witness_replays():
    """A contended invalid cas-register key (200 ops over 10
    processes, one read flipped): the witness replay orders the
    deepest configuration, so certification replays the proof instead
    of recording it unreplayed (VC009)."""
    from test_witness_replay import contended_history
    spec = mbase.model_spec("cas-register")
    hist = h.ensure_indexed(contended_history())
    lin = Linearizable(spec, algorithm="linear")
    r = lin.check({}, hist, {})
    assert r["valid"] is False
    client = lin.prepare_history(h.client_ops(hist))
    cert, diags = certify.certify_with_diagnostics(spec, client, r,
                                                   samples=0)
    status = [c["status"] for c in cert["checks"]
              if c["name"].startswith("witness")]
    assert status and "unreplayed" not in status
    assert "VC009" not in _codes(diags)
    assert not [d for d in diags if d.severity == "error"]


def test_vc010_differential_divergence(monkeypatch):
    """A lying engine in the differential table is caught."""
    monkeypatch.setitem(certify.DIFF_ENGINES, "wgl",
                        lambda spec, e, st, budget: {"valid": True})
    r, _ = _linear_result(invalid_sequential())
    _, diags = _certify(r, invalid_sequential(), samples=1)
    assert "VC010" in _codes(diags)


def test_vc011_undecided_engine_is_info(monkeypatch):
    monkeypatch.setitem(certify.DIFF_ENGINES, "wgl",
                        lambda spec, e, st, budget: {"valid": "unknown"})
    r, _ = _linear_result(invalid_sequential())
    _, diags = _certify(r, invalid_sequential(), samples=1)
    assert any(d.code == "VC011" and d.severity == "info"
               for d in diags)


# ---------------------------------------------------------------------------
# segment provenance (VC007)


def _segmented_result(test=None):
    """A planned, merged result over a sequential history: each
    segment's witness built and provenance-stamped exactly like
    checkers._check_planned does."""
    from jepsen_tpu.analysis import searchplan
    hist = _pairs([("write", i) for i in range(1, 7)])
    client = h.client_ops(h.ensure_indexed(hist))
    min_seg = 2
    segs, _info = searchplan.plan_segments(SPEC, client, min_seg)
    assert len(segs) > 1, "history failed to segment"
    wits = []
    for i, s in enumerate(segs):
        e, st = SPEC.encode(s.events)
        w = witness.build(SPEC, e, "jax-wgl", True,
                          np.ones(len(e), bool), st)
        w["segment"] = {"index": i, "count": len(segs),
                        "seed": s.seed}
        wits.append(w)
    result = {"valid": True, "engine": "jax-wgl",
              "witnesses": wits,
              "searchplan": {"segments": len(segs)}}
    t = {"searchplan-min-segment": min_seg, **(test or {})}
    return result, client, t


def test_segments_recertify_clean():
    result, client, t = _segmented_result()
    cert, diags = certify.certify_with_diagnostics(
        SPEC, client, result, test=t, samples=0)
    assert diags == [], [d.message for d in diags]
    seg_checks = [c for c in cert["checks"]
                  if c["name"].startswith("witness.segment")]
    assert len(seg_checks) == result["searchplan"]["segments"]
    assert all(c["status"] == "replayed" for c in seg_checks)


def test_vc007_segment_provenance_mismatch():
    for tamper in (
        lambda r: r["witnesses"][1]["segment"].update(seed={"f": 9}),
        lambda r: r["witnesses"][1]["segment"].update(index=0),
        lambda r: r["witnesses"].pop(),
    ):
        result, client, t = _segmented_result()
        tamper(result)
        _, diags = certify.certify_with_diagnostics(
            SPEC, client, result, test=t, samples=0)
        assert "VC007" in _codes(diags), tamper


# ---------------------------------------------------------------------------
# checker.core wiring + THE containment property


def test_check_hook_builds_certificate():
    test = {}
    r = ccore.check(Linearizable(SPEC, algorithm="linear"), test,
                    invalid_sequential())
    assert r["valid"] is False
    cert = test["certificate"]
    assert cert["schema"] == 1 and cert["verdict"] is False
    assert cert["counts"]["error"] == 0
    rep = test["analysis"]["certify"]
    assert rep["counts"]["error"] == 0
    assert rep["summary"]["verdict"] is False
    assert test["certify-done?"]


def test_check_hook_opt_out():
    test = {"certify?": False}
    ccore.check(Linearizable(SPEC, algorithm="linear"), test,
                invalid_sequential())
    assert "certificate" not in test


def test_certification_never_flips_verdict(monkeypatch):
    """THE acceptance property: a certifier crash (or a FAILING
    certification) leaves the verdict and the result untouched."""
    def boom(*a, **k):
        raise RuntimeError("certifier bug")
    monkeypatch.setattr(certify, "certify_with_diagnostics", boom)
    for hist, want in ((valid_concurrent(), True),
                       (invalid_sequential(), False)):
        test = {}
        r = ccore.check(Linearizable(SPEC, algorithm="linear"),
                        test, hist)
        assert r["valid"] is want
        assert "certificate" not in test


def test_failing_certification_reports_but_does_not_flip():
    """A certificate that FAILS (flipped witness) is recorded with VC
    errors while the returned verdict stands."""
    test = {}
    lin = Linearizable(SPEC, algorithm="linear")
    real = lin.check

    def lying_check(t, hist, opts=None):
        r = real(t, hist, opts)
        if isinstance(r.get("witness"), dict):
            r["witness"]["verdict"] = not r["witness"]["verdict"]
        return r

    lin.check = lying_check
    r = ccore.check(lin, test, invalid_sequential())
    assert r["valid"] is False  # unflipped
    assert test["certificate"]["counts"]["error"] >= 1
    assert "VC004" in {d["code"] for d in
                       test["certificate"]["diagnostics"]}


# ---------------------------------------------------------------------------
# persistence: certificate.json, byte determinism, disk re-certification


def _persisted_run(hist, name="certrun"):
    test = {"name": name, "start-time": store.local_time(),
            "history": h.ensure_indexed(hist)}
    r = ccore.check(Linearizable(SPEC, algorithm="linear"), test,
                    test["history"])
    test["results"] = r
    store.save_2(test)
    return test, store.path(test)


def test_certificate_persisted_and_byte_deterministic():
    test, run_dir = _persisted_run(invalid_sequential())
    p = os.path.join(run_dir, "certificate.json")
    b1 = open(p, "rb").read()
    store.write_certificate(test)
    assert open(p, "rb").read() == b1
    cert = json.loads(b1)
    assert cert["verdict"] is False and cert["schema"] == 1


def test_certify_run_clean_and_tampered():
    _, run_dir = _persisted_run(invalid_sequential())
    summary, diags = certify.certify_run(run_dir)
    assert summary["certified"] and diags == []

    p = os.path.join(run_dir, "certificate.json")
    cert = json.load(open(p))
    cert["verdict"] = True
    cert["witness"]["verdict"] = True
    json.dump(cert, open(p, "w"))
    _, diags = certify.certify_run(run_dir)
    codes = _codes(diags)
    assert "VC012" in codes and "VC004" in codes

    # unreadable certificate: VC012, never a crash
    open(p, "w").write("{not json")
    _, diags = certify.certify_run(run_dir)
    assert "VC012" in _codes(diags)


def test_lint_driver_certify_exit_codes(tmp_path):
    import tools.lint as tl
    _, run_dir = _persisted_run(invalid_sequential())
    assert tl.run_certify(run_dir) == 0
    p = os.path.join(run_dir, "certificate.json")
    cert = json.load(open(p))
    cert["witness"]["order"] = list(reversed(cert["witness"]["order"]))
    json.dump(cert, open(p, "w"))
    assert tl.run_certify(run_dir) == 1
    assert tl.run_certify(str(tmp_path / "nope")) == 2


def test_certify_campaign_fold():
    _, d1 = _persisted_run(invalid_sequential(), name="cella")
    _, d2 = _persisted_run(valid_concurrent(), name="cellb")
    p = os.path.join(d1, "certificate.json")
    cert = json.load(open(p))
    cert["verdict"] = True
    json.dump(cert, open(p, "w"))
    block = certify.certify_campaign(
        [{"path": d1}, {"path": d2}, {"path": "/nope"}])
    assert block["sampled"] == 2 and block["of"] == 2
    assert block["counts"]["error"] >= 1
    assert "VC012" in block["codes"]
    bad = [r for r in block["runs"] if r["path"] == d1][0]
    assert "VC012" in bad["codes"]


def _keyed_hist():
    """Key 0 clean, key 1 non-linearizable, on distinct processes."""
    from jepsen_tpu import independent as ind
    ev = []
    for k, ops in ((0, [("write", 1), ("read", 1)]),
                   (1, [("write", 1), ("write", 2), ("read", 1),
                        ("read", 2)])):
        for f, v in ops:
            ev.append({"type": "invoke", "process": k * 2, "f": f,
                       "value": ind.tuple_(k,
                                           None if f == "read" else v)})
            ev.append({"type": "ok", "process": k * 2, "f": f,
                       "value": ind.tuple_(k, v)})
    return h.ensure_indexed(ev)


def test_keyed_workload_certifies_failing_key():
    """The independent checker's batched path certifies ONE
    deterministically chosen key (the failing one), records the key in
    the certificate context, and the disk path re-derives the same
    subhistory from the reloaded [k v] history."""
    from jepsen_tpu import independent as ind
    hist = _keyed_hist()
    test = {"name": "keyed-cert", "start-time": store.local_time(),
            "history": hist}
    chk = ind.checker(Linearizable(SPEC, algorithm="jax-wgl"))
    r = ccore.check(chk, test, hist)
    assert r["valid"] is False and r["failures"] == [1]
    cert = test["certificate"]
    assert cert["context"]["key"] == 1
    assert cert["verdict"] is False
    assert cert["counts"]["error"] == 0, cert["diagnostics"]

    test["results"] = r
    store.save_2(test)
    summary, diags = certify.certify_run(store.path(test))
    assert summary["certified"] and diags == [], \
        [d.message for d in diags]


def test_keyed_fallback_path_certifies_deterministically():
    """The per-key thread-pool fallback (CPU algorithm) must certify
    the same deterministically chosen key, not whichever subcheck
    finished first."""
    from jepsen_tpu import independent as ind
    hist = _keyed_hist()
    test = {}
    chk = ind.checker(Linearizable(SPEC, algorithm="linear"))
    r = ccore.check(chk, test, hist)
    assert r["valid"] is False
    assert test["certificate"]["context"]["key"] == 1
    assert test["certificate"]["counts"]["error"] == 0
    assert test["certify-done?"] is True


# ---------------------------------------------------------------------------
# monitor backstop


def test_certify_monitor_confirms_violation():
    e, st = _encoded(invalid_sequential())
    r = linear.check_encoded(SPEC, e, st)
    assert r["valid"] is False
    ev = {"spec": SPEC, "e": e, "init_state": st, "result": r,
          "key": 3}
    summary, diags = certify.certify_monitor(ev)
    assert summary["confirmed"] is True
    assert summary["counts"]["error"] == 0
    # independence: the linear-engined monitor cross-checks via wgl
    assert any(c.get("engine") == "wgl" for c in summary["checks"]
               if c["name"] == "cross-check")
    assert summary["key"] == "3"


def test_analyze_backstop_wiring():
    test = {"results": {"valid": False},
            "monitor-evidence": {
                "spec": SPEC, **dict(zip(("e", "init_state"),
                                         _encoded(invalid_sequential()))),
                "result": {"valid": False, "engine": "linear"},
                "key": None}}
    jcore._certify_monitor_verdict(test, {"verdict": False})
    mc = test["results"]["monitor-certification"]
    assert mc["confirmed"] is True and mc["counts"]["error"] == 0
    assert "monitor-evidence" not in test
    assert test["analysis"]["certify-monitor"]["verdict"] is False


def test_analyze_backstop_contained(monkeypatch):
    def boom(*a, **k):
        raise RuntimeError("backstop bug")
    monkeypatch.setattr(certify, "certify_monitor", boom)
    test = {"results": {"valid": False},
            "monitor-evidence": {"spec": SPEC}}
    jcore._certify_monitor_verdict(test, {"verdict": False})
    assert test["results"] == {"valid": False}


def test_monitor_parks_evidence():
    """A streaming monitor that detects a violation parks certifiable
    evidence, and finalize moves it onto the test map."""
    from jepsen_tpu import monitor as jmon
    test = {"monitor": {"chunk": 1, "engine": "linear"},
            "checker": Linearizable(SPEC, algorithm="linear"),
            "model": "register"}
    mon = jmon.install(test)
    if mon is None:
        pytest.skip("monitor could not start")
    for op in h.ensure_indexed(invalid_sequential()):
        mon.offer(dict(op))
    jmon.finalize(mon, test)
    assert test["monitor-verdict"]["verdict"] is False
    ev = test.get("monitor-evidence")
    assert ev is not None and ev["result"]["valid"] is False
    summary, _ = certify.certify_monitor(ev)
    assert summary["confirmed"] is True


# ---------------------------------------------------------------------------
# service path


def test_service_check_certify_payload():
    from jepsen_tpu.fleet import service
    hist = invalid_sequential()
    payload = {"history": hist, "model": "register",
               "engine": "linear", "certify": True}
    out = service._check_admitted(payload, hist)
    assert out["valid"] is False
    c = out["certify"]
    assert c["certified"] is True and c["verdict"] is False
    assert c["counts"]["error"] == 0
    assert not any(k.startswith("_") for k in out)


def test_service_check_certify_validation():
    from jepsen_tpu.fleet import service
    hist = valid_concurrent()
    with pytest.raises(service.ApiError):
        service._check_admitted({"history": hist, "model": "register",
                                 "engine": "linear", "certify": "yes"},
                                hist)
    out = service._check_admitted({"history": hist,
                                   "model": "register",
                                   "engine": "linear"}, hist)
    assert "certify" not in out


# ---------------------------------------------------------------------------
# planlint PL023


def test_pl023_bad_knobs_are_errors():
    diags = planlint.lint_certify({"certify": {"samples": 0,
                                               "budget": -5}})
    assert [d.code for d in diags] == ["PL023", "PL023"]
    assert all(d.severity == "error" for d in diags)
    assert planlint.lint_certify({"certify": "yes"})[0].severity == \
        "error"


def test_pl023_skip_offline_backstop_note():
    diags = planlint.lint_certify(
        {"monitor": {"skip-offline?": True}})
    assert [(d.code, d.severity) for d in diags] == [("PL023", "info")]
    # opted out: the note is moot, the knobs warn
    diags = planlint.lint_certify(
        {"certify?": False, "certify": {"samples": 2},
         "monitor": {"skip-offline?": True}})
    assert [(d.code, d.severity) for d in diags] == \
        [("PL023", "warning")]


def test_pl023_rides_lint_plan():
    diags = planlint.lint_plan(
        {"name": "x", "certify": {"budget": 0}})
    assert any(d.code == "PL023" and d.severity == "error"
               for d in diags)


def test_pl023_clean():
    assert planlint.lint_certify({}) == []
    assert planlint.lint_certify(
        {"certify": {"samples": 2, "budget": 1000}}) == []


# ---------------------------------------------------------------------------
# the budget knob reaches the certifier through the test map


def test_config_defaults_and_overrides():
    assert certify.config({}) == {"samples": certify.DEFAULT_SAMPLES,
                                  "budget": certify.DEFAULT_BUDGET}
    assert certify.config({"certify": {"samples": 3,
                                       "budget": 10}}) == \
        {"samples": 3, "budget": 10}
    # junk falls back to defaults (PL023 reports it; config contains)
    assert certify.config({"certify": {"samples": True,
                                       "budget": -1}}) == \
        {"samples": certify.DEFAULT_SAMPLES,
         "budget": certify.DEFAULT_BUDGET}
    assert certify.enabled({})
    assert not certify.enabled({"certify?": False})
    assert not certify.enabled({"analysis?": False})
