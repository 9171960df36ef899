"""Idle gaps put down to program spans (``trace/attribute.py``) and the
readers of the four metrics built on it."""

import gzip
import json
import os

import pytest

from benchmark import harness
from benchmark.trace import attribute, profile

HERE = os.path.dirname(os.path.abspath(__file__))
TRIMMED = os.path.join(HERE, "data", "trace_long_v5e.json.gz")
MS = 1_000_000

HOST = [("bench.window", 0, 100 * MS),
        ("handoff", 0, 10 * MS), ("check", 10 * MS, 90 * MS)]
DEVICE = {"/device:TPU:0": [("fusion.1", 20 * MS, 10 * MS),
                            ("fusion.2", 60 * MS, 10 * MS)]}
#: idle 0-20, 30-60, 70-100 ms
PROGRAM = [("independent.split", 12 * MS, 13 * MS),      # 12-25
           ("analysis.certify", 35 * MS, 20 * MS),       # 35-55
           ("certify.cross_check", 40 * MS, 10 * MS),    # 40-50, inner
           ("wgl.phase.host", 70 * MS, 10 * MS),         # 70-80
           ("wgl.phase.d2h", 95 * MS, 20 * MS)]          # past the end


def _ms(gaps):
    return [(n, round(s * 1e3, 6)) for n, s in gaps]


def test_gaps_cut_at_span_edges_innermost_label_wins():
    r = attribute.reduce_events(DEVICE, HOST, PROGRAM)
    assert _ms(r.gaps) == [
        ("handoff", 12.0),               # uncovered: the harness label
        ("independent.split", 8.0),
        ("check", 5.0),
        ("analysis.certify", 5.0),
        ("certify.cross_check", 10.0),   # nested: the innermost wins
        ("analysis.certify", 5.0),
        ("check", 5.0),
        ("wgl.phase.host", 10.0),
        ("check", 15.0),
        ("wgl.phase.d2h", 5.0)]          # clipped at the window's end
    base = profile.reduce_events(DEVICE, HOST)
    assert (r.window_s, r.busy_s, r.device_ops) == (
        base.window_s, base.busy_s, base.device_ops)
    assert sum(s for _, s in r.gaps) == pytest.approx(
        sum(s for _, s in base.gaps))
    assert r.idle_named_s == pytest.approx(0.043)
    assert {k: round(v * 1e3, 6) for k, v in r.program_s.items()} == {
        "independent.split": 13.0, "analysis.certify": 20.0,
        "certify.cross_check": 10.0, "wgl.phase.host": 10.0,
        "wgl.phase.d2h": 5.0}
    assert r.named_s == pytest.approx(0.048)


def test_no_program_spans_gives_todays_result():
    base = profile.reduce_events(DEVICE, HOST)
    assert attribute.reduce_events(DEVICE, HOST) == base
    assert attribute.reduce_events(DEVICE, HOST, []) == base
    assert attribute.reduce_events({}, HOST, PROGRAM) is None
    # spans wholly outside the window name nothing
    r = attribute.reduce_events(DEVICE, HOST,
                                [("wgl.phase.host", 200 * MS, MS)])
    assert r.gaps == base.gaps and r.program_s == {}
    if os.path.exists(TRIMMED):
        with gzip.open(TRIMMED, "rt") as f:
            rec = json.load(f)
        device = {k: [tuple(e) for e in v]
                  for k, v in rec["device"].items()}
        host = [tuple(e) for e in rec["host"]]
        assert attribute.reduce_events(device, host) == \
            profile.reduce_events(device, host)


def test_program_spans_take_host_work_only():
    mapped = [("wgl.phase.host", "phase", 1, 2),
              ("independent.split", "dispatch", 3, 4),
              ("certify.differential", "analysis", 5, 6),
              ("bench.window", "anchor", 0, 9),
              ("checker.linearizable", "checker", 7, 8)]
    assert attribute.program_spans(mapped) == [
        ("wgl.phase.host", 1, 2), ("independent.split", 3, 4),
        ("certify.differential", 5, 6)]
    assert attribute.program_spans(None) == []


class _Run:
    def __init__(self, trace):
        self.trace = trace


def _reader(name):
    return harness.load_module(
        os.path.join(HERE, "..", "metrics", name + ".py"),
        "bench_metric_" + name.replace(".", "_"))


@pytest.mark.parametrize("name,want", [
    ("dispatch.split_share", 0.13),
    ("dispatch.prepare_share", None),     # no such span in the run
    ("dispatch.certify_share", 0.20),
    ("device.idle_attributed_share", 0.043 / 0.080)])
def test_metric_readers_on_a_synthetic_run(name, want):
    read = _reader(name).read
    got = read(_Run(attribute.reduce_events(DEVICE, HOST, PROGRAM)))
    assert got == (pytest.approx(want) if want is not None else None)
    # a run without program spans, or without a trace, reads nothing
    assert read(_Run(profile.reduce_events(DEVICE, HOST))) is None
    assert read(_Run(None)) is None
