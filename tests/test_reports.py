"""Clock-skew plot and linearizability witness rendering tests
(reference checker/clock.clj; checker.clj:206-212 linear.svg)."""

import os

import pytest

from jepsen_tpu import store
from jepsen_tpu.checker import checkers as ck
from jepsen_tpu.checker import clock as cclock
from jepsen_tpu.checker import linear_report


@pytest.fixture(autouse=True)
def store_tmpdir(tmp_path, monkeypatch):
    monkeypatch.setattr(store, "base_dir", str(tmp_path / "store"))


def _test_map():
    return {"name": "clocky", "start-time": "20260730T000000.000000+0000",
            "nodes": ["n1.foo.com", "n2.foo.com"]}


SEC = 1_000_000_000


def test_history_datasets_and_short_names():
    hist = [
        {"type": "info", "process": "nemesis", "f": "bump", "time": 1 * SEC,
         "clock_offsets": {"n1.foo.com": 0.5, "n2.foo.com": -0.25}},
        {"type": "info", "process": "nemesis", "f": "reset",
         "time": 3 * SEC, "clock_offsets": {"n1.foo.com": 0.0}},
        {"type": "ok", "process": 0, "f": "read", "time": 4 * SEC},
    ]
    ds = cclock.history_datasets(hist)
    assert ds["n1.foo.com"] == [(1.0, 0.5), (3.0, 0.0), (4.0, 0.0)]
    assert ds["n2.foo.com"] == [(1.0, -0.25), (4.0, -0.25)]
    assert cclock.short_node_names(["n1.foo.com", "n2.foo.com"]) == \
        ["n1", "n2"]
    assert cclock.short_node_names(["solo"]) == ["solo"]


def test_clock_plot_writes_png():
    test = _test_map()
    hist = [
        {"type": "info", "process": "nemesis", "f": "bump", "time": 1 * SEC,
         "clock_offsets": {"n1.foo.com": 2.0, "n2.foo.com": -1.0}},
        {"type": "info", "process": "nemesis", "f": "reset",
         "time": 5 * SEC,
         "clock_offsets": {"n1.foo.com": 0.0, "n2.foo.com": 0.0}},
    ]
    r = cclock.clock_plot().check(test, hist)
    assert r["valid"] is True
    assert os.path.exists(store.path(test, "clock-skew.png"))


def test_clock_plot_no_data_no_file():
    test = _test_map()
    r = cclock.clock_plot().check(test, [{"type": "ok", "process": 0,
                                          "f": "read", "time": 0}])
    assert r["valid"] is True
    assert not os.path.exists(os.path.join(store.base_dir, "clocky"))


def _invalid_register_history():
    """Write 1 completes, then a read sees 2: not linearizable."""
    ms = 1_000_000
    return [
        {"type": "invoke", "process": 0, "f": "write", "value": 1,
         "time": 0, "index": 0},
        {"type": "ok", "process": 0, "f": "write", "value": 1,
         "time": 1 * ms, "index": 1},
        {"type": "invoke", "process": 1, "f": "read", "value": None,
         "time": 2 * ms, "index": 2},
        {"type": "ok", "process": 1, "f": "read", "value": 2,
         "time": 3 * ms, "index": 3},
    ]


def test_linearizable_failure_renders_witness():
    test = _test_map()
    checker = ck.linearizable({"model": "register", "algorithm": "wgl"})
    res = checker.check(test, _invalid_register_history())
    assert res["valid"] is False
    p = store.path(test, "linear.png")
    assert os.path.exists(p)
    assert os.path.getsize(p) > 1000


def test_device_engine_failures_render_into_each_keys_subdirectory():
    """Two invalid keys checked by the device engine, as independent's
    per-key path checks them: each key's linear.png lands in its own
    subdirectory, where the caller's options put it."""
    test = _test_map()
    checker = ck.linearizable({"model": "register",
                               "algorithm": "jax-wgl"})
    for k in (0, 1):
        res = checker.check(test, _invalid_register_history(),
                            {"subdirectory": ["independent", k]})
        assert res["valid"] is False
    for k in (0, 1):
        p = store.path(test, "independent", k, "linear.png")
        assert os.path.getsize(p) > 1000
    assert not os.path.exists(store.path(test, "linear.png"))


def test_render_analysis_returns_none_without_witness():
    assert linear_report.render_analysis(
        _test_map(), _invalid_register_history(), {"valid": False}) is None


def test_linearizable_valid_renders_nothing():
    test = _test_map()
    hist = _invalid_register_history()
    hist[3] = dict(hist[3], value=1)
    checker = ck.linearizable({"model": "register", "algorithm": "wgl"})
    res = checker.check(test, hist)
    assert res["valid"] is True
    assert not os.path.exists(store.path(test, "linear.png"))
