"""Tests for jepsen_tpu.independent: tuples, sequential/concurrent
generators, and the per-key checker with its batched device fast path
(reference independent.clj + independent_test.clj semantics)."""

import pytest

from jepsen_tpu import checker as cc
from jepsen_tpu import generator as gen
from jepsen_tpu import history as h
from jepsen_tpu import independent
from jepsen_tpu.checker import checkers as ck
from jepsen_tpu.generator import testing as gt

inv = h.invoke_op
ok = h.ok_op
T = independent.tuple_


def test_tuple():
    t = T("k", 5)
    assert independent.is_tuple(t)
    assert t.key == "k" and t.value == 5
    assert not independent.is_tuple(("k", 5))
    assert not independent.is_tuple([1, 2])
    assert list(t) == ["k", 5]   # serializes like a 2-list


def test_sequential_generator():
    g = independent.sequential_generator(
        [0, 1], lambda k: gen.limit(2, gen.repeat({"f": "w", "value": "x"})))
    hist = [o for o in gt.quick(gen.clients(g)) if h.invoke(o)]
    vals = [o["value"] for o in hist]
    assert vals == [T(0, "x"), T(0, "x"), T(1, "x"), T(1, "x")]


def test_history_keys_and_subhistory():
    hist = [
        inv(0, "w", T("a", 1)),
        h.op("info", "nemesis", "start", "whoops"),
        ok(0, "w", T("a", 1)),
        inv(1, "w", T("b", 2)),
        ok(1, "w", T("b", 2)),
    ]
    assert independent.history_keys(hist) == {"a", "b"}
    sub = independent.subhistory("a", hist)
    # unkeyed nemesis op appears; key b's ops don't; values unwrapped
    assert [o.get("value") for o in sub] == [1, "whoops", 1]


def _nem(f):
    return h.op("info", "nemesis", f, None)


_SPLIT_CASES = {
    "unkeyed-before-between-after": (
        [_nem("start"), inv(0, "w", T("a", 1)), _nem("kill"),
         inv(1, "w", T("b", 2)), ok(0, "w", T("a", 1)), _nem("heal"),
         ok(1, "w", T("b", 2)), _nem("stop")], None),
    "no-unkeyed": (
        [inv(0, "w", T("a", 1)), inv(1, "w", T("b", 2)),
         ok(1, "w", T("b", 2)), ok(0, "w", T("a", 1))], None),
    "one-key": (
        [_nem("start"), inv(0, "w", T(7, 1)), ok(0, "w", T(7, 1))], None),
    "mixed-key-types": (
        [inv(0, "w", T(10, 1)), inv(1, "w", T("x", 2)), _nem("kill"),
         inv(2, "w", T(2, 3)), inv(3, "w", T((1, "y"), 4)),
         ok(2, "w", T(2, 3)), ok(0, "w", T(10, 1))], None),
    "plain-2-list-stays-unkeyed": (
        [inv(0, "w", T("a", 1)), inv(1, "w", ["b", 2]),
         ok(1, "w", ["b", 2]), ok(0, "w", T("a", 1))], None),
    "key-subset-drops-others": (
        [inv(0, "w", T("a", 1)), _nem("kill"), inv(1, "w", T("b", 2)),
         inv(2, "w", T("c", 3)), ok(1, "w", T("b", 2)),
         ok(2, "w", T("c", 3)), ok(0, "w", T("a", 1))], ["c", "a"]),
}


@pytest.mark.parametrize("case", sorted(_SPLIT_CASES))
def test_split_equals_subhistory_per_key(case):
    hist, keys = _SPLIT_CASES[case]
    if keys is None:
        keys = sorted(independent.history_keys(hist), key=repr)
    got = independent.split(hist, keys)
    assert list(got) == list(keys)
    assert got == {k: independent.subhistory(k, hist) for k in keys}
    events = {id(o) for o in hist}
    unkeyed = {id(o) for o in hist
               if not independent.is_tuple(o.get("value"))}
    for sub in got.values():
        # un-keyed events are shared, keyed ones are unwrapped copies
        assert all((id(o) in unkeyed) == (id(o) in events) for o in sub)
        assert sum(id(o) in unkeyed for o in sub) == len(unkeyed)


class _CountingHistory(list):
    """A history that counts the walks over it."""
    walks = 0

    def __iter__(self):
        self.walks += 1
        return super().__iter__()


@pytest.mark.parametrize("n_keys", [1, 16, 256])
def test_split_walks_history_at_most_twice(n_keys):
    events = [_nem("start")]
    for k in range(n_keys):
        events += [inv(k, "w", T(k, 1)), ok(k, "w", T(k, 1))]
    hist = _CountingHistory(events)
    ks = sorted(independent.history_keys(hist), key=repr)
    subs = independent.split(hist, ks)
    assert hist.walks <= 2
    assert len(subs) == n_keys
    assert all(len(sub) == 3 for sub in subs.values())


def test_per_key_parts_none_when_unkeyed():
    from jepsen_tpu.analysis import searchplan
    hist = [inv(0, "w", 1), ok(0, "w", 1), _nem("kill"),
            inv(1, "w", ["a", 2]), ok(1, "w", ["a", 2])]
    assert searchplan.per_key_parts(hist) is None
    keyed = hist + [inv(2, "w", T("b", 3)), ok(2, "w", T("b", 3))]
    parts = searchplan.per_key_parts(keyed)
    assert list(parts) == ["b"]
    assert parts["b"] == independent.subhistory("b", keyed)


def test_concurrent_generator_groups_and_rotation():
    """2 threads per key over 4 worker threads: two keys in flight;
    exhausted groups rotate to fresh keys (independent.clj:103-236)."""
    n_per_key = 2
    g = independent.concurrent_generator(
        n_per_key, range(10),
        lambda k: gen.limit(3, gen.repeat({"f": "w", "value": k})))
    test = {"concurrency": 4, "nodes": ["n1", "n2"]}
    hist = gt.simulate(test, g, gt.perfect)
    invs = [o for o in hist if h.invoke(o)]
    # every op carries a tuple value wrapping its key
    assert all(independent.is_tuple(o["value"]) for o in invs)
    by_key = {}
    for o in invs:
        by_key.setdefault(o["value"].key, []).append(o)
    # each key gets exactly its 3 ops, all 10 keys eventually run
    assert set(by_key) == set(range(10))
    assert all(len(ops) == 3 for ops in by_key.values())
    # each key is executed by exactly one group of n threads
    for k, ops in by_key.items():
        assert len({o["process"] % 4 for o in ops}) <= n_per_key
    # two keys genuinely interleave at the start (two groups in parallel)
    first8 = [o["value"].key for o in invs[:8]]
    assert len(set(first8)) >= 2


def test_concurrent_generator_concurrency_assertion():
    g = independent.concurrent_generator(
        8, [0], lambda k: gen.once({"f": "w"}))
    test = {"concurrency": 4, "nodes": ["n1"]}
    with pytest.raises(Exception, match="concurrency"):
        gt.simulate(test, g, gt.perfect)


def _keyed_history(keys, bad_keys=()):
    """Valid (or corrupted) per-key cas-register histories interleaved."""
    hist = []
    for i, k in enumerate(keys):
        p = i % 3
        hist += [
            inv(p, "write", T(k, 1)),
            ok(p, "write", T(k, 1)),
            inv(p, "read", T(k, None)),
            ok(p, "read", T(k, 99 if k in bad_keys else 1)),
        ]
    return hist


def test_independent_checker_splits_and_merges():
    c = independent.checker(ck.linearizable({"model": "cas-register",
                                             "algorithm": "wgl"}))
    r = cc.check(c, {}, _keyed_history(["a", "b", "c"], bad_keys={"b"}))
    assert r["valid"] is False
    assert r["failures"] == ["b"]
    assert r["results"]["a"]["valid"] is True
    assert r["results"]["b"]["valid"] is False
    assert r["results"]["c"]["valid"] is True


def test_independent_checker_all_valid():
    c = independent.checker(ck.linearizable({"model": "cas-register",
                                             "algorithm": "wgl"}))
    r = cc.check(c, {}, _keyed_history(list(range(4))))
    assert r["valid"] is True
    assert r["failures"] == []


def test_independent_batched_single_device_call(monkeypatch):
    """With a device-engine Linearizable inner checker, ALL keys go to
    parallel.check_batch_encoded in ONE call (the TPU fast path)."""
    from jepsen_tpu import parallel

    calls = []
    real = parallel.check_batch_encoded

    def counting(spec, pairs, **kw):
        calls.append(len(pairs))
        return real(spec, pairs, **kw)

    monkeypatch.setattr(parallel, "check_batch_encoded", counting)
    c = independent.checker(ck.linearizable({"model": "cas-register",
                                             "algorithm": "jax-wgl"}))
    keys = list(range(6))
    r = cc.check(c, {}, _keyed_history(keys, bad_keys={2, 4}))
    assert calls == [6]        # one batched call for all six keys
    assert r["valid"] is False
    assert sorted(r["failures"]) == [2, 4]
    for k in keys:
        assert r["results"][k]["valid"] is (k not in (2, 4))


def test_independent_batched_through_compose(monkeypatch):
    """The register workload wraps Linearizable in a compose with
    timeline; the fast path must still batch the linearizable member and
    run the other members per key."""
    from jepsen_tpu import parallel
    from jepsen_tpu.checker import timeline

    calls = []
    real = parallel.check_batch_encoded

    def counting(spec, pairs, **kw):
        calls.append(len(pairs))
        return real(spec, pairs, **kw)

    monkeypatch.setattr(parallel, "check_batch_encoded", counting)
    c = independent.checker(cc.compose({
        "linearizable": ck.linearizable({"model": "cas-register",
                                         "algorithm": "jax-wgl"}),
        "timeline": timeline.html(),
    }))
    keys = ["a", "b", "c"]
    r = cc.check(c, {}, _keyed_history(keys, bad_keys={"b"}))
    assert calls == [3]
    assert r["valid"] is False
    assert r["failures"] == ["b"]
    for k in keys:
        kr = r["results"][k]
        assert kr["linearizable"]["valid"] is (k != "b")
        assert kr["timeline"]["valid"] is True
        assert kr["valid"] is (k != "b")


def test_independent_nonlinearizable_inner_uses_pmap():
    """A non-Linearizable inner checker goes through the per-key path."""
    seen = []

    class Probe(cc.Checker):
        def check(self, test, hist, opts=None):
            seen.append(opts.get("history-key"))
            return {"valid": True}

    c = independent.checker(Probe())
    r = cc.check(c, {}, _keyed_history(["x", "y"]))
    assert r["valid"] is True
    assert sorted(seen) == ["x", "y"]


def test_independent_per_key_store_files(tmp_path, monkeypatch):
    from jepsen_tpu import store
    monkeypatch.setattr(store, "base_dir", str(tmp_path))
    test = {"name": "indy", "start-time": "20260729T000000.000000+0000",
            "nodes": []}
    c = independent.checker(ck.linearizable({"model": "cas-register",
                                             "algorithm": "wgl"}))
    cc.check(c, test, _keyed_history(["a"]))
    import os
    d = store.path(test, independent.DIR, "a")
    assert sorted(os.listdir(d)) == ["history.txt", "results.json"]


def _hard_keyed_history(keys):
    """Per-key ~150-op corrupt-but-in-range cas histories (the search,
    not the state abstraction, must decide them), values wrapped in
    independent tuples with disjoint per-key processes."""
    import random as _r

    from jepsen_tpu.simulate import corrupt, random_history
    hist = []
    idx = 0
    for k in keys:
        h = corrupt(_r.Random(100 + k),
                    random_history(_r.Random(k), "cas-register", 6, 150,
                                   0.05))
        for o in h:
            if o["type"] == "ok" and o["f"] == "read" \
                    and o.get("value") is not None:
                o["value"] = o["value"] % 4
        for o in h:
            o = dict(o)
            o["process"] = o["process"] + 10 * k
            o["value"] = T(k, o.get("value"))
            o["index"] = idx
            idx += 1
            hist.append(o)
    return hist


def test_direct_and_batched_paths_filter_identically():
    """VERDICT r4 weak #7: the direct Linearizable.check and the batched
    independent path must select the same client ops (one shared
    history.client_ops), so a nemesis-laced history with init ops gets
    identical verdicts on both paths."""
    keys = ["a", "b", "c"]
    hist = _keyed_history(keys, bad_keys={"b"})
    # lace with nemesis ops and a non-client log-ish op (string process)
    laced = [h.op("info", "nemesis", "start-partition", "part")]
    for i, o in enumerate(hist):
        laced.append(o)
        if i % 3 == 0:
            laced.append(h.op("info", "nemesis", "kill", None))
    laced.append(h.op("info", "logger", "snarf", "n1.log"))
    opts = {"model": "cas-register", "init-ops": [{"f": "write",
                                                   "value": 1}]}
    batched = cc.check(
        independent.checker(ck.linearizable({**opts,
                                             "algorithm": "jax-wgl"})),
        {}, laced)
    for k in keys:
        direct = cc.check(ck.linearizable({**opts, "algorithm": "wgl"}),
                          {}, independent.subhistory(k, laced))
        assert batched["results"][k]["valid"] == direct["valid"], k
    assert batched["failures"] == ["b"]


def test_independent_engine_opts_checkpoint_flows_through(tmp_path,
                                                          monkeypatch):
    """engine_opts reach the batched device call: a checkpoint path set
    on the inner linearizable checker produces a batch snapshot when the
    check is interrupted, and a rerun resumes it (the documented
    long-run resume surface)."""
    import os

    from jepsen_tpu import parallel

    # assert the BATCHED path actually ran: the silent per-key fallback
    # would also write checkpoints and mask a broken batched call
    calls = []
    real = parallel.check_batch_encoded

    def counting(spec, pairs, **kw):
        calls.append((len(pairs), kw.get("checkpoint")))
        return real(spec, pairs, **kw)

    monkeypatch.setattr(parallel, "check_batch_encoded", counting)

    ck_path = str(tmp_path / "indep.npz")
    keys = list(range(4))
    c = independent.checker(ck.linearizable(
        {"model": "cas-register", "algorithm": "jax-wgl",
         "engine_opts": {"checkpoint": ck_path, "timeout_s": 0,
                         "chunk_iters": 1, "checkpoint_every_s": 0}}))
    r = cc.check(c, {}, _hard_keyed_history(keys))
    # the search planner may slice keys into more than one segment per
    # key: at least one pair per key, and the checkpoint path must
    # reach the batch either way
    assert calls and calls[0][0] >= 4 and calls[0][1] == ck_path
    # interrupted: some keys unknown, snapshot on disk
    assert os.path.exists(ck_path)
    assert any(res["valid"] == "unknown"
               for res in r["results"].values())
    # rerun with full budget: resumes and decides everything
    c2 = independent.checker(ck.linearizable(
        {"model": "cas-register", "algorithm": "jax-wgl",
         "engine_opts": {"checkpoint": ck_path}}))
    r2 = cc.check(c2, {}, _hard_keyed_history(keys))
    assert all(res["valid"] in (True, False)
               for res in r2["results"].values())
    assert not os.path.exists(ck_path)
