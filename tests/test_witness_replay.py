"""The witness replay (checker/witness.py ``final_path``) remembers the
(placed set, model state) pairs it has seen fail: wherever the
unmemoised DFS finds an order it returns the identical order, under the
same step budget, and on contended histories it orders the deepest
configuration where the unmemoised DFS runs out of budget."""

import functools
import random

import numpy as np
import pytest

from jepsen_tpu import history as h
from jepsen_tpu import obs
from jepsen_tpu.analysis import certify
from jepsen_tpu.checker import linear, wgl, witness
from jepsen_tpu.checker.witness import _RetMin
from jepsen_tpu.models import base as mbase

SPEC = mbase.model_spec("cas-register")


# ---------------------------------------------------------------------------
# histories as Jepsen's etcd register workload draws them: 10 threads, a
# mix of read / write / cas, values 0..4, 2% of completions lost (info)

def _op(type_, process, f, value):
    return {"type": type_, "process": process, "f": f, "value": value}


def register_history(rng, n_procs, n_ops, crash_p=0.02, values=5):
    """A random concurrent schedule of ``n_ops`` cas-register ops against
    a real register: linearizable by construction (each op takes effect
    at its completion event, crashed ops included)."""
    hist, outstanding, ops_done = [], {}, 0
    state = {"v": None}

    def gen_invoke(p):
        f = rng.choice(["read", "write", "cas"])
        if f == "read":
            return _op("invoke", p, "read", None)
        if f == "write":
            return _op("invoke", p, "write", rng.randrange(values))
        return _op("invoke", p, "cas",
                   (rng.randrange(values), rng.randrange(values)))

    def apply(inv):
        f, v = inv["f"], inv["value"]
        if f == "read":
            return True, state["v"]
        if f == "write":
            state["v"] = v
            return True, v
        if state["v"] == v[0]:
            state["v"] = v[1]
            return True, v
        return False, v

    while ops_done < n_ops or outstanding:
        free = [p for p in range(n_procs) if p not in outstanding]
        if free and ops_done < n_ops and (not outstanding
                                          or rng.random() < .6):
            p = rng.choice(free)
            outstanding[p] = inv = gen_invoke(p)
            hist.append(inv)
            ops_done += 1
        else:
            p = rng.choice(list(outstanding))
            inv = outstanding.pop(p)
            took_effect, res = apply(inv)
            if rng.random() < crash_p:
                hist.append(_op("info", p, inv["f"], inv["value"]))
            elif took_effect:
                v = res if inv["f"] == "read" else inv["value"]
                hist.append(_op("ok", p, inv["f"], v))
            else:
                hist.append(_op("fail", p, inv["f"], inv["value"]))
    for i, o in enumerate(hist):
        o["index"] = i
    return hist


def flip_read(rng, hist):
    """Raise one ok read's value: the history (almost always) stops
    being linearizable."""
    hist = [dict(o) for o in hist]
    cands = [i for i, o in enumerate(hist)
             if o["type"] == "ok" and o["f"] == "read"
             and o.get("value") is not None]
    if cands:
        i = rng.choice(cands)
        hist[i]["value"] = (hist[i]["value"] or 0) + rng.randrange(1, 5)
    return hist


def case_history(seed, n_ops, flipped):
    rng = random.Random(seed)
    hist = register_history(rng, 10, n_ops)
    return flip_read(rng, hist) if flipped else hist


def contended_history():
    """200 ops (153 encoded rows), one read flipped: the `linear`
    engine's deepest configuration holds 104 ops whose orders the
    unmemoised replay cannot get through within REPLAY_BUDGET."""
    return case_history(23, 200, True)


def encode(hist):
    return SPEC.encode(h.client_ops(h.ensure_indexed(hist)))


@functools.lru_cache(maxsize=1)
def contended():
    """The contended history encoded, and the `linear` engine's result
    on it (computed once: the engine's search takes seconds)."""
    e, init_state = encode(contended_history())
    return e, init_state, linear.check_encoded(SPEC, e, init_state)


def contended_deepest():
    e, init_state, r = contended()
    lin = np.zeros(len(e), bool)
    lin[r["witness"]["linearized_rows"]] = True
    return e, init_state, lin


def deepest(engine, e, init_state):
    """The engine's deepest configuration: its witness's linearized set
    when it finds the history invalid, else every ok row."""
    r = engine.check_encoded(SPEC, e, init_state)
    linearized = np.zeros(len(e), bool)
    if r["valid"] is False:
        linearized[r["witness"]["linearized_rows"]] = True
    else:
        linearized[np.asarray(e.is_ok, bool)] = True
    return linearized


# ---------------------------------------------------------------------------
# the replay DFS as it was before dead subtrees were remembered

def unmemoised_final_path(spec, e, linearized, init_state,
                          budget=witness.REPLAY_BUDGET):
    n = len(e)
    member = np.asarray(linearized, bool)
    total = int(member.sum())
    if total == 0:
        return []
    invoke = np.asarray(e.invoke_idx, np.int64)
    rets = np.asarray(e.return_idx, np.int64)
    f = np.asarray(e.f)
    args = np.asarray(e.args).reshape(n, -1)
    rvals = np.asarray(e.ret).reshape(n, -1)
    if spec.hint is not None:
        from jepsen_tpu.checker.jax_wgl import _encode_arrays
        inv32, ret32, _ = _encode_arrays(e)
        pri = np.asarray(spec.hint(e, inv32, ret32), np.int64)
    else:
        pri = rets
    members = sorted(np.flatnonzero(member).tolist(),
                     key=lambda i: (pri[i], i))
    tree = _RetMin(rets)
    head = total
    nxt = list(range(1, total + 1)) + [0]
    prv = [head] + list(range(total))

    def remove(j):
        nxt[prv[j]] = nxt[j]
        prv[nxt[j]] = prv[j]

    def restore(j):
        nxt[prv[j]] = j
        prv[nxt[j]] = j

    path = []
    states = [np.asarray(init_state, np.int32)]
    scan = [nxt[head]]
    work = budget
    while True:
        if len(path) == total:
            return path
        j = scan[-1]
        state = states[-1]
        taken = False
        while j != head:
            work -= 1
            if work < 0:
                return None
            i = members[j]
            if invoke[i] < tree.min():
                st2, ok = spec.step(state, f[i], args[i], rvals[i], np)
                if bool(ok):
                    st2 = np.asarray(st2, np.int32)
                    tree.set_active(i, False)
                    remove(j)
                    path.append((i, st2))
                    states.append(st2)
                    scan[-1] = j
                    scan.append(nxt[head])
                    taken = True
                    break
            j = nxt[j]
        if not taken:
            scan.pop()
            states.pop()
            if not path:
                return None
            i, _ = path.pop()
            jprev = scan[-1]
            restore(jprev)
            tree.set_active(i, True)
            scan[-1] = nxt[jprev]


def _rows(path):
    return None if path is None else [int(i) for i, _ in path]


def _states(path):
    return [st.tolist() for _, st in path]


# ---------------------------------------------------------------------------
# equivalence with the unmemoised DFS

#: step attempts both DFSs get in the equivalence cases: enough for
#: the unmemoised one to order every case below, few enough to keep
#: the cases quick
EQUIV_BUDGET = 60_000

#: (seed, ops, a read flipped): 20-200 ops, valid and invalid, several
#: of them backtracking enough that the memo prunes
CASES = [(seed, (20, 50, 90, 130, 170, 200)[seed % 6], seed % 3 != 0)
         for seed in (0, 1, 2, 3, 5, 6, 14, 16, 17, 22, 32, 39, 43, 50,
                      53)]


@pytest.mark.parametrize("seed,n_ops,flipped", CASES)
def test_same_order_as_unmemoised_dfs(seed, n_ops, flipped):
    e, init_state = encode(case_history(seed, n_ops, flipped))
    for engine in (linear, wgl):
        lin = deepest(engine, e, init_state)
        ref = unmemoised_final_path(SPEC, e, lin, init_state,
                                    budget=EQUIV_BUDGET)
        assert ref is not None
        got = witness.final_path(SPEC, e, lin, init_state,
                                 budget=EQUIV_BUDGET)
        assert _rows(got) == _rows(ref)
        assert _states(got) == _states(ref)
        assert _rows(witness.final_path(SPEC, e, lin, init_state)) \
            == _rows(ref)


def test_orders_where_unmemoised_dfs_runs_out_of_budget():
    e, init_state, r = contended()
    assert len(e) == 153 and r["valid"] is False
    _, _, lin = contended_deepest()
    assert unmemoised_final_path(SPEC, e, lin, init_state) is None

    w = r["witness"]
    assert w["order"] is not None
    assert sorted(w["order"]) == w["linearized_rows"]
    checks = []
    diags = certify._witness_diags(SPEC, e, init_state, w, False, checks)
    assert not [d for d in diags if d.severity == "error"]
    assert "VC009" not in {d.code for d in diags}
    assert checks == [{"name": "witness", "status": "replayed",
                       "steps": len(w["order"])}]


def test_budget_one_returns_none():
    e, init_state, lin = contended_deepest()
    assert witness.final_path(SPEC, e, lin, init_state, budget=1) is None
    path, stats = witness._replay(SPEC, e, lin, init_state, 1)
    assert path is None
    assert stats["outcome"] == "budget" and stats["attempts"] == 1


def test_exhausted_when_no_order_exists():
    # w1 then r=2, sequential: no legal order of the pair
    hist = [{"index": 0, "type": "invoke", "process": 0, "f": "write",
             "value": 1},
            {"index": 1, "type": "ok", "process": 0, "f": "write",
             "value": 1},
            {"index": 2, "type": "invoke", "process": 0, "f": "read",
             "value": None},
            {"index": 3, "type": "ok", "process": 0, "f": "read",
             "value": 2}]
    e, init_state = encode(hist)
    path, stats = witness._replay(SPEC, e, np.ones(len(e), bool),
                                  init_state, witness.REPLAY_BUDGET)
    assert path is None and stats["outcome"] == "exhausted"


def test_memo_cap_stops_entries_and_keeps_the_result(monkeypatch):
    e, init_state = encode(case_history(50, 90, True))
    lin = deepest(linear, e, init_state)
    ref = unmemoised_final_path(SPEC, e, lin, init_state,
                                budget=EQUIV_BUDGET)
    free_path, free = witness._replay(SPEC, e, lin, init_state,
                                      EQUIV_BUDGET)
    assert free["pruned"] > 0 and free["memo_entries"] > 20
    entry = (int(lin.sum()) + 7) // 8 \
        + np.asarray(init_state, np.int32).nbytes \
        + witness.REPLAY_MEMO_ENTRY_OVERHEAD
    assert free["memo_bytes"] == free["memo_entries"] * entry

    cap = free["memo_entries"] // 2
    monkeypatch.setattr(witness, "REPLAY_MEMO_BYTES", cap * entry + 1)
    capped_path, capped = witness._replay(SPEC, e, lin, init_state,
                                          EQUIV_BUDGET)
    assert capped["memo_entries"] == cap
    assert capped["memo_bytes"] == cap * entry
    assert capped["attempts"] >= free["attempts"]

    # no room at all: the plain DFS
    monkeypatch.setattr(witness, "REPLAY_MEMO_BYTES", 0)
    bare_path, bare = witness._replay(SPEC, e, lin, init_state,
                                      EQUIV_BUDGET)
    assert bare["memo_entries"] == 0 and bare["pruned"] == 0
    assert bare["attempts"] >= capped["attempts"]
    assert ref is not None
    assert _rows(free_path) == _rows(capped_path) == _rows(bare_path) \
        == _rows(ref)


# ---------------------------------------------------------------------------
# counters and span

def _concurrent_writes(n):
    """``n`` writes all invoked before any returns, each reading back
    nothing: every order is legal, so the replay never backtracks."""
    ev = [{"type": "invoke", "process": p, "f": "write", "value": p % 5}
          for p in range(n)]
    ev += [{"type": "ok", "process": p, "f": "write", "value": p % 5}
           for p in range(n)]
    for i, o in enumerate(ev):
        o["index"] = i
    return ev


def test_counters_and_span_for_a_64_member_replay():
    e, init_state = encode(_concurrent_writes(64))
    lin = np.ones(len(e), bool)
    tr, reg = obs.Tracer(), obs.Registry()
    with obs.sink_scope(tr, reg):
        path = witness.final_path(SPEC, e, lin, init_state)
        assert witness.final_path(SPEC, e, lin, init_state,
                                  budget=1) is None
    assert len(path) == 64
    assert reg.counter_value("witness.replays", outcome="ordered") == 1
    assert reg.counter_value("witness.replays", outcome="budget") == 1
    assert reg.counter_value("witness.replay_attempts") == 64 + 1
    assert reg.counter_value("witness.replay_pruned") == 0
    spans = [ev for ev in tr.events() if ev.get("name") == "witness.replay"]
    assert [(ev["cat"], ev["args"]["members"], ev["args"]["ordered"])
            for ev in spans] == [("witness", 64, True),
                                 ("witness", 64, False)]


def test_pruned_counter_and_no_span_for_a_short_replay():
    e, init_state, lin = contended_deepest()
    tr, reg = obs.Tracer(), obs.Registry()
    with obs.sink_scope(tr, reg):
        path = witness.final_path(SPEC, e, lin, init_state)
        # a prefix of a legal order is a legal order of its own set
        prefix = np.zeros(len(e), bool)
        prefix[_rows(path)[:10]] = True
        assert witness.final_path(SPEC, e, prefix, init_state)
    _, stats = witness._replay(SPEC, e, lin, init_state,
                               witness.REPLAY_BUDGET)
    assert reg.counter_value("witness.replays", outcome="ordered") == 2
    assert reg.counter_value("witness.replay_pruned") == stats["pruned"] > 0
    spans = [ev for ev in tr.events() if ev.get("name") == "witness.replay"]
    assert [ev["args"]["members"] for ev in spans] == [int(lin.sum())]
