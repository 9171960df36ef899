import random, os
from jepsen_tpu.checker import jax_wgl
from jepsen_tpu.models import cas_register_spec
from jepsen_tpu.simulate import corrupt, random_history


def test_checkpoint_resume(tmp_path):
    rng = random.Random(45100)
    hist = random_history(rng, "cas-register", 6, 120, 0.05)
    e, st = cas_register_spec.encode(hist)
    ck = str(tmp_path / "frontier.npz")
    # fresh full run for the expected verdict
    want = jax_wgl.check_encoded(cas_register_spec, e, st)
    # interrupted run: tiny chunks + instant timeout -> snapshot written
    r1 = jax_wgl.check_encoded(cas_register_spec, e, st, chunk_iters=1,
                               timeout_s=0, checkpoint=ck)
    assert r1["valid"] == "unknown" and r1["error"] == "timeout"
    assert os.path.exists(ck)
    # resumed run completes from the snapshot and agrees, then cleans up
    r2 = jax_wgl.check_encoded(cas_register_spec, e, st, chunk_iters=1,
                               checkpoint=ck)
    assert r2["valid"] == want["valid"]
    assert r2["iterations"] >= r1["iterations"]
    assert not os.path.exists(ck)


def test_checkpoint_fingerprint_mismatch_ignored(tmp_path):
    rng = random.Random(45100)
    h1 = random_history(rng, "cas-register", 4, 40, 0.0)
    h2 = random_history(rng, "cas-register", 4, 40, 0.0)
    e1, st1 = cas_register_spec.encode(h1)
    e2, st2 = cas_register_spec.encode(h2)
    ck = str(tmp_path / "frontier.npz")
    r = jax_wgl.check_encoded(cas_register_spec, e1, st1, chunk_iters=1,
                              timeout_s=0, checkpoint=ck)
    assert os.path.exists(ck)
    # a different history must not resume from this snapshot
    r2 = jax_wgl.check_encoded(cas_register_spec, e2, st2, checkpoint=ck)
    assert r2["valid"] in (True, False)


def test_checkpoint_kept_on_budget_exhaustion(tmp_path):
    """An undecided max-configs run keeps its snapshot so a bigger-budget
    rerun resumes instead of restarting."""
    rng = random.Random(2)
    # corrupt: the rollout cannot decide an invalid history in one
    # iteration, so the tiny budget genuinely exhausts
    hist = corrupt(rng, random_history(rng, "cas-register", 6, 120, 0.05))
    e, st = cas_register_spec.encode(hist)
    ck = str(tmp_path / "frontier.npz")
    r1 = jax_wgl.check_encoded(cas_register_spec, e, st, chunk_iters=1,
                               max_configs=1, checkpoint=ck)
    assert r1["valid"] == "unknown"
    assert os.path.exists(ck)
    assert r1.get("checkpoint") == ck
    r2 = jax_wgl.check_encoded(cas_register_spec, e, st, checkpoint=ck)
    assert r2["valid"] in (True, False)
    assert not os.path.exists(ck)


def test_checkpoint_of_other_check_preserved(tmp_path):
    """A run pointed at another check's snapshot must not destroy it."""
    rng = random.Random(4)
    # corrupt: an undecided-after-one-iteration run is what leaves a
    # snapshot behind (valid histories now decide via the rollout).
    # Clamp the corrupted read back into the written 0-3 range so the
    # state-abstraction pre-check can't decide it without searching.
    h1 = corrupt(rng, random_history(rng, "cas-register", 6, 120, 0.05))
    for o in h1:
        if o["type"] == "ok" and o["f"] == "read" \
                and o.get("value") is not None:
            o["value"] = o["value"] % 4
    h2 = random_history(rng, "cas-register", 4, 40, 0.0)
    e1, st1 = cas_register_spec.encode(h1)
    e2, st2 = cas_register_spec.encode(h2)
    ck = str(tmp_path / "frontier.npz")
    jax_wgl.check_encoded(cas_register_spec, e1, st1, chunk_iters=1,
                          timeout_s=0, checkpoint=ck)
    before = open(ck, "rb").read()
    # a different decided check at the same path: snapshot untouched
    r = jax_wgl.check_encoded(cas_register_spec, e2, st2, checkpoint=ck)
    assert r["valid"] in (True, False)
    assert open(ck, "rb").read() == before
    # resuming the original still works
    r1 = jax_wgl.check_encoded(cas_register_spec, e1, st1, checkpoint=ck)
    assert r1["valid"] in (True, False)


def test_checkpoint_fingerprint_covers_init_state(tmp_path):
    rng = random.Random(4)
    hist = random_history(rng, "cas-register", 4, 40, 0.0)
    e, st = cas_register_spec.encode(hist)
    ck = str(tmp_path / "frontier.npz")
    jax_wgl.check_encoded(cas_register_spec, e, st, chunk_iters=1,
                          timeout_s=0, checkpoint=ck)
    import numpy as np
    st2 = np.asarray(st).copy()
    st2[0] = st2[0] + 1
    # different init state: must not resume the stale frontier
    r = jax_wgl.check_encoded(cas_register_spec, e, st2)
    ck2 = str(tmp_path / "other.npz")
    r2 = jax_wgl.check_encoded(cas_register_spec, e, st2, checkpoint=ck2)
    assert r2["valid"] == r["valid"]


def test_batch_checkpoint_resume(tmp_path):
    """The batched keyshard path checkpoints mid-run: a timed-out
    multi-key check leaves a snapshot carrying the compacted frontier
    AND the already-decided keys; a rerun with the same arguments
    resumes and agrees with an uncheckpointed run (round-2 weak #5)."""
    import numpy as np
    from jepsen_tpu.parallel import check_batch_encoded

    rng = random.Random(7)
    hists = []
    for k in range(6):
        h = random_history(rng, "cas-register", 8, 150, 0.05)
        if k % 2 == 1:
            h = corrupt(rng, h)
            # clamp the corrupt read into the written range so the
            # state-abstraction pre-check can't decide it: these keys
            # must reach the search (and often exhaust slowly)
            for o in h:
                if o["type"] == "ok" and o["f"] == "read" \
                        and o.get("value") is not None:
                    o["value"] = o["value"] % 4
        hists.append(h)
    pairs = [cas_register_spec.encode(h) for h in hists]
    ck = str(tmp_path / "batch.npz")

    want = check_batch_encoded(cas_register_spec, pairs)
    r1 = check_batch_encoded(cas_register_spec, pairs, timeout_s=0,
                             chunk_iters=16, checkpoint=ck,
                             checkpoint_every_s=0)
    assert os.path.exists(ck), "snapshot written on timeout"
    assert any(r["valid"] == "unknown" for r in r1)
    # snapshot must carry the alive map + any harvested keys
    with np.load(ck) as data:
        assert "alive" in data.files and "hkeys" in data.files
    r2 = check_batch_encoded(cas_register_spec, pairs, chunk_iters=16,
                             checkpoint=ck)
    assert [r["valid"] for r in r2] == [r["valid"] for r in want]
    assert not os.path.exists(ck), "spent snapshot removed"


def test_batch_checkpoint_foreign_snapshot_ignored(tmp_path):
    from jepsen_tpu.parallel import check_batch_encoded
    rng = random.Random(9)
    p1 = [cas_register_spec.encode(
        random_history(rng, "cas-register", 4, 60, 0.05))]
    p2 = [cas_register_spec.encode(
        random_history(rng, "cas-register", 4, 60, 0.05))]
    ck = str(tmp_path / "batch.npz")
    check_batch_encoded(cas_register_spec, p1, timeout_s=0,
                        chunk_iters=1, checkpoint=ck)
    # a different batch at the same path must not resume from it
    r = check_batch_encoded(cas_register_spec, p2, checkpoint=ck)
    assert r[0]["valid"] in (True, False, "unknown")


def test_batch_checkpoint_survives_budget_change(tmp_path):
    """max_iters is not fingerprinted: a budget-exhausted batch snapshot
    resumes under a LARGER budget (advisor finding r3)."""
    from jepsen_tpu.parallel import check_batch_encoded
    rng = random.Random(11)
    h = corrupt(rng, random_history(rng, "cas-register", 8, 150, 0.05))
    for o in h:
        if o["type"] == "ok" and o["f"] == "read" \
                and o.get("value") is not None:
            o["value"] = o["value"] % 4
    pairs = [cas_register_spec.encode(h)]
    ck = str(tmp_path / "batch.npz")
    r1 = check_batch_encoded(cas_register_spec, pairs, max_configs=64,
                             chunk_iters=1, checkpoint=ck)
    if r1[0]["valid"] == "unknown":
        assert os.path.exists(ck)
        r2 = check_batch_encoded(cas_register_spec, pairs,
                                 checkpoint=ck)
        assert r2[0]["valid"] in (True, False)
        assert not os.path.exists(ck)


def test_single_key_snapshot_of_the_old_layout_is_kept_and_not_read(
        tmp_path):
    """A snapshot in the single-key layout of earlier builds (the carry
    alone, fingerprinted over the prepared columns) sits at the path.
    The check ignores it, leaves it as it is, and checkpoints nothing.
    The old carry claims success, so a misread would answer True."""
    import hashlib

    import numpy as np

    from jepsen_tpu.checker import drive

    rng = random.Random(4)
    h = corrupt(rng, random_history(rng, "cas-register", 6, 120, 0.05))
    for o in h:
        if o["type"] == "ok" and o["f"] == "read" \
                and o.get("value") is not None:
            o["value"] = o["value"] % 4
    e, st = cas_register_spec.encode(h)
    want = jax_wgl.check_encoded(cas_register_spec, e, st)
    assert want["valid"] is False

    kind, prep = jax_wgl._prepare_search(cas_register_spec, e, st)
    assert kind == "search"
    (_perm, inv32, ret32, fop, args, rets, ok_words, init_state, n_pad,
     C, A, S) = prep
    B, W, O, T = jax_wgl._plan_sizes(n_pad, S, C)
    old = hashlib.sha256()
    old.update(jax_wgl.CARRY_LAYOUT.encode())
    old.update(cas_register_spec.name.encode())
    for a in (inv32, ret32, fop, args, rets, ok_words, init_state,
              np.asarray([n_pad, B, S, C, W, O, T], np.int64)):
        old.update(np.ascontiguousarray(a).tobytes())
    carry = [np.array(x) for x in jax_wgl._build_search(
        cas_register_spec.step, 1, n_pad, B, S, C, A, W, O, T)
        .init_carry(init_state[None])]
    carry[jax_wgl.IDX_STATUS][:] = jax_wgl.VALID
    ck = str(tmp_path / "frontier.npz")
    drive.write_snapshot(ck, old.hexdigest(),
                         {f"c{i}": x for i, x in enumerate(carry)})
    before = open(ck, "rb").read()

    r = jax_wgl.check_encoded(cas_register_spec, e, st, chunk_iters=1,
                              max_configs=W, checkpoint=ck)
    assert r["valid"] == "unknown" and "checkpoint" not in r
    assert open(ck, "rb").read() == before
    r = jax_wgl.check_encoded(cas_register_spec, e, st, checkpoint=ck)
    assert r["valid"] is False
    assert open(ck, "rb").read() == before
