"""ONE search sharded across the mesh (parallel/searchshard.py).

The last SURVEY §7 promise: partition a single history's DFS across
devices with per-device dedup tables and a collective steal ring
(all_gather work-balance vector + ppermute hand-off). These tests run
on the 8-virtual-CPU-device mesh from conftest and check the sharded
engine against the single-device engine and the CPU oracle on
histories large enough to need real iteration counts."""

import random

import pytest

import jax

from jepsen_tpu import models
from jepsen_tpu.checker import jax_wgl, wgl
from jepsen_tpu.parallel import check_encoded_sharded
from jepsen_tpu.simulate import corrupt, random_history


def _mesh(n=8):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"need {n} devices, have {len(devs)}")
    from jax.sharding import Mesh
    import numpy as np
    return Mesh(np.array(devs[:n]), ("search",))


def _inrange(hist):
    for o in hist:
        if o["type"] == "ok" and o["f"] == "read" \
                and isinstance(o.get("value"), int):
            o["value"] = o["value"] % 4
    return hist


def test_sharded_matches_single_device_verdicts():
    """Valid, invalid (exhaustion proof), and oracle-checked random
    histories all decide identically on the 8-shard mesh and the
    1-device engine."""
    mesh = _mesh()
    spec = models.cas_register_spec
    rng = random.Random(45100)
    decided_invalid = 0
    for trial in range(6):
        hist = random_history(rng, "cas-register", n_procs=6,
                              n_ops=160, crash_p=0.05)
        if trial % 2:
            hist = _inrange(corrupt(rng, hist))
        e, st = spec.encode(hist)
        single = jax_wgl.check_encoded(spec, e, st,
                                       rollout_kernel="scan")
        shard = check_encoded_sharded(spec, e, st, mesh)
        assert shard["valid"] == single["valid"], trial
        assert shard.get("engine", "aspect") in ("aspect", "jax-wgl",
                                                 "jax-wgl-sharded")
        if shard["valid"] is False:
            decided_invalid += 1
            # invalid verdicts carry a merged witness
            assert shard["configs"], trial
        oracle = wgl.check_encoded(spec, e, st)
        assert shard["valid"] == oracle["valid"], trial
    assert decided_invalid, "no exhaustion proof exercised"


def test_sharded_steal_spreads_work():
    """An exhaustion proof big enough to need >100 iterations must
    genuinely use the mesh: the steal ring feeds every starving shard,
    so exploration counts are non-zero beyond shard 0."""
    mesh = _mesh()
    spec = models.cas_register_spec
    rng = random.Random(11)
    hist = _inrange(corrupt(rng, random_history(
        rng, "cas-register", n_procs=10, n_ops=300, crash_p=0.1)))
    e, st = spec.encode(hist)
    single = jax_wgl.check_encoded(spec, e, st, rollout_kernel="scan")
    assert single.get("iterations", 0) > 100, \
        "history too easy to exercise sharding"
    shard = check_encoded_sharded(spec, e, st, mesh)
    assert shard["valid"] == single["valid"]
    assert shard["engine"] == "jax-wgl-sharded"
    busy = [x for x in shard["shard_explored"] if x > 0]
    assert len(busy) >= 4, shard["shard_explored"]


def test_sharded_mutex_and_register():
    """Model coverage beyond cas: mutex + plain register verdicts
    agree with the single-device engine."""
    mesh = _mesh()
    rng = random.Random(7)
    for name, spec in (("mutex", models.mutex_spec),
                       ("register", models.register_spec)):
        for trial in range(2):
            hist = random_history(rng, name, n_procs=6, n_ops=120,
                                  crash_p=0.05)
            if trial:
                hist = _inrange(corrupt(rng, hist))
            e, st = spec.encode(hist)
            single = jax_wgl.check_encoded(spec, e, st,
                                           rollout_kernel="scan")
            shard = check_encoded_sharded(spec, e, st, mesh)
            assert shard["valid"] == single["valid"], (name, trial)


def test_sharded_via_linearizable_checker():
    """The public gate: algorithm jax-wgl with engine_opts {"mesh"}
    routes one single-key search through the sharded engine."""
    from jepsen_tpu import history as h
    from jepsen_tpu.checker import checkers as ck
    from jepsen_tpu.checker import core as cc
    mesh = _mesh()
    inv, ok = h.invoke_op, h.ok_op
    good = [inv(0, "write", 1), ok(0, "write", 1),
            inv(1, "read"), ok(1, "read", 1)]
    bad = [inv(0, "write", 1), ok(0, "write", 1),
           inv(1, "read"), ok(1, "read", 2),
           inv(0, "write", 2), ok(0, "write", 2)]
    c = ck.linearizable({"model": "cas-register",
                         "algorithm": "jax-wgl",
                         "engine_opts": {"mesh": mesh}})
    assert cc.check(c, {}, good)["valid"] is True
    assert cc.check(c, {}, bad)["valid"] is False


def test_sharded_timeout_returns_unknown():
    mesh = _mesh()
    spec = models.cas_register_spec
    # the steal test's seed: needs hundreds of iterations, so a
    # 1-iteration budget cannot decide it
    rng = random.Random(11)
    hist = _inrange(corrupt(rng, random_history(
        rng, "cas-register", n_procs=10, n_ops=300, crash_p=0.1)))
    e, st = spec.encode(hist)
    r = check_encoded_sharded(spec, e, st, mesh, timeout_s=0,
                              chunk_iters=1)
    assert r["valid"] == "unknown"
    assert r["error"] == "timeout"


def test_repeated_sharded_check_compiles_nothing():
    """The steal ring's shard_map runner is built once per shape: a
    second search of the same shape compiles nothing."""
    mesh = _mesh(4)
    spec = models.cas_register_spec
    rng = random.Random(45100)
    hist = _inrange(corrupt(rng, random_history(
        rng, "cas-register", n_procs=6, n_ops=160, crash_p=0.05)))
    e, st = spec.encode(hist)
    compiles = []

    def on_duration(event, _secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        first = check_encoded_sharded(spec, e, st, mesh, chunk_iters=4)
        n_first = len(compiles)
        second = check_encoded_sharded(spec, e, st, mesh, chunk_iters=4)
    finally:
        jax.monitoring.clear_event_listeners()
    assert len(compiles) == n_first, \
        f"{len(compiles) - n_first} compiles in the second search"
    assert second["valid"] == first["valid"]
    assert second["shard_explored"] == first["shard_explored"]
