"""What the device search's host loop does per dispatch, pinned for its
three entry points: the single key (``jax_wgl.check_encoded``), the key
batch with and without a ``keys`` mesh (``keyshard.check_batch_encoded``)
and the steal ring (``searchshard.check_encoded_sharded``).

Every dispatch laps ``host``, then ``device`` (``compile`` on a compile
miss), then ``d2h``, makes ONE progress ``jax.device_get`` and beats
once; each search ends in one summary under its engine label. Beyond
those, the loop fetches once per compaction, once for the final harvest
and once for ``table_stats``; the steal ring also fetches its initial
carry once. The counts below were taken on fixed seeded histories at
one iteration a dispatch, where the quantum does not follow wall time.
"""

import random
import threading

import numpy as np
import pytest

import jax

from jepsen_tpu import obs
from jepsen_tpu.checker import jax_wgl
from jepsen_tpu.models import cas_register_spec as spec
from jepsen_tpu.parallel import keys_mesh, keyshard, searchshard
from jepsen_tpu.simulate import corrupt, random_history


def _inrange(hist):
    # a corrupted read kept inside the written values: the
    # state-abstraction pre-check cannot decide it, the search must
    for o in hist:
        if o["type"] == "ok" and o["f"] == "read" \
                and isinstance(o.get("value"), int):
            o["value"] = o["value"] % 4
    return hist


def _single_pair():
    rng = random.Random(4)
    hist = _inrange(corrupt(rng, random_history(rng, "cas-register", 6,
                                                120, 0.05)))
    return spec.encode(hist)


def _batch_pairs():
    rng = random.Random(7)
    pairs = []
    for k in range(6):
        hist = random_history(rng, "cas-register", 6, 60 + 20 * k, 0.05)
        if k % 2:
            hist = _inrange(corrupt(rng, hist))
        pairs.append(spec.encode(hist))
    return pairs


def _run(case):
    if case == "single":
        e, st = _single_pair()
        return [jax_wgl.check_encoded(spec, e, st, chunk_iters=1)]
    if case.startswith("batch"):
        mesh = keys_mesh(2) if case == "batch-mesh2" else None
        return keyshard.check_batch_encoded(spec, _batch_pairs(),
                                            chunk_iters=1, mesh=mesh)
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:2]), ("search",))
    e, st = _single_pair()
    return [searchshard.check_encoded_sharded(spec, e, st, mesh,
                                              chunk_iters=1)]


# case -> (engine label, device_get calls, dispatches)
CASES = {
    "single": ("jax-wgl", 29, 27),
    "batch": ("jax-wgl-batch", 29, 25),
    "batch-mesh2": ("jax-wgl-batch", 29, 25),
    "steal": ("jax-wgl-sharded", 31, 28),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dispatch_laps_and_device_gets(case, monkeypatch):
    label, want_gets, want_dispatches = CASES[case]
    # obs sinks and the patched device_get are process-wide: count only
    # this thread's, not a stray thread's left by an earlier test
    me = threading.get_ident()
    gets = []
    real_get = jax.device_get

    def counting_get(x):
        if threading.get_ident() == me:
            gets.append(1)
        return real_get(x)

    tr, reg = obs.Tracer(), obs.Registry()
    monkeypatch.setattr(jax, "device_get", counting_get)
    with obs.bind(tr, reg):
        results = _run(case)
    monkeypatch.undo()

    events = [e for e in tr.events() if e.get("tid") == me]
    spans = sorted((e for e in events
                    if e.get("ph") == "X" and e.get("cat") == "phase"),
                   key=lambda e: e["ts"])
    assert {e["args"]["engine"] for e in spans} == {label}
    laps = [e["name"][len("wgl.phase."):] for e in spans]
    dispatches = [i for i, p in enumerate(laps)
                  if p in ("device", "compile")]
    for i in dispatches:
        assert laps[i - 1:i + 2] in (["host", "device", "d2h"],
                                     ["host", "compile", "d2h"]), laps
    beats = [e for e in events
             if e.get("name") == f"wgl.heartbeat.{label}"]
    assert len(beats) == len(dispatches) > 1
    assert [e["name"] for e in events
            if e.get("name", "").startswith("wgl.done.")] == \
        [f"wgl.done.{label}"]
    compactions = max(r.get("compactions", 0) for r in results)
    extra = 1 if case == "steal" else 0      # the ring's initial carry
    assert len(gets) == len(dispatches) + compactions + 2 + extra
    assert (len(gets), len(dispatches)) == (want_gets, want_dispatches)
