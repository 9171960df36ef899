"""A chip count through the normal keyed path: ``engine_opts={"chips": n}``
on the linearizable checker becomes a 1-D ``keys`` mesh, and
``independent`` hands every key to the sharded key batch. Run on the
virtual 8-device CPU mesh of ``conftest.py``."""

import random

import pytest

import jax

from jepsen_tpu import history as h
from jepsen_tpu import independent
from jepsen_tpu.checker import checkers, wgl
from jepsen_tpu.models import cas_register_spec
from jepsen_tpu.parallel import keys_mesh

from test_jax_wgl import _corrupt, _random_history

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def keyed_cas_history(n_keys=64, seed=27004096):
    """Jepsen's linearizable-register shape over ``n_keys`` keys: 10
    threads and 19-20 ops a key, some ``:info``, 1 key in 8 with a read
    flipped. Returns the per-key event histories and the keyed history
    (keys one after another, each on processes of its own)."""
    rng = random.Random(seed)
    per_key, out = [], []
    for k in range(n_keys):
        hist = _random_history(rng, "cas-register", n_procs=10,
                               n_ops=rng.randint(19, 20), crash_p=0.05)
        if k % 8 == 7:
            hist = _corrupt(rng, hist)
        per_key.append(hist)
        for op in hist:
            out.append(dict(op, process=100 * k + op["process"],
                            value=independent.tuple_(k, op.get("value")),
                            index=len(out)))
    return per_key, out


def keyed_checker(**engine_opts):
    # one iteration a dispatch: the quantum otherwise follows wall time,
    # and a loaded host would compact at other batch widths
    return independent.checker(checkers.linearizable(
        {"model": "cas-register", "algorithm": "jax-wgl",
         "engine_opts": dict(engine_opts, chunk_iters=1)}))


def check(checker, events):
    return checker.check({}, h.History(h.Op(o) for o in events), {})


@pytest.fixture(scope="module")
def history():
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    return keyed_cas_history()


def test_chips_verdicts_equal_wgl_and_one_device(history):
    per_key, events = history
    want = [wgl.check_history(cas_register_spec, hist)["valid"]
            for hist in per_key]
    assert False in want and True in want
    for chips in (4, 1):
        res = check(keyed_checker(chips=chips), events)["results"]
        assert [res[k]["valid"] for k in range(len(per_key))] == want, \
            f"chips={chips}"
        # one batch, not independent's per-key fallback
        splits = {str(r.get("shard_explored")) for r in res.values()
                  if "compactions" in r}
        assert len(splits) == 1 and ("None" in splits) is (chips == 1)


def test_chips_shard_explored_sums_to_batch_configs(history):
    res = check(keyed_checker(chips=4), history[1])["results"]
    # a key cut into segments by the search plan carries its merged
    # result, without the batch-wide fields
    searched = [r for r in res.values() if "shard_explored" in r]
    assert searched, "no key reached the device search"
    split = searched[0]["shard_explored"]
    assert len(split) == 4
    assert all(r["shard_explored"] == split for r in searched)
    assert sum(split) == sum(r["configs_explored"]
                             for r in res.values()) > 0
    assert sum(1 for x in split if x > 0) >= 2


def test_repeated_mesh_check_compiles_nothing(history):
    compiles = []

    def on_duration(event, _secs, **_):
        if event == COMPILE_EVENT:
            compiles.append(event)

    checker = keyed_checker(chips=4)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        first = check(checker, history[1])
        n_first = len(compiles)
        second = check(checker, history[1])
    finally:
        jax.monitoring.clear_event_listeners()
    assert len(compiles) == n_first, \
        f"{len(compiles) - n_first} compiles in the second check"
    assert any(r.get("compactions") for r in second["results"].values())
    assert {k: r["valid"] for k, r in first["results"].items()} == \
        {k: r["valid"] for k, r in second["results"].items()}


def test_keys_mesh_is_one_axis_over_the_first_devices():
    mesh = keys_mesh(2)
    assert mesh.axis_names == ("keys",)
    assert list(mesh.devices) == jax.devices()[:2]
    n = len(jax.devices())
    with pytest.raises(ValueError, match=f"{n + 1} chips.*{n} devices"):
        keys_mesh(n + 1)
    with pytest.raises(ValueError):
        keys_mesh(0)


@pytest.mark.parametrize("opts, mesh", [
    ({"chips": 1, "max_configs": 7}, False),
    ({"max_configs": 7}, False),
    ({"chips": 2, "max_configs": 7}, True),
])
def test_chips_resolves_to_a_keys_mesh_once(opts, mesh):
    lin = checkers.Linearizable("cas-register", "jax-wgl", dict(opts))
    assert "chips" not in lin.engine_opts
    assert lin.engine_opts.get("max_configs") == 7
    assert ("mesh" in lin.engine_opts) is mesh
    if mesh:
        assert lin.engine_opts["mesh"].axis_names == ("keys",)
        assert lin.engine_opts["mesh"].devices.size == 2


def test_explicit_mesh_wins_over_chips():
    mine = keys_mesh(2)
    lin = checkers.Linearizable("cas-register", "jax-wgl",
                                {"chips": 4, "mesh": mine})
    assert lin.engine_opts == {"mesh": mine}


@pytest.mark.parametrize("chips", [0, 2.5, "4"])
def test_chips_must_be_a_whole_number(chips):
    with pytest.raises(ValueError, match="chips"):
        checkers.Linearizable("cas-register", "jax-wgl", {"chips": chips})


def test_two_axis_mesh_is_refused_with_its_axes():
    import numpy as np
    from jax.sharding import Mesh

    from jepsen_tpu.parallel import check_batch_histories, \
        check_history_sharded
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("a", "b"))
    hists = keyed_cas_history(n_keys=2)[0]
    with pytest.raises(ValueError, match=r"\('a', 'b'\)"):
        check_batch_histories(cas_register_spec, hists, mesh=mesh)
    with pytest.raises(ValueError, match=r"\('a', 'b'\)"):
        check_history_sharded(cas_register_spec, hists[0], mesh)
