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


# ---------------------------------------------------------------------------
# the mesh batch's initial carry, built on the chips that hold it

def _old_mesh_setup(spec, cols, salts, plan, mesh):
    """The mesh set-up as it was before the carry was built in place:
    the full-width init on the default device, then every element
    pulled to the host and pushed out to the ``keys`` sharding. The
    oracle of the carry and op columns the batch builds now."""
    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from jepsen_tpu.checker.jax_wgl import _build_search
    n_pad, B, S_pad, C, A, W, O, T, G, K = plan
    consts = tuple(jnp.asarray(np.stack([c[i] for c in cols]))
                   for i in range(7)) + (jnp.asarray(np.asarray(salts)),)
    init_states = consts[6]
    consts = consts[:6] + (consts[7],)
    R = 0 if n_pad <= 64 else min(256, n_pad)
    carry = _build_search(spec.step, K, n_pad, B, S_pad, C, A, W, O, T, G,
                          R=R, NS=1, rollout_kernel="scan").init_carry(
                              init_states)
    keyed = NamedSharding(mesh, P("keys"))
    consts = tuple(jax.device_put(x, keyed) for x in consts)
    carry = tuple(jax.device_put(np.asarray(x), keyed) for x in carry)
    return carry, consts


def _handed_to_drive(monkeypatch, hists, **kw):
    """What ``check_batch_encoded`` hands the host loop, copied to the
    host with each element's sharding; the search itself never runs."""
    import numpy as np

    from jepsen_tpu.checker import drive
    from jepsen_tpu.parallel import check_batch_histories
    seen = {}

    def grab(xs):
        return [(np.asarray(x), x.dtype, x.sharding) for x in xs]

    def fake_drive(ph, engine, spec, keys, carry, consts, run, **dkw):
        seen.update(keys=len(keys), carry=grab(carry),
                    consts=grab(consts), snapshot_of=dkw["snapshot_of"])
        return [None] * len(keys)

    monkeypatch.setattr(drive, "drive", fake_drive)
    check_batch_histories(cas_register_spec, hists, **kw)
    return seen


def _assert_same(got, want):
    assert len(got) == len(want)
    for i, ((g, gdt, gsh), w) in enumerate(zip(got, want)):
        assert gdt == w.dtype, f"element {i}"
        assert g.shape == w.shape, f"element {i}"
        assert gsh.is_equivalent_to(w.sharding, w.ndim), f"element {i}"
        assert (g == jax.device_get(w)).all(), f"element {i}"


@pytest.mark.parametrize("chips", [2, 4])
@pytest.mark.parametrize("n_keys, dummies", [(4, False), (5, True)])
def test_mesh_carry_equals_full_width_init_put_to_mesh(
        monkeypatch, chips, n_keys, dummies):
    if len(jax.devices()) < chips:
        pytest.skip(f"needs {chips} devices")
    mesh = keys_mesh(chips)
    hists = keyed_cas_history(n_keys=n_keys)[0]
    seen = _handed_to_drive(monkeypatch, hists, mesh=mesh)
    cols, salts, plan = seen["snapshot_of"]
    assert (plan[-1] > seen["keys"]) is dummies    # K rows, dummies last
    carry, consts = _old_mesh_setup(cas_register_spec, cols, salts, plan,
                                    mesh)
    _assert_same(seen["carry"], carry)
    _assert_same(seen["consts"], consts)


def test_mesh_check_builds_no_full_width_init(history, monkeypatch):
    from jepsen_tpu import obs
    from jepsen_tpu.checker import drive
    from jepsen_tpu.checker.jax_wgl import IDX_TOP
    from jepsen_tpu.parallel import check_batch_histories, keyshard
    rows, handed = [], []
    real_build, real_drive = keyshard._build_search, drive.drive

    def recording_build(*a, **kw):
        s = real_build(*a, **kw)

        def init_carry(states):
            rows.append(states.shape[0])
            return s.init_carry(states)
        return s._replace(init_carry=init_carry)

    def recording_drive(ph, engine, spec, keys, carry, *a, **kw):
        handed.append((carry[IDX_TOP].shape[0],
                       sum(x.nbytes for x in carry)))
        return real_drive(ph, engine, spec, keys, carry, *a, **kw)

    compiles = []

    def on_duration(event, _secs, **_):
        if event == COMPILE_EVENT:
            compiles.append(event)

    monkeypatch.setattr(keyshard, "_build_search", recording_build)
    monkeypatch.setattr(drive, "drive", recording_drive)
    keyshard._mesh_search.cache_clear()
    hists = history[0][:16]
    reg = obs.Registry()
    try:
        with obs.sink_scope(None, reg):
            check_batch_histories(cas_register_spec, hists,
                                  mesh=keys_mesh(4), chunk_iters=1)
            n_rows, n_misses = len(rows), \
                keyshard._mesh_search.cache_info().misses
            jax.monitoring.register_event_duration_secs_listener(
                on_duration)
            check_batch_histories(cas_register_spec, hists,
                                  mesh=keys_mesh(4), chunk_iters=1)
            jax.monitoring.clear_event_listeners()
            misses = keyshard._mesh_search.cache_info().misses
            one_device = obs.Registry()
            with obs.sink_scope(None, one_device):
                check_batch_histories(cas_register_spec, hists,
                                      chunk_iters=1)
    finally:
        jax.monitoring.clear_event_listeners()
        keyshard._mesh_search.cache_clear()
    (K, nbytes), again = handed[0], handed[1]
    assert again == (K, nbytes) and K >= 8
    # every init the mesh checks called was one chip's rows
    assert rows and set(rows[:n_rows]) == {K // 4}
    # the repeated check traced, built and compiled nothing new
    assert len(rows) == n_rows + 1 and rows[-1] == K    # the one device
    assert misses == n_misses
    assert not compiles, f"{len(compiles)} compiles in the second check"
    # the carry's bytes, once a mesh check; nothing off the mesh
    assert reg.counter_value("keyshard.sharded_init_bytes",
                             chips="4") == 2 * nbytes
    assert not [k for k in one_device.snapshot()["counters"]
                if k.startswith("keyshard.")]
