"""The main path's kernels compile for a TPU v5e (no chip needed).

Each test compiles one program for a described, unattached v5e chip
(``jax.experimental.topologies``) at the shape ``chip_smoke.py`` runs:
what the chip's compiler (Mosaic for the Pallas kernel) refuses fails
here, at no chip time. Nothing runs, so nothing here is a result or a
time. The topology is described inside a fixture, never at import: a
process that loads the TPU library keeps it, and xdist workers must
all collect the same tests.
"""

import contextlib

import pytest

import jax
import jax.numpy as jnp

from jepsen_tpu.checker import jax_wgl, pallas_rollout, streamlin
from jepsen_tpu.models import cas_register_spec as spec

#: cas-register state and argument widths
S, A = 1, 2
#: the single-key rollout defaults (jax_wgl.check_encoded)
NS, R = 8, 1024


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "can't"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prior)
        compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding)
            for s, d in shapes]
    with _no_persistent_cache():
        return jax.jit(fn).lower(*args).compile()


def _largest_admitted_n():
    """The largest pow-2 history the VMEM gate (``_pick_chunk`` under
    ``VMEM_BUDGET``) admits for the fused rollout."""
    n = 1 << 24
    while pallas_rollout._pick_chunk(NS, R, n, S, A) is None:
        n //= 2
    return n


@pytest.mark.parametrize("n", [8192, 16384, None],
                         ids=["phase-c", "16k", "largest-admitted"])
def test_fused_rollout_compiles_for_v5e(one_chip, n):
    n = n or _largest_admitted_n()
    B = n // 32
    built = pallas_rollout.build_fused_rollout(spec.step, NS, R, n, B, S,
                                               A, interpret=False)
    assert built is not None, f"the gate refused n={n}"
    prep, run = built

    def f(seed_lin, seed_st, seed_ok, inv, ret, fop, args, rets):
        return run(seed_lin, seed_st, seed_ok,
                   *prep(inv, ret, fop, args, rets))

    c = _compile(f, one_chip, ((NS, B), jnp.uint32), ((NS, S), jnp.int32),
                 ((NS,), jnp.bool_), ((n,), jnp.int32), ((n,), jnp.int32),
                 ((n,), jnp.int32), ((n, A), jnp.int32),
                 ((n, A), jnp.int32))
    assert "tpu_custom_call" in c.as_text()


def test_search_chunk_k1_compiles_for_v5e(one_chip):
    import __graft_entry__
    fn, example = __graft_entry__.entry()
    _compile(fn, one_chip, *((a.shape, a.dtype) for a in example))


def test_batch_search_chunk_k256_compiles_for_v5e(one_chip):
    """One chunk of the keyed batch at chip_smoke phase b's shape: 256
    keys of 200 ops pad to n=256, with keyshard's batch budgets."""
    K, n, C = 256, 256, 8
    B, W, O, T = jax_wgl._plan_sizes(n, S, C)
    W = min(W, max(32, 4096 // K))
    O = max(4096, O // 8)
    search = jax_wgl._build_search(spec.step, K, n, B, S, C, A, W, O, T,
                                   R=min(256, n), NS=1,
                                   rollout_kernel="scan")

    def step(init_states, inv, ret, fop, args, rets, okw, salt):
        carry = search.init_carry(init_states)
        return search.run_chunk(carry, inv, ret, fop, args, rets, okw,
                                salt, jnp.int32(8))

    _compile(step, one_chip, ((K, S), jnp.int32), ((K, n), jnp.int32),
             ((K, n), jnp.int32), ((K, n), jnp.int32),
             ((K, n, A), jnp.int32), ((K, n, A), jnp.int32),
             ((K, B), jnp.uint32), ((K,), jnp.uint32))


def test_keyed_mesh_chunk_compiles_for_v5e_2x2(topo):
    """One chunk of the key batch under ``shard_map`` over a ``keys``
    mesh of the four chips of a v5e host, at the per-key widths of the
    4096-key register cell (n=64, C=16, W=32, O=32768, T=2**20) with 8
    keys a chip: the chips share no collective in the hot loop."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from jepsen_tpu.parallel import keyshard
    k_local, n, B, C, W, O, T = 8, 64, 2, 16, 32, 32768, 1 << 20
    mesh = Mesh(np.array(topo.devices[:4]), ("keys",))
    G = mesh.devices.size
    K = k_local * G
    keyed = NamedSharding(mesh, P("keys"))
    full = jax_wgl._build_search(spec.step, K, n, B, S, C, A, W, O, T, G,
                                 R=0, NS=1, rollout_kernel="scan")
    carry = jax.eval_shape(full.init_carry,
                           jax.ShapeDtypeStruct((K, S), jnp.int32))
    args = [jax.ShapeDtypeStruct(c.shape, c.dtype, sharding=keyed)
            for c in carry]
    args += [jax.ShapeDtypeStruct(shape, dtype, sharding=keyed)
             for shape, dtype in [((K, n), jnp.int32)] * 3
             + [((K, n, A), jnp.int32)] * 2
             + [((K, B), jnp.uint32), ((K,), jnp.uint32)]]
    bound = jax.ShapeDtypeStruct((), jnp.int32,
                                 sharding=NamedSharding(mesh, P()))
    _, run = keyshard._mesh_search(spec.step, k_local, n, B, S, C, A, W,
                                   O, T, 0, 1, mesh)
    with _no_persistent_cache():
        text = run.lower(tuple(args[:len(carry)]), *args[len(carry):],
                         bound).compile().as_text()
    for op in ("all-reduce", "all-gather", "collective-permute",
               "all-to-all", "reduce-scatter"):
        assert op not in text


def test_keyed_mesh_init_compiles_for_v5e_2x2(topo):
    """The key batch's initial carry built under ``shard_map`` over the
    four chips of a v5e host, at the 4096-key register cell's widths
    (1024 keys a chip): no collective, and each chip's output is its
    own quarter of the full-width carry."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from jepsen_tpu.parallel import keyshard
    k_local, n, B, C, W, O, T = 1024, 64, 2, 16, 32, 32768, 1 << 20
    mesh = Mesh(np.array(topo.devices[:4]), ("keys",))
    G = mesh.devices.size
    K = k_local * G
    full = jax_wgl._build_search(spec.step, K, n, B, S, C, A, W, O, T, G,
                                 R=0, NS=1, rollout_kernel="scan")
    carry = jax.eval_shape(full.init_carry,
                           jax.ShapeDtypeStruct((K, S), jnp.int32))
    init, _ = keyshard._mesh_search(spec.step, k_local, n, B, S, C, A, W,
                                    O, T, 0, 1, mesh)
    states = jax.ShapeDtypeStruct((K, S), jnp.int32,
                                  sharding=NamedSharding(mesh, P("keys")))
    with _no_persistent_cache():
        compiled = init.lower(states).compile()
    text = compiled.as_text()
    for op in ("all-reduce", "all-gather", "collective-permute",
               "all-to-all", "reduce-scatter"):
        assert op not in text
    # a quarter of ~2.7 GB, give or take the chip's buffer alignment
    nbytes = sum(np.prod(c.shape) * c.dtype.itemsize for c in carry)
    out = compiled.memory_analysis().output_size_in_bytes
    assert nbytes // G <= out < nbytes // G + (1 << 16)


def test_streamlin_solo_fold_compiles_for_v5e(one_chip):
    """The monitor's fold at its starting frontier/window shape."""
    F, NW = streamlin.FRONTIER_FLOOR, streamlin.WINDOW_FLOOR
    B, C, E = NW // 32, streamlin.OPEN_FLOOR, streamlin.EVENT_FLOOR
    fold = streamlin._build_fold(spec.step, 1, F, B, S, C, E, A)
    _compile(fold, one_chip, ((F, B), jnp.uint32), ((F, S), jnp.int32),
             ((F,), jnp.bool_), ((B,), jnp.uint32), ((E,), jnp.int32),
             ((E,), jnp.int32), ((NW,), jnp.int32), ((NW, A), jnp.int32),
             ((NW, A), jnp.int32), ((B,), jnp.uint32))
