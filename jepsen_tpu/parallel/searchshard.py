"""ONE single-key linearizability search sharded across a device mesh.

`keyshard.py` scales MULTI-key workloads by making the key axis a batch
dimension — embarrassingly parallel, no collectives. This module covers
the other shape: a SINGLE long history whose search should use the
whole mesh (SURVEY.md §5 "Distributed communication backend", §7 step
9; the reference's CPU analogue is search-level parallelism only,
jepsen/src/jepsen/checker.clj:101-116, 199-202 — it cannot split one
search).

Design (implemented inside the search kernel, jax_wgl._build_search
``axis_name=...``):

* The DFS stack/frontier is **partitioned per device**: each shard
  runs the full expansion/rollout/dedup pipeline on its own configs
  over ICI-local memory. Shard 0 starts with the root configuration;
  everyone else starts empty.
* **Per-device dedup tables.** Cross-shard duplicates are possible and
  sound: the table is insert-failure-tolerant by design (a missed
  insert only means re-exploration), so skipping cross-device dedup
  costs work, never answers.
* **Collectives over ICI, tiny and fixed-shape.** Per iteration: one
  `all_gather` of the per-shard frontier sizes (the work-balance
  vector), one `ppermute` shipping a bounded hand-off buffer of the
  donor's deepest configs to a STARVING right neighbor around the
  ring, and two scalar `psum`s in the loop condition so every shard
  agrees on termination (any shard's work keeps all stepping; any
  shard's success stops all). Work diffuses around the ring within
  D-1 iterations of a shard going idle.
* **Verdict assembly on host.** Valid if ANY shard found a
  linearization; invalid (exhausted) only when every shard's stack is
  empty AND no shard overflowed its ring (dropping forfeits exhaustion
  proofs exactly as on one chip); otherwise unknown (budget). Witness
  slots merge across shards (deepest-first).

Perf honesty: this environment exposes ONE real TPU chip — multi-chip
wall-clock cannot be measured here. What is verified (virtual CPU
mesh, tests/test_searchshard.py + the driver's dryrun): an 8-device
mesh decides the same verdicts as the single-device engine on
histories needing hundreds of iterations, work-stealing genuinely
spreads exploration across shards, and the single-chip path is
untouched (the collective code only exists when ``axis_name`` is set).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from ..checker import drive, jax_wgl
from ..checker.jax_wgl import IDX_TOP, _build_search, _plan_sizes
from ..obs import phases as obs_phases
from .keyshard import _mesh_search, mesh_axis


def check_encoded_sharded(spec, e, init_state, mesh,
                          max_configs=50_000_000, frontier_width=None,
                          stack_size=None, table_size=None,
                          timeout_s=None, chunk_iters=256, steal=16,
                          rollout_seeds=None):
    """Run ONE search for ``e`` sharded over ``mesh`` (1-D). Result
    dict matches jax_wgl.check_encoded, plus per-shard diagnostics
    (``shard_explored``) proving the steal ring spread the work."""
    ax = mesh_axis(mesh)
    D = int(mesh.shape[ax])
    # phase cursor (obs.phases): per-dispatch encode/plan/h2d/compile/
    # device/d2h/host attribution for the mesh loop
    ph = obs_phases.capture("jax-wgl-sharded")
    prep = jax_wgl._prepare_search(spec, e, init_state)
    if prep[0] == "fast":
        return prep[1]
    (perm, inv32, ret32, fop, args, rets, ok_words, init_state, n_pad,
     C, A, S) = prep[1]
    ph.lap("encode")

    B, W, O, T = _plan_sizes(n_pad, S, C, frontier_width, stack_size,
                             table_size)
    # cross-run compile-reuse ledger: mirrors the _build_search keys
    # below (both the local kernel and the init builder feed them)
    ph.note_compile(jax_wgl._note_compile(
        "jax-wgl-sharded", (spec.name, D, n_pad, B, S, C, A, W, O, T,
                            steal, rollout_seeds)))
    max_iters = max(1, max_configs // (W * D))

    # the local kernel: ONE shard of the search (K=1, its own table
    # group), with the steal ring + global-termination collectives
    run = _mesh_search(spec.step, 1, n_pad, B, S, C, A, W, O, T, None,
                       rollout_seeds, mesh, steal=steal)[1]
    ph.lap("plan")

    # global init: the builder's init_carry for K=D shards, then only
    # shard 0 keeps the root configuration (symmetric shards would
    # explore identically forever); the steal ring feeds the rest
    init_carry = _build_search(spec.step, D, n_pad, B, S, C, A, W, O,
                               T, D, NS=rollout_seeds,
                               rollout_kernel="scan").init_carry
    carry = [np.asarray(x) for x in
             jax.device_get(init_carry(jnp.asarray(
                 np.tile(init_state[None], (D, 1)))))]
    top0 = np.zeros(D, np.int32)
    top0[0] = 1
    carry[IDX_TOP] = top0

    from jax.sharding import NamedSharding, PartitionSpec as P
    shd = NamedSharding(mesh, P(ax))
    carry = tuple(jax.device_put(x, shd) for x in carry)
    consts = tuple(
        jax.device_put(jnp.asarray(np.tile(col[None], (D,) + (1,) *
                                           col.ndim)), shd)
        for col in (inv32, ret32, fop, args, rets, ok_words)) + (
        jax.device_put(jnp.zeros(D, jnp.uint32), shd),)
    result, = drive.drive(
        ph, "jax-wgl-sharded", spec, [(e, init_state, perm)], carry,
        consts, run, max_iters=max_iters, chunk_iters=chunk_iters,
        timeout_s=timeout_s, mesh=mesh)
    result["shards"] = D
    return result


def check_history_sharded(spec, history, mesh, **kw):
    """Encode an event history and run the mesh-sharded search."""
    e, init_state = spec.encode(history)
    return check_encoded_sharded(spec, e, init_state, mesh, **kw)
