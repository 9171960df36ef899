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

import logging
import time as _time

import numpy as np

import jax
import jax.numpy as jnp

from ..checker import jax_wgl
from ..checker.jax_wgl import (IDX_BEST_DEPTH, IDX_BEST_LIN,
                               IDX_BEST_STATE, IDX_DROPPED, IDX_EXPLORED,
                               IDX_IT, IDX_ITS, IDX_STATUS, IDX_TOP,
                               RUNNING, VALID, _build_search, _plan_sizes)
from ..obs import phases as obs_phases
from ..obs import search as obs_search
from .keyshard import _shard_specs, mesh_axis, shard_map_compat

logger = logging.getLogger(__name__)

AXIS = "search"


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
    run_local = _build_search(spec.step, 1, n_pad, B, S, C, A, W, O,
                              T, 1, NS=rollout_seeds,
                              rollout_kernel="scan", axis_name=ax,
                              axis_size=D, steal=steal).run_chunk
    carry_specs, const_specs = _shard_specs(mesh)
    run_b = jax.jit(shard_map_compat(
        run_local.__wrapped__, mesh,
        (carry_specs,) + const_specs, carry_specs),
        donate_argnums=(0,))
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
    ph.sync(carry)
    ph.lap("h2d")

    t0 = _time.monotonic()
    timed_out = False
    # sinks captured once at search start (see obs.search docstring)
    so = obs_search.capture()
    # padding accounting: one real history of len(e) rows in an
    # n_pad-row plan (the D-way replication of the op columns is
    # sharding, not padding, so it does not count as waste)
    so.plan("jax-wgl-sharded", n_pad, len(e), n_pad)
    it = 0
    eff = min(chunk_iters, 32, max(1, (32 * 16384) // n_pad))
    while True:
        prev_it = it
        t_chunk = _time.monotonic()
        bound = min(it + eff, max_iters)
        ph.lap("host")
        carry = run_b(carry, *consts, jnp.int32(bound))
        # device-compute bracket: sync only while phase attribution is
        # on (otherwise the progress device_get below stays the
        # dispatch's one sync, as before)
        ph.sync(carry)
        dev_s = ph.lap("device", iteration=bound)
        # ONE batched device_get of the progress tensor (replacing the
        # three separate per-array transfers): per-shard status/top,
        # the iteration counter, cumulative explored, and the witness
        # depths whose max is the deepest linearized-ok count reached
        status, top, it_g, explored_d, bdepth = jax.device_get(
            (carry[IDX_STATUS], carry[IDX_TOP], carry[IDX_IT],
             carry[IDX_EXPLORED], carry[IDX_BEST_DEPTH]))
        status = np.asarray(status)
        top = np.asarray(top)
        it = int(np.asarray(it_g)[0])
        ph.lap("d2h")
        # per-shard frontier sizes ARE the steal-ring balance signal:
        # all work stuck on one shard = the ring is starved. Built from
        # the arrays this poll already fetched — no extra per-chunk
        # device round trips
        so.heartbeat(
            "jax-wgl-sharded", iteration=it,
            chunk_s=_time.monotonic() - t_chunk,
            device_s=dev_s if ph.enabled else None,
            frontier=int(top.sum()),
            explored=int(np.asarray(explored_d).sum()),
            depth=max(0, int(np.asarray(bdepth).max())),
            shard_tops=[int(t) for t in top])
        if (status == VALID).any() or not ((status == RUNNING)
                                           & (top > 0)).any() \
                or it >= max_iters:
            break
        now = _time.monotonic()
        per_it = max(1e-4, (now - t_chunk) / max(1, it - prev_it))
        eff = jax_wgl._adapt_quantum(
            chunk_iters, per_it, 3.0,
            timeout_s - (now - t0) if timeout_s is not None else None)
        if timeout_s is not None and now - t0 > timeout_s:
            timed_out = True
            break

    ph.lap("host")
    got = jax.device_get({
        "status": carry[IDX_STATUS], "top": carry[IDX_TOP],
        "dropped": carry[IDX_DROPPED], "explored": carry[IDX_EXPLORED],
        "iterations": carry[IDX_ITS],
        "best_depth": carry[IDX_BEST_DEPTH],
        "best_lin": carry[IDX_BEST_LIN],
        "best_state": carry[IDX_BEST_STATE]})
    tstats = jax_wgl.table_stats(carry)
    ph.lap("d2h")
    status = np.asarray(got["status"])
    top = np.asarray(got["top"])
    explored = np.asarray(got["explored"])
    result = {"configs_explored": int(explored.sum()),
              "iterations": int(np.asarray(got["iterations"]).max()),
              "engine": "jax-wgl-sharded", "shards": D,
              "shard_explored": [int(x) for x in explored],
              **tstats}

    def _done(result):
        so.summary("jax-wgl-sharded", result,
                   shard_explored=result["shard_explored"])
        ph.lap("host")
        return result

    def _merged_slots():
        # every shard's TOPK witness slots as one slot group (the
        # decoder sorts by depth), so witness decoding matches the
        # single-device engine's exactly
        return {"best_depth": np.asarray(got["best_depth"]).reshape(-1),
                "best_lin": np.asarray(got["best_lin"])
                .reshape(D * jax_wgl.TOPK, -1),
                "best_state": np.asarray(got["best_state"])
                .reshape(D * jax_wgl.TOPK, -1)}

    if (status == VALID).any():
        result["valid"] = True
        # the winning shard's slot carries the full linearization: emit
        # the same normalized witness as the single-device VALID path
        jax_wgl._attach_valid_witness(result, e, _merged_slots(), perm,
                                      spec, init_state)
        return _done(result)
    if timed_out and ((status == RUNNING) & (top > 0)).any():
        result.update(valid="unknown", error="timeout")
        return _done(result)
    # an empty-everywhere, nothing-dropped state is a sound exhaustion
    # proof no matter when it was reached (even on the last allowed
    # iteration -- the single-device _interpret has no it guard either)
    exhausted = not (top > 0).any()
    dropped = bool(np.asarray(got["dropped"]).any())
    if exhausted and not dropped:
        result["valid"] = False
        jax_wgl._attach_witness(result, e, _merged_slots(), perm, spec,
                                init_state)
        return _done(result)
    result.update(valid="unknown",
                  error="stack-overflow" if dropped
                  else "max-configs-exceeded")
    return _done(result)


def check_history_sharded(spec, history, mesh, **kw):
    """Encode an event history and run the mesh-sharded search."""
    e, init_state = spec.encode(history)
    return check_encoded_sharded(spec, e, init_state, mesh, **kw)
