"""Multi-key batched linearizability checking, sharded over a device mesh.

jepsen.independent lifts a single-key test to many keys and checks per-key
subhistories in parallel on CPU threads (reference independent.clj:264-315,
bounded-pmap at :285). The TPU design makes the key axis an explicit batch
dimension of the WGL search kernel (BASELINE.json config 2): every key's
branch-and-bound advances in lockstep inside one compiled program, sharing
one key-salted dedup table and one flat scatter per structure per iteration.

Scale-out: with a 1-D ``Mesh`` the same kernel runs under ``shard_map`` --
keys shard over the mesh axis, and every carry element (including the dedup
tables, which carry a leading group axis sized to the mesh) shards with
them, so each device runs its shard's searches independently over ICI-local
memory with no collectives in the hot loop (embarrassingly parallel, the
right layout for this workload; SURVEY.md section 5).

Keys finish at different times; the host polls per-key status between
bounded chunks, harvests finished keys, and *compacts* the batch (power-of-
two buckets) so stragglers don't drag finished keys' lanes along -- widening
the per-key frontier as the batch shrinks to keep the chip busy.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import time as _time

import numpy as np

import jax
import jax.numpy as jnp

from ..checker import jax_wgl
from ..checker.jax_wgl import (IDX_BEST_DEPTH, IDX_BEST_LIN,
                               IDX_BEST_STATE, IDX_DROPPED, IDX_EXPLORED,
                               IDX_ITS, IDX_STATUS, IDX_TOP, INF32, KEYED,
                               N_CARRY, RUNNING, _bucket, _build_search,
                               _encode_arrays, _plan_sizes,
                               max_point_concurrency, table_stats)
from .. import obs
from ..history import INF_TIME
from ..obs import phases as obs_phases
from ..obs import search as obs_search

logger = logging.getLogger(__name__)


def shard_map_compat(fn, mesh, in_specs, out_specs):
    """The one place both mesh paths build their shard_map. These
    kernels must disable the replication check (check_vma=False: the
    steal-ring collectives aren't replicated)."""
    from jax import shard_map
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def _pad_key(e, init_state, spec, n_pad, S_pad, A, enc=None):
    """Priority-sort one key's encoded arrays (see
    jax_wgl._priority_order) and pad to the common bucket sizes. Returns
    the padded columns plus the priority perm for witness decoding."""
    n = len(e)
    inv32, ret32, _ = enc if enc is not None else _encode_arrays(e)
    perm, inv32, ret32, fop, args, rets, ok_words = \
        jax_wgl._priority_order(spec, e, inv32, ret32)
    pn = n_pad - n
    inv32 = np.concatenate([inv32, np.full(pn, INF32 - 1, np.int32)])
    ret32 = np.concatenate([ret32, np.full(pn, INF32, np.int32)])
    fop = np.concatenate([fop, np.zeros(pn, np.int32)])
    args = np.concatenate([args, np.zeros((pn, A), np.int32)])
    rets = np.concatenate([rets, np.zeros((pn, A), np.int32)])
    extra = (n_pad + 31) // 32 - len(ok_words)
    ok_words = np.concatenate([ok_words, np.zeros(extra, np.uint32)])
    st = np.asarray(init_state, np.int32)
    if len(st) < S_pad:
        if spec.pad_state is not None:
            st = np.asarray(spec.pad_state(st, S_pad), np.int32)
        else:
            raise ValueError(
                f"model {spec.name} has varying state sizes but no pad_state")
    return inv32, ret32, fop, args, rets, ok_words, st, perm


def _dummy_key(n_pad, S_pad, A):
    """All padding rows, no ok ops: exhausts on its first iteration."""
    return (np.full(n_pad, INF32 - 1, np.int32),
            np.full(n_pad, INF32, np.int32),
            np.zeros(n_pad, np.int32),
            np.zeros((n_pad, A), np.int32),
            np.zeros((n_pad, A), np.int32),
            np.zeros((n_pad + 31) // 32, np.uint32),
            np.zeros(S_pad, np.int32),
            None)


def keys_mesh(chips):
    """The 1-D ``keys`` mesh over the first ``chips`` devices: the layout
    ``engine_opts={"chips": n}`` asks the key batch to shard over."""
    from jax.sharding import Mesh
    devs = jax.devices()
    if not 1 <= chips <= len(devs):
        raise ValueError(f"keys_mesh: {chips} chips asked for, JAX has "
                         f"{len(devs)} devices")
    return Mesh(np.array(devs[:chips]), ("keys",))


def mesh_axis(mesh):
    """The one axis of a 1-D mesh; both mesh paths shard over it."""
    if len(mesh.axis_names) != 1:
        raise ValueError(f"a key or search mesh must be 1-D; this one "
                         f"has axes {tuple(mesh.axis_names)}")
    return mesh.axis_names[0]


def _shard_specs(mesh, n_carry=N_CARRY, n_consts=8):
    from jax.sharding import PartitionSpec as P
    ax = mesh_axis(mesh)
    carry_specs = tuple(P(ax) for _ in range(n_carry))
    const_specs = tuple(P(ax) for _ in range(n_consts - 1)) + (P(),)
    return carry_specs, const_specs


@functools.lru_cache(maxsize=64)
def _mesh_runner(step, k_local, n_pad, B, S_pad, C, A, W, O, T, R, NS,
                 mesh):
    """run_chunk of the key batch under ``shard_map`` over ``mesh``, built
    once per shape: a fresh ``jax.jit`` per check or per compaction would
    compile again every time. The kernel sees LOCAL shapes: ``k_local``
    keys and one table group per device."""
    carry_specs, const_specs = _shard_specs(mesh)
    run_local = _build_search(step, k_local, n_pad, B, S_pad, C, A, W, O,
                              T, 1, R=R, NS=NS,
                              rollout_kernel="scan").run_chunk
    return jax.jit(shard_map_compat(
        run_local.__wrapped__, mesh,
        (carry_specs,) + const_specs, carry_specs),
        donate_argnums=(0,))


def check_batch_encoded(spec, pairs, max_configs=50_000_000,
                        chunk_iters=256, timeout_s=None, mesh=None,
                        frontier_width=None, stack_size=None,
                        table_size=None, checkpoint=None,
                        checkpoint_every_s=60.0, rollout_seeds=None,
                        owners=None, n_floor=None):
    """Check many keys' histories at once.

    ``pairs`` is a list of (EncodedHistory, init_state). Returns a list of
    per-key result dicts (same shape as jax_wgl.check_encoded results).
    With ``mesh`` (a 1-D ``jax.sharding.Mesh``), keys shard over its first
    axis via shard_map; the batch is padded to a multiple of the axis size
    with dummy keys.

    ``owners`` (optional, parallel to ``pairs``) labels each key with
    the tenant that submitted it -- the fleet service's cross-tenant
    coalescer passes caller ids here. Pure metadata: it never reaches
    the device or the compile-ledger key (cross-tenant batches MUST
    hit the shapes campaigns already compiled), but the distinct-owner
    count of the searched keys lands in the padding-plan telemetry and
    every searched key's result carries it as ``batch_owners``, so a
    coalesced submission can see how many strangers shared its batch.

    ``n_floor`` (optional) overrides the campaign-tunable op-count
    bucket floor (``jax_wgl._n_floor``) for THIS batch: the service
    coalescer passes its group's (possibly capacity-plan-raised)
    bucket here so the batch compiles at the PLANNED shape rather
    than re-deriving a smaller one from the members' raw lengths.
    Only ever raises the pad (padding rows are inert), never lowers
    it below the shared floor.

    ``checkpoint`` names a file the batch state is periodically
    snapshotted to (every ``checkpoint_every_s``, between chunks):
    the compacted carry, the alive-row map, AND every already-harvested
    key's verdict, so a killed multi-key check rerun with the same
    arguments resumes mid-search instead of restarting (round 2 only
    checkpointed the single-key path -- a 10-hour independent run
    restarted from zero, VERDICT r2 weak #5). Snapshots carry a
    fingerprint of all per-key inputs + plan sizes + the carry-layout
    version; a stale or foreign file is ignored. Surfaced through the
    linearizable checker's engine_opts (independent's batched path
    passes them through).
    """
    K_real = len(pairs)
    if K_real == 0:
        return []

    # phase cursor (obs.phases): per-dispatch encode/plan/h2d/compile/
    # device/d2h/host attribution for the batch loop
    ph = obs_phases.capture("jax-wgl-batch")
    results = [None] * K_real
    live = []
    encs = {}
    for k, (e, st) in enumerate(pairs):
        if len(e) == 0 or e.n_ok == 0:
            results[k] = {"valid": True, "configs_explored": 0}
            continue
        enc = _encode_arrays(e)          # computed once, reused below
        fast = (spec.fast_check(e, enc[0], enc[1])
                if spec.fast_check is not None else None)
        if fast is None and spec.pad_state is None:
            fast = jax_wgl._state_abstraction_check(spec, e, st)
        if fast is not None:
            results[k] = jax_wgl._fast_result(spec, e, st, fast)
            continue
        inv32, ret32 = jax_wgl._apply_prune(spec, e, enc[0], enc[1])
        encs[k] = (inv32, ret32, enc[2])
        live.append(k)
    if not live:
        return results
    ph.lap("encode")

    # common bucket sizes across live keys (the op-count floor is the
    # campaign-tunable shared bucket, jax_wgl._n_floor; a caller may
    # RAISE it per batch -- the coalescer's planned-bucket path)
    n_pad = _bucket(max(len(pairs[k][0]) for k in live),
                    max(jax_wgl._n_floor(), int(n_floor or 1)))
    A = max(int(pairs[k][0].args.reshape(len(pairs[k][0]), -1).shape[1])
            for k in live)
    S_pad = max(len(pairs[k][1]) for k in live)
    if spec.pad_state is not None:
        S_pad = _bucket(S_pad, 2)
    C = 4
    for k in live:
        inv32, ret32, _ = encs[k]
        C = max(C, max_point_concurrency(
            inv32, np.where(ret32 == INF32, INF_TIME,
                            ret32.astype(np.int64))))
    C = min(_bucket(C, 4), n_pad)

    # shrink per-key budgets relative to single-key defaults: many keys
    # share the chip, and a narrow per-key frontier keeps the batched
    # search depth-first (wide frontiers degenerate to BFS over the whole
    # config space, which is catastrophic for valid histories)
    n_live = len(live)
    B, W, O, T = _plan_sizes(n_pad, S_pad, C, frontier_width, stack_size,
                             table_size)
    if frontier_width is None:
        # narrow per key as the batch grows, but never RAISE W above
        # what _plan_sizes chose -- its (W, C, S) memory cap must
        # survive (a max(32, ...) floor here once re-inflated a
        # capped-for-big-states W and rebuilt the crash tensor)
        W = min(W, max(32, 4096 // _bucket(n_live, 1)))
    O = max(4096, O // _bucket(min(n_live, 8), 1))
    max_iters = max(1, max_configs // (W * n_live))
    if rollout_seeds is None:
        # batches roll ONE greedy chain per key: the chip is already
        # filled by the key axis and extra seeds measured ~1.4x pure
        # overhead (PROFILE.md round 4). Pinned here explicitly so a
        # batch compacted down to one key (or mesh shards of one key
        # each) can't silently flip into the single-key NS=8 regime.
        rollout_seeds = 1
    # likewise pin the batch rollout depth: the single-key default
    # deepened to R=1024 in round 5 (fused-kernel regime), but on the
    # batch's NS=1 scan chains a deep rollout is 4x the wall per
    # iteration exactly where straggler chains wedge -- keep the
    # measured R=256, including for a batch compacted down to one key
    R_batch = 0 if n_pad <= 64 else min(256, n_pad)

    cols = [_pad_key(pairs[k][0], pairs[k][1], spec, n_pad, S_pad, A,
                     encs[k])
            for k in live]
    salts = [np.uint32(k + 1) for k in live]
    # pad the key batch with dummy keys (exhaust immediately) up to a power
    # of two (and a multiple of the mesh axis) so compiled batch sizes are
    # reused and compaction steps hit the same buckets
    K = _bucket(len(cols), 1)
    G = 1
    if mesh is not None:
        G = int(mesh.shape[mesh_axis(mesh)])
        while K % G:
            K += 1
    while len(cols) < K:
        cols.append(_dummy_key(n_pad, S_pad, A))
        salts.append(np.uint32(0))
    # cross-run compile-reuse ledger (campaign.compile_cache): the key
    # mirrors the initial _build_search lru/jit key; compaction
    # rebuilds mid-search are not separately accounted
    ph.note_compile(jax_wgl._note_compile(
        "jax-wgl-batch",
        (spec.name, K, W, n_pad, B, S_pad, C, A, O, T, G, R_batch,
         rollout_seeds, mesh is not None)))
    ph.lap("plan")
    perms = [c[7] for c in cols]          # host-only: witness decoding
    consts = tuple(jnp.asarray(np.stack([c[i] for c in cols]))
                   for i in range(7)) + (jnp.asarray(np.asarray(salts)),)
    init_states = consts[6]
    consts = consts[:6] + (consts[7],)   # drop states, keep salt

    def _keyed_sharding():
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(mesh, P(mesh_axis(mesh)))

    def build_runner(Kc, Wc):
        """run_chunk for a (possibly compacted/resumed) batch width."""
        if mesh is None:
            # the batch path keeps the lax.scan rollout even when a
            # compaction shrinks it to one key: its NS=1 chain is not
            # the bottleneck and the measured numbers are scan-based
            return _build_search(spec.step, Kc, n_pad, B, S_pad, C, A,
                                 Wc, O, T, G, R=R_batch,
                                 NS=rollout_seeds,
                                 rollout_kernel="scan").run_chunk
        return _mesh_runner(spec.step, Kc // G, n_pad, B, S_pad, C, A,
                            Wc, O, T, R_batch, rollout_seeds, mesh)

    def wide_W(Kc):
        # budget lanes per DEVICE (each shard runs Kc // G keys),
        # honoring the same (W, C, S) ~256 MB step-tensor cap as
        # _plan_sizes -- widening a compacted straggler with a big
        # padded state would otherwise rebuild the crash tensor
        return max(W, min(2048, 4096 // max(1, Kc // G),
                          max(8, (64 << 20) // max(1, C * S_pad))))

    def consts_for(alive_rows):
        sel = [cols[j] if j >= 0 else _dummy_key(n_pad, S_pad, A)
               for j in alive_rows]
        salt = np.asarray([np.uint32(live[j] + 1) if j >= 0
                           else np.uint32(0) for j in alive_rows])
        out = tuple(jnp.asarray(np.stack([c[i] for c in sel]))
                    for i in range(6)) + (jnp.asarray(salt),)
        if mesh is not None:
            out = tuple(jax.device_put(x, _keyed_sharding())
                        for x in out)
        return out

    fingerprint = resumed = None
    if checkpoint is not None:
        # max_iters is deliberately NOT part of the fingerprint: a
        # budget-exhausted snapshot must resume under a LARGER budget
        # instead of restarting (mirrors the single-key path)
        fingerprint = _batch_fingerprint(
            spec, cols, salts,
            (n_pad, B, S_pad, C, A, W, O, T, G, K))
        resumed = _load_batch_checkpoint(checkpoint, fingerprint)
        if resumed is None and not jax_wgl._checkpoint_owned(
                checkpoint, fingerprint):
            logger.warning(
                "checkpoint %s belongs to a different check; "
                "checkpointing disabled for this run", checkpoint)
            checkpoint = None

    if resumed is not None:
        carry_np, alive, it, harvested = resumed
        consts = consts_for(alive)
        run_b = build_runner(len(alive),
                             W if len(alive) == K else wide_W(len(alive)))
        if mesh is not None:
            carry = tuple(jax.device_put(np.asarray(x), _keyed_sharding())
                          for x in carry_np)
        else:
            carry = tuple(jnp.asarray(x) for x in carry_np)
    else:
        init_carry, run_chunk, _ = _build_search(
            spec.step, K, n_pad, B, S_pad, C, A, W, O, T, G, R=R_batch,
            NS=rollout_seeds, rollout_kernel="scan")
        run_b = build_runner(K, W) if mesh is not None else run_chunk
        carry = init_carry(init_states)
        if mesh is not None:
            consts = tuple(jax.device_put(x, _keyed_sharding())
                           for x in consts)
            carry = tuple(jax.device_put(np.asarray(x), _keyed_sharding())
                          for x in carry)
        # alive[r] = index into `live` for row r, or -1 for dummy rows
        alive = [j if j < len(live) else -1 for j in range(K)]
        harvested = {}
        it = 0
    ph.sync(carry)
    ph.lap("h2d")
    t0 = _time.monotonic()
    last_ckpt = t0
    timed_out = False
    n_compactions = 0
    # per-chip work under a mesh, from the explored counters each poll
    # already fetches: each live key's growth since the last poll goes
    # to the chip that holds its row (work done before a checkpoint
    # resume goes to the chip the key resumes on; keys harvested before
    # the resume are not credited)
    shard_explored = credited = None
    if mesh is not None:
        shard_explored = np.zeros(G, np.int64)
        credited = np.zeros(len(live), np.int64)
    # sinks captured once at search start (see obs.search docstring)
    so = obs_search.capture()
    # padding accounting: the batch pads every live key to the common
    # n_pad bucket AND pads the key axis to a power of two (dummy
    # keys), so real rows = the live keys' actual op counts against
    # K * n_pad padded rows — the per-bucket waste the campaign fold
    # tables
    n_owners = len({str(owners[k]) for k in live}) \
        if owners is not None else None
    so.plan("jax-wgl-batch", n_pad,
            sum(len(pairs[k][0]) for k in live), K * n_pad,
            keys=len(live), lanes=K, owners=n_owners)
    # adaptive dispatch quantum (jax_wgl._adapt_quantum, shared with
    # the single-key loop): calibrated from the measured per-iteration
    # wall. The batch targets ~1 s per dispatch (shorter than the
    # single-key 3 s: harvest/compaction polls between dispatches are
    # load-bearing here), still capped by the live-width term below
    # and by ``chunk_iters``.
    eff_chunk = max(1, min(chunk_iters, 8, (8 * 16384) // n_pad))

    def harvest(rows, carry):
        fields = {"status": carry[IDX_STATUS], "top": carry[IDX_TOP],
                  "dropped": carry[IDX_DROPPED],
                  "explored": carry[IDX_EXPLORED],
                  "iterations": carry[IDX_ITS],
                  "best_depth": carry[IDX_BEST_DEPTH],
                  "best_lin": carry[IDX_BEST_LIN],
                  "best_state": carry[IDX_BEST_STATE]}
        ph.lap("host")
        got = jax.device_get(fields)
        ph.lap("d2h")
        for r in rows:
            if alive[r] >= 0:
                harvested[alive[r]] = {k: np.asarray(v)[r]
                                       for k, v in got.items()}

    while True:
        bound = min(it + eff_chunk, max_iters)
        t_chunk = _time.monotonic()
        prev_it = it
        ph.lap("host")
        carry = run_b(carry, *consts, jnp.int32(bound))
        # device-compute bracket: sync only while phase attribution is
        # on (the progress device_get below stays the sole sync
        # otherwise, as before)
        ph.sync(carry)
        dev_s = ph.lap("device", iteration=bound)
        it = bound
        # the dispatch returns asynchronously: sync on ONE batched
        # device_get of the whole progress tensor BEFORE measuring the
        # chunk's wall time. This replaces the old three separate
        # np.asarray transfers (status/top/its) with a single host
        # round-trip that now also carries the per-key explored
        # counters and witness depths — per-chunk progress telemetry
        # at strictly FEWER round trips than before (the old loop
        # deliberately skipped explored to save a separate
        # device_get sync)
        status, top, its, explored_k, bdepth = jax.device_get(
            (carry[IDX_STATUS], carry[IDX_TOP], carry[IDX_ITS],
             carry[IDX_EXPLORED], carry[IDX_BEST_DEPTH]))
        status = np.asarray(status)
        ph.lap("d2h")
        now = _time.monotonic()
        per_it = max(1e-4, (now - t_chunk) / max(1, it - prev_it))
        # chunk granularity shrinks as the live batch width grows or
        # the whole run completes inside ONE dispatch and compaction
        # never fires (measured at K=256: a single 256-iteration chunk
        # ate 23 s, with 25 exhaustion-proof stragglers dragging 231
        # finished keys' lanes the whole way)
        width_cap = max(4, chunk_iters * 8 // max(16, len(alive)))
        eff_chunk = jax_wgl._adapt_quantum(
            min(chunk_iters, width_cap), per_it, 1.0,
            timeout_s - (now - t0) if timeout_s is not None else None)
        top = np.asarray(top)
        if logger.isEnabledFor(logging.DEBUG):
            # from the arrays the batched device_get above already
            # fetched: a debug log must not add a device round trip
            logger.debug(
                "chunk to it=%d: %.3fs, K=%d running=%d", it,
                _time.monotonic() - t_chunk, len(alive),
                int(((status == RUNNING) & (top > 0)).sum()))
        its = np.asarray(its)
        running = (status == RUNNING) & (top > 0) & (its < max_iters)
        n_run = int(running.sum())
        # heartbeat from the arrays the batched device_get above
        # already fetched — live batch explored sums LIVE rows only
        # (compaction pads with a copy of a finished row, whose
        # explored count must not double) plus what already-harvested
        # keys contributed before their rows were compacted away, so
        # the gauge stays monotone across compactions
        explored_k = np.asarray(explored_k)
        bdepth = np.asarray(bdepth)
        shard_tops = None
        if mesh is not None:
            # credited here, before a compaction below moves any row
            rows_per_chip = len(alive) // G
            alive_np = np.asarray(alive)
            rows = np.flatnonzero(alive_np >= 0)
            keys_at = alive_np[rows]
            grown = explored_k[rows].astype(np.int64) - credited[keys_at]
            np.add.at(shard_explored, rows // rows_per_chip, grown)
            credited[keys_at] += grown
            shard_tops = np.bincount(
                np.flatnonzero(running) // rows_per_chip, minlength=G)
        so.heartbeat(
            "jax-wgl-batch", iteration=it,
            chunk_s=_time.monotonic() - t_chunk,
            device_s=dev_s if ph.enabled else None,
            frontier=int(top.sum()),
            explored=sum(int(explored_k[r])
                         for r in range(len(alive)) if alive[r] >= 0)
            + sum(int(h["explored"]) for h in harvested.values()),
            depth=max(0, int(bdepth.max())),
            keys_alive=len(alive), keys_running=n_run,
            compactions=n_compactions, shard_tops=shard_tops)
        if n_run == 0:
            harvest(range(len(alive)), carry)
            break
        now = _time.monotonic()
        if checkpoint is not None and now - last_ckpt >= checkpoint_every_s:
            _save_batch_checkpoint(checkpoint, fingerprint, carry,
                                   alive, it, harvested)
            last_ckpt = now
        if timeout_s is not None and now - t0 > timeout_s:
            # the post-loop not-all-decided save writes the snapshot
            timed_out = True
            harvest(range(len(alive)), carry)
            break
        # Compact the batch once most keys are done: stragglers (deep
        # exhaustion proofs) would otherwise drag every finished key's
        # lanes through thousands more lockstep iterations. As the batch
        # shrinks, widen the per-key frontier to keep the chip busy --
        # carries are W-independent, so the wider kernel picks up the
        # straggler's stack and dedup table as-is.
        if len(alive) > G and n_run <= len(alive) // 2:
            n_compactions += 1
            done_rows = [r for r in range(len(alive)) if not running[r]]
            harvest(done_rows, carry)
            keep = [r for r in range(len(alive)) if running[r]]
            newK = _bucket(n_run, 1)
            while newK % G:            # keep a whole number of keys per
                newK += 1              # device under a mesh
            pad_row = done_rows[0]
            idx = keep + [pad_row] * (newK - n_run)
            span = contextlib.nullcontext()
            if mesh is not None:
                # a key moves when its chip before and after differ
                moved = sum(r // (len(alive) // G) != i // (newK // G)
                            for i, r in enumerate(keep))
                span = obs.span("keyshard.compact", cat="search", chips=G,
                                keys_before=len(alive), keys_after=newK,
                                moved=moved)
            with span:
                sel = jnp.asarray(np.asarray(idx, np.int32))
                carry = tuple(jnp.take(c, sel, axis=0) if i in KEYED
                              else c for i, c in enumerate(carry))
                consts = tuple(jnp.take(c, sel, axis=0) for c in consts)
                alive = [alive[r] for r in keep] + [-1] * (newK - n_run)
                # widen per-key frontiers as the batch shrinks; under a
                # mesh, keys reshard and a moved key misses its old
                # device's dedup entries (key-salted, so only a perf
                # cost, never a correctness one)
                run_b = build_runner(newK, wide_W(newK))
                if mesh is not None:
                    carry = tuple(jax.device_put(x, _keyed_sharding())
                                  if i in KEYED else x
                                  for i, x in enumerate(carry))
                    consts = tuple(jax.device_put(x, _keyed_sharding())
                                   for x in consts)

    # never clobber a snapshot that belongs to a DIFFERENT check: the
    # path may have been (re)claimed by a concurrent run since startup
    if checkpoint is not None and jax_wgl._checkpoint_owned(checkpoint,
                                                            fingerprint):
        import os
        all_decided = (not timed_out and len(harvested) == len(live)
                       and all(int(h["status"]) != RUNNING
                               or int(h["top"]) == 0
                               for h in harvested.values()))
        if all_decided:
            with contextlib.suppress(FileNotFoundError):
                os.unlink(checkpoint)
        else:
            _save_batch_checkpoint(checkpoint, fingerprint, carry,
                                   alive, it, harvested)

    # the dedup table is shared across keys (key-salted), so occupancy
    # diagnostics are batch-wide: the same numbers go on every searched
    # key's result (summed over table groups under a mesh)
    ph.lap("host")
    tstats = table_stats(carry)
    ph.lap("d2h")
    for j, k in enumerate(live):
        per = harvested[j]
        if (timed_out and int(per["status"]) == RUNNING
                and int(per["top"]) > 0):
            results[k] = {"valid": "unknown", "error": "timeout",
                          "configs_explored": int(per["explored"]),
                          "engine": "jax-wgl"}
        else:
            results[k] = jax_wgl._interpret(spec, pairs[k][0], per,
                                            max_iters, False, pairs[k][1],
                                            perms[j])
        results[k].update(tstats)
        # batch-wide diagnostic: how often stragglers were compacted
        # (and, under a mesh, resharded) during this run
        results[k]["compactions"] = n_compactions
        if n_owners is not None:
            results[k]["batch_owners"] = n_owners
        if shard_explored is not None:
            # batch-wide, like the table stats: the search each chip ran
            results[k]["shard_explored"] = shard_explored.tolist()
    if so.enabled():
        so.summary(
            "jax-wgl-batch",
            {"valid": "batch",
             "configs_explored": sum(
                 int(h["explored"]) for h in harvested.values()),
             "iterations": max(
                 (int(h["iterations"]) for h in harvested.values()),
                 default=0),
             **tstats},
            keys=len(live),
            shard_explored=None if shard_explored is None
            else shard_explored.tolist())
    ph.lap("host")
    return results


_HARVEST_FIELDS = ("status", "top", "dropped", "explored", "iterations",
                   "best_depth", "best_lin", "best_state")


def _batch_fingerprint(spec, cols, salts, plan):
    """sha256 over the carry-layout version, model, every padded per-key
    input column, the salts, and the plan sizes."""
    import hashlib
    h = hashlib.sha256()
    h.update(jax_wgl.CARRY_LAYOUT.encode())
    h.update(spec.name.encode())
    h.update(np.asarray(plan, np.int64).tobytes())
    h.update(np.asarray(salts).tobytes())
    for c in cols:
        for i in range(7):                     # perm (c[7]) is derived
            h.update(np.ascontiguousarray(c[i]).tobytes())
    return h.hexdigest()


def _save_batch_checkpoint(path, fingerprint, carry, alive, it,
                           harvested):
    """Atomic snapshot: carry + alive map + already-harvested verdicts
    (the fingerprint/atomic-write machinery is shared with the
    single-key path, jax_wgl.write_snapshot)."""
    host = [np.asarray(x) for x in jax.device_get(carry)]
    hk = sorted(harvested)
    arrays = {f"c{i}": x for i, x in enumerate(host)}
    arrays.update(alive=np.asarray(alive, np.int64),
                  it=np.int64(it),
                  hkeys=np.asarray(hk, np.int64))
    for name in _HARVEST_FIELDS:
        if hk:
            arrays[f"h_{name}"] = np.stack(
                [np.asarray(harvested[j][name]) for j in hk])
    jax_wgl.write_snapshot(path, fingerprint, arrays)


def _load_batch_checkpoint(path, fingerprint):
    """-> (carry arrays, alive list, it, harvested dict) or None."""
    data = jax_wgl.read_snapshot(path, fingerprint)
    if data is None:
        return None
    try:
        n_carry = sum(1 for k in data if k.startswith("c")
                      and k[1:].isdigit())
        carry = [data[f"c{i}"] for i in range(n_carry)]
        alive = [int(x) for x in data["alive"]]
        it = int(data["it"])
        harvested = {}
        for pos, j in enumerate(int(x) for x in data["hkeys"]):
            harvested[j] = {name: data[f"h_{name}"][pos]
                            for name in _HARVEST_FIELDS}
        return carry, alive, it, harvested
    except Exception:  # noqa: BLE001 - corrupt snapshot = start fresh
        return None


def check_batch_histories(spec, histories, **kw):
    """Encode per-key event histories and check them all on device."""
    pairs = [spec.encode(hist) for hist in histories]
    return check_batch_encoded(spec, pairs, **kw)
