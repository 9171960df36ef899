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
them and is built on the device that holds it, so each device runs its
shard's searches independently over ICI-local memory with no collectives
in the hot loop (embarrassingly parallel, the right layout for this
workload; SURVEY.md section 5).

Keys finish at different times; the host loop (``checker/drive.py``)
polls per-key status between bounded chunks, harvests finished keys, and
*compacts* the batch (power-of-two buckets) so stragglers don't drag
finished keys' lanes along -- widening the per-key frontier as the batch
shrinks to keep the chip busy.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..checker import drive, jax_wgl
from ..checker.jax_wgl import (INF32, N_CARRY, _bucket, _build_search,
                               _encode_arrays, _plan_sizes,
                               max_point_concurrency)
from ..history import INF_TIME
from ..obs import phases as obs_phases


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


@functools.lru_cache(maxsize=64)
def _mesh_search(step, k_local, n_pad, B, S_pad, C, A, W, O, T, R, NS,
                 mesh, steal=None):
    """``(init_carry, run_chunk)`` under ``shard_map`` over ``mesh``,
    built once per shape: a fresh ``jax.jit`` per check or per
    compaction would compile again every time. Both see LOCAL shapes:
    ``k_local`` rows and one table group per device, which is exactly
    that device's shard of the full-width carry, so the init builds
    each chip's rows where they live and nothing goes through the host.
    ``steal`` (the hand-off size) makes it the steal ring's one search
    over the mesh; without it the rows are keys and the chips share no
    collective. The replication check is off: the ring's collectives
    are not replicated."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    ax = mesh_axis(mesh)
    ring = {} if steal is None else dict(
        axis_name=ax, axis_size=int(mesh.shape[ax]), steal=steal)
    local = _build_search(step, k_local, n_pad, B, S_pad, C, A, W, O, T,
                          1, R=R, NS=NS, rollout_kernel="scan", **ring)
    # every carry element and op column shards over the axis; the
    # dispatch bound is replicated
    keyed = tuple(P(ax) for _ in range(N_CARRY))
    specs = (keyed,) + (P(ax),) * 7 + (P(),)
    init = jax.jit(shard_map(local.init_carry, mesh=mesh,
                             in_specs=(P(ax),), out_specs=keyed,
                             check_vma=False))
    run = jax.jit(shard_map(local.run_chunk.__wrapped__, mesh=mesh,
                            in_specs=specs, out_specs=keyed,
                            check_vma=False),
                  donate_argnums=(0,))
    return init, run


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
    put = jnp.asarray
    if mesh is not None:
        # the host columns go straight to the chips that hold their rows
        from jax.sharding import NamedSharding, PartitionSpec as P
        keyed = NamedSharding(mesh, P(mesh_axis(mesh)))
        put = functools.partial(jax.device_put, device=keyed)
    consts = tuple(put(np.stack([c[i] for c in cols]))
                   for i in range(7)) + (put(np.asarray(salts)),)
    init_states = consts[6]
    consts = consts[:6] + (consts[7],)   # drop states, keep salt

    def wide_W(Kc):
        # budget lanes per DEVICE (each shard runs Kc // G keys),
        # honoring the same (W, C, S) ~256 MB step-tensor cap as
        # _plan_sizes -- widening a compacted straggler with a big
        # padded state would otherwise rebuild the crash tensor
        return max(W, min(2048, 4096 // max(1, Kc // G),
                          max(8, (64 << 20) // max(1, C * S_pad))))

    def build_runner(Kc):
        """run_chunk for a batch of Kc rows: the planned width, or a
        compacted batch's wider per-key frontier."""
        Wc = W if Kc == K else wide_W(Kc)
        if mesh is None:
            # the batch path keeps the lax.scan rollout even when a
            # compaction shrinks it to one key: its NS=1 chain is not
            # the bottleneck and the measured numbers are scan-based
            return _build_search(spec.step, Kc, n_pad, B, S_pad, C, A,
                                 Wc, O, T, G, R=R_batch,
                                 NS=rollout_seeds,
                                 rollout_kernel="scan").run_chunk
        return _mesh_search(spec.step, Kc // G, n_pad, B, S_pad, C, A,
                            Wc, O, T, R_batch, rollout_seeds, mesh)[1]

    if mesh is None:
        init_carry = _build_search(
            spec.step, K, n_pad, B, S_pad, C, A, W, O, T, G, R=R_batch,
            NS=rollout_seeds, rollout_kernel="scan").init_carry
    else:
        # each chip builds its own rows where they live: no chip holds
        # the full width, and no carry element passes through the host
        init_carry = _mesh_search(spec.step, K // G, n_pad, B, S_pad, C,
                                  A, W, O, T, R_batch, rollout_seeds,
                                  mesh)[0]
    run = build_runner(K)
    carry = init_carry(init_states)
    if mesh is not None:
        obs.inc("keyshard.sharded_init_bytes",
                sum(x.nbytes for x in carry), chips=G)
    # the distinct tenants among the searched keys
    n_owners = len({str(owners[k]) for k in live}) \
        if owners is not None else None
    # max_iters is deliberately NOT in the fingerprint: a
    # budget-exhausted snapshot must resume under a LARGER budget
    out = drive.drive(
        ph, "jax-wgl-batch", spec,
        [(pairs[k][0], pairs[k][1], c[7]) for k, c in zip(live, cols)],
        carry, consts, run, max_iters=max_iters, chunk_iters=chunk_iters,
        timeout_s=timeout_s, keyed=True, mesh=mesh, rebuild=build_runner,
        owners=n_owners, checkpoint=checkpoint,
        checkpoint_every_s=checkpoint_every_s,
        snapshot_of=(cols, salts, (n_pad, B, S_pad, C, A, W, O, T, G, K)))
    for k, r in zip(live, out):
        results[k] = r
    return results


def check_batch_histories(spec, histories, **kw):
    """Encode per-key event histories and check them all on device."""
    pairs = [spec.encode(hist) for hist in histories]
    return check_batch_encoded(spec, pairs, **kw)
