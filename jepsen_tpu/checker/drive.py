"""One host loop for the device search.

Every device search is ``jax_wgl._build_search``'s bounded
``while_loop``, driven from the host one dispatch at a time. Three
entry points set a search up and hand it to :func:`drive`:

* ``jax_wgl.check_encoded``: one key, one row (engine ``jax-wgl``);
* ``parallel.keyshard.check_batch_encoded``: a batch of keys, one row a
  key, optionally sharded over a 1-D ``keys`` mesh (``jax-wgl-batch``);
* ``parallel.searchshard.check_encoded_sharded``: one key's search
  spread over a mesh by the steal ring, one row a shard
  (``jax-wgl-sharded``).

The loop owns what they share: the dispatch and its phase laps (``host``,
``device``, ``d2h``), ONE batched progress ``device_get`` a dispatch,
the heartbeat, the adaptive quantum (``jax_wgl._adapt_quantum``), the
timeout and ``cancel`` check, checkpoints, the final harvest and the
interpretation of each key's verdict (``jax_wgl._interpret``). What is
left to the entry points is what really differs: the runner, the op
columns and the initial carry, and, for the key batch, how to rebuild
the runner when stragglers are compacted.

A batch (``keyed``) reports one result a key; the single key and the
steal ring report one result, merged over their rows.
"""

from __future__ import annotations

import contextlib
import logging
import os
import time as _time

import numpy as np

import jax
import jax.numpy as jnp

from .. import obs
from ..obs import search as obs_search
from . import jax_wgl
from .jax_wgl import (IDX_BEST_DEPTH, IDX_BEST_LIN, IDX_BEST_STATE,
                      IDX_DROPPED, IDX_EXPLORED, IDX_ITS, IDX_STATUS,
                      IDX_TOP, KEYED, RUNNING, VALID)

logger = logging.getLogger(__name__)

#: the carry fields each row's verdict is read from
HARVEST = {"status": IDX_STATUS, "top": IDX_TOP, "dropped": IDX_DROPPED,
           "explored": IDX_EXPLORED, "iterations": IDX_ITS,
           "best_depth": IDX_BEST_DEPTH, "best_lin": IDX_BEST_LIN,
           "best_state": IDX_BEST_STATE}

#: hashed into every snapshot fingerprint: the carry layout plus the
#: snapshot's own layout (carry, alive map, iteration, harvested rows),
#: so a snapshot of any other layout is foreign and never misread
SNAPSHOT_LAYOUT = (f"{jax_wgl.CARRY_LAYOUT};"
                   "snapshot-v2:carry,alive,it,harvested")


def fingerprint(spec, cols, salts, plan):
    """sha256 over the snapshot layout, the model, the plan sizes, the
    key salts and every row's padded input columns (op columns and
    initial state)."""
    import hashlib
    h = hashlib.sha256()
    h.update(SNAPSHOT_LAYOUT.encode())
    h.update(spec.name.encode())
    h.update(np.asarray(plan, np.int64).tobytes())
    h.update(np.asarray(salts, np.uint32).tobytes())
    for c in cols:
        for a in c[:7]:
            h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def write_snapshot(path, fingerprint, arrays):
    """Atomically write a fingerprinted npz snapshot."""
    tmp = f"{path}.tmp"     # np.savez appends .npz to names without it
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    np.savez_compressed(
        tmp, fingerprint=np.frombuffer(fingerprint.encode(), np.uint8),
        **arrays)
    os.replace(f"{tmp}.npz", path)


def _owned(path, fingerprint):
    """True when ``path`` is free (absent or corrupt) or holds a snapshot
    with this fingerprint."""
    try:
        with np.load(path) as data:
            return bytes(data["fingerprint"]).decode() == fingerprint
    except Exception:  # noqa: BLE001 - absent or corrupt: free
        return True


def _load(path, fingerprint):
    """-> (carry arrays, alive list, it, harvested dict), or None when
    the file is absent, corrupt, or another check's."""
    try:
        with np.load(path) as data:
            if bytes(data["fingerprint"]).decode() != fingerprint:
                return None
            n_carry = sum(1 for k in data.files
                          if k[0] == "c" and k[1:].isdigit())
            harvested = {int(j): {name: data[f"h_{name}"][pos]
                                  for name in HARVEST}
                         for pos, j in enumerate(data["hkeys"])}
            return ([data[f"c{i}"] for i in range(n_carry)],
                    [int(x) for x in data["alive"]], int(data["it"]),
                    harvested)
    except Exception:  # noqa: BLE001 - absent or corrupt: start fresh
        return None


def _merge(rows):
    """The rows of one search (the steal ring's shards, or the single
    key's one row) as one: valid when any row is, exhausted when every
    row is, its TOPK witness slots pooled (the decoder sorts them by
    depth)."""
    valid = any(int(r["status"]) == VALID for r in rows)
    return {"status": VALID if valid else RUNNING,
            "top": sum(int(r["top"]) for r in rows),
            "dropped": any(bool(r["dropped"]) for r in rows),
            "explored": sum(int(r["explored"]) for r in rows),
            "iterations": max(int(r["iterations"]) for r in rows),
            **{k: np.concatenate([r[k] for r in rows])
               for k in ("best_depth", "best_lin", "best_state")}}


def drive(ph, engine, spec, keys, carry, consts, run, *, max_iters,
          chunk_iters, timeout_s=None, cancel=None, confirm=False,
          keyed=False, mesh=None, rebuild=None, owners=None,
          checkpoint=None, checkpoint_every_s=60.0, snapshot_of=None):
    """Run a search set up by one of the entry points to its end and
    return its results: one per key of ``keys`` when ``keyed``, else one.

    ``ph`` is the entry point's phase session, lapped up to ``plan``;
    the h2d lap that follows covers the carry and op columns it built.
    ``keys`` holds each key's (EncodedHistory, init_state, priority
    perm). ``carry`` / ``consts`` / ``run`` are the device batch and
    its ``run_chunk(carry, *consts, bound)``; row ``r`` holds key ``r``
    (dummy rows beyond ``len(keys)`` in a batch), or shard ``r`` of
    the one search.

    For a ``keyed`` batch the loop polls at a ~1 s quantum capped by the
    batch width, and compacts stragglers once half the rows are done,
    running ``rebuild(K)`` for the narrower runner. Otherwise it polls
    at ~3 s, and any row's success stops the search. Under ``mesh``
    rows shard over its one axis, and each chip's share of the work is
    reported as ``shard_explored``.

    ``checkpoint`` names a snapshot file, written every
    ``checkpoint_every_s`` and kept while the search is undecided;
    ``snapshot_of`` = (cols, salts, plan) are its fingerprint's inputs
    (:func:`fingerprint`)."""
    K0 = carry[IDX_TOP].shape[0]
    n_pad = consts[0].shape[1]
    G = 1 if mesh is None else int(mesh.devices.size)
    # alive[r] = the key row r holds, -1 for a dummy row; one search
    # keeps a slot a row (a shard) and merges them at the end
    alive = [r if r < len(keys) or not keyed else -1 for r in range(K0)]
    harvested = {}
    it = 0

    def shard(x):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return jax.device_put(x, NamedSharding(mesh,
                                               P(mesh.axis_names[0])))

    fp = None
    if checkpoint is not None:
        fp = fingerprint(spec, *snapshot_of)
        resumed = _load(checkpoint, fp)
        if resumed is not None:
            carry, alive, it, harvested = resumed
            if len(alive) != K0:
                # compacted before the snapshot: dummy rows' columns are
                # never read (their carry rows are done)
                sel = jnp.asarray([max(j, 0) for j in alive], jnp.int32)
                consts = tuple(jnp.take(c, sel, axis=0) for c in consts)
                run = rebuild(len(alive))
            put = jnp.asarray if mesh is None else shard
            carry = tuple(put(np.asarray(x)) for x in carry)
            if mesh is not None:
                consts = tuple(shard(c) for c in consts)
        elif not _owned(checkpoint, fp):
            # another check's live snapshot: leave it be
            logger.warning("checkpoint %s belongs to a different check; "
                           "checkpointing disabled for this run",
                           checkpoint)
            checkpoint = None
    ph.sync(carry)
    ph.lap("h2d")

    t0 = last_ckpt = _time.monotonic()
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
        credited = np.zeros(max(alive) + 1, np.int64)
    # sinks captured once at search start (see obs.search docstring)
    so = obs_search.capture()
    # padding accounting: real op rows against padded rows; a batch
    # also pads its key axis (dummy keys), while a steal ring's copies
    # of the op columns are sharding, not padding
    lanes = K0 if keyed else 1
    so.plan(engine, n_pad, sum(len(k[0]) for k in keys), lanes * n_pad,
            **(dict(keys=len(keys), lanes=lanes, owners=owners)
               if keyed else {}))
    # first quantum small enough to calibrate cheaply even at huge
    # shapes; _adapt_quantum takes over after it. A batch targets ~1 s
    # (its harvest and compaction polls between dispatches matter), one
    # search ~3 s
    q, target_s = (8, 1.0) if keyed else (32, 3.0)
    eff = max(1, min(chunk_iters, q, q * 16384 // n_pad))

    def fetch():
        return jax.device_get({k: carry[i] for k, i in HARVEST.items()})

    def keep(rows, got):
        for r in rows:
            if alive[r] >= 0:
                harvested[alive[r]] = {k: np.asarray(v)[r]
                                       for k, v in got.items()}

    def save():
        arrays = {f"c{i}": np.asarray(x)
                  for i, x in enumerate(jax.device_get(carry))}
        hk = sorted(harvested)
        arrays.update(alive=np.asarray(alive, np.int64), it=np.int64(it),
                      hkeys=np.asarray(hk, np.int64))
        for name in HARVEST if hk else ():
            arrays[f"h_{name}"] = np.stack(
                [np.asarray(harvested[j][name]) for j in hk])
        write_snapshot(checkpoint, fp, arrays)

    while True:
        prev_it = it
        it = min(it + eff, max_iters)
        t_chunk = _time.monotonic()
        ph.lap("host")
        carry = run(carry, *consts, jnp.int32(it))
        # device-compute bracket: the sync exists only while phase
        # attribution is on (otherwise the progress fetch below is the
        # dispatch's one sync)
        ph.sync(carry)
        dev_s = ph.lap("device", iteration=it)
        # ONE host round trip for the whole progress tensor
        status, top, its, explored, bdepth = map(np.asarray, jax.device_get(
            (carry[IDX_STATUS], carry[IDX_TOP], carry[IDX_ITS],
             carry[IDX_EXPLORED], carry[IDX_BEST_DEPTH])))
        ph.lap("d2h")
        now = _time.monotonic()
        per_it = max(1e-4, (now - t_chunk) / max(1, it - prev_it))
        running = (status == RUNNING) & (top > 0) & (its < max_iters)
        n_run = int(running.sum())
        rows = np.flatnonzero(np.asarray(alive) >= 0)
        beat = {}
        if keyed:
            beat = dict(keys_alive=len(alive), keys_running=n_run,
                        compactions=n_compactions)
        if mesh is not None:
            # credited before a compaction below moves any row
            chip = np.arange(len(alive)) // (len(alive) // G)
            keys_at = np.asarray(alive)[rows]
            grown = explored[rows].astype(np.int64) - credited[keys_at]
            np.add.at(shard_explored, chip[rows], grown)
            credited[keys_at] += grown
            beat["shard_tops"] = np.bincount(
                chip, weights=np.where(running, top, 0), minlength=G)
        # the live rows' explored counts plus what harvested keys did
        # before compaction took their rows: monotone across compactions
        so.heartbeat(
            engine, iteration=it, chunk_s=_time.monotonic() - t_chunk,
            device_s=dev_s if ph.enabled else None,
            frontier=int(top.sum()),
            explored=int(explored[rows].sum())
            + sum(int(h["explored"]) for h in harvested.values()),
            depth=max(0, int(bdepth.max())), **beat)
        if n_run == 0 or it >= max_iters or (
                not keyed and (status == VALID).any()):
            break
        cap = chunk_iters
        if keyed:
            # the quantum shrinks as the live batch widens, or a whole
            # run fits one dispatch and compaction never fires
            cap = min(cap, max(4, chunk_iters * 8 // max(16, len(alive))))
        eff = jax_wgl._adapt_quantum(
            cap, per_it, target_s,
            timeout_s - (now - t0) if timeout_s is not None else None)
        if checkpoint is not None and now - last_ckpt >= checkpoint_every_s:
            save()
            last_ckpt = now
        if (timeout_s is not None and now - t0 > timeout_s) or \
                (cancel is not None and cancel.is_set()):
            timed_out = True
            break
        # Compact the batch once most keys are done: stragglers (deep
        # exhaustion proofs) would otherwise drag every finished key's
        # lanes through thousands more lockstep iterations. Carries do
        # not depend on the frontier width, so the rebuilt runner
        # (wider per key) picks up each straggler's stack and table
        # as they are.
        if keyed and len(alive) > G and n_run <= len(alive) // 2:
            n_compactions += 1
            done = np.flatnonzero(~running)
            ph.lap("host")
            got = fetch()
            ph.lap("d2h")
            keep(done, got)
            live = np.flatnonzero(running)
            newK = jax_wgl._bucket(n_run, 1)
            while newK % G:           # a whole number of keys a chip
                newK += 1
            idx = list(live) + [done[0]] * (newK - n_run)
            span = contextlib.nullcontext()
            if mesh is not None:
                # a key moves when its chip before and after differ
                moved = int(sum(chip[r] != i // (newK // G)
                                for i, r in enumerate(live)))
                span = obs.span("keyshard.compact", cat="search", chips=G,
                                keys_before=len(alive), keys_after=newK,
                                moved=moved)
            with span:
                sel = jnp.asarray(np.asarray(idx, np.int32))
                carry = tuple(jnp.take(c, sel, axis=0) if i in KEYED
                              else c for i, c in enumerate(carry))
                consts = tuple(jnp.take(c, sel, axis=0) for c in consts)
                alive = [alive[r] for r in live] + [-1] * (newK - n_run)
                # under a mesh keys reshard, and a moved key misses its
                # old chip's dedup entries (key-salted: a cost in work,
                # never in answers)
                run = rebuild(newK)
                if mesh is not None:
                    carry = tuple(shard(x) if i in KEYED else x
                                  for i, x in enumerate(carry))
                    consts = tuple(shard(x) for x in consts)

    # the dedup table is shared by the rows (key-salted), so its
    # occupancy is batch-wide: the same numbers go on every result
    ph.lap("host")
    got = fetch()
    tstats = jax_wgl.table_stats(carry)
    ph.lap("d2h")
    keep(range(len(alive)), got)
    kept = False
    # never clobber a snapshot that belongs to a different check: the
    # path may have been claimed by a concurrent run since the start
    if checkpoint is not None and _owned(checkpoint, fp):
        if timed_out or any(int(h["status"]) == RUNNING and int(h["top"])
                            for h in harvested.values()):
            # undecided (timeout or budget): a rerun resumes from here
            save()
            kept = True
        else:
            with contextlib.suppress(FileNotFoundError):
                os.unlink(checkpoint)

    def verdict(out, e, init_state, perm):
        # a batch's per-key results carry the single-key engine's name
        return jax_wgl._interpret(
            spec, e, out, confirm, init_state, perm,
            timed_out=timed_out,
            engine="jax-wgl" if keyed else engine)

    if keyed:
        results = [verdict(harvested[j], *k) for j, k in enumerate(keys)]
    else:
        results = [verdict(_merge([harvested[s]
                                   for s in sorted(harvested)]), *keys[0])]
    for r in results:
        r.update(tstats)
        if keyed:
            # how often stragglers were compacted (and resharded)
            r["compactions"] = n_compactions
            if owners is not None:
                r["batch_owners"] = owners
        if shard_explored is not None:
            r["shard_explored"] = shard_explored.tolist()
        if kept and not keyed:
            r["checkpoint"] = checkpoint
    split = None if shard_explored is None else shard_explored.tolist()
    if not keyed:
        so.summary(engine, results[0], shard_explored=split)
    elif so.enabled():
        so.summary(engine, {
            "valid": "batch",
            "configs_explored": sum(int(h["explored"])
                                    for h in harvested.values()),
            "iterations": max(int(h["iterations"])
                              for h in harvested.values()),
            **tstats}, keys=len(keys), shard_explored=split)
    ph.lap("host")
    return results
