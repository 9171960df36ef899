"""Batched Wing-Gong-Lowe linearizability search on TPU.

This is the TPU-native replacement for the engine the reference outsources to
knossos (jepsen/project.clj:14, dispatched from jepsen/src/jepsen/checker.clj:
199-202). The sequential oracle in wgl.py defines the semantics; this module
runs the same search as a *batched branch-and-bound* entirely on device, one
``lax.while_loop`` per check (SURVEY.md section 7: "keep the whole B&B loop in
one lax.while_loop").

Design (everything fixed-shape so XLA traces once):

* A **configuration** is (bitset of linearized ops, model state). Bitsets are
  ``uint32[B]`` words, B = ceil(n/32); states are ``int32[S]``.
* The search keeps a DFS **stack** of configurations in HBM
  (``buf_lin: uint32[O,B]``, ``buf_state: int32[O,S]``, scalar ``top``).
* Each iteration pops the top ``W`` configs (a *frontier*), expands all of
  them at once:
    - unlinearized-op bits are unpacked with a word gather + shift,
    - the WGL rule (op i may linearize next iff ``invoke[i] < min`` return
      over unlinearized ops) becomes a masked row-min + compare,
    - up to ``C`` candidate ops per config are selected with ``top_k``
      (C is the history's max point-concurrency, a static bound on how many
      ops can ever be eligible at once),
    - the model step function is vmapped over (frontier, candidate).
* **Dedup** uses a device-resident open-addressing hash table of 64-bit
  fingerprints (two independent 32-bit multiply-shift hashes over the config
  words). The table is insert-only with linear probing; scatter races between
  distinct keys are resolved by re-gathering ("landed?") and probing on.
  Crucially the table is *best-effort in the safe direction*: a failed insert
  only means the config may be re-explored (children strictly grow the
  bitset, so the search still terminates). A false "seen" requires a 64-bit
  fingerprint collision (~2^-64 per pair); invalid verdicts can be confirmed
  exactly with the sequential oracle via ``confirm=...``.
* New configs are pushed back on the stack with a cumsum scatter; stack
  overflow sets a ``dropped`` flag which degrades an "exhausted" verdict to
  ``unknown`` (success verdicts are unaffected -- dropping work can never
  manufacture a linearization).
* The loop ends on: success (a child linearizes every ``ok`` op), exhaustion
  (stack empty), or budget (iteration cap). Witness for invalid verdicts:
  the deepest config reached (max linearized-ok count) is tracked on device
  and decoded on host.

The same compiled search is reused across histories with identical padded
shapes (shapes are bucketed to powers of two for reuse). The search body is
pure, so a vmapped variant over a leading key axis (jepsen.independent-style
multi-key checks) builds on the same kernel.
"""

from __future__ import annotations

import functools
from typing import Callable, NamedTuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..history import INF_TIME
from ..obs import phases as obs_phases

INF32 = np.int32(2**31 - 1)

#: linear-probe length for the dedup hash table (4 keeps the probe
#: gather -- the kernel's dominant cost, see PROFILE.md -- half the
#: width of the original 8 at no measured dedup-quality cost)
PROBES = 4

#: deepest-distinct-config witness slots per key. knossos returns up to
#: 10 stuck :configs (reference checker.clj:213-216 truncates a list);
#: round 3 tracked exactly one, so the truncation guard could never
#: fire. 8 slots make the masked-reduction update one vector op wide.
TOPK = 8


# ---------------------------------------------------------------------------
# host-side helpers

def max_point_concurrency(invoke_idx, return_idx):
    """Static bound C on WGL candidates: the max, over return points t, of
    |{i : invoke_i < t <= return_i}| (info ops stay open forever). Every
    candidate set at any reachable configuration is contained in one such
    interval stab (see module docstring). Single O(n log n) event sweep."""
    n = len(invoke_idx)
    if n == 0:
        return 1
    finite = return_idx < INF_TIME
    if not finite.any():
        return n
    # +1 just after each invoke, -1 just after each finite return; the open
    # count sampled at a return point t is |{i: invoke_i < t <= return_i}|.
    # Returns sort before invokes at equal positions so an invoke AT t is
    # not counted (the stab requires invoke_i strictly < t).
    events = sorted(
        [(int(t), 1, +1) for t in invoke_idx] +
        [(int(t), 0, -1) for t in return_idx[finite]])
    best, open_ops = 1, 0
    for _t, kind, delta in events:
        if kind == 0:  # sample before closing the op at its return point
            best = max(best, open_ops)
        open_ops += delta
    return min(best, n)


def _hash_keys(length, seed=0x9E3779B9):
    """Two vectors of random odd uint32 multipliers (multiply-shift
    universal hashing over config words)."""
    rng = np.random.RandomState(seed & 0x7FFFFFFF)
    k = rng.randint(0, 2**31, size=(2, length)).astype(np.uint32)
    return (k[0] * 2 + 1), (k[1] * 2 + 1)


def _mix32(h):
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    h = h * jnp.uint32(0x846CA68B)
    h = h ^ (h >> 16)
    return h


# status codes
RUNNING, VALID = np.int32(0), np.int32(1)


#: carry tuple element indices with a per-key leading axis (the rest are
#: shared per table-group); the batch checker's compaction gathers these
KEYED = (0, 1, 2, 3, 5, 6, 7, 8, 9, 10, 11)

#: version tag hashed into checkpoint fingerprints: bump whenever the
#: carry layout or table format changes, so snapshots from an older
#: build are cleanly ignored instead of crashing the resume
CARRY_LAYOUT = (f"carry-v6:tab-interleaved,probes{PROBES},topk{TOPK},"
                "incfp,tfail")

#: carry tuple indices (v6 layout; single source of truth for every
#: consumer -- hardcoded copies desynchronized once already when v2's
#: split tables were merged). v5 adds buf_fp: per-config PARTIAL HASH
#: SUMS over the lin bitset, updated O(1) per child instead of re-
#: hashing all B words per lane per iteration (the profiled dominant
#: cost at 100k+ ops -- see PROFILE.md round 4)
#: ... v6 appends tfail: per-table-group count of configs that WANTED a
#: dedup-table insert but found no empty slot in their probe window --
#: safe (only re-exploration) but a throughput tell: a saturated table
#: is otherwise indistinguishable from a slow search (VERDICT r4 #5)
(IDX_BUF_LIN, IDX_BUF_STATE, IDX_BUF_FP, IDX_TOP, IDX_TAB, IDX_DROPPED,
 IDX_STATUS, IDX_EXPLORED, IDX_BEST_DEPTH, IDX_BEST_LIN, IDX_BEST_STATE,
 IDX_ITS, IDX_IT, IDX_CLAIM, IDX_TFAIL) = range(15)

#: number of carry tuple elements (shard_map specs, checkpoint loaders)
N_CARRY = IDX_TFAIL + 1


class Search(NamedTuple):
    """One compiled search bundle (``_build_search``). ``rollout``
    names the greedy-rollout kernel the search runs: ``"pallas"``
    (compiled fused kernel), ``"pallas-interpret"`` (the same kernel
    in interpret mode, off-TPU), ``"scan"`` (the ``lax.scan`` chain)
    or None (no rollout: short histories or oversized states)."""
    init_carry: Callable
    run_chunk: Callable
    rollout: str | None


@functools.lru_cache(maxsize=64)
def _build_search(step_fn, K, n, B, S, C, A, W, O, T, G=1, R=None,
                  NS=None, rollout_kernel="auto", axis_name=None,
                  axis_size=1, steal=16):
    """Compile the search for one shape bundle with an explicit key-batch
    axis K (jepsen.independent keys, BASELINE config 2). Returns jitted

        init_carry(init_states (K,S)) -> carry
        run_chunk(carry, invoke, ret, f, args, rets, ok_words, salt, bound)
          -> carry

    The K axis is batched *manually* (not vmap): all keys share one dedup
    table (fingerprints salted by key id) and one flat scatter per
    structure per iteration -- vmapping the table ops made XLA:TPU
    serialize the scatters per key and copy the (K,T) tables every
    iteration, which dominated runtime.

    Carry layout (see KEYED): buf_lin (K,O,B) u32, buf_state (K,O,S)
    i32, buf_fp (K,O,2) u32 (per-config incremental fingerprint sums
    over the lin bitset; v5), top (K,) i32, tab (G,T,2) u32 shared
    (h1/h2 fingerprint pairs
    interleaved so one gather fetches both words -- the two separate
    tables cost a second 590k-row gather per iteration, the kernel's
    single biggest op), dropped (K,) bool, status (K,)
    i32, explored (K,) i32, best_depth (K,TOPK) i32, best_lin (K,TOPK,B)
    u32, best_state (K,TOPK,S) i32 (TOPK distinct deepest-config witness
    slots, knossos's multi-:configs parity), its (K,) i32, it (G,) i32,
    claim (G,Tc) i32 shared, tfail (G,) i32 shared (dedup insert-failure
    count; v6). G is the table-group count: 1 locally; under shard_map over a
    mesh, G = mesh size so each device shard sees exactly one group (the
    body always indexes group 0 of its local view). Buffers depend
    on O/B/S/T but NOT on W, so kernel variants with
    different frontier widths are interchangeable mid-search (the batch
    checker widens W once stragglers remain).
    """
    word_idx = np.arange(n, dtype=np.int32) // 32          # (n,)
    bit_idx = (np.arange(n, dtype=np.int32) % 32).astype(np.uint32)
    k1, k2 = _hash_keys(B + S + 1)                         # +1: key salt
    arange_n = np.arange(n, dtype=np.int32)
    arange_W = np.arange(W, dtype=np.int32)
    arange_B = np.arange(B, dtype=np.uint32)
    arange_C = np.arange(C, dtype=np.int32)
    arange_K = np.arange(K, dtype=np.int32)
    M = W * C
    KM = K * M
    if R is None:
        # Greedy-rollout chain length per iteration. Each rollout step is
        # a handful of tiny sequential device ops, so the chain only pays
        # for itself once advancing R levels per iteration beats plain
        # branch-and-bound; only trivially short histories skip it.
        # (Round 2 used a 256-op cutoff, which left the multi-key batch
        # -- 200-op histories per key -- grinding one depth level per
        # iteration.)
        # SINGLE-KEY searches run deep chains (R=1024): a deep rollout
        # amortizes the expensive expansion/dedup iteration over 4x
        # the depth, and the win holds on BOTH rollout kernels (A/B,
        # rung-0 shapes: 144k-request cas 64.9 s / 1102 iterations at
        # R=256 -> 29.2 s / 264 at R=1024 on the scan path; mutex
        # 224k-request scan-R256 timed out at 90 s where fused-R1024
        # decided in 28.3 s). Wedge-prone histories pay more wall per
        # iteration for chains that die early, but those searches were
        # undecidable at R=256 too. The BATCH path keeps R=256: its
        # chip is filled by the key axis and (K, NS*R) push lanes
        # scale with R.
        R = 0 if n <= 64 else min(1024 if K == 1 else 256, n)
    if NS is None:
        # Greedy chains rolled per iteration, for SINGLE-KEY searches
        # only. On the latency-bound single-key chain (PROFILE.md rung
        # 5: 67 us/micro-step on O(n) values) widening each micro-step
        # to NS seeds is nearly free and multiplies depth progress
        # wherever one chain wedges on a plateau: measured 2.7x on a
        # 58.8k-op (112k requested) mutex, 27.7 s -> 10.3 s at NS=8.
        # On the key batch the O(K*NS*n) step work is no longer
        # latency-shadowed and NS=8 measured ~1.4x SLOWER (256-key
        # rung: 4.7 s -> 6.7 s), so the batch path pins NS=1
        # explicitly (keyshard.py) -- this K==1 default only governs
        # genuine single-key searches. Capped so the (NS, n, S)
        # rollout tensor stays ~<=256 MB: big queue states otherwise
        # build multi-GB intermediates that crash the TPU worker
        # (observed on a 9k-op FIFO search).
        NS = max(1, min(8, (64 << 20) // max(1, n * S))) if K == 1 else 1
    if R and K * NS * n * S > (256 << 20):
        # even at the chosen NS the rollout's (K, NS, n, S) step tensor
        # would exceed ~1 GB (huge padded states x many keys): drop the
        # rollout rather than risk the worker -- the search still
        # progresses one depth level per iteration
        R, NS = 0, 1

    # Fused Pallas rollout (VERDICT r4 #1): single-key searches only --
    # the chain is their latency bottleneck (~8 ms busy / ~60 ms wall
    # per iteration, PROFILE.md). "auto" engages it on real TPU when
    # the shape fits VMEM; "pallas" forces it (interpret mode off-TPU,
    # for tests); "scan" keeps the measured lax.scan path (the batch
    # checker pins this -- its chip is filled by the key axis).
    fused = None
    rollout = "scan" if R else None
    if K == 1 and R and rollout_kernel != "scan":
        on_tpu = jax.default_backend() == "tpu"
        if rollout_kernel == "pallas" or on_tpu:
            from . import pallas_rollout
            fused = pallas_rollout.build_fused_rollout(
                step_fn, NS, R, n, B, S, A, interpret=not on_tpu)
            if fused is not None:
                rollout = "pallas" if on_tpu else "pallas-interpret"
    ML = M + NS * R
    KML = K * ML
    Tc = 1 << 16   # twin-claim scratch; fixed so carries are W-independent

    step_one = lambda st, f, a, r: step_fn(st, f, a, r, jnp)  # noqa: E731
    # vmap over candidates (state shared), frontier rows, then keys
    step_vvv = jax.vmap(jax.vmap(jax.vmap(
        step_one, in_axes=(None, 0, 0, 0)), in_axes=(0, 0, 0, 0)),
        in_axes=(0, 0, 0, 0))
    # vmap over all n ops from one state, then NS seed chains (ops
    # shared), then keys (rollout)
    step_vn = jax.vmap(jax.vmap(jax.vmap(
        step_one, in_axes=(None, 0, 0, 0)), in_axes=(0, None, None, None)),
        in_axes=(0, 0, 0, 0))

    k1j, k2j = jnp.asarray(k1), jnp.asarray(k2)

    # Fingerprints are mix(sum_i mix(word_i ^ key_i)) over the config's
    # (lin bitset, state, salt) words. Each word is xored with a
    # per-position random key and passed through the bijective
    # finalizer _before_ summing -- a plain keyed linear sum (sum of
    # w*k mod 2^32) is catastrophically weak in the high bits: configs
    # differing only in bit 31 of two different words always collide,
    # since 2^31*(k_i - k_j) = 0 mod 2^32 for odd keys, and such
    # sibling configs are extremely common in this search.
    #
    # The LIN part of the inner sum is carried per config (buf_fp) and
    # updated O(1) per child -- every child flips exactly one bitset
    # word, and the sum is mod-2^32 linear, so the incremental value
    # is bit-identical to a from-scratch hash. Re-hashing all B words
    # for every lane was the profiled dominant per-iteration cost at
    # 100k+ ops (PROFILE.md round 4: ~0.5-1 GB of hash work per
    # iteration at n=262k). State words still hash fresh (they change
    # wholesale each step; O(S) per lane).

    def finalize_fp(sum1, sum2, st, saltv):
        """Combine incremental lin-sums (leading shape L) with freshly
        hashed state words st (L, S) and the per-key salt (L,) into
        the table fingerprint pair."""
        stw = st.astype(jnp.uint32)
        s1 = sum1 + jnp.sum(_mix32(stw ^ k1j[B:B + S]), axis=-1,
                            dtype=jnp.uint32) + _mix32(saltv
                                                       ^ k1j[B + S])
        s2 = sum2 + jnp.sum(_mix32(stw ^ k2j[B:B + S]), axis=-1,
                            dtype=jnp.uint32) + _mix32(saltv
                                                       ^ k2j[B + S])
        h1 = _mix32(s1)
        h2 = _mix32(s2)
        # reserve (0,0): the empty table slot
        return h1, jnp.where((h1 == 0) & (h2 == 0), jnp.uint32(1), h2)

    def lin_deltas(oldw, neww, wsel):
        """Sum deltas for flipping word index ``wsel`` (any shape) from
        oldw to neww: mix(new^k_w) - mix(old^k_w), mod 2^32."""
        kw1 = jnp.take(k1j, wsel)
        kw2 = jnp.take(k2j, wsel)
        return (_mix32(neww ^ kw1) - _mix32(oldw ^ kw1),
                _mix32(neww ^ kw2) - _mix32(oldw ^ kw2))

    def body(carry, consts):
        (buf_lin, buf_state, buf_fp, top, tabg, dropped, status,
         explored, best_depth, best_lin, best_state, its, it,
         claimg, tfailg) = carry
        tab, claim = tabg[0], claimg[0]
        # fx: the fused rollout's pre-permuted op columns (empty tuple
        # when the scan path is active), built once per dispatch in
        # run_chunk -- never per iteration
        (invoke, ret, fop, args, rets, ok_words, salt, bound, fx) = consts
        running = (status == RUNNING) & (top > 0)             # (K,)

        # -- pop per-key frontiers ------------------------------------------
        # The stack is a RING over O slots with an absolute top counter:
        # overflow overwrites the OLDEST (shallowest) entries rather than
        # dropping the newest. Deep rollout chains must always land --
        # dropping them stalls the search at a plateau forever. Any
        # overwrite forfeits exhaustion proofs only (dropped flag);
        # popping a slot that was overwritten yields some other real
        # config, which is sound to explore.
        start = jnp.where(running, jnp.maximum(top - W, 0), top)
        idx = start[:, None] + arange_W[None, :]              # (K,W)
        fvalid = (idx < top[:, None]) & running[:, None]
        gidx = (arange_K[:, None] * O + idx % O).reshape(KM // C)
        lin = jnp.take(buf_lin.reshape(K * O, B), gidx,
                       axis=0).reshape(K, W, B)
        state = jnp.take(buf_state.reshape(K * O, S), gidx,
                         axis=0).reshape(K, W, S)
        fsum = jnp.take(buf_fp.reshape(K * O, 2), gidx,
                        axis=0).reshape(K, W, 2)
        top = start

        # -- candidate selection (the WGL rule) -----------------------------
        # word_idx[i] == i // 32 exactly, so the word gather is a
        # gather-free repeat + slice (TPU gathers are the kernel's
        # slowest ops; see PROFILE.md)
        wbits = jnp.repeat(lin, 32, axis=2)[:, :, :n]          # (K,W,n)
        unlin = ((wbits >> bit_idx[None, None, :]) & jnp.uint32(1)) == 0
        rmin = jnp.min(jnp.where(unlin, ret[:, None, :], INF32), axis=2)
        cand = unlin & (invoke[:, None, :] < rmin[..., None]) \
            & fvalid[..., None]
        # First C candidate positions per row without top_k (which lowers
        # to per-row sorts on TPU). Ops arrive ALREADY RENUMBERED into
        # linearization-priority order (host-side argsort by the model
        # hint / earliest deadline, see _priority_order), so "first C by
        # index" IS "best C by priority" -- the kernel stays all-static
        # index math with no per-iteration gathers.
        rank = jnp.cumsum(cand.astype(jnp.int32), axis=2)     # (K,W,n)
        if n * C <= 32768:
            # small problems: a dense one-hot reduction beats a dynamic
            # scatter (TPU scatters have high fixed cost)
            onehot = (rank[..., None]
                      == (arange_C[None, None, None, :] + 1)) \
                & cand[..., None]                             # (K,W,n,C)
            ci = jnp.sum(
                onehot * arange_n[None, None, :, None],
                axis=2).astype(jnp.int32)
        else:
            tgt = jnp.where(cand & (rank <= C), rank - 1, C)
            row = jnp.broadcast_to(
                (arange_K[:, None] * W + arange_W[None, :])[..., None],
                (K, W, n))
            ops_b = jnp.broadcast_to(arange_n[None, None, :], (K, W, n))
            ci = jnp.zeros((K * W, C), jnp.int32) \
                .at[row.reshape(-1), tgt.reshape(-1)] \
                .set(ops_b.reshape(-1), mode="drop").reshape(K, W, C)
        cvalid = arange_C[None, None, :] < rank[..., -1:]     # (K,W,C)

        # -- model step over (key, frontier, candidate) ---------------------
        gci = (arange_K[:, None, None] * n + ci).reshape(KM)
        fc = jnp.take(fop.reshape(K * n), gci).reshape(K, W, C)
        ac = jnp.take(args.reshape(K * n, A), gci,
                      axis=0).reshape(K, W, C, A)
        rc = jnp.take(rets.reshape(K * n, A), gci,
                      axis=0).reshape(K, W, C, A)
        st2, okf = step_vvv(state, fc, ac, rc)            # (K,W,C,S),(K,W,C)
        st2 = st2.astype(jnp.int32)

        wselc = jnp.take(word_idx, ci)                        # (K,W,C)
        bitc = jnp.uint32(1) << jnp.take(bit_idx, ci)
        addmask = jnp.where(
            arange_B[None, None, None, :]
            == wselc[..., None].astype(jnp.uint32),
            bitc[..., None], jnp.uint32(0))                   # (K,W,C,B)
        lin2 = lin[:, :, None, :] | addmask
        # incremental fingerprint sums: each child flips exactly one
        # bitset word (oldw -> oldw|bit); one gather per lane replaces
        # a full B-word re-hash
        oldw = jnp.take_along_axis(lin, wselc, axis=2)        # (K,W,C)
        d1, d2 = lin_deltas(oldw, oldw | bitc, wselc)
        sum1c = fsum[..., 0][:, :, None] + d1                 # (K,W,C)
        sum2c = fsum[..., 1][:, :, None] + d2

        child_valid = cvalid & okf & fvalid[..., None]
        okw = ok_words[:, None, None, :]
        done = jnp.all((lin2 & okw) == okw, axis=-1)
        status = jnp.where(
            running & jnp.any(child_valid & done, axis=(1, 2)),
            VALID, status)

        # -- witness tracking ----------------------------------------------
        # Row selection is a first-occurrence one-hot + masked SUM, not
        # argmax + take_along_axis: the per-key gathers lowered to
        # serialized scalar-memory fusions costing ~15 ms/iteration at
        # K=256 (profiled; see PROFILE.md), the masked reduction is a
        # plain vector op. Each update site inserts the iteration's best
        # candidate into TOPK distinct deepest-config slots.
        def topk_insert(bd3, bl3, bs3, cd, cl, cs):
            """Insert one candidate config per key (cd (K,), cl (K,B),
            cs (K,S)) into the TOPK distinct-deepest slots. Eviction
            replaces a min-depth slot; ``>=`` admits equal-depth DISTINCT
            configs (a stuck frontier is many configs at one max depth),
            and a max-depth slot can only be evicted by an equally deep
            distinct config, so the deepest witness is never lost."""
            dup = ((bl3 == cl[:, None, :]).all(-1)
                   & (bs3 == cs[:, None, :]).all(-1)
                   & (bd3 >= 0)).any(axis=1)                  # (K,)
            mind = jnp.min(bd3, axis=1)                       # (K,)
            do = (cd >= 0) & (cd >= mind) & ~dup
            sloteq = bd3 == mind[:, None]                     # (K,TOPK)
            spk = (sloteq
                   & (jnp.cumsum(sloteq.astype(jnp.int32), axis=1) == 1)
                   & do[:, None])
            return (jnp.where(spk, cd[:, None], bd3),
                    jnp.where(spk[..., None], cl[:, None, :], bl3),
                    jnp.where(spk[..., None], cs[:, None, :], bs3))

        depth = lax.population_count(lin2 & okw).sum(axis=-1) \
            .astype(jnp.int32)
        depth = jnp.where(child_valid, depth, -1).reshape(K, M)
        bd = jnp.max(depth, axis=1)                           # (K,)
        lin2k = lin2.reshape(K, M, B)
        st2k = st2.reshape(K, M, S)
        eq = depth == bd[:, None]
        pick = eq & (jnp.cumsum(eq.astype(jnp.int32), axis=1) == 1)
        cand_lin = jnp.sum(jnp.where(pick[..., None], lin2k, 0), axis=1,
                           dtype=jnp.uint32)                  # (K,B)
        cand_st = jnp.sum(jnp.where(pick[..., None], st2k, 0), axis=1,
                          dtype=jnp.int32)                    # (K,S)
        best_depth, best_lin, best_state = topk_insert(
            best_depth, best_lin, best_state, bd, cand_lin, cand_st)

        # -- greedy rollout -------------------------------------------------
        # Branch-and-bound advances depth at most 1 per iteration, and
        # iterations are latency-bound (~ms), so a 10k-op history would
        # need 10k dispapched iterations. Instead, from the deepest fresh
        # child, follow the greedy chain -- always linearize the eligible
        # op with the EARLIEST DEADLINE whose model step succeeds -- for
        # up to R steps inside this same kernel (one lax.scan; per-step
        # work is O(K*n), trivial). On valid histories the chain usually
        # just walks the witness, advancing depth R per iteration; the
        # chain configs are pushed (deepest on top) and deduped like any
        # others, so backtracking still explores alternatives around any
        # step the greedy choice got wrong.
        # Seed from the top-NS children in DFS order: the deepest popped
        # parent's best-priority surviving children first -- exactly what
        # plain DFS would pop next (parents are popped in w-ascending =
        # shallowest-first order, candidates in c-ascending = priority
        # order). Seeding from argmax-depth instead ties toward the
        # FIRST max-depth lane, i.e. some shallow parent's plateau
        # child, whose state wedges the chain immediately on brittle
        # models (FIFO: an equal-depth config with the wrong queue
        # contents is a dead end; measured as the chain advancing ~1
        # level/iteration). NS > 1 chains diversify around exactly the
        # choice points where one greedy chain wedges: the seeds differ
        # in which candidate linearizes at the current deepest level.
        # Selection is NS unrolled masked-max reductions over (K, M) --
        # no sort, no gather (dfs_rank values are distinct per lane, so
        # each "== smax" one-hot hits exactly one lane).
        dfs_rank = (arange_W[:, None] * C
                    + (C - 1 - arange_C)[None, :]).reshape(M)   # (M,)
        score = jnp.where(child_valid.reshape(K, M),
                          dfs_rank[None, :], -1)
        sum1k = sum1c.reshape(K, M)
        sum2k = sum2c.reshape(K, M)
        seed_lin_l, seed_st_l, seed_ok_l = [], [], []
        seed_s1_l, seed_s2_l = [], []
        for _s in range(NS):
            smax = jnp.max(score, axis=1)                      # (K,)
            ok_s = running & (smax >= 0)
            seq = score == smax[:, None]
            spick = seq & (jnp.cumsum(seq.astype(jnp.int32), axis=1)
                           == 1) & ok_s[:, None]               # (K,M)
            seed_lin_l.append(jnp.sum(
                jnp.where(spick[..., None], lin2k, 0), axis=1,
                dtype=jnp.uint32))
            seed_st_l.append(jnp.sum(
                jnp.where(spick[..., None], st2k, 0), axis=1,
                dtype=jnp.int32))
            seed_s1_l.append(jnp.sum(jnp.where(spick, sum1k, 0),
                                     axis=1, dtype=jnp.uint32))
            seed_s2_l.append(jnp.sum(jnp.where(spick, sum2k, 0),
                                     axis=1, dtype=jnp.uint32))
            seed_ok_l.append(ok_s)
            score = jnp.where(spick, -1, score)
        seed_lin = jnp.stack(seed_lin_l, axis=1)               # (K,NS,B)
        seed_st = jnp.stack(seed_st_l, axis=1)                 # (K,NS,S)
        seed_ok = jnp.stack(seed_ok_l, axis=1)                 # (K,NS)
        seed_s1 = jnp.stack(seed_s1_l, axis=1)                 # (K,NS)
        seed_s2 = jnp.stack(seed_s2_l, axis=1)                 # (K,NS)

        def roll_step(rc_, _):
            lin_r, st_r, alive, s1_r, s2_r = rc_            # (K,NS,B) ...
            wb = jnp.repeat(lin_r, 32, axis=2)[:, :, :n]      # (K,NS,n)
            unl = ((wb >> bit_idx[None, None, :]) & jnp.uint32(1)) == 0
            rm = jnp.min(jnp.where(unl, ret[:, None, :], INF32),
                         axis=2)                              # (K,NS)
            elig = unl & (invoke[:, None, :] < rm[..., None])
            stn, okn = step_vn(st_r, fop, args, rets)       # (K,NS,n,S)
            succ = elig & okn & alive[..., None]
            # first succeeding op in index order = best priority (ops are
            # pre-sorted by the linearization hint)
            j = jnp.argmax(succ, axis=2).astype(jnp.int32)    # (K,NS)
            took = succ.any(axis=2)
            wsel = jnp.take(word_idx, j)
            bitj = jnp.uint32(1) << jnp.take(bit_idx, j)
            bmask = (arange_B[None, None, :]
                     == wsel[..., None].astype(jnp.uint32))
            newlin = lin_r | jnp.where(
                bmask & took[..., None], bitj[..., None],
                jnp.uint32(0))
            newst = jnp.where(
                took[..., None],
                jnp.take_along_axis(stn, j[..., None, None],
                                    axis=2)[:, :, 0]
                .astype(jnp.int32), st_r)
            oldw = jnp.take_along_axis(lin_r, wsel[..., None],
                                       axis=2)[..., 0]        # (K,NS)
            d1, d2 = lin_deltas(oldw, oldw | bitj, wsel)
            s1_r = jnp.where(took, s1_r + d1, s1_r)
            s2_r = jnp.where(took, s2_r + d2, s2_r)
            alive = alive & took
            return ((newlin, newst, alive, s1_r, s2_r),
                    (newlin, newst, alive, s1_r, s2_r))

        if R and fused is not None:
            # one kernel rolls all NS chains R steps with bitsets and
            # states in VMEM; the per-step bitsets and incremental
            # fingerprint sums are reconstructed OUT here with wide
            # parallel ops (associative OR-scan over one-hot word
            # masks) -- bit-identical to the scan path's carries, but
            # without R sequential dispatch dependencies
            j_rs, st_rs = fused[1](seed_lin[0], seed_st[0], seed_ok[0],
                                   *fx)
            jt = j_rs[None]                               # (1,NS,R)
            took = jt >= 0
            jc = jnp.maximum(jt, 0)
            wselr = jnp.take(word_idx, jc)                # (1,NS,R)
            bitr = jnp.uint32(1) << jnp.take(bit_idx, jc)
            onehotw = (arange_B[None, None, None, :]
                       == wselr[..., None].astype(jnp.uint32))
            masks = jnp.where(onehotw & took[..., None],
                              bitr[..., None], jnp.uint32(0))
            cum = lax.associative_scan(jnp.bitwise_or, masks, axis=2)
            ch_lin = seed_lin[:, :, None, :] | cum        # (1,NS,R,B)
            prev_lin = jnp.concatenate(
                [seed_lin[:, :, None, :], ch_lin[:, :, :-1]], axis=2)
            # gather-free oldw: masked reduce over the B axis (per-key
            # take_along_axis lowered to serialized scalar fusions
            # once already -- see the witness-tracking note above)
            oldw = jnp.sum(jnp.where(onehotw, prev_lin, jnp.uint32(0)),
                           axis=3, dtype=jnp.uint32)      # (1,NS,R)
            d1r, d2r = lin_deltas(oldw, oldw | bitr, wselr)
            ch_s1 = seed_s1[:, :, None] + jnp.cumsum(
                jnp.where(took, d1r, jnp.uint32(0)), axis=2,
                dtype=jnp.uint32)
            ch_s2 = seed_s2[:, :, None] + jnp.cumsum(
                jnp.where(took, d2r, jnp.uint32(0)), axis=2,
                dtype=jnp.uint32)
            ch_st = st_rs[None]                           # (1,NS,R,S)
            ch_alive = took
            # flip the seed axis so the BEST seed's chain flattens to
            # the LAST lanes (top of stack), as in the scan path below
            ch_lin = ch_lin[:, ::-1].reshape(K, NS * R, B)
            ch_st = ch_st[:, ::-1].reshape(K, NS * R, S)
            ch_alive = ch_alive[:, ::-1].reshape(K, NS * R)
            ch_s1 = ch_s1[:, ::-1].reshape(K, NS * R)
            ch_s2 = ch_s2[:, ::-1].reshape(K, NS * R)
        elif R:
            # unroll: the chain is LATENCY-bound (PROFILE.md: ~26 us
            # busy vs ~175 us wall per micro-step at n=131k -- the gap
            # is loop-boundary dispatch latency); unrolling fuses 8
            # micro-steps per loop iteration so XLA schedules across
            # step boundaries
            _, (ch_lin, ch_st, ch_alive, ch_s1, ch_s2) = lax.scan(
                roll_step, (seed_lin, seed_st, seed_ok, seed_s1,
                            seed_s2), None, length=R, unroll=8)
            # (R,K,NS,*) -> (K,NS,R,*); flip the seed axis so the BEST
            # seed's chain flattens to the LAST lanes (= top of stack,
            # its deepest config on the very top), then fold seeds into
            # one chain-lane axis of NS*R
            ch_lin = jnp.transpose(ch_lin, (1, 2, 0, 3))[:, ::-1] \
                .reshape(K, NS * R, B)
            ch_st = jnp.transpose(ch_st, (1, 2, 0, 3))[:, ::-1] \
                .reshape(K, NS * R, S)
            ch_alive = jnp.transpose(ch_alive, (1, 2, 0))[:, ::-1] \
                .reshape(K, NS * R)
            ch_s1 = jnp.transpose(ch_s1, (1, 2, 0))[:, ::-1] \
                .reshape(K, NS * R)
            ch_s2 = jnp.transpose(ch_s2, (1, 2, 0))[:, ::-1] \
                .reshape(K, NS * R)

        if R:
            okw2 = ok_words[:, None, :]
            ch_done = jnp.all((ch_lin & okw2) == okw2, axis=-1) & ch_alive
            status = jnp.where(running & ch_done.any(axis=1), VALID,
                               status)
            ch_depth = jnp.where(
                ch_alive,
                lax.population_count(ch_lin & okw2).sum(-1)
                .astype(jnp.int32),
                -1)                                           # (K,NS*R)
            cbd = jnp.max(ch_depth, axis=1)
            ceq = ch_depth == cbd[:, None]
            cpick = ceq & (jnp.cumsum(ceq.astype(jnp.int32), axis=1)
                           == 1)                              # (K,NS*R)
            cc_lin = jnp.sum(jnp.where(cpick[..., None], ch_lin, 0),
                             axis=1, dtype=jnp.uint32)
            cc_st = jnp.sum(jnp.where(cpick[..., None], ch_st, 0),
                            axis=1, dtype=jnp.int32)
            best_depth, best_lin, best_state = topk_insert(
                best_depth, best_lin, best_state, cbd, cc_lin, cc_st)

        # -- combined lanes (expansion then chain, natural order) -----------
        # Stack positions are assigned ARITHMETICALLY below so lane data
        # never needs reordering (flipping the (K,M,B) tensors every
        # iteration costs real bandwidth).
        exp_lin = lin2.reshape(K, M, B)
        exp_st = st2.reshape(K, M, S)
        exp_val = child_valid.reshape(K, M)
        if R:
            all_lin = jnp.concatenate([exp_lin, ch_lin], axis=1)
            all_st = jnp.concatenate([exp_st, ch_st], axis=1)
            all_val = jnp.concatenate([exp_val, ch_alive], axis=1)
            all_s1 = jnp.concatenate([sum1k, ch_s1], axis=1)
            all_s2 = jnp.concatenate([sum2k, ch_s2], axis=1)
        else:
            all_lin, all_st, all_val = exp_lin, exp_st, exp_val
            all_s1, all_s2 = sum1k, sum2k

        # -- fingerprints (key-salted: all keys share the tables) -----------
        lin2f = all_lin.reshape(KML, B)
        st2f = all_st.reshape(KML, S)
        sum1f = all_s1.reshape(KML)
        sum2f = all_s2.reshape(KML)
        saltw = jnp.broadcast_to(salt[:, None], (K, ML)).reshape(KML)
        h1, h2 = finalize_fp(sum1f, sum2f, st2f, saltw)
        cv = all_val.reshape(KML)

        # In-batch twin dedup: parents in the same frontier often generate
        # identical children (diamond orders); left unchecked each copy is
        # pushed and re-expanded (~6x measured blowup on exhaustion
        # proofs). Every valid lane claims a slot keyed by fingerprint in a
        # small persistent scratch; of the lanes with equal fingerprints at
        # a claimed slot, exactly the scatter winner survives. Distinct-
        # fingerprint collisions just mean both survive (extra work only).
        # Stale claims are unreadable: a slot is only read by lanes that
        # wrote it this iteration.
        lane = jnp.arange(KML, dtype=jnp.int32)
        cslot = jnp.where(cv, (h1 & jnp.uint32(Tc - 1)).astype(jnp.int32),
                          Tc)
        claim = claim.at[cslot].set(lane, mode="drop")
        winner = claim.at[cslot].get(mode="fill", fill_value=0)
        dup = cv & (winner != lane) & (jnp.take(h1, winner) == h1) \
            & (jnp.take(h2, winner) == h2)

        # One vectorized probe round against the shared seen-table: gather
        # all PROBES slots at once, then a single scatter into the first
        # empty slot. Scatter-race losers are simply not recorded (their
        # configs may be re-explored later; extra work, never lost work).
        slot0 = (h1 & jnp.uint32(T - 1)).astype(jnp.int32)
        slots = (slot0[:, None]
                 + jnp.arange(PROBES, dtype=jnp.int32)[None, :]) & (T - 1)
        slots = jnp.where((cv & ~dup)[:, None], slots, T)
        cur = tab.at[slots].get(mode="fill", fill_value=0)   # (KM,P,2)
        cur1, cur2 = cur[..., 0], cur[..., 1]
        seen = ((cur1 == h1[:, None]) & (cur2 == h2[:, None])).any(axis=1) \
            & cv & ~dup
        empty = (cur1 == 0) & (cur2 == 0)
        first_empty = jnp.argmax(empty, axis=1)
        islot = jnp.take_along_axis(slots, first_empty[:, None],
                                    axis=1)[:, 0]
        has_empty = empty.any(axis=1)
        want = cv & ~dup & ~seen & has_empty
        wslot = jnp.where(want, islot, T)
        tab = tab.at[wslot].set(jnp.stack([h1, h2], axis=-1),
                                mode="drop")
        # saturation tell: lanes that wanted an insert but every probe
        # slot was full (safe -- only re-exploration -- but it silently
        # costs throughput, so it is counted and surfaced at harvest)
        tfailg = tfailg.at[0].add(
            jnp.sum(cv & ~dup & ~seen & ~has_empty, dtype=jnp.int32))

        # -- push fresh configs (per-key positions, one flat scatter) -------
        # Stack order (ascending position = popped sooner next time):
        # expansion lanes in (w asc, c desc) -- so the deepest popped
        # parent's best-priority child sits highest among expansions --
        # then the chain ascending, its deepest config on the very top.
        # Ranks are computed from cumsums over the masks alone; lane
        # DATA stays in natural order.
        fresh = (cv & ~dup & ~seen).reshape(K, ML)
        fe = fresh[:, :M].reshape(K, W, C).astype(jnp.int32)
        row_tot = fe.sum(axis=2)                               # (K,W)
        rows_before = jnp.cumsum(row_tot, axis=1) - row_tot
        suffix_in_row = row_tot[:, :, None] - jnp.cumsum(fe, axis=2)
        rank_e = (rows_before[:, :, None] + suffix_in_row).reshape(K, M)
        exp_total = row_tot.sum(axis=1)                        # (K,)
        if R:
            fc_ = fresh[:, M:].astype(jnp.int32)
            rank_c = exp_total[:, None] + jnp.cumsum(fc_, axis=1) - 1
            offs = jnp.concatenate([rank_e, rank_c], axis=1)
            cnt = exp_total + fc_.sum(axis=1)
        else:
            offs = rank_e
            cnt = exp_total
        pos = top[:, None] + offs
        dropped = dropped | (running & (top + cnt > O))
        fpos = jnp.where(fresh, arange_K[:, None] * O + pos % O,
                         K * O).reshape(KML)
        buf_lin = buf_lin.reshape(K * O, B).at[fpos] \
            .set(lin2f, mode="drop").reshape(K, O, B)
        buf_state = buf_state.reshape(K * O, S).at[fpos] \
            .set(st2f, mode="drop").reshape(K, O, S)
        buf_fp = buf_fp.reshape(K * O, 2).at[fpos] \
            .set(jnp.stack([sum1f, sum2f], axis=-1),
                 mode="drop").reshape(K, O, 2)
        # renormalize so the absolute counter can't overflow int32 over
        # long runs: shifting by O preserves every slot index mod O, and
        # `dropped` has already latched once a wrap occurred
        top = top + cnt
        top = jnp.where(top >= 2 * O, top - O, top)

        if axis_name is not None:
            # -- single-search mesh sharding (SURVEY §7 step 9) ---------
            # This kernel instance is ONE SHARD of a single search: the
            # DFS stack/frontier is partitioned per device (K == 1
            # locally), dedup tables are per-device (insert failures
            # only cost re-exploration, so skipping cross-device dedup
            # is sound), and the only cross-device traffic is a tiny
            # per-iteration work-balance vector (all_gather of frontier
            # sizes) plus a bounded hand-off of configs donated to a
            # STARVING right neighbor over the ring (ppermute) -- the
            # ICI-collective design SURVEY §5 promises, not a port of
            # the reference's thread-pool parallelism
            # (checker.clj:101-116).
            D, H = axis_size, steal
            me = lax.axis_index(axis_name)
            loads = lax.all_gather(top[0], axis_name)         # (D,)
            starving = jnp.take(loads, (me + 1) % D) == 0
            donate = (top[0] > 2 * H) & starving \
                & (status[0] == RUNNING)
            # deepest H entries (ring positions top-1 .. top-H); a
            # donor keeps plenty and the thief resumes depth-first
            # from the donor's best configs
            idxh = (top[0] - 1
                    - jnp.arange(H, dtype=jnp.int32)) % O     # (H,)
            hval = jnp.where(donate, 1, 0) \
                * jnp.ones(H, jnp.int32)                      # (H,)
            h_lin = jnp.take(buf_lin[0], idxh, axis=0)        # (H, B)
            h_st = jnp.take(buf_state[0], idxh, axis=0)
            h_fp = jnp.take(buf_fp[0], idxh, axis=0)
            top = jnp.where(donate, top - H, top)
            ring = [(i, (i + 1) % D) for i in range(D)]
            r_lin = lax.ppermute(h_lin, axis_name, ring)
            r_st = lax.ppermute(h_st, axis_name, ring)
            r_fp = lax.ppermute(h_fp, axis_name, ring)
            r_val = lax.ppermute(hval, axis_name, ring) != 0  # (H,)
            # push the received configs (shallowest of the donation on
            # the bottom: they arrive deepest-first, so reverse)
            r_val = r_val[::-1]
            cnt_r = jnp.sum(r_val, dtype=jnp.int32)
            pos_r = top[0] + jnp.cumsum(
                r_val.astype(jnp.int32)) - 1
            dropped = dropped | ((status == RUNNING)
                                 & (top + cnt_r > O))
            fpos_r = jnp.where(r_val, pos_r % O, O)
            buf_lin = buf_lin.reshape(O, B).at[fpos_r] \
                .set(r_lin[::-1], mode="drop").reshape(K, O, B)
            buf_state = buf_state.reshape(O, S).at[fpos_r] \
                .set(r_st[::-1], mode="drop").reshape(K, O, S)
            buf_fp = buf_fp.reshape(O, 2).at[fpos_r] \
                .set(r_fp[::-1], mode="drop").reshape(K, O, 2)
            top = top + cnt_r

        explored = explored + jnp.where(running,
                                        fvalid.sum(axis=1,
                                                   dtype=jnp.int32), 0)
        its = its + running.astype(jnp.int32)
        it = it + 1
        return (buf_lin, buf_state, buf_fp, top, tab[None], dropped,
                status, explored, best_depth, best_lin, best_state, its,
                it, claim[None], tfailg)

    def init_carry(init_states):
        buf_lin = jnp.zeros((K, O, B), jnp.uint32)
        buf_state = jnp.zeros((K, O, S), jnp.int32) \
            .at[:, 0, :].set(init_states)
        # every slot starts with the all-zero bitset's lin-sums (only
        # slot 0 is live; the rest are overwritten before any pop)
        z = jnp.stack([jnp.sum(_mix32(k1j[:B]), dtype=jnp.uint32),
                       jnp.sum(_mix32(k2j[:B]), dtype=jnp.uint32)])
        buf_fp = jnp.broadcast_to(z, (K, O, 2)).astype(jnp.uint32)
        return (buf_lin, buf_state, buf_fp, jnp.ones(K, jnp.int32),
                jnp.zeros((G, T, 2), jnp.uint32),
                jnp.zeros(K, bool), jnp.full(K, RUNNING),
                jnp.zeros(K, jnp.int32),
                jnp.full((K, TOPK), -1, jnp.int32),
                jnp.zeros((K, TOPK, B), jnp.uint32),
                jnp.zeros((K, TOPK, S), jnp.int32),
                jnp.zeros(K, jnp.int32),
                jnp.zeros(G, jnp.int32), jnp.zeros((G, Tc), jnp.int32),
                jnp.zeros(G, jnp.int32))

    def run_chunk(carry, invoke, ret, fop, args, rets, ok_words, salt,
                  bound):
        """Advance the search until every key succeeds/exhausts or the
        iteration counter reaches ``bound``. Bounded dispatches keep device
        kernels short (long single while_loops can trip runtime watchdogs)
        and let the host enforce wall-clock budgets between chunks.

        Op arrays must be pre-sorted into linearization-priority order
        (_priority_order): index order IS search order."""
        fx = (fused[0](invoke[0], ret[0], fop[0], args[0], rets[0])
              if fused is not None else ())
        consts = (invoke, ret, fop, args, rets, ok_words, salt, bound,
                  fx)

        def cond(c):
            local = jnp.any((c[IDX_STATUS] == RUNNING)
                            & (c[IDX_TOP] > 0))
            if axis_name is None:
                return local & (c[IDX_IT][0] < bound)
            # sharded single search: every shard must agree on the
            # loop trip count (a shard exiting early would desert the
            # body's collectives), so continuation is GLOBAL -- any
            # shard holding work keeps everyone stepping (starved
            # shards idle until the ring feeds them), and any shard's
            # success stops everyone
            work = lax.psum(jnp.where(local, 1, 0), axis_name)
            found = lax.psum(
                jnp.sum((c[IDX_STATUS] == VALID).astype(jnp.int32)),
                axis_name)
            return (work > 0) & (found == 0) & (c[IDX_IT][0] < bound)

        return lax.while_loop(cond, lambda c: body(c, consts), carry)

    return Search(jax.jit(init_carry),
                  jax.jit(run_chunk, donate_argnums=(0,)), rollout)


# ---------------------------------------------------------------------------
# public entry points

def table_stats(carry):
    """Dedup-table occupancy diagnostics (VERDICT r4 #5): load factor
    from one reduction over the table at harvest time -- off the hot
    loop -- plus the accumulated insert-failure count. Failed inserts
    are safe (re-exploration only, never wrong answers) but silently
    degrade throughput as the table fills; without these numbers a
    saturated table is indistinguishable from a slow search."""
    tab = carry[IDX_TAB]
    # ONE host round-trip for both scalars: each separate device_get
    # is a fixed per-check sync
    used, fails = jax.device_get(
        (jnp.sum((tab != jnp.uint32(0)).any(-1), dtype=jnp.int32),
         jnp.sum(carry[IDX_TFAIL])))
    total = int(tab.shape[0] * tab.shape[1])
    return {"table_load": round(int(used) / total, 4),
            "table_insert_failures": int(fails)}


def _bucket(x, lo):
    """Round up to a power of two (>= lo) so compiled searches are reused
    across histories of similar size."""
    return max(lo, 1 << (int(x) - 1).bit_length())


def _n_floor():
    """Minimum op-count bucket. Campaigns raise it
    (campaign.compile_cache.set_n_floor) so sweep cells whose op
    counts straddle a power of two still share one compiled search;
    padding rows are inert, so a coarser bucket is always sound."""
    from ..campaign import compile_cache
    return compile_cache.n_floor()


def _note_compile(engine, key):
    """Report this search's compile plan to the campaign-level
    compile-reuse ledger (hit/miss counters; never verdict-bearing).
    Returns True when the ledger calls it a MISS — the phase plane
    attributes the next dispatch's wall to XLA compile, not
    device-compute."""
    try:
        from ..campaign import compile_cache
        return not compile_cache.note(engine, key)
    except Exception:  # noqa: BLE001 - telemetry only
        return False


def _adapt_quantum(cap, per_it, target_s, left_s=None):
    """Next dispatch quantum of the search's host loop
    (``checker/drive.py``): ~``target_s`` of measured per-iteration
    wall, capped by the caller's ``chunk_iters`` contract, and shrunk to
    fit the remaining wall budget ``left_s`` (budgets are only enforced
    BETWEEN dispatches, so a mispredicted quantum is the whole
    overshoot). Both fixed policies failed measurably: large chunks
    overshot a 60 s budget to 282 s; fixed-small chunks made big
    searches bound by host round trips (PROFILE.md round 4)."""
    eff = max(1, min(cap, int(target_s / per_it)))
    if left_s is not None:
        eff = max(1, min(eff, int(left_s / per_it) + 1))
    return eff


def _plan_sizes(n, S, C, frontier_width=None, stack_size=None,
                table_size=None):
    B = max(1, (n + 31) // 32)
    if frontier_width is None:
        # aim for ~32k candidate expansions per iteration, capped so
        # the (W, C, S) model-step tensor stays ~<=256 MB -- large
        # padded queue states at high point-concurrency otherwise
        # build multi-GB intermediates that crash the TPU worker
        # (observed on a 9k-op FIFO search: C=512, S=8192) -- AND at
        # 16*C: width beyond the candidate branching buys nothing
        # (measured on a 37k-op 2-process history: identical iteration
        # counts at W=64/256/1024, wall 6.4 s / 15.8 s / 52.3 s --
        # every extra lane is pure cost at low point-concurrency;
        # exhaustion proofs trade the wider pop for more, cheaper
        # iterations)
        frontier_width = max(
            8, min(4096, 32768 // max(1, C), 16 * C,
                   (64 << 20) // max(1, C * S)))
    if stack_size is None:
        # ~128 MB of stack at most
        per = (B + S) * 4
        stack_size = max(4096, min(1 << 18, (128 << 20) // per))
    if table_size is None:
        # a fixed 2^20 table SATURATES at rung-0 scales (round-5
        # instrumentation measured load 0.985 on a 64k-request cas
        # search after only 194 iterations): failed inserts silently
        # degrade the search to re-exploration. Scale with the history
        # size -- ~32 slots per op -- capped at 2^23 (64 MB of HBM)
        table_size = max(1 << 20, min(1 << 23, 32 * n))
    # slot indexing uses h & (T-1): every size must be a power of two
    return (B, _bucket(frontier_width, 8), _bucket(stack_size, 1024),
            _bucket(table_size, 1024))


def _encode_arrays(e):
    """Dense int32 arrays for the device search. Invoke/return indices are
    re-ranked to small ints; INF_TIME becomes INF32."""
    n = len(e)
    invoke = e.invoke_idx.astype(np.int64)
    ret = e.return_idx
    finite = np.concatenate([invoke, ret[ret < INF_TIME]])
    ranks = {v: i for i, v in enumerate(np.unique(finite))}
    inv32 = np.array([ranks[v] for v in invoke], np.int32) \
        if n else np.zeros(0, np.int32)
    ret32 = np.array([ranks[v] if v < INF_TIME else INF32 for v in ret],
                     np.int32) if n else np.zeros(0, np.int32)
    ok_words = np.zeros(max(1, (n + 31) // 32), np.uint32)
    for i in range(n):
        if e.is_ok[i]:
            ok_words[i // 32] |= np.uint32(1) << np.uint32(i % 32)
    return inv32, ret32, ok_words


def _state_abstraction_check(spec, e, init_state, max_states=4096,
                             max_rounds=64):
    """Sound invalidity pre-check: enumerate an over-approximation of
    the reachable model states (fixpoint of applying every op to every
    state, ignoring timing -- a superset of all linearization-prefix
    states). An ok op whose step fails from EVERY reachable state can
    appear in no linearization, so the history is invalid -- this
    decides e.g. a read of a never-written value on histories far too
    large to exhaust. Models with big state spaces overflow the cap and
    return None (no claim)."""
    n = len(e)
    # distinct (f, args, ret) rows: a 10k-op register history has a
    # few dozen, so the fixpoint is tiny regardless of history length
    rows = np.concatenate(
        [np.asarray(e.f, np.int32)[:, None],
         np.asarray(e.args, np.int32).reshape(n, -1),
         np.asarray(e.ret, np.int32).reshape(n, -1)], axis=1)
    uniq, inverse = np.unique(rows, axis=0, return_inverse=True)
    if len(uniq) > 512:
        return None
    A = np.asarray(e.args, np.int32).reshape(n, -1).shape[1]
    uf = uniq[:, 0]
    ua = uniq[:, 1:1 + A]
    ur = uniq[:, 1 + A:]
    states = {np.asarray(init_state, np.int32).tobytes():
              np.asarray(init_state, np.int32)}
    frontier = list(states.values())
    # per-row "some reachable state accepts it", accumulated as the
    # fixpoint steps every (state, row) pair exactly once
    possible = np.zeros(len(uniq), bool)
    for _ in range(max_rounds):
        new = []
        for st in frontier:
            for u in range(len(uniq)):
                st2, ok = spec.step(st, uf[u], ua[u], ur[u], np)
                if not ok:
                    continue
                possible[u] = True
                st2 = np.asarray(st2, np.int32)
                key = st2.tobytes()
                if key not in states:
                    if len(states) >= max_states:
                        return None
                    states[key] = st2
                    new.append(st2)
        if not new:
            break
        frontier = new
    else:
        return None   # no fixpoint within the round budget
    bad = np.flatnonzero(~possible[inverse] & np.asarray(e.is_ok, bool))
    if len(bad):
        return False, {"op_index": int(bad[0]),
                       "pattern": "impossible-from-every-state",
                       "reachable_states": len(states)}
    return None


def _fast_result(spec, e, init_state, fast, confirm=False):
    """Shape a fast_check decision like a search result, including the
    failure witness op and optional oracle confirmation."""
    result = {"configs_explored": 0, "iterations": 0, "engine": "aspect"}
    if fast is True:
        result["valid"] = True
        return result
    valid, info = fast
    result["valid"] = valid
    result.update({k: v for k, v in info.items() if k != "op_index"})
    i = info.get("op_index")
    if i is not None and e.ops is not None:
        inv, comp = e.ops[i]
        result["op"] = dict(comp if comp is not None else inv)
    if confirm:
        from . import wgl
        oracle = wgl.check_encoded(spec, e, init_state)
        result["confirmed"] = oracle["valid"] is valid
        result["valid"] = oracle["valid"]
    return result


def _apply_prune(spec, e, inv32, ret32):
    """Apply the model's validity-preserving candidate prune (if any):
    dropped rows get the padding-row treatment (invoke just below INF so
    they are never candidates while any ok op is outstanding, return at
    INF so they never constrain the WGL rule). Pruning only ever removes
    non-ok ops, so the success condition is untouched."""
    if spec.prune is None:
        return inv32, ret32
    keep = spec.prune(e, inv32, ret32)
    if keep is None:
        return inv32, ret32
    keep = np.asarray(keep, bool)
    assert not np.any(~keep & np.asarray(e.is_ok, bool)), \
        "prune must never drop ok ops"
    return (np.where(keep, inv32, INF32 - 1).astype(np.int32),
            np.where(keep, ret32, INF32).astype(np.int32))


def _priority_order(spec, e, inv32, ret32):
    """Renumber ops into linearization-priority order: argsort by the
    model hint (default: earliest deadline / return index). The kernel
    then searches candidates in plain index order with zero per-iteration
    gather cost. Returns (perm, inv32, ret32, fop, args, rets, ok_words)
    all permuted; witnesses decode back through perm."""
    n = len(e)
    pri = (np.asarray(spec.hint(e, inv32, ret32), np.int64)
           if spec.hint is not None else ret32.astype(np.int64))
    perm = np.argsort(pri, kind="stable").astype(np.int64)
    inv_s = inv32[perm]
    ret_s = ret32[perm]
    fop = np.asarray(e.f, np.int32)[perm]
    args = np.asarray(e.args, np.int32).reshape(n, -1)[perm]
    rets = np.asarray(e.ret, np.int32).reshape(n, -1)[perm]
    ok_s = np.asarray(e.is_ok, bool)[perm]
    ok_words = np.zeros(max(1, (n + 31) // 32), np.uint32)
    for i in np.flatnonzero(ok_s):
        ok_words[i // 32] |= np.uint32(1) << np.uint32(i % 32)
    return perm, inv_s, ret_s, fop, args, rets, ok_words


def _prepare_search(spec, e, init_state, confirm=False):
    """Shared host-side preparation for a single-key search: empty/fast
    paths, prune, priority order, padding to power-of-two buckets,
    state padding. Returns ``("fast", result)`` when a polynomial path
    decided the history, else ``("search", (perm, inv32, ret32, fop,
    args, rets, ok_words, init_state, n_pad, C, A, S))``. Used by both
    the single-chip path below and the mesh-sharded single search
    (parallel/searchshard.py)."""
    n = len(e)
    if n == 0 or e.n_ok == 0:
        return ("fast", {"valid": True, "configs_explored": 0})

    inv32, ret32, _ = _encode_arrays(e)
    if spec.fast_check is not None:
        fast = spec.fast_check(e, inv32, ret32)
        if fast is not None:
            # exact polynomial decision (e.g. queue bad patterns) --
            # no search needed at any history size
            return ("fast", _fast_result(spec, e, init_state, fast,
                                         confirm))
    if spec.pad_state is None:   # fixed small state spaces only
        fast = _state_abstraction_check(spec, e, init_state)
        if fast is not None:
            return ("fast", _fast_result(spec, e, init_state, fast,
                                         confirm))
    inv32, ret32 = _apply_prune(spec, e, inv32, ret32)
    C = max_point_concurrency(
        inv32,
        np.where(ret32 == INF32, INF_TIME, ret32.astype(np.int64)))
    A = int(e.args.shape[1]) if e.args.ndim == 2 else 1
    perm, inv32, ret32, fop, args, rets, ok_words = _priority_order(
        spec, e, inv32, ret32)

    # Pad shapes to power-of-two buckets so the compiled search is reused.
    # Padding rows are never candidates: they "invoke" after every finite
    # return (invoke INF32-1 >= any reachable r_min) and are not ok ops.
    n_pad = _bucket(n, _n_floor())
    C = min(_bucket(C, 4), n_pad)
    if n_pad > n:
        pn = n_pad - n
        inv32 = np.concatenate([inv32, np.full(pn, INF32 - 1, np.int32)])
        ret32 = np.concatenate([ret32, np.full(pn, INF32, np.int32)])
        fop = np.concatenate([fop, np.zeros(pn, np.int32)])
        args = np.concatenate([args, np.zeros((pn, A), np.int32)])
        rets = np.concatenate([rets, np.zeros((pn, A), np.int32)])
        # padding rows are never ok ops: just zero-extend the packed bits
        extra = (n_pad + 31) // 32 - len(ok_words)
        ok_words = np.concatenate([ok_words, np.zeros(extra, np.uint32)])

    init_state = np.asarray(init_state, np.int32)
    if spec.pad_state is not None:
        S_pad = _bucket(init_state.shape[0], 2)
        init_state = np.asarray(spec.pad_state(init_state, S_pad), np.int32)
    S = int(init_state.shape[0])
    return ("search", (perm, inv32, ret32, fop, args, rets, ok_words,
                       init_state, n_pad, C, A, S))


def check_encoded(spec, e, init_state, max_configs=50_000_000,
                  frontier_width=None, stack_size=None, table_size=None,
                  confirm=False, timeout_s=None, chunk_iters=256,
                  checkpoint=None, checkpoint_every_s=60.0, cancel=None,
                  rollout_seeds=None, rollout_kernel="auto",
                  rollout_depth=None):
    """Device WGL search over an EncodedHistory. Result dict mirrors
    wgl.check_encoded: {"valid": True|False|"unknown", "configs_explored",
    ...}, plus device budget diagnostics. ``timeout_s`` bounds wall clock
    (checked between device chunks of ``chunk_iters`` iterations);
    exceeding it yields {"valid": "unknown", "error": "timeout"}.

    ``checkpoint`` names a file the search frontier is periodically
    snapshotted to (every ``checkpoint_every_s``, between chunks) — the
    checkpoint/resume capability for long checks (SURVEY.md §5; the
    reference has nothing comparable, its unit of durability is a whole
    phase). A timed-out/killed check rerun with the same arguments
    resumes from the snapshot instead of restarting; snapshots carry a
    fingerprint of the search inputs so a stale file for a different
    history or plan is ignored."""
    from . import drive
    # phase cursor (obs.phases): attributes this search's wall to
    # encode/plan/h2d/compile/device/d2h/host spans; a pair of clock
    # reads per lap when obs is unbound
    ph = obs_phases.capture("jax-wgl")
    prep = _prepare_search(spec, e, init_state, confirm)
    if prep[0] == "fast":
        return prep[1]
    (perm, inv32, ret32, fop, args, rets, ok_words, init_state, n_pad,
     C, A, S) = prep[1]
    ph.lap("encode")

    B, W, O, T = _plan_sizes(n_pad, S, C, frontier_width, stack_size,
                             table_size)
    # cross-run compile-reuse ledger: everything feeding _build_search's
    # lru/jit key must feed this key too, or a "hit" could lie
    ph.note_compile(_note_compile(
        "jax-wgl", (spec.name, n_pad, B, S, C, A, W, O, T,
                    rollout_kernel, rollout_seeds, rollout_depth)))
    # honor tiny explicit budgets (a 1-iteration run must bail after 1
    # iteration, not 64 -- the checkpoint tests rely on it); the default
    # 50M-config budget keeps max_iters far above any real search
    max_iters = max(1, max_configs // W)

    init_carry, run_chunk, rollout = _build_search(
        spec.step, 1, n_pad, B, S, C, A, W, O, T, R=rollout_depth,
        NS=rollout_seeds, rollout_kernel=rollout_kernel)
    ph.lap("plan")
    # the key batch's row model with one row: key 0, salt 0
    col = (inv32, ret32, fop, args, rets, ok_words, init_state)
    consts = tuple(jnp.asarray(x[None]) for x in col[:6]) + (
        jnp.zeros(1, jnp.uint32),)
    carry = init_carry(jnp.asarray(init_state[None]))
    result, = drive.drive(
        ph, "jax-wgl", spec, [(e, init_state, perm)], carry, consts,
        run_chunk, max_iters=max_iters, chunk_iters=chunk_iters,
        timeout_s=timeout_s, cancel=cancel, confirm=confirm,
        checkpoint=checkpoint, checkpoint_every_s=checkpoint_every_s,
        snapshot_of=([col], [0], (n_pad, B, S, C, A, W, O, T, 1, 1)))
    result["rollout"] = rollout
    return result


def _interpret(spec, e, out, confirm, init_state, perm=None,
               timed_out=False, engine="jax-wgl"):
    """One key's verdict from its harvested carry fields ``out``. A
    search stopped by its wall budget while it still had work is
    ``unknown`` with error ``timeout``."""
    status = int(out["status"])
    explored = int(out["explored"])
    result = {"configs_explored": explored,
              "iterations": int(out["iterations"]),
              "engine": engine}
    if timed_out and status == RUNNING and int(out["top"]) > 0:
        result.update(valid="unknown", error="timeout")
        return result
    if status == VALID:
        result["valid"] = True
        _attach_valid_witness(result, e, out, perm, spec, init_state)
        return result
    exhausted = int(out["top"]) == 0
    dropped = bool(out["dropped"])
    if exhausted and not dropped:
        result["valid"] = False
        _attach_witness(result, e, out, perm, spec, init_state)
        if confirm:
            from . import wgl
            oracle = wgl.check_encoded(spec, e, init_state)
            result["confirmed"] = oracle["valid"] is False
            result["valid"] = oracle["valid"]
        return result
    result["valid"] = "unknown"
    result["error"] = ("stack-overflow" if dropped
                       else "max-configs-exceeded")
    return result


def _decode_slots(e, out, perm):
    """Decode the TOPK witness slots into (linearized bool[n], state)
    pairs, deepest-first. Bit positions are in priority-sorted space;
    perm maps them back to original op indices. Shared by the invalid
    path (stuck configurations) and the VALID path (the winning
    configuration rides the same slots)."""
    depths = np.asarray(out["best_depth"], np.int32).reshape(-1)
    lins = np.asarray(out["best_lin"], np.uint32).reshape(len(depths), -1)
    states = np.asarray(out["best_state"],
                        np.int32).reshape(len(depths), -1)
    n = len(e)
    slots = []
    for s in np.argsort(-depths, kind="stable"):
        if depths[s] < 0:
            continue
        lin = lins[s]
        linearized = np.zeros(n, bool)
        for i in range(n):
            pos = int(perm[i]) if perm is not None else i
            linearized[pos] = bool((lin[i // 32] >> np.uint32(i % 32)) & 1)
        slots.append((linearized, states[s]))
    return slots


def _attach_witness(result, e, out, perm, spec, init_state):
    """Decode the TOPK deepest distinct stuck configurations into
    knossos-style witness fields (op / final_paths / previous_ok /
    configs, see checker/witness.py; knossos returns a LIST of stuck
    :configs, reference checker.clj:213-216)."""
    slots = _decode_slots(e, out, perm)
    if not slots:
        # no child ever linearized (the search wedged at the root):
        # the root config IS the stuck config
        slots = [(np.zeros(len(e), bool),
                  np.asarray(init_state, np.int32))]
    from . import witness
    witness.attach_multi(result, spec, e, slots, init_state)


def _attach_valid_witness(result, e, out, perm, spec, init_state):
    """On VALID the winning configuration sits in the TOPK witness
    slots too (both kernel success sites topk_insert the candidate
    before raising the status), so a valid verdict's proof decodes
    exactly like the invalid path's: the deepest slot covering every
    ok op IS the linearization the search found. The normalized
    witness (checker/witness.py ``build``) lands on
    ``result["witness"]`` for the certifier to replay. Absence -- a
    slot-layout drift -- leaves the witness off; the certifier
    reports it (VC006), never a crash here."""
    is_ok = np.asarray(e.is_ok, bool)
    n_ok = int(is_ok.sum())
    for linearized, _state in _decode_slots(e, out, perm):
        if int((linearized & is_ok).sum()) == n_ok:
            from . import witness
            result["witness"] = witness.build(
                spec, e, result.get("engine"), True, linearized,
                init_state)
            return


def check_history(spec, history, **kw):
    """Encode an event history for ``spec`` and run the device search."""
    e, init_state = spec.encode(history)
    return check_encoded(spec, e, init_state, **kw)
