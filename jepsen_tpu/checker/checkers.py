"""The checker library: histories in, verdict maps out (reference
jepsen/src/jepsen/checker.clj:124-795).

Each checker returns {"valid": True|False|"unknown", ...}. The
``linearizable`` checker is the gate to the linearizability engines: it
dispatches on "algorithm" exactly like the reference dispatches to knossos
(checker.clj:199-202), with "jax-wgl" selecting the TPU engine and
"competition" racing the CPU oracle against it."""

from __future__ import annotations

import collections
import logging
import re
import threading

from .. import history as h
from .. import obs
from ..models import base as mbase
from .core import Checker, merge_valid

logger = logging.getLogger(__name__)

__all__ = [
    "unhandled_exceptions", "stats", "linearizable", "queue", "set_checker",
    "set_full", "expand_queue_drain_ops", "total_queue", "unique_ids",
    "counter", "log_file_pattern",
]


class _UnhandledExceptions(Checker):
    """Aggregates info ops carrying exceptions by class
    (checker.clj:124-151)."""

    def check(self, test, hist, opts=None):
        exes = [o for o in hist
                if o.get("exception") and o.get("type") == "info"]
        groups = collections.defaultdict(list)
        for o in exes:
            groups[o.get("exception")].append(o)
        out = sorted(groups.values(), key=len, reverse=True)
        result = {"valid": True}
        if out:
            result["exceptions"] = [
                {"count": len(ops), "class": ops[0].get("exception"),
                 "example": ops[0]} for ops in out]
        return result


def unhandled_exceptions():
    return _UnhandledExceptions()


def _stats_map(hist):
    ok = sum(1 for o in hist if h.ok(o))
    fail = sum(1 for o in hist if h.fail(o))
    info = sum(1 for o in hist if h.info(o))
    return {"valid": ok > 0, "count": ok + fail + info,
            "ok-count": ok, "fail-count": fail, "info-count": info}


class _Stats(Checker):
    """ok/fail/info counts overall and by :f; valid iff every f saw an ok
    (checker.clj:153-183)."""

    def check(self, test, hist, opts=None):
        hist = [o for o in hist
                if not h.invoke(o) and o.get("process") != "nemesis"]
        by_f = collections.defaultdict(list)
        for o in hist:
            by_f[o.get("f")].append(o)
        groups = {f: _stats_map(sub) for f, sub in sorted(
            by_f.items(), key=lambda kv: str(kv[0]))}
        out = _stats_map(hist)
        out["by-f"] = groups
        out["valid"] = merge_valid([g["valid"] for g in groups.values()])
        return out


def stats():
    return _Stats()


class Linearizable(Checker):
    """THE gate to the linearizability engines (checker.clj:185-216).
    algorithm: "wgl" (sequential CPU oracle), "jax-wgl" (batched device
    search), "linear" (just-in-time linearization; bounded config set,
    may return "unknown" on overflow), or default "competition" (races
    all three; the first definite verdict wins)."""

    def __init__(self, model, algorithm="competition", engine_opts=None,
                 init_ops=None):
        assert model is not None, \
            "the linearizable checker requires a model"
        self.spec = mbase.model_spec(model)
        self.algorithm = algorithm
        self.engine_opts = engine_opts or {}
        if "chips" in self.engine_opts:
            # the chip count becomes the 1-D keys mesh the device
            # engines take; an explicit mesh wins, and one chip is the
            # one-device path
            opts = dict(self.engine_opts)
            chips = opts.pop("chips")
            if not isinstance(chips, int) or chips < 1:
                raise ValueError(f"engine_opts chips must be a whole "
                                 f"number >= 1, not {chips!r}")
            if chips > 1 and "mesh" not in opts:
                from ..parallel import keys_mesh
                opts["mesh"] = keys_mesh(chips)
            self.engine_opts = opts
        #: ops establishing the initial state, e.g. [{"f": "write",
        #: "value": 0}] for a register pre-set to 0 (the reference's
        #: (model/cas-register 0)). Prepended as already-completed pairs
        #: before every real op.
        self.init_ops = list(init_ops or [])

    def prepare_history(self, client_hist):
        """Prepend the init ops as already-completed pairs ordered before
        every real op (negative indices). Both the direct check and
        independent's batched per-key path go through this, and both
        must feed it the SAME selection of ops — ``history.client_ops``
        (integer process ids only; the nemesis and log lines never
        linearize). A nemesis-laced history must produce identical
        verdicts on either path."""
        if not self.init_ops:
            return client_hist
        lo = min((o.get("index", 0) for o in client_hist), default=0)
        synth = []
        for j, op in enumerate(self.init_ops):
            base = lo - 2 * (len(self.init_ops) - j)
            synth.append({"type": "invoke", "process": -1,
                          "f": op["f"], "value": op.get("value"),
                          "index": base, "time": base})
            synth.append({"type": "ok", "process": -1,
                          "f": op["f"], "value": op.get("value"),
                          "index": base + 1, "time": base + 1})
        return synth + client_hist

    def check(self, test, hist, opts=None):
        from . import jax_wgl, linear, wgl
        client_hist = self.prepare_history(h.client_ops(hist))
        algo = self.algorithm
        # search planning (analysis/searchplan.py): sealed quiescent
        # cuts slice the history into independent segments routed as
        # ONE batch through parallel/keyshard (same _n_floor buckets,
        # so the compile ledger still hits). Default on; opt out with
        # test["searchplan?"] = False. None = no reduction / planning
        # failed -> the unplanned search below runs as always.
        a = None
        if algo == "jax-wgl" and "mesh" not in self.engine_opts:
            a = self._check_planned(test, client_hist)
        if a is None:
            e, init_state = self.spec.encode(client_hist)
            if algo == "wgl":
                a = wgl.check_encoded(self.spec, e, init_state)
            elif algo == "linear":
                a = linear.check_encoded(self.spec, e, init_state)
            elif algo == "jax-wgl":
                eopts = dict(self.engine_opts)
                mesh = eopts.pop("mesh", None)
                if mesh is not None:
                    # one SINGLE-key search sharded across the mesh
                    # (parallel/searchshard.py); the multi-key batched
                    # path takes mesh via independent's engine_opts.
                    # Forward only the options the sharded engine
                    # supports; warn-drop the rest rather than crash a
                    # whole check over e.g. a checkpoint path
                    from ..parallel import check_encoded_sharded
                    keep = {"max_configs", "frontier_width",
                            "stack_size", "table_size", "timeout_s",
                            "chunk_iters", "steal", "rollout_seeds"}
                    dropped = sorted(set(eopts) - keep)
                    if dropped:
                        logger.warning(
                            "engine_opts %s are not supported by the "
                            "mesh-sharded search; ignoring", dropped)
                    a = check_encoded_sharded(
                        self.spec, e, init_state, mesh,
                        **{k: v for k, v in eopts.items() if k in keep})
                else:
                    a = jax_wgl.check_encoded(self.spec, e, init_state,
                                              **eopts)
            else:
                a = self._competition(e, init_state)
        # truncate heavyweight fields (checker.clj:213-216: "writing
        # these can take *hours*"): at most 10 paths / 10 configs
        if "final_paths" in a:
            a["final_paths"] = a["final_paths"][:10]
        if "configs" in a:
            a["configs"] = a["configs"][:10]
        if a.get("valid") is False:
            # render the failure witness like the reference's linear.svg
            # (checker.clj:206-212); never let plotting break the verdict
            try:
                from . import linear_report
                linear_report.render_analysis(test, client_hist, a, opts)
            except Exception:  # noqa: BLE001
                logger.warning("couldn't render linear.png",
                               exc_info=True)
        a["valid?"] = a["valid"]
        return a

    #: engine_opts forwarded to the planned batch path — everything
    #: check_batch_encoded supports, including checkpoint/resume (its
    #: fingerprint covers the per-segment inputs, so a rerun of the
    #: same plan resumes). The rest are single-search-only
    #: (confirm/rollout_kernel/rollout_depth); mesh is excluded up
    #: front in check().
    _PLANNED_OPTS = frozenset({"max_configs", "chunk_iters", "timeout_s",
                               "frontier_width", "stack_size",
                               "table_size", "rollout_seeds",
                               "checkpoint", "checkpoint_every_s"})

    def _check_planned(self, test, client_hist):
        """Consult the search plan for this (already init-op-prepared)
        client history: when sealed quiescent cuts slice it into >= 2
        segments, run them as one batched device call and merge.
        Returns None when planning is off, yields no reduction, or
        fails -- the caller then runs the unplanned search, so a
        planner bug can never change a verdict."""
        if not isinstance(test, dict):
            return None
        from ..analysis import searchplan
        # this path's only reduction IS quiescent-cut segmentation, so
        # it honors the predicate list, not just the on/off knob
        if not searchplan.segments_enabled(test):
            return None
        unsupported = set(self.engine_opts) - self._PLANNED_OPTS
        if "confirm" in unsupported:
            # oracle confirmation changes the result contract
            # (result["confirmed"]); the flat search honors it, so
            # planning steps aside rather than silently dropping it
            return None
        if unsupported:
            logger.warning(
                "engine_opts %s are not supported by the planned "
                "batch search; ignoring", sorted(unsupported))
        try:
            import time as _time
            t0 = _time.monotonic()
            segs, info = searchplan.plan_segments(
                self.spec, client_hist, searchplan.min_segment(test))
            if len(segs) < 2:
                return None
            # plan_s = the analyzer's own cost (matching the
            # independent path's measurement); encoding is charged to
            # the search like it is on the unplanned path
            plan_s = _time.monotonic() - t0
            from ..parallel import check_batch_encoded
            pairs = [self.spec.encode(s.events) for s in segs]
            eopts = {k: v for k, v in self.engine_opts.items()
                     if k in self._PLANNED_OPTS}
            results = check_batch_encoded(self.spec, pairs, **eopts)
            # stamp segment provenance onto each normalized witness
            # before the merge folds them: the certifier re-derives
            # the same cuts and matches index/count/seed exactly
            for i, (r, s) in enumerate(zip(results, segs)):
                w = r.get("witness")
                if isinstance(w, dict):
                    w["segment"] = {"index": i, "count": len(segs),
                                    "seed": s.seed}
            merged = searchplan.merge_segment_results(results, info,
                                                      plan_s)
            if obs.enabled():
                obs.inc("checker.planned_checks",
                        valid=str(merged.get("valid")))
                obs.observe("checker.plan_s", plan_s)
            return merged
        except Exception:  # noqa: BLE001 - fall back to the flat search
            logger.warning("planned search failed; falling back to the "
                           "unplanned path", exc_info=True)
            return None

    def _competition(self, e, init_state):
        """Race the sequential oracle against the device engine; the first
        *definite* verdict wins (knossos.competition semantics,
        checker.clj:199-202). If the first engine to finish returns
        "unknown" (config-budget overflow, timeout, crash), wait for the
        other engine and prefer its verdict when definite. Errors of the
        engines that finished but lost land under ``engine_errors``."""
        from . import jax_wgl, linear, wgl
        first_done = threading.Event()
        results = {}
        order = []
        lock = threading.Lock()

        def run(name, fn):
            try:
                r = fn()
            except Exception as exc:  # noqa: BLE001
                if name == "jax-wgl":
                    logger.error("device engine failed in the "
                                 "competition", exc_info=True)
                r = {"valid": "unknown", "error": repr(exc)}
            with lock:
                results[name] = r
                order.append(name)
            first_done.set()

        # the CPU engines get config budgets so they yield on hard
        # searches; knossos.competition likewise races linear + wgl.
        # Each racer runs under a contextvars snapshot (like the
        # interpreter/control fan-outs) so the run-scoped obs sinks —
        # and span parentage — follow it: the device engine's
        # heartbeats must land in THIS run's registry even while an
        # overlapping campaign cell holds the process-global binding.
        import contextvars
        cancel = threading.Event()
        threads = [
            threading.Thread(
                target=contextvars.copy_context().run,
                args=(run, "wgl", lambda: wgl.check_encoded(
                    self.spec, e, init_state, max_configs=2_000_000,
                    cancel=cancel)),
                daemon=True),
            threading.Thread(
                target=contextvars.copy_context().run,
                args=(run, "linear", lambda: linear.check_encoded(
                    self.spec, e, init_state, max_configs=200_000,
                    cancel=cancel)),
                daemon=True),
            threading.Thread(
                target=contextvars.copy_context().run,
                args=(run, "jax-wgl", lambda: jax_wgl.check_encoded(
                    self.spec, e, init_state, cancel=cancel,
                    **self.engine_opts)),
                daemon=True),
        ]
        for t in threads:
            t.start()
        # wait for the first DEFINITE verdict (or everyone to give up)
        while True:
            first_done.wait()
            with lock:
                first_done.clear()
                definite = [(nm, results[nm]) for nm in order
                            if results[nm].get("valid") != "unknown"]
                if definite:
                    name, r = definite[0]
                    break
                if len(order) == len(threads):
                    name, r = order[0], results[order[0]]
                    break
        # ask the losing engines to stop (checked between device chunks
        # / every few thousand host configs). Join only briefly: a
        # device compile in flight can take tens of seconds and the
        # verdict is already in hand -- the daemon threads drain
        # themselves once they next check the flag.
        cancel.set()
        for t in threads:
            t.join(timeout=0.5)
        r = dict(r)
        r["engine"] = name
        with lock:
            # the losers' errors ride the verdict: a device crash must
            # not vanish behind a CPU engine's win
            errors = {nm: results[nm]["error"] for nm in order
                      if nm != name and results[nm].get("error")}
        if errors:
            r["engine_errors"] = errors
        if obs.enabled():
            obs.inc("checker.competition_wins", engine=name)
            obs.instant("checker.competition", cat="checker",
                        winner=name, valid=str(r.get("valid")))
        return r


def linearizable(opts):
    """linearizable({"model": ..., "algorithm": ...})
    (checker.clj:185-216)."""
    if isinstance(opts, dict):
        return Linearizable(opts["model"], opts.get("algorithm",
                                                    "competition"),
                            opts.get("engine_opts"),
                            opts.get("init-ops"))
    return Linearizable(opts)


class _Queue(Checker):
    """Model-fold queue check: non-failing enqueues count, only ok
    dequeues count (checker.clj:218-238)."""

    def __init__(self, model):
        self.model = model

    def check(self, test, hist, opts=None):
        state = self.model
        for op in hist:
            f = op.get("f")
            take = (f == "enqueue" and h.invoke(op)) or \
                   (f == "dequeue" and h.ok(op))
            if not take:
                continue
            state = state.step(op)
            if mbase.is_inconsistent(state):
                return {"valid": False, "error": state.msg}
        return {"valid": True, "final-queue": state}


def queue(model):
    return _Queue(model)


class _SetChecker(Checker):
    """adds + final read: lost/unexpected/recovered analysis
    (checker.clj:240-291)."""

    def check(self, test, hist, opts=None):
        attempts = {o.get("value") for o in hist
                    if h.invoke(o) and o.get("f") == "add"}
        adds = {o.get("value") for o in hist
                if h.ok(o) and o.get("f") == "add"}
        final_read = None
        for o in hist:
            if h.ok(o) and o.get("f") == "read":
                final_read = o.get("value")
        if final_read is None:
            return {"valid": "unknown", "error": "Set was never read"}
        final = set(final_read)
        ok = final & attempts
        unexpected = final - attempts
        lost = adds - final
        recovered = ok - adds
        return {"valid": not lost and not unexpected,
                "attempt-count": len(attempts),
                "acknowledged-count": len(adds),
                "ok-count": len(ok),
                "lost-count": len(lost),
                "recovered-count": len(recovered),
                "unexpected-count": len(unexpected),
                "ok": sorted(ok), "lost": sorted(lost),
                "unexpected": sorted(unexpected),
                "recovered": sorted(recovered)}


def set_checker():
    return _SetChecker()


class _SetFullElement:
    """Per-element timeline state (checker.clj SetFullElement,
    :300-340)."""

    __slots__ = ("element", "known", "last_present", "last_absent")

    def __init__(self, element):
        self.element = element
        self.known = None
        self.last_present = None
        self.last_absent = None

    def add_ok(self, op):
        if self.known is None:
            self.known = op

    def read_present(self, inv, op):
        if self.known is None:
            self.known = op
        if self.last_present is None or \
                self.last_present["index"] < inv["index"]:
            self.last_present = inv

    def read_absent(self, inv, op):
        if self.last_absent is None or \
                self.last_absent["index"] < inv["index"]:
            self.last_absent = inv

    def results(self):
        """Outcome classification (checker.clj:346-405)."""
        idx = lambda o, d=-1: o["index"] if o is not None else d  # noqa:E731
        stable = bool(self.last_present is not None and
                      idx(self.last_absent) < idx(self.last_present))
        lost = bool(self.known is not None and
                    self.last_absent is not None and
                    idx(self.last_present) < idx(self.last_absent) and
                    self.known["index"] < idx(self.last_absent))
        never_read = not (stable or lost)
        known_time = self.known["time"] if self.known else None
        stable_latency = None
        lost_latency = None
        if stable:
            stable_time = (self.last_absent["time"] + 1
                           if self.last_absent else 0)
            stable_latency = int(max(0, stable_time - known_time) / 1e6)
        if lost:
            lost_time = (self.last_present["time"] + 1
                         if self.last_present else 0)
            lost_latency = int(max(0, lost_time - known_time) / 1e6)
        return {"element": self.element,
                "outcome": ("stable" if stable else
                            "lost" if lost else "never-read"),
                "stable-latency": stable_latency,
                "lost-latency": lost_latency,
                "known": self.known,
                "last-absent": self.last_absent,
                "never_read": never_read}


def _frequency_distribution(points, values):
    values = sorted(values)
    if not values:
        return None
    n = len(values)
    return {p: values[min(n - 1, int(n * p))] for p in points}


class _SetFull(Checker):
    """Per-element stable/lost timeline analysis with latency quantiles
    (checker.clj:294-592)."""

    def __init__(self, linearizable=False):
        self.linearizable = linearizable

    def check(self, test, hist, opts=None):
        hist = h.ensure_indexed(hist)
        elements = {}
        reads = {}
        dups = {}
        for op in hist:
            if not isinstance(op.get("process"), int):
                continue
            f = op.get("f")
            v = op.get("value")
            p = op.get("process")
            if f == "add":
                if h.invoke(op):
                    elements[v] = _SetFullElement(v)
                elif h.ok(op) and v in elements:
                    elements[v].add_ok(op)
            elif f == "read":
                if h.invoke(op):
                    reads[p] = op
                elif h.fail(op):
                    reads.pop(p, None)
                elif h.ok(op):
                    inv = reads.pop(p, op)
                    counts = collections.Counter(v)
                    for k, c in counts.items():
                        if c > 1:
                            dups[k] = max(dups.get(k, 0), c)
                    vs = set(v)
                    for el, state in elements.items():
                        if el in vs:
                            state.read_present(inv, op)
                        else:
                            state.read_absent(inv, op)
        rs = [elements[k].results()
              for k in sorted(elements, key=lambda x: (str(type(x)), x))]
        outcomes = collections.defaultdict(list)
        for r in rs:
            outcomes[r["outcome"]].append(r)
        stale = [r for r in outcomes["stable"]
                 if r["stable-latency"] and r["stable-latency"] > 0]
        valid = (False if outcomes["lost"] else
                 "unknown" if not outcomes["stable"] else
                 False if self.linearizable and stale else True)
        if dups:
            valid = False
        out = {"valid": valid,
               "attempt-count": len(rs),
               "stable-count": len(outcomes["stable"]),
               "lost-count": len(outcomes["lost"]),
               "lost": sorted(r["element"] for r in outcomes["lost"]),
               "never-read-count": len(outcomes["never-read"]),
               "never-read": sorted(r["element"]
                                    for r in outcomes["never-read"]),
               "stale-count": len(stale),
               "stale": sorted(r["element"] for r in stale),
               "worst-stale": sorted(stale, key=lambda r:
                                     -(r["stable-latency"] or 0))[:8],
               "duplicated-count": len(dups),
               "duplicated": dups}
        points = (0, 0.5, 0.95, 0.99, 1)
        sl = [r["stable-latency"] for r in rs
              if r["stable-latency"] is not None]
        ll = [r["lost-latency"] for r in rs
              if r["lost-latency"] is not None]
        if sl:
            out["stable-latencies"] = _frequency_distribution(points, sl)
        if ll:
            out["lost-latencies"] = _frequency_distribution(points, ll)
        return out


def set_full(checker_opts=None):
    opts = checker_opts or {}
    return _SetFull(linearizable=opts.get("linearizable?", False))


def expand_queue_drain_ops(hist):
    """Expand ok :drain ops into dequeue invoke/ok pairs
    (checker.clj:594-626)."""
    out = []
    for op in hist:
        if op.get("f") != "drain":
            out.append(op)
        elif h.invoke(op) or h.fail(op):
            continue
        elif h.ok(op):
            for element in op.get("value") or []:
                inv = dict(op)
                inv.update(type="invoke", f="dequeue", value=None)
                comp = dict(op)
                comp.update(type="ok", f="dequeue", value=element)
                out.extend([inv, comp])
        else:
            raise ValueError(
                f"not sure how to handle a crashed drain: {op!r}")
    return out


class _TotalQueue(Checker):
    """Multiset conservation: what goes in must come out
    (checker.clj:628-687)."""

    def check(self, test, hist, opts=None):
        hist = expand_queue_drain_ops(hist)
        attempts = collections.Counter(
            o.get("value") for o in hist
            if h.invoke(o) and o.get("f") == "enqueue")
        enqueues = collections.Counter(
            o.get("value") for o in hist
            if h.ok(o) and o.get("f") == "enqueue")
        dequeues = collections.Counter(
            o.get("value") for o in hist
            if h.ok(o) and o.get("f") == "dequeue")
        ok = dequeues & attempts
        unexpected = collections.Counter(
            {k: v for k, v in dequeues.items() if k not in attempts})
        duplicated = dequeues - attempts - unexpected
        lost = enqueues - dequeues
        recovered = ok - enqueues
        return {"valid": not lost and not unexpected,
                "attempt-count": sum(attempts.values()),
                "acknowledged-count": sum(enqueues.values()),
                "ok-count": sum(ok.values()),
                "unexpected-count": sum(unexpected.values()),
                "duplicated-count": sum(duplicated.values()),
                "lost-count": sum(lost.values()),
                "recovered-count": sum(recovered.values()),
                "lost": dict(lost), "unexpected": dict(unexpected),
                "duplicated": dict(duplicated),
                "recovered": dict(recovered)}


def total_queue():
    return _TotalQueue()


class _UniqueIds(Checker):
    """Are generated IDs distinct? (checker.clj:689-734)"""

    def check(self, test, hist, opts=None):
        attempted = sum(1 for o in hist
                        if h.invoke(o) and o.get("f") == "generate")
        acks = [o.get("value") for o in hist
                if h.ok(o) and o.get("f") == "generate"]
        counts = collections.Counter(acks)
        dups = {k: v for k, v in counts.items() if v > 1}
        rng = [min(acks), max(acks)] if acks else None
        top_dups = dict(sorted(dups.items(), key=lambda kv: -kv[1])[:48])
        return {"valid": not dups,
                "attempted-count": attempted,
                "acknowledged-count": len(acks),
                "duplicated-count": len(dups),
                "duplicated": top_dups,
                "range": rng}


def unique_ids():
    return _UniqueIds()


class _Counter(Checker):
    """Bounds-interval counter check: each read must fall within
    [sum of ok adds at invoke, sum of attempted adds at completion]
    (checker.clj:737-795)."""

    def check(self, test, hist, opts=None):
        hist = [o for o in h.complete(hist)
                if not h.fail(o) and not o.get("fails?")]
        lower = 0
        upper = 0
        pending = {}
        reads = []
        for op in hist:
            key = (op.get("type"), op.get("f"))
            if key == ("invoke", "read"):
                pending[op["process"]] = [lower, op.get("value")]
            elif key == ("ok", "read"):
                r = pending.pop(op["process"], None)
                if r is not None:
                    reads.append(r + [upper])
            elif key == ("invoke", "add"):
                v = op.get("value") or 0
                # a pending add widens the bound in its direction; a
                # negative add lowers the reachable floor instead
                if v >= 0:
                    upper += v
                else:
                    lower += v
            elif key == ("ok", "add"):
                v = op.get("value") or 0
                if v >= 0:
                    lower += v
                else:
                    upper += v
        errors = [r for r in reads
                  if not (r[0] <= r[1] <= r[2])]
        return {"valid": not errors, "reads": reads, "errors": errors}


def counter():
    return _Counter()


class _LogFilePattern(Checker):
    """Greps downloaded node logs in the store dir for a pattern
    (checker.clj:839-881)."""

    def __init__(self, pattern, filename):
        self.pattern = re.compile(pattern)
        self.filename = filename

    def check(self, test, hist, opts=None):
        from .. import store
        try:
            paths = {node: store.path(test, node, self.filename)
                     for node in test.get("nodes", [])}
        except (AssertionError, KeyError):
            return {"valid": "unknown",
                    "error": "no store directory for this test"}
        matches = []
        for node, path in paths.items():
            try:
                with open(path, errors="replace") as f:
                    for line in f:
                        if self.pattern.search(line):
                            matches.append({"node": node,
                                            "line": line.rstrip("\n")})
            except FileNotFoundError:
                continue
        return {"valid": not matches, "count": len(matches),
                "matches": matches}


def log_file_pattern(pattern, filename):
    return _LogFilePattern(pattern, filename)
