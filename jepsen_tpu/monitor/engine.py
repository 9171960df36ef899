"""Prefix-check dispatch for the streaming monitor.

One function, three engines -- the same trio the offline
``Linearizable`` gate races, minus the race (the monitor re-checks
every chunk, so it wants one predictable engine per run):

* ``jax-wgl`` -- the batched device search. Prefixes pad to the same
  pow-2 buckets as offline checks (``jax_wgl._bucket`` /
  ``_n_floor``), so a run's successive chunk checks reuse ONE
  compiled kernel per bucket, the campaign compile-reuse ledger
  counts the hits, and the carry advances through the existing
  ``run_chunk`` donate-argnums dispatch loop.
* ``linear`` -- just-in-time linearization: the CPU engine whose
  event-sweep formulation is itself the incremental-monitoring
  algorithm of the papers; the natural choice for CPU-only runs.
* ``wgl`` -- the sequential oracle, for tests and tiny histories.
* ``streamlin`` -- the device-resident incremental frontier
  (``checker/streamlin.py``). Through THIS dispatcher it runs as a
  one-shot fold over the whole prefix (the flat face the offline
  equivalence tests exercise); the real O(window) streaming driver is
  ``monitor/wgl_stream.StreamCheck``, which the monitor wires in
  ``_encoder`` and which only reaches this function for its contained
  flat fall-back and violation confirms.

Budgets are deliberately modest: a monitor check that can't decide
quickly returns "unknown" and the monitor moves on -- the offline
checker still owns the final word; the monitor only ever *adds* an
early abort.
"""

from __future__ import annotations

import logging

logger = logging.getLogger(__name__)

__all__ = ["ENGINES", "TXN_WORKLOADS", "check_prefix",
           "check_txn_prefix"]

#: engines the monitor can drive (planlint PL013 validates against it)
ENGINES = ("jax-wgl", "linear", "wgl", "streamlin")

#: txn-family workloads the monitor can stream (monitor/txn.py;
#: planlint PL025 validates against it)
TXN_WORKLOADS = ("append", "wr")

#: CPU-engine budgets: chunk checks repeat, so each one must stay small
LINEAR_MAX_CONFIGS = 200_000
WGL_MAX_CONFIGS = 2_000_000


def check_prefix(spec, e, init_state, engine="jax-wgl",
                 engine_opts=None, cancel=None):
    """Check one encoded prefix; returns the engine's result dict
    ({"valid": True|False|"unknown", ...}). Exceptions become
    "unknown": a monitor bug must never abort a healthy run."""
    if len(e) == 0 or e.n_ok == 0:
        return {"valid": True, "configs_explored": 0, "engine": engine}
    try:
        if engine == "linear":
            from ..checker import linear
            return linear.check_encoded(
                spec, e, init_state, max_configs=LINEAR_MAX_CONFIGS,
                cancel=cancel)
        if engine == "wgl":
            from ..checker import wgl
            return wgl.check_encoded(
                spec, e, init_state, max_configs=WGL_MAX_CONFIGS,
                cancel=cancel)
        if engine == "streamlin":
            from ..checker import streamlin
            opts = dict(engine_opts or {})
            return streamlin.check_encoded(
                spec, e, init_state,
                max_configs=int(opts.get("frontier-cap")
                                or streamlin.DEFAULT_FRONTIER_CAP),
                cancel=cancel)
        from ..checker import jax_wgl
        opts = dict(engine_opts or {})
        # the mesh/checkpoint machinery is offline-only; a monitor
        # check is short-lived and re-runs every chunk
        for k in ("chips", "mesh", "checkpoint", "checkpoint_every_s",
                  "confirm"):
            opts.pop(k, None)
        return jax_wgl.check_encoded(spec, e, init_state, cancel=cancel,
                                     **opts)
    except Exception as exc:  # noqa: BLE001 - contained per check
        logger.warning("monitor prefix check crashed", exc_info=True)
        return {"valid": "unknown", "error": repr(exc), "engine": engine}


def check_txn_prefix(history, workload="append", opts=None, cancel=None):
    """family="txn" dispatch: run the full offline ``cycle/`` analysis
    over a consumed txn prefix -- the verdict of record the streaming
    frontier's suspicion defers to (monitor/txn.py only calls this when
    the incremental closure closed a cycle or inference flagged an
    anomaly). Same containment as ``check_prefix``: exceptions become
    "unknown", never an abort."""
    opts = dict(opts or {})
    try:
        if workload == "wr":
            from ..cycle import wr
            return wr.analyze(list(history), opts)
        from ..cycle import DEFAULT_ANOMALIES
        from ..cycle import append as app
        return app.analyze(
            list(history),
            tuple(opts.get("anomalies", DEFAULT_ANOMALIES)),
            realtime=opts.get("realtime", True),
            process=opts.get("process", False),
            skew_bound=opts.get("skew-bound",
                                opts.get("skew_bound", 0)))
    except Exception as exc:  # noqa: BLE001 - contained per check
        logger.warning("monitor txn prefix check crashed", exc_info=True)
        return {"valid": "unknown", "error": repr(exc),
                "engine": f"txn-{workload}"}
