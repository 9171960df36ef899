"""Opt-in XLA profiler capture around a run's device searches.

The telemetry plane says WHAT the search did (heartbeats, padding
accounting, duty cycle); the XLA profiler says WHY a dispatch cost
what it did — per-op device timelines, fusion shapes, HBM traffic.
``--profile`` (``test["profile?"]``) wraps the analyze phase — the
run's device searches — in ``jax.profiler`` trace capture persisted
NEXT TO ``trace.jsonl``:

* **Layout.** Captures land in ``<run dir>/profile/`` (or an explicit
  ``test["profile-dir"]``); XLA writes its TensorBoard-shaped tree
  under ``plugins/profile/<ts>/``. A ``profile.json`` marker beside it
  records the capture's status — the web UI links both.
* **Bounded.** ``test["profile-max-s"]`` (default 120 s) arms a timer
  that stops the capture even when the search wedges: an unbounded
  profile of a stuck multi-hour search would fill the disk the run's
  own artifacts need. Best effort: ``jax.profiler.stop_trace`` from
  the timer thread blocks until in-flight device dispatches quiesce
  (measured: it returns the moment the dispatch loop pauses), so the
  bound takes effect at the next dispatch boundary, not mid-kernel —
  and profiling LARGE multi-compile workloads (e.g. a keyed demo's
  hundreds of per-key checks) multiplies their compile wall; profile
  compact runs.
* **Crash-tolerant (journal discipline).** The marker is written
  ``status: "capturing"`` + flushed BEFORE the profiler starts and
  atomically rewritten at stop, so a kill -9 mid-capture leaves a
  readable marker naming the partial capture directory — the same
  append-then-finalize contract the trace/metrics journals follow.
* **Contained.** Every failure path — jax.profiler missing, an
  unwritable directory, a start/stop error, a second concurrent
  capture (the profiler is process-global) — degrades to a marker
  with the reason; the run itself NEVER fails because profiling
  could not (the CI profile smoke pins this).

* **One clock.** The capture runs inside `anchor` (``ANCHOR``): one
  ``TraceAnnotation`` in the profile and one span of the same name in
  ``trace.jsonl``, opened back to back. `on_profile_clock` maps every
  span of ``trace.jsonl`` onto the profile's clock by the difference
  of the two starts (error: the few microseconds between the two
  enters), so a device idle gap in the capture can be put down to the
  program span that covers it.

``JEPSEN_NO_PROFILER=1`` forces `available()` False — how the
containment path is exercised deterministically in CI.
"""

from __future__ import annotations

import contextlib
import json
import logging
import os
import threading
import time as _time

logger = logging.getLogger(__name__)

__all__ = ["available", "scope", "anchor", "on_profile_clock",
           "profile_dir_for", "MARKER_FILE", "PROFILE_DIR",
           "DEFAULT_MAX_S", "ANCHOR"]

#: subdirectory of the run dir the capture lands in
PROFILE_DIR = "profile"
#: the crash-tolerant status marker written next to trace.jsonl
MARKER_FILE = "profile.json"
#: capture wall bound: a wedged search must not grow the capture
#: forever (the stop timer fires mid-search and the run continues)
DEFAULT_MAX_S = 120.0
#: the anchor `scope` opens around the captured body
ANCHOR = "obs.profile"

#: the profiler is process-global state; a second concurrent capture
#: (overlapping campaign cells) must refuse, not corrupt the first
_capture_lock = threading.Lock()
_capturing = False


def available():
    """Whether jax.profiler trace capture can run here. Env
    ``JEPSEN_NO_PROFILER=1`` forces False (containment smoke)."""
    if os.environ.get("JEPSEN_NO_PROFILER"):
        return False
    try:
        from jax import profiler as _p
        return callable(getattr(_p, "start_trace", None)) \
            and callable(getattr(_p, "stop_trace", None))
    except Exception:  # noqa: BLE001 - no jax / broken install
        return False


@contextlib.contextmanager
def anchor(name):
    """A ``jax.profiler.TraceAnnotation`` and a span of the bound tracer
    (cat ``anchor``), both named ``name``, opened back to back: the pair
    `on_profile_clock` maps one clock onto the other by."""
    import jax
    from .. import obs
    with jax.profiler.TraceAnnotation(name), obs.span(name, cat="anchor"):
        yield


def on_profile_clock(events, name, profile_start_ns):
    """The complete (``X``) events of a tracer (``Tracer.events()`` or a
    loaded ``trace.jsonl``) as ``(name, cat, start_ns, dur_ns)`` on the
    profiler's clock, given where the profiler put the start of the
    `anchor` called ``name``. None when ``events`` hold no such
    anchor."""
    starts = [ev["ts"] for ev in events
              if ev.get("ph") == "X" and ev.get("name") == name
              and ev.get("cat") == "anchor"]
    if not starts:
        return None
    off = profile_start_ns - starts[0] * 1e3
    return [(ev["name"], ev.get("cat"), ev["ts"] * 1e3 + off,
             ev.get("dur", 0.0) * 1e3)
            for ev in events if ev.get("ph") == "X"]


def profile_dir_for(test):
    """Where this test's capture would land: the explicit
    ``profile-dir``, else ``<run dir>/profile`` for named tests, else
    None (nowhere to persist — planlint PL019 flags it ahead of
    time)."""
    d = test.get("profile-dir")
    if d:
        return str(d)
    if test.get("name"):
        from .. import store
        try:
            return store.path(test, PROFILE_DIR)
        except Exception:  # noqa: BLE001 - store layout problems
            return None
    return None


def _write_marker(path, payload):
    """Atomic marker write (tmp + rename), flushed to disk: the
    ``status: capturing`` line must survive a kill -9 an instant
    later."""
    d = os.path.dirname(path)
    if d:
        os.makedirs(d, exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
        f.write("\n")
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def _capture_files(pdir):
    n = 0
    for _root, _dirs, files in os.walk(pdir):
        n += len(files)
    return n


@contextlib.contextmanager
def scope(test):
    """Capture the XLA profile around the body when
    ``test["profile?"]`` is set; a no-op context otherwise. Never
    raises — profiling is a byproduct, and the verdict must not
    depend on it."""
    global _capturing
    if not isinstance(test, dict) or not test.get("profile?"):
        yield None
        return
    pdir = profile_dir_for(test)
    marker = None
    state = {"status": "unavailable", "dir": pdir}
    if test.get("name"):
        # the marker belongs NEXT TO trace.jsonl whatever directory
        # the capture itself lands in (an explicit profile-dir may
        # point anywhere; web links the run dir's marker)
        try:
            from .. import store
            marker = store.path(test, MARKER_FILE)
        except Exception:  # noqa: BLE001 - store layout problems
            marker = None
    if marker is None and pdir is not None:
        marker = os.path.join(os.path.dirname(pdir) or ".",
                              MARKER_FILE)
    try:
        max_s = float(test.get("profile-max-s") or DEFAULT_MAX_S)
    except (TypeError, ValueError):
        max_s = DEFAULT_MAX_S
    started = False
    timer = None
    stop_lock = threading.Lock()

    def _stop(reason):
        """Stop the capture exactly once (body exit or the bound
        timer, whichever first)."""
        nonlocal started
        global _capturing
        with stop_lock:
            if not started:
                return
            started = False
        try:
            from jax import profiler as _p
            _p.stop_trace()
            state["status"] = "done"
        except Exception as exc:  # noqa: BLE001 - contained
            state["status"] = "failed"
            state["error"] = repr(exc)[:300]
            logger.warning("profiler stop failed", exc_info=True)
        with _capture_lock:
            _capturing = False
        state["stopped_by"] = reason

    try:
        if pdir is None:
            state["error"] = ("no profile directory: name the test or "
                              "pass profile-dir")
        elif not available():
            state["error"] = "jax.profiler unavailable"
        else:
            with _capture_lock:
                if _capturing:
                    state["status"] = "skipped"
                    state["error"] = ("another capture is already "
                                      "running (the profiler is "
                                      "process-global)")
                else:
                    _capturing = True
                    started = True
            if started:
                os.makedirs(pdir, exist_ok=True)
                if marker:
                    _write_marker(marker, {"status": "capturing",
                                           "dir": pdir,
                                           "max_s": max_s,
                                           "started":
                                               _time.strftime(
                                                   "%Y%m%dT%H%M%S")})
                from jax import profiler as _p
                try:
                    _p.start_trace(pdir)
                except Exception as exc:  # noqa: BLE001 - contained
                    with _capture_lock:
                        _capturing = False
                    started = False
                    state["status"] = "failed"
                    state["error"] = repr(exc)[:300]
                    logger.warning("profiler start failed",
                                   exc_info=True)
                if started:
                    state["status"] = "capturing"
                    timer = threading.Timer(
                        max_s, _stop, args=("max-s-bound",))
                    timer.daemon = True
                    timer.start()
    except Exception as exc:  # noqa: BLE001 - setup must not kill runs
        # a failure between claiming the capture slot and start_trace
        # (makedirs, the marker write) must release the claim AND
        # clear started, or the finally's _stop would call stop_trace
        # on a never-started trace and overwrite this (root-cause)
        # error with the bogus stop error. status == "capturing"
        # means start_trace already succeeded (a timer failure landed
        # here): keep started so the finally stops the live trace.
        if started and state.get("status") != "capturing":
            with _capture_lock:
                _capturing = False
            started = False
        state["status"] = "failed"
        state["error"] = repr(exc)[:300]
        logger.warning("profiler setup failed", exc_info=True)
    t0 = _time.monotonic()
    try:
        if started:
            state["anchor"] = ANCHOR
            with anchor(ANCHOR):
                yield pdir
        else:
            yield None
    finally:
        if timer is not None:
            timer.cancel()
        _stop("scope-exit")
        state["wall_s"] = round(_time.monotonic() - t0, 3)
        if state.get("status") == "done" and pdir is not None:
            try:
                state["files"] = _capture_files(pdir)
            except OSError:
                pass
        if marker:
            try:
                _write_marker(marker, state)
            except Exception:  # noqa: BLE001 - marker is best effort
                logger.warning("couldn't write the profile marker",
                               exc_info=True)
        if state.get("status") != "done":
            logger.warning("XLA profile capture: %s (%s)",
                           state.get("status"), state.get("error"))
