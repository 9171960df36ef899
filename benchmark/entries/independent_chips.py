"""Entry point: ``jepsen.independent`` over a linearizable checker whose
keys shard over several chips.

``independent.checker(checkers.linearizable({"model": <model>,
"algorithm": "jax-wgl", "engine_opts": {"chips": n}}))``: the one line
a Jepsen test author writes to check a keyed history on a whole host.
The history is handed over as ``entries/independent.py`` hands it; the
key batch (``parallel/keyshard.check_batch_encoded``) runs over a 1-D
``keys`` mesh of ``n`` chips.
"""

from __future__ import annotations

from benchmark.entries.independent import handoff, prepare  # noqa: F401
from benchmark.entries.independent import read as read_keys


def make_check(config, traffic):
    """The checker, built once in set-up with the configuration's
    ``engine_opts``; returns ``check(hist)``. A program that cannot
    place keys on a chip mesh has no ``keys_mesh`` and stops here, in
    set-up, rather than checking thousands of keys one by one."""
    from jepsen_tpu import independent
    from jepsen_tpu.checker import checkers
    from jepsen_tpu.parallel import keys_mesh  # noqa: F401
    checker = independent.checker(checkers.linearizable(
        {"model": config["model"], "algorithm": "jax-wgl",
         "engine_opts": dict(config["engine_opts"])}))
    test = dict(traffic.get("test", {}))
    return lambda hist: checker.check(dict(test), hist, {})


def read(result, n_keys):
    """``entries/independent.read``, and the configurations each chip
    explored (``shard_explored``): one list for the check's one key
    batch, the same on every key that reached it; None when no key
    did."""
    out = read_keys(result, n_keys)
    out["shard_explored"] = next(
        (list(r["shard_explored"])
         for r in result.get("results", {}).values()
         if isinstance(r, dict) and r.get("shard_explored")), None)
    return out
