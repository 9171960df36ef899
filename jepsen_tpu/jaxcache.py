"""The one place JAX's persistent compilation cache is placed.

Every entry point (``cli.run``, ``web.serve``, ``benchmark/run.py``,
``chip_smoke.py``, ``fleet.ledger.enable_jax_cache``) and
``tests/conftest.py`` calls :func:`enable` before its first compile.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is
  set in code.
* unset: the cache lives at one fixed path inside the checkout,
  ``<repo>/.jax_cache`` (listed in ``.gitignore``). The path is part of
  the cache's key, so it is never built from a temp name, a pid, the
  cwd or the time.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fixed in-checkout cache directory used when ``ENV`` is unset
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable():
    """Place the persistent compilation cache; returns its directory."""
    env = os.environ.get(ENV)
    if env:
        return env
    import jax
    if jax.config.jax_compilation_cache_dir != DEFAULT_DIR:
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
