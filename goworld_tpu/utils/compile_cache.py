"""One place that decides where JAX's persistent compile cache lives.

A 131,072-entity tick takes most of a minute to compile for a TPU; every
process that owns the chip (``gw.run()``, the bench child, the children
of ``chip_smoke.py``) calls :func:`setup` before its first compile so a
``start``, a ``reload`` and a supervised restart pay that once.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  in code.
* unset: ``<checkout>/.jax_compile_cache``, derived from this package's
  location. Games run with ``cwd = server_dir`` (cli.py ``_spawn``), so
  never from the cwd — and never from a temp name, a pid or the time:
  the path is part of the cache key, a directory that moves never hits.
"""

from __future__ import annotations

import os

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))),
    ".jax_compile_cache",
)

# persistent-cache traffic of this process, counted from JAX's own
# monitoring events: "hits" are executables read back; "misses" are
# compiles the cache could not serve AND kept — jax 0.9 records its
# cache_misses event where it writes the entry, so a compile under the
# cache's time/size thresholds (1 s by default) shows in neither. A
# reload's restore process reads back everything the frozen process
# read or wrote when the tick came from the cache.
stats = {"hits": 0, "misses": 0}
_listening = False


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        stats["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        stats["misses"] += 1


def setup() -> str:
    """Point JAX at the cache directory (see module docstring) and
    return it. Call before the first compile; idempotent."""
    global _listening
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        _listening = True
    return path
