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
* either way the cache key includes the program's metadata (op names,
  source lines), so what a profiler capture names is what was traced.
"""

from __future__ import annotations

import os

from goworld_tpu.utils import metrics

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

# what compiling costs this process, from JAX's own duration events: a
# program's trace to a jaxpr, its lowering to an MLIR module and the
# backend's compile (or the read-back from the persistent cache, which
# JAX times under the same event). Published in /metrics as
# ``jax_compile_seconds`` (``_count`` = programs the backend compiled,
# ``_sum`` = seconds of all three stages).
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_BACKEND = "/jax/core/compile/backend_compile_duration"
_COMPILE_BUCKETS_S = (0.01, 0.03, 0.1, 0.3, 1.0, 3.0, 10.0, 30.0, 100.0)
_pending_s = 0.0      # trace + lowering seconds of the compile under way


def _on_event(event: str, **_kw) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        stats["hits"] += 1
    elif event == "/jax/compilation_cache/cache_misses":
        stats["misses"] += 1


def _on_duration(event: str, duration_secs: float, **_kw) -> None:
    """One ``jax_compile_seconds`` observation per backend compile,
    holding the tracing and lowering that led up to it (a trace that
    ends in no compile, e.g. of an inner jitted function, rides the
    next one: the sum stays whole)."""
    global _pending_s
    if event in (_TRACE, _LOWER):
        _pending_s += duration_secs
    elif event == _BACKEND:
        total, _pending_s = _pending_s + duration_secs, 0.0
        metrics.histogram(
            "jax_compile_seconds", buckets=_COMPILE_BUCKETS_S,
            help="seconds JAX spent tracing, lowering and compiling, "
                 "one observation per compiled program",
        ).observe(total)


def setup() -> str:
    """Point JAX at the cache directory (see module docstring) and
    return it. Call before the first compile; idempotent."""
    global _listening
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # An executable read back from the cache keeps the metadata it was
    # compiled with: by default the key leaves metadata out, so after a
    # change of names or line numbers a profiler capture shows the OLD
    # op names — the tick's gw. scopes (ops/scopes.py) were missing
    # from the first capture of the program that had them (PERF.md,
    # PR 25). With metadata in the key a release compiles once more and
    # its captures name what it runs.
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      True)
    if not _listening:
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(
            _on_duration)
        _listening = True
    return path
