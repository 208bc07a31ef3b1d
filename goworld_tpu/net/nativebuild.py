"""Lazy build of the native C++ cores (``goworld_tpu/native/*.cpp``).

The ``.so`` files are build outputs (``.gitignore``): a clean checkout
has none, and the first process that needs a core compiles it — or
``python -m goworld_tpu build`` does, ahead of time. One helper for the
three loaders (sync codec, snappy, kcp), so they agree on two things:

* an ``.so`` OLDER than its source is rebuilt, not trusted: a tree
  copied from elsewhere must run what its sources say;
* the build lands through a temp file and ``os.replace``: dispatcher,
  game and gate start together on a fresh checkout, and a concurrent or
  interrupted build must never leave a half-written ``.so`` that pins
  every later process to the fallback.
"""

from __future__ import annotations

import os
import subprocess

NATIVE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "native"))


def ensure_built(so_name: str, src_name: str, logger) -> str | None:
    """Path of an up-to-date ``so_name`` built from ``src_name``, or
    None when it cannot be had (no source, no compiler, build error —
    logged; the caller falls back to its pure-Python core)."""
    so = os.path.join(NATIVE_DIR, so_name)
    src = os.path.join(NATIVE_DIR, src_name)
    have_src = os.path.exists(src)
    if os.path.exists(so) and not (
            have_src and os.path.getmtime(src) > os.path.getmtime(so)):
        return so
    if not have_src:
        return None
    tmp = f"{so}.{os.getpid()}.tmp"
    cxx = os.environ.get("CXX", "g++")  # match the Makefile
    try:
        subprocess.run(
            [cxx, "-O3", "-Wall", "-Wextra", "-std=c++17", "-fPIC",
             "-shared", "-o", tmp, src],
            check=True, capture_output=True, timeout=120,
        )
        os.replace(tmp, so)
        return so
    except (subprocess.SubprocessError, FileNotFoundError, OSError) as e:
        logger.warning("native build of %s failed (%s); using the "
                       "pure-python core", so_name, e)
        try:
            os.unlink(tmp)
        except OSError:
            pass
        return None
