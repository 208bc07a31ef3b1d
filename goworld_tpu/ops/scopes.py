"""Profiler names of the tick's phases (``jax.named_scope``).

A named scope is metadata: it changes no operation of the compiled
program (tests/test_trace_scopes.py holds the HLO to that), it only
names, in a profiler capture, what the compiler would otherwise call
``while.13``. The names are fixed so that a capture of one PR can be
read against a capture of the next, whatever the implementation under
a name has become:

==================  ==================================================
``gw.inputs``       client position inputs (scatter)
``gw.behave``       NPC behaviours
``gw.integrate``    integrate, world clamp, dirty bits
``gw.aoi``          the AOI sweep, with four sub-phases whichever
                    ``sweep_impl`` runs: ``gw.aoi.cells`` (cell rows,
                    cell sort), ``gw.aoi.index`` (sorted view, row
                    ranges or cell table), ``gw.aoi.gather`` (the
                    window fetch), ``gw.aoi.rank`` (distances, key
                    pack, top-k, unpack)
``gw.delta``        interest deltas -> enter/leave pairs
``gw.sync``         position sync records
``gw.attrs``        hot-attr deltas
``gw.telemetry``    the live-telemetry fold (its own program)
``gw.migrate``      migration pack, ``all_to_all``, insert (mesh)
``gw.halo``         halo ghost exchange (megaspace)
==================  ==================================================

``benchmark/phase_reduce.py`` reads them back from a capture;
docs/OBSERVABILITY.md shows what an operator sees.
"""

from __future__ import annotations

from functools import wraps

import jax


def scoped(name: str):
    """Run a function under ``jax.named_scope(name)``. A fresh scope
    per call: one ``named_scope`` object used as a decorator would be
    shared by every thread that traces, and it keeps the context it
    saved on itself."""
    def deco(fn):
        @wraps(fn)
        def run(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)
        return run
    return deco
