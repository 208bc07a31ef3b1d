"""Kernels (ops/delta.py, ops/sync.py): device time per frame under
``gw.delta``, ``gw.sync`` or ``gw.attrs`` (the union of their
operations' intervals), read by name from the capture
(phase_reduce.py)."""
from phase_reduce import scope_ms


def read(scrapes, trace, cell):
    return scope_ms(cell, "delta_sync")
