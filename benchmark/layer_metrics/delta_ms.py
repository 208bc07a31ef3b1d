"""Kernels (ops/delta.py): device time per frame under ``gw.delta``
alone — the interest delta, whose every ``lax.cond`` is a select that
runs both branches where the tick is vmapped over many spaces — read by
name from the capture (phase_reduce.py)."""
from phase_reduce import scope_ms


def read(scrapes, trace, cell):
    return scope_ms(cell, "gw.delta")
