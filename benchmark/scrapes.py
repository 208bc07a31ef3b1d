"""Helpers the per-layer readers share: window deltas of the series
``run.py`` scraped at both edges (``open``, ``close``; in a traced run
``close`` holds the game's and the gate's series as they stood when the
capture began: run.py ``report``)."""
from __future__ import annotations


def delta(scrapes: dict, side: str, name: str, label: str = "",
          edges: tuple[str, str] = ("open", "close")) -> float | None:
    """Close minus open of every series of the game's or the gate's
    /metrics called ``name`` whose labels contain ``label`` (as
    printed, e.g. ``hop="gate_flush"``), summed. ``edges`` names the
    two scrapes: the window's, or (``span_open``, ``span_close``) the
    game's around the profiler capture of a traced run."""
    def total(edge):
        if not scrapes.get(edge):
            return None
        vals = [v for k, v in scrapes[edge][side].items()
                if k.partition("{")[0] == name and label in k]
        return sum(vals) if vals else None

    a, b = total(edges[0]), total(edges[1])
    if a is None or b is None:
        return None
    return b - a


def mean_ms(scrapes: dict, side: str, family: str, label: str = "",
            edges: tuple[str, str] = ("open", "close")) -> float | None:
    """Mean of a histogram between two scrapes: delta of its sum over
    delta of its count (record-weighted where the program weights it
    so)."""
    s = delta(scrapes, side, f"{family}_sum", label, edges)
    n = delta(scrapes, side, f"{family}_count", label, edges)
    if s is None or not n:
        return None
    return s / n


def frame_ms(scrapes: dict) -> float | None:
    return mean_ms(scrapes, "game", "tick_latency_ms")


def frame_ms_traced(scrapes: dict) -> float | None:
    """The frames the game served while the profiler captured."""
    return mean_ms(scrapes, "game", "tick_latency_ms",
                   edges=("span_open", "span_close"))


def busy_ms_per_frame(trace: dict | None, cell: dict) -> float | None:
    """Device busy milliseconds per served frame over the traced
    window's whole frames: on the BUSIEST device plane where the cell
    lies on several chips (the device every frame waits for)."""
    one = plane_of(trace)
    if not one or not one.get("busy_s") or not one.get("frames"):
        return None
    return 1e3 * one["busy_s"] / one["frames"]


def plane_of(trace: dict | None) -> dict | None:
    """``busy_s``, ``window_s`` and ``frames`` of the busiest device
    plane of a reduced capture (the only one, on one chip)."""
    if not trace:
        return None
    return trace.get("busiest") or trace
