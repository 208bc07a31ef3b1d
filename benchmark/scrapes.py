"""Helpers the per-layer readers share: window deltas of the series
``run.py`` scraped at both edges."""
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
    window's whole frames."""
    if not trace or not trace.get("busy_s") or not trace.get("frames"):
        return None
    return 1e3 * trace["busy_s"] / trace["frames"]
