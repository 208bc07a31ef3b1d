#!/usr/bin/env python3
"""Reduce a profiler capture (``.xplane.pb``) to what the per-layer
metrics read: device busy and idle seconds over the captured span, the
operations that took most device time, and the longest idle gaps.

    JAX_PLATFORMS=cpu python benchmark/trace_reduce.py <xplane.pb> <out.json> --frame-s <s>

* device planes are the planes named ``/device:TPU:<n>``; on
  each, the line ``XLA Ops`` holds one event per executed operation
  (``XLA Modules`` stands in where a trace has no such line). Busy is
  the UNION of those events' intervals — nested or overlapping events
  count once. A cell on several chips leaves one plane per chip, and
  one rule holds for every per-layer metric (benchmark/README.md): it
  is read on the BUSIEST plane — the one with the most busy time per
  frame, the device every frame waits for — never as a mean
  (``busiest``, ``per_plane``; the breakdown's operations and gaps are
  that plane's). Only ``busy_s`` and ``window_s``, which the contract's
  ``device`` block carries, are means over the planes;
* the traced window (``window_s``) is cut to whole frames: from the
  first to the last start of the tick's program on the device (see
  ``reduce_planes``);
* an idle gap is labelled by the only thing the benchmark can tell
  today: a gap longer than a quarter of the frame is the game's pacing
  sleep (the frame's remainder), every other gap is "between ops" (host
  flush, fetch, decode, pump — no host span is on the profiler's clock
  yet, ROADMAP S2).

A trace without a device plane gives ``busy_s`` null: the readers then
report nothing, never 0.
"""
from __future__ import annotations

import argparse
import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINES = ("XLA Ops", "XLA Modules")
# operations that move data between chips, by the HLO instruction's name
COLLECTIVE = re.compile(
    r"^(all-to-all|all-reduce|all-gather|reduce-scatter|collective-permute"
    r"|collective-broadcast|ragged-all-to-all|send|recv)\b")
ASYNC_LINE = "Async XLA Ops"
IN_FLIGHT = " (async, in flight)"


# the first run of the tick's program marks a frame's start only if it
# is this much of the median run: one cut at its beginning to two
# thirds of itself was read as whole under the rule of one half (busy
# 340.8 ms a frame for 384.1, my chip run, PR 29). A whole first run
# that is dropped for being a little short costs a frame, and bends none
FIRST_WHOLE = 0.95


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def short_name(event: str) -> str:
    """An XLA Ops event is named by its whole HLO line (``%fusion.7 =
    f32[...] fusion(...)``): keep the instruction's name."""
    return event.split(" = ", 1)[0].lstrip("%")[:80]


def clip(merged, lo: float, hi: float) -> float:
    """Nanoseconds of the merged intervals that fall inside [lo, hi)."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def reduce_planes(planes: list[dict], frame_s: float) -> dict:
    """``planes``: [{"name", "lines": [{"name", "events": [(name,
    start_ns, dur_ns)]}]}]. Pure, so a test can feed it by hand.

    The traced window is cut to WHOLE FRAMES: it runs from the first to
    the last start of the tick's program — the module of the ``XLA
    Modules`` line with the most device time — so a capture that starts
    or ends inside a tick, or idles while the profiler starts and
    stops, does not bend busy per frame. A run of that program cut short
    by the capture's edge (under half the median run; the first one:
    under ``FIRST_WHOLE`` of it) marks no start. A
    capture with fewer than two such starts is taken from its first to
    its last event."""
    out: dict = {"planes": [p["name"] for p in planes], "lines": {},
                 "busy_s": None, "window_s": None, "frames": None,
                 "breakdown": None}
    devs = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    busy, spans, frames, names = [], [], [], []
    per_ops, per_gaps, per_longest = [], [], []
    for p in devs:
        ops, gaps, longest = {}, {}, 0.0
        out["lines"][p["name"]] = {ln["name"]: len(ln["events"])
                                   for ln in p["lines"]}
        line = next((ln for want in OPS_LINES for ln in p["lines"]
                     if ln["name"] == want and ln["events"]), None)
        if line is None:
            continue
        lo = min(s for _n, s, _d in line["events"])
        hi = max(s + d for _n, s, d in line["events"])
        n_frames = None
        mods = next((ln for ln in p["lines"]
                     if ln["name"] == "XLA Modules" and ln["events"]), None)
        if mods is not None:
            total: dict = {}
            for name, _s, d in mods["events"]:
                total[name] = total.get(name, 0.0) + d
            tick = max(total, key=total.get)
            out.setdefault("modules", {})[p["name"]] = {
                name: {"n": sum(1 for m in mods["events"] if m[0] == name),
                       "total_s": t / 1e9,
                       "starts_s": [round((m[1] - lo) / 1e9, 4)
                                    for m in mods["events"]
                                    if m[0] == name][:8]}
                for name, t in total.items()}
            # a run cut by the capture's edge is shorter than the rest:
            # only whole runs mark a frame's start
            durs = sorted(d for name, _s, d in mods["events"]
                          if name == tick)
            whole = 0.5 * durs[len(durs) // 2]
            runs = sorted((s, d) for name, s, d in mods["events"]
                          if name == tick and d >= whole)
            # ... and the FIRST run has to be all there (FIRST_WHOLE of
            # the median): cut at its beginning it starts where the
            # capture does, not where the frame did, and the frame it
            # would mark lacks the operations that ran before
            if runs and runs[0][1] < FIRST_WHOLE * durs[len(durs) // 2]:
                del runs[0]
            starts = [s for s, _d in runs]
            if len(starts) >= 2:
                lo, hi, n_frames = starts[0], starts[-1], len(starts) - 1
        merged = union([(s, s + d) for _n, s, d in line["events"]])
        busy.append(clip(merged, lo, hi) / 1e9)
        spans.append((hi - lo) / 1e9)
        frames.append(n_frames)
        names.append(p["name"])
        per_ops.append(ops)
        per_gaps.append(gaps)
        for name, s, d in line["events"]:
            if s >= lo and s + d <= hi:
                name = short_name(name)
                ops[name] = ops.get(name, 0.0) + d / 1e9
        # an asynchronous collective costs the ops line microseconds
        # (its start and its done); how long it was in flight, beside
        # the operations it overlaps, is on a line of its own where the
        # plane has one (a v5e's first chip, PR 28)
        for ln in p["lines"]:
            if ln["name"] == ASYNC_LINE:
                for name, s, d in ln["events"]:
                    name = short_name(name)
                    if s >= lo and s + d <= hi and COLLECTIVE.match(name):
                        name += IN_FLIGHT
                        ops[name] = ops.get(name, 0.0) + d / 1e9
        edges = [lo] + [min(max(x, lo), hi)
                        for se in merged for x in se] + [hi]
        for i in range(0, len(edges), 2):
            g = (edges[i + 1] - edges[i]) / 1e9
            if g <= 0:
                continue
            label = "pacing sleep (frame remainder)" \
                if g > 0.25 * frame_s else \
                "between ops (host: flush, fetch, decode, pump)"
            gaps[label] = gaps.get(label, 0.0) + g
            longest = max(longest, g)
        per_longest.append(longest)
    if not busy:
        return out
    out["busy_s"] = sum(busy) / len(busy)
    out["window_s"] = sum(spans) / len(spans)
    if all(f is not None for f in frames):
        out["frames"] = sum(frames) / len(frames)
    out["device_planes"] = len(busy)
    out["per_plane"] = [
        {"plane": n, "busy_s": b, "window_s": w, "frames": f}
        for n, b, w, f in zip(names, busy, spans, frames)]
    # the plane every frame waits for: most busy time per frame
    at = max(range(len(busy)), key=lambda i: busy[i] / (frames[i] or 1))
    out["busiest"] = out["per_plane"][at]
    out["longest_gap_s"] = per_longest[at]
    ops, gaps = per_ops[at], per_gaps[at]
    out["breakdown"] = {
        "device_ops": [[n, s] for n, s in
                       sorted(ops.items(), key=lambda kv: -kv[1])[:10]],
        "idle_gaps": [[n, s] for n, s in
                      sorted(gaps.items(), key=lambda kv: -kv[1])[:10]]}
    return out


def read_xplane(path: str) -> list[dict]:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for p in data.planes:
        lines = []
        for ln in p.lines:
            lines.append({"name": ln.name, "events": [
                (ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in ln.events]})
        planes.append({"name": p.name, "lines": lines})
    return planes


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("xplane")
    ap.add_argument("out")
    ap.add_argument("--frame-s", type=float, default=1.0)
    a = ap.parse_args()
    res = reduce_planes(read_xplane(a.xplane), a.frame_s)
    with open(a.out, "w") as f:
        json.dump(res, f)
    return 0


if __name__ == "__main__":
    sys.exit(main())
