#!/usr/bin/env python3
"""Reduce a profiler capture (``.xplane.pb``) BY NAME: device time per
``gw.`` scope of the tick, and the device's idle time per ``gw.`` host
span of the serve loop.

    python benchmark/phase_reduce.py <xplane.pb> [<out.json>]

``trace_reduce.py`` reads a capture through ``jax.profiler.ProfileData``,
which shows an operation's name, start and duration and nothing else.
The scope an operation was traced under (``jax.named_scope``,
goworld_tpu/ops/scopes.py) is in its ``op_name``
(``jit(step1)/gw.aoi/gw.aoi.gather/dynamic_slice``), which only the raw
``XSpace`` proto holds, in two places: the ``tf_op`` stat of the
event's METADATA, and the program's own HLO proto in the plane
``/host:metadata``. ``tf_op`` alone is not enough on a TPU v5e (PR 25,
my chip run): a ``while`` has none, and what the compiler makes itself
— the loop it expands a gather into, ``while.12`` with its
``bitcast_dynamic-update-slice_fusion.6`` and ``slice.353``, 88% of
the tile's tick — has no ``op_name`` at all. So an operation is named
from the HLO proto: by its own ``op_name`` where that holds a scope,
else by the instruction that calls its computation (a loop's body takes
the loop's scopes), and by ``tf_op`` only where the capture holds no
HLO proto. This file decodes both protos with the messages of
``xplane.proto`` (seven) and the five of ``hlo.proto`` it reads, built
as descriptors on ``google.protobuf`` (parsed in C; no tensorflow, no
jax), and gives, over the SAME whole-frame window ``trace_reduce.py``
cuts (first to last whole run of the tick's program):

* ``scopes``: per ``gw.`` scope, device milliseconds per frame = the
  union of the ``XLA Ops`` intervals whose ``tf_op`` path holds the
  scope (an operation under ``gw.aoi/gw.aoi.gather`` counts in both);
* ``unscoped_ms``: busy time under no ``gw.`` scope;
* ``clock_shift_ms``: the device's and the host's lines of one capture
  are not on one clock (a run of the tick's program STARTS on the
  device line about 1.4 ms before the host line says it was launched).
  The shift is the smallest one that puts every whole run of the tick's
  program after the host event that has to precede it: its own
  ``DoEnqueueProgram`` (matched by ``run_id``; in the served game it
  runs on a worker thread ~1.9 ms after the
  ``PJRT_LoadedExecutable_Execute`` that asked for it, so it bounds the
  shift more tightly than the launch does) or, where a capture has no
  such link, the nearest launch before the run;
* ``idle``: every idle gap of the device, moved by that shift, split
  over the innermost ``gw.`` host span of the logic thread that covers
  it (``gw.pacing_sleep``, ``gw.fetch_outputs``, ... or ``gw.frame``
  where only the tick record covers it); the rest is ``unlabelled``.

A cell on several chips leaves one device plane per chip. Everything
above is then read on the BUSIEST plane (most busy time per frame: the
device every frame waits for; benchmark/README.md), never as a mean,
and the planes are held against each other:

* ``planes``: per plane, busy and idle ms per frame;
* ``skew_ms``: the busiest plane's busy time minus the least busy's.

The per-layer readers (``layer_metrics/aoi_ms.py`` and the rest) call
:func:`phases`, which reduces the run's capture once and leaves
``.bench_work/<cell>/phases.json`` for the others. Where no decoder can
be had, or the capture holds no scope (a program without them), the
readers get ``None`` and stderr says why: never 0, never an exception.
"""
from __future__ import annotations

import json
import os
import re
import sys
import time

import numpy as np
from scrapes import delta
from trace_reduce import DEVICE_PLANE, FIRST_WHOLE, clip, short_name

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(os.path.dirname(HERE), ".bench_work")
HOST_PLANE = "/host:CPU"
SCOPE_PREFIX = "gw."
EXECUTE = "PJRT_LoadedExecutable_Execute"
ENQUEUE = "DoEnqueueProgram"
# without a run_id link: a run belongs to the last launch that began
# no later than this after the run's own (unshifted) start
NEAREST_LAUNCH_NS = 5e6
PACING = "gw.pacing_sleep"
UNLABELLED = "unlabelled"
# scopes read together: the UNION of their operations' intervals
GROUPS = {"delta_sync": ("gw.delta", "gw.sync", "gw.attrs")}


def note(msg: str) -> None:
    print(f"[phase_reduce] {msg}", file=sys.stderr, flush=True)


# ----------------------------------------------------------------------
# the decoder: xplane.proto's seven messages and five of hlo.proto's, as
# a descriptor in code
# ----------------------------------------------------------------------
_PROTOS = None


def proto_classes():
    """The (``XSpace``, ``HloProto``) message classes, or ``None`` where
    google.protobuf is missing or refuses the descriptor."""
    global _PROTOS
    if _PROTOS is not None:
        return _PROTOS or None
    try:
        from google.protobuf import (descriptor_pb2, descriptor_pool,
                                     message_factory)

        F = descriptor_pb2.FieldDescriptorProto
        pkg = "gw.xplane"
        fd = descriptor_pb2.FileDescriptorProto(
            name="gw_xplane.proto", package=pkg, syntax="proto3")
        scalar = {"int64": F.TYPE_INT64, "uint64": F.TYPE_UINT64,
                  "double": F.TYPE_DOUBLE, "string": F.TYPE_STRING,
                  "bytes": F.TYPE_BYTES}

        def message(name, fields, oneof=None, parent=None):
            m = (parent.nested_type if parent else fd.message_type).add(
                name=name)
            if oneof:
                m.oneof_decl.add(name=oneof[0])
            for fname, num, typ, repeated in fields:
                f = m.field.add(
                    name=fname, number=num,
                    label=F.LABEL_REPEATED if repeated
                    else F.LABEL_OPTIONAL)
                if typ in scalar:
                    f.type = scalar[typ]
                else:
                    f.type, f.type_name = F.TYPE_MESSAGE, f".{pkg}.{typ}"
                if oneof and fname in oneof[1]:
                    f.oneof_index = 0
            return m

        message("XSpace", [("planes", 1, "XPlane", True)])
        plane = message("XPlane", [
            ("id", 1, "int64", False), ("name", 2, "string", False),
            ("lines", 3, "XLine", True),
            ("event_metadata", 4, "XPlane.EventMetadataEntry", True),
            ("stat_metadata", 5, "XPlane.StatMetadataEntry", True),
            ("stats", 6, "XStat", True)])
        for entry, value in (("EventMetadataEntry", "XEventMetadata"),
                             ("StatMetadataEntry", "XStatMetadata")):
            e = message(entry, [("key", 1, "int64", False),
                                ("value", 2, value, False)],
                        parent=plane)
            e.options.map_entry = True
        message("XLine", [
            ("id", 1, "int64", False), ("name", 2, "string", False),
            ("timestamp_ns", 3, "int64", False),
            ("events", 4, "XEvent", True),
            ("duration_ps", 9, "int64", False),
            ("display_id", 10, "int64", False),
            ("display_name", 11, "string", False)])
        message("XEvent", [
            ("metadata_id", 1, "int64", False),
            ("offset_ps", 2, "int64", False),
            ("duration_ps", 3, "int64", False),
            ("stats", 4, "XStat", True),
            ("num_occurrences", 5, "int64", False)],
            oneof=("data", ("offset_ps", "num_occurrences")))
        message("XStat", [
            ("metadata_id", 1, "int64", False),
            ("double_value", 2, "double", False),
            ("uint64_value", 3, "uint64", False),
            ("int64_value", 4, "int64", False),
            ("str_value", 5, "string", False),
            ("bytes_value", 6, "bytes", False),
            ("ref_value", 7, "uint64", False)],
            oneof=("value", ("double_value", "uint64_value",
                             "int64_value", "str_value", "bytes_value",
                             "ref_value")))
        message("XEventMetadata", [
            ("id", 1, "int64", False), ("name", 2, "string", False),
            ("metadata", 3, "bytes", False),
            ("display_name", 4, "string", False),
            ("stats", 5, "XStat", True), ("child_id", 6, "int64", True)])
        message("XStatMetadata", [
            ("id", 1, "int64", False), ("name", 2, "string", False),
            ("description", 3, "string", False)])
        # hlo.proto / xla_data.proto: the fields that are read (a
        # parser skips the rest)
        message("HloProto", [("hlo_module", 1, "HloModuleProto", False)])
        message("HloModuleProto", [
            ("name", 1, "string", False),
            ("computations", 3, "HloComputationProto", True)])
        message("HloComputationProto", [
            ("name", 1, "string", False),
            ("instructions", 2, "HloInstructionProto", True),
            ("id", 5, "int64", False)])
        message("HloInstructionProto", [
            ("name", 1, "string", False), ("opcode", 2, "string", False),
            ("metadata", 7, "OpMetadata", False),
            ("id", 35, "int64", False),
            ("called_computation_ids", 38, "int64", True)])
        message("OpMetadata", [("op_type", 1, "string", False),
                               ("op_name", 2, "string", False)])
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fd)
        _PROTOS = tuple(message_factory.GetMessageClass(
            pool.FindMessageTypeByName(f"{pkg}.{name}"))
            for name in ("XSpace", "HloProto"))
    except Exception as e:          # no decoder: the readers say so
        note(f"no decoder for the raw capture ({e!r})")
        _PROTOS = False
    return _PROTOS or None


WANTED_STATS = ("tf_op", "run_id", "program_id")
METADATA_PLANE = "/host:metadata"
HLO_STAT = "Hlo Proto"


_WRAPPED = re.compile(r"^\w+\((.*)\)$")


def scope_of(part: str) -> str | None:
    """The ``gw.`` scope one part of a name-stack path stands for, or
    ``None``: the part itself, or what a transform wraps — under
    ``vmap`` (a tick over many spaces) jax prints ``gw.sync`` as
    ``vmap(gw.sync)``."""
    while True:
        if part.startswith(SCOPE_PREFIX):
            return part
        m = _WRAPPED.match(part)
        if m is None:
            return None
        part = m.group(1)


def has_scope(op_name: str) -> bool:
    return any(scope_of(part) for part in op_name.split("/"))


def hlo_names(hlo_bytes: bytes, hlo_cls) -> dict[str, str]:
    """{instruction name: the ``op_name`` it runs under} of one
    program: an instruction's own ``op_name`` where that holds a
    ``gw.`` scope, else that of the instruction which calls its
    computation (a loop's body, a conditional's branch), up to the
    entry computation; its own, scope-less or empty, where none
    does."""
    module = hlo_cls()
    module.ParseFromString(hlo_bytes)
    caller: dict = {}           # computation id -> the calling instruction
    where: dict = {}            # instruction name -> its computation id
    own: dict = {}
    for comp in module.hlo_module.computations:
        for ins in comp.instructions:
            own[ins.name] = ins.metadata.op_name
            where[ins.name] = comp.id
            for cid in ins.called_computation_ids:
                caller[cid] = ins.name
    out: dict = {}

    def under(name: str) -> str:
        if name not in out:
            out[name] = own[name]           # (also ends a cycle)
            up = caller.get(where[name])
            if not has_scope(own[name]) and up is not None \
                    and has_scope(under(up)):
                out[name] = under(up)
        return out[name]

    for name in own:
        under(name)
    return out


def read_raw(path: str) -> list[dict] | None:
    """``[{"name", "lines": [{"name", "events": [(name, start_ns,
    dur_ns, stats)]}]}]`` of the device planes and the host plane, with
    the stats the reduction reads: ``tf_op`` of an operation (the name
    path it runs under, from the program's HLO proto where the capture
    holds it, see the module's docstring), ``run_id`` of a program run
    and of its enqueue. Of the host's lines only launches, enqueues and
    ``gw.`` annotations are kept. ``None`` without a decoder."""
    classes = proto_classes()
    if classes is None:
        return None
    space = classes[0]()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    # {program id: {instruction name: name path}}
    programs: dict = {}
    for p in space.planes:
        if p.name != METADATA_PLANE:
            continue
        hlo_stat = {k for k, v in p.stat_metadata.items()
                    if v.name == HLO_STAT}
        for pid, md in p.event_metadata.items():
            for st in md.stats:
                if st.metadata_id in hlo_stat and st.bytes_value:
                    try:
                        programs[int(pid)] = hlo_names(st.bytes_value,
                                                       classes[1])
                    except Exception as e:
                        note(f"HLO proto of program {pid} not read "
                             f"({e!r}): its operations keep tf_op")
    planes = []
    for p in space.planes:
        device = bool(DEVICE_PLANE.match(p.name))
        if not device and p.name != HOST_PLANE:
            continue
        stat_name = {k: v.name for k, v in p.stat_metadata.items()}
        wanted = {k for k, n in stat_name.items() if n in WANTED_STATS}

        def stats_of(stats):
            out = {}
            for s in stats:
                if s.metadata_id in wanted:
                    which = s.WhichOneof("value")
                    v = getattr(s, which)
                    if which == "ref_value":
                        v = stat_name.get(v, "")
                    out[stat_name[s.metadata_id]] = v
            return out

        meta = {}
        for k, md in p.event_metadata.items():
            if not (device or md.name in (EXECUTE, ENQUEUE)
                    or md.name.startswith(SCOPE_PREFIX)):
                continue
            st = stats_of(md.stats)
            try:
                names = programs.get(int(st.pop("program_id", -1)))
            except ValueError:
                names = None
            if names is not None:
                named = names.get(short_name(md.name))
                if named is not None:
                    st["tf_op"] = named
            meta[k] = (md.name, st)
        lines = []
        for ln in p.lines:
            # an operation's own stats are its device offsets: only a
            # program run and an enqueue carry one that is read
            own = ln.name != "XLA Ops"
            t0 = float(ln.timestamp_ns)
            events = []
            for ev in ln.events:
                m = meta.get(ev.metadata_id)
                if m is None:
                    continue
                stats = m[1]
                if own and ev.stats:
                    more = stats_of(ev.stats)
                    if more:
                        stats = dict(stats, **more)
                events.append((m[0], t0 + ev.offset_ps * 1e-3,
                               ev.duration_ps * 1e-3, stats))
            if events:
                lines.append({"name": ln.name, "events": events})
        planes.append({"name": p.name, "lines": lines})
    return planes


# ----------------------------------------------------------------------
# the reduction (pure: a test feeds it planes built by hand)
# ----------------------------------------------------------------------
def scopes_of(tf_op: str) -> tuple[str, ...]:
    """The ``gw.`` parts of an operation's name-stack path."""
    return tuple(dict.fromkeys(
        sc for part in str(tf_op).split("/")
        if (sc := scope_of(part))))


def merged(starts, ends) -> list[tuple[float, float]]:
    """``trace_reduce.union`` for arrays: the merged [start, end)
    intervals, in order. A tile's capture holds millions of operations,
    most of them nested in a loop's own event; numpy sorts and folds
    them in a fraction of the time."""
    if len(starts) == 0:
        return []
    order = np.argsort(starts, kind="stable")
    s, e = np.asarray(starts)[order], np.asarray(ends)[order]
    reach = np.maximum.accumulate(e)
    first = np.flatnonzero(np.r_[True, s[1:] > reach[:-1]])
    last = np.r_[first[1:] - 1, len(s) - 1]
    return list(zip(s[first].tolist(), reach[last].tolist()))


def frame_window(mods: list) -> tuple[float, float, int, list] | None:
    """The whole-frame window as ``trace_reduce.reduce_planes`` cuts it:
    from the first to the last start of a WHOLE run (at least half the
    median run; the first one ``FIRST_WHOLE`` of it) of the tick's program — the module with the most device
    time. Returns (lo, hi, frames, the whole runs) or ``None`` with
    fewer than two such starts."""
    total: dict = {}
    for name, _s, d, _st in mods:
        total[name] = total.get(name, 0.0) + d
    if not total:
        return None
    tick = max(total, key=total.get)
    durs = sorted(d for name, _s, d, _st in mods if name == tick)
    whole = 0.5 * durs[len(durs) // 2]
    runs = sorted((ev for ev in mods if ev[0] == tick and ev[2] >= whole),
                  key=lambda ev: ev[1])
    if runs and runs[0][2] < FIRST_WHOLE * durs[len(durs) // 2]:
        del runs[0]             # cut at its beginning: see trace_reduce
    if len(runs) < 2:
        return None
    return runs[0][1], runs[-1][1], len(runs) - 1, runs


def clock_shift(runs: list, host_lines: list) -> tuple[float, str] | None:
    """Nanoseconds to add to the device's clock so that every given run
    of the tick's program starts after the host event that has to
    precede it, and which event that was: ``enqueue`` (the run's
    ``DoEnqueueProgram``, by ``run_id``) or ``nearest_launch`` (no id
    to follow: the last ``PJRT_LoadedExecutable_Execute`` that began no
    later than 5 ms after the run's unshifted start)."""
    enqueued = {st["run_id"]: s for ln in host_lines
                for name, s, _d, st in ln["events"]
                if name == ENQUEUE and "run_id" in st}
    linked = [enqueued[st["run_id"]] - s for _n, s, _d, st in runs
              if st.get("run_id") in enqueued]
    if linked:
        return max(linked), "enqueue"
    launches = sorted(s for ln in host_lines
                      for name, s, _d, _st in ln["events"]
                      if name == EXECUTE)
    near = []
    for _n, s, _d, _st in runs:
        before = [a for a in launches if a <= s + NEAREST_LAUNCH_NS]
        if before:
            near.append(before[-1] - s)
    if near:
        return max(near), "nearest_launch"
    return None


def line_summary(planes: list[dict]) -> list[str]:
    """One line of text per line of the capture that was read: where
    the launches, the enqueues and the ``gw.`` spans live."""
    out = []
    for p in planes:
        for ln in p["lines"]:
            n: dict = {}
            for name, _s, _d, st in ln["events"]:
                key = name if name in (EXECUTE, ENQUEUE) else \
                    "gw.*" if name.startswith(SCOPE_PREFIX) else "other"
                if key == ENQUEUE and "run_id" in st:
                    key += "+run_id"
                n[key] = n.get(key, 0) + 1
            out.append(f"{p['name']} | {ln['name']}: {n}")
    return out


def host_segments(events: list) -> list[tuple[float, float, str]]:
    """Flatten one thread's nested ``gw.`` spans into non-overlapping
    (start, end, innermost span's name) segments, in time order."""
    spans = sorted(((s, s + d, name) for name, s, d, _st in events
                    if name.startswith(SCOPE_PREFIX)),
                   key=lambda x: (x[0], -x[1]))
    out: list[tuple[float, float, str]] = []
    stack: list[tuple[float, float, str]] = []
    at = None                       # start of the piece being written

    def close_until(t: float) -> None:
        nonlocal at
        while stack and stack[-1][1] <= t:
            _s, e, name = stack.pop()
            if e > at:
                out.append((at, e, name))
                at = e
        if not stack:
            at = None

    for s, e, name in spans:
        close_until(s)
        if stack and s > at:
            out.append((at, s, stack[-1][2]))
        stack.append((s, e, name))
        at = s
    close_until(float("inf"))
    return out


def split_gaps(gaps: list[tuple[float, float]],
               segments: list[tuple[float, float, str]]) -> dict:
    """Nanoseconds of the (sorted) gaps under each segment label; what
    no segment covers is ``unlabelled``."""
    out: dict = {}
    i = 0
    for lo, hi in gaps:
        covered = 0.0
        while i < len(segments) and segments[i][1] <= lo:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < hi:
            s, e, name = segments[j]
            part = min(e, hi) - max(s, lo)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            j += 1
        if hi - lo - covered > 0:
            out[UNLABELLED] = out.get(UNLABELLED, 0.0) + hi - lo - covered
    return out


def reduce_phases(planes: list[dict]) -> dict | None:
    """See the module's docstring. ``None`` where the capture has no
    device plane with whole frames of a program."""
    host = next((p for p in planes if p["name"] == HOST_PLANE), None)
    host_lines = host["lines"] if host else []
    logic = next((ln for ln in host_lines if any(
        ev[0] == "gw.frame" for ev in ln["events"])), None)
    segments = host_segments(logic["events"]) if logic else []
    per_dev = []
    for p in planes:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        line = {ln["name"]: ln["events"] for ln in p["lines"]}
        ops, mods = line.get("XLA Ops"), line.get("XLA Modules")
        if not ops or not mods:
            continue
        win = frame_window(mods)
        if win is None:
            continue
        lo, hi, frames, runs = win
        starts = np.fromiter((ev[1] for ev in ops), float, len(ops))
        ends = starts + np.fromiter((ev[2] for ev in ops), float,
                                    len(ops))
        # one row of scopes per distinct operation (name, name path)
        paths: dict = {}
        which = np.fromiter(
            (paths.setdefault((ev[0], ev[3].get("tf_op", "")), len(paths))
             for ev in ops), np.int64, len(ops))
        path_scopes = [scopes_of(tf) for _name, tf in paths]

        def under(names) -> float:
            """ns of the window under any of the scopes ``names``
            (None: under any scope at all)."""
            hit = np.array([bool(sc) if names is None
                            else any(n in sc for n in names)
                            for sc in path_scopes], bool)[which]
            return clip(merged(starts[hit], ends[hit]), lo, hi)

        every = merged(starts, ends)
        busy = clip(every, lo, hi)
        out = {
            "plane": p["name"],
            "frames": frames, "window_ns": hi - lo, "busy_ns": busy,
            "scopes": {n: under((n,)) for n in sorted(
                {n for sc in path_scopes for n in sc})},
            "groups": {g: under(names) for g, names in GROUPS.items()},
            "unscoped_ns": busy - under(None),
            "shift": clock_shift(runs, host_lines),
        }
        # the nameless operations that took most time (a loop's own
        # event and its children both count here: it is a list of names)
        inside = (starts >= lo) & (ends <= hi)
        spent = np.bincount(which[inside], (ends - starts)[inside],
                            len(paths))
        out["unscoped_top"] = sorted(
            ((short_name(name), tf, spent[i] / frames / 1e6)
             for i, (name, tf) in enumerate(paths)
             if not path_scopes[i] and spent[i] > 0),
            key=lambda row: -row[2])[:8]
        edges = [lo] + [min(max(x, lo), hi)
                        for se in every for x in se] + [hi]
        shift = out["shift"][0] if out["shift"] else 0.0
        gaps = [(edges[i] + shift, edges[i + 1] + shift)
                for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        out["idle"] = split_gaps(gaps, segments)
        per_dev.append(out)
    if not per_dev:
        return None

    def ms(d: dict, ns: float) -> float:
        """Nanoseconds of plane ``d`` -> ms per frame."""
        return ns / d["frames"] / 1e6

    # the plane every frame waits for: most busy time per frame
    top = max(per_dev, key=lambda d: d["busy_ns"] / d["frames"])
    planes = [{"plane": d["plane"], "busy_ms": ms(d, d["busy_ns"]),
               "idle_ms": ms(d, d["window_ns"] - d["busy_ns"])}
              for d in per_dev]
    shifts = [d["shift"] for d in per_dev if d["shift"]]
    return {
        "device_planes": len(per_dev),
        "busiest": top["plane"],
        "planes": planes,
        "skew_ms": max(q["busy_ms"] for q in planes)
        - min(q["busy_ms"] for q in planes),
        "frames": top["frames"],
        "window_s": top["window_ns"] / 1e9,
        "busy_ms": ms(top, top["busy_ns"]),
        "scopes": {s: ms(top, v) for s, v in top["scopes"].items()},
        "groups": {g: ms(top, v) for g, v in top["groups"].items()},
        "unscoped_ms": ms(top, top["unscoped_ns"]),
        "unscoped_top": top["unscoped_top"],
        "clock_shift_ms": max(s for s, _m in shifts) / 1e6
        if shifts else None,
        "clock_shift_by": shifts[0][1] if shifts else None,
        "host_line": logic["name"] if logic else None,
        "idle_ms": ms(top, top["window_ns"] - top["busy_ns"]),
        "idle": {s: ms(top, v) for s, v in top["idle"].items()},
    }


# ----------------------------------------------------------------------
# what the readers call
# ----------------------------------------------------------------------
def find_xplane(logdir: str) -> str | None:
    """The newest ``.xplane.pb`` under ``logdir`` (as ``run.py``)."""
    best = None
    for base, _dirs, files in os.walk(logdir):
        for name in files:
            if name.endswith(".xplane.pb"):
                p = os.path.join(base, name)
                if best is None or os.path.getmtime(p) > \
                        os.path.getmtime(best):
                    best = p
    return best


def reduce_file(path: str) -> dict | None:
    t0 = time.monotonic()
    planes = read_raw(path)
    if planes is None:
        return None
    events = sum(len(ln["events"]) for p in planes for ln in p["lines"])
    for text in line_summary(planes):
        note(text)
    res = reduce_phases(planes)
    if res is None:
        note(f"{path}: no device plane with whole frames of a program")
        return None
    res["events_read"] = events
    res["reduce_s"] = time.monotonic() - t0
    return res


_CACHE: dict = {}


def phases(cell: dict) -> dict | None:
    """The reduction of this run's capture, made once (the first reader
    pays for it, ``phases.json`` serves the rest) — or ``None`` where
    the run left no capture, no decoder can be had, or the program under
    test has no ``gw.`` scope to read."""
    name = cell["cell"]["name"]
    if name in _CACHE:
        return _CACHE[name]
    sd = os.path.join(WORK, name)
    out = os.path.join(sd, "phases.json")
    res = None
    try:
        if os.path.isfile(out):
            with open(out) as f:
                res = json.load(f)
        else:
            xp = find_xplane(os.path.join(sd, "profile"))
            if xp is None:
                note(f"no capture under {sd}/profile")
            else:
                res = reduce_file(xp)
            if res is not None:
                with open(out, "w") as f:
                    json.dump(res, f)
                note(f"{os.path.getsize(xp)} bytes, "
                     f"{res['events_read']} events read in "
                     f"{res['reduce_s']:.1f} s; clock shift "
                     f"{res['clock_shift_ms']} ms (by "
                     f"{res['clock_shift_by']}); host spans on line "
                     f"{res['host_line']!r}; scopes "
                     f"{sorted(res['scopes'])}")
    except Exception as e:      # a reader never raises: it reports nothing
        note(f"reduction failed: {e!r}")
        res = None
    _CACHE[name] = res
    return res


def scope_ms(cell: dict, scope: str) -> float | None:
    """Device ms per frame under a ``gw.`` scope, or under a group of
    :data:`GROUPS`; ``None`` where the capture holds no operation of
    it."""
    res = phases(cell)
    if not res:
        return None
    if scope in GROUPS:
        if not any(n in res["scopes"] for n in GROUPS[scope]):
            return None
        return res["groups"][scope]
    return res["scopes"].get(scope)


def skew_ms(cell: dict) -> float | None:
    """``skew_ms`` of a capture with several device planes; ``None`` on
    one chip (nothing to hold against each other)."""
    res = phases(cell)
    if not res or res.get("device_planes", 1) < 2:
        return None
    return res.get("skew_ms")


def idle_ms(cell: dict, want) -> float | None:
    """Device idle ms per frame under the host labels ``want(label)``
    accepts; ``None`` where the capture holds no ``gw.`` host span at
    all (a program without them: everything would read unlabelled)."""
    res = phases(cell)
    if not res or not res.get("host_line"):
        return None
    return sum(v for k, v in res["idle"].items() if want(k))


def phase_ms(scrapes: dict, cell: dict, *names: str) -> float | None:
    """Host ms per frame under the timeline spans ``names``: window
    delta of ``tick_phase_ms_sum{phase}`` over the frames served
    (``tick_latency_ms_count``). ``None`` where the game exports no
    such series. The first call also leaves every phase of the window
    in ``.bench_work/<cell>/host_phases.json``, beside ``frame_ms``."""
    frames = delta(scrapes, "game", "tick_latency_ms_count")
    if not frames:
        return None
    _write_host_phases(scrapes, cell, frames)
    sums = [delta(scrapes, "game", "tick_phase_ms_sum", f'phase="{n}"')
            for n in names]
    if any(v is None for v in sums):
        return None
    return sum(sums) / frames


def _write_host_phases(scrapes: dict, cell: dict, frames: float) -> None:
    path = os.path.join(WORK, cell["cell"]["name"], "host_phases.json")
    if os.path.isfile(path) or not os.path.isdir(os.path.dirname(path)):
        return
    out = {}
    for series in scrapes["close"]["game"]:
        if series.startswith("tick_phase_ms_sum{"):
            label = series.partition("{")[2].rstrip("}")
            v = delta(scrapes, "game", "tick_phase_ms_sum", label)
            if v is not None:
                out[label.partition('"')[2].rstrip('"')] = v / frames
    try:
        with open(path, "w") as f:
            json.dump({"frames": frames, "phase_ms": out, "frame_ms":
                       delta(scrapes, "game", "tick_latency_ms_sum")
                       / frames}, f)
    except OSError as e:
        note(f"host_phases.json not written: {e!r}")


def main(argv: list[str]) -> int:
    if len(argv) < 2:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    res = reduce_file(argv[1])
    text = json.dumps(res, indent=1)
    if len(argv) > 2:
        with open(argv[2], "w") as f:
            f.write(text)
    print(text)
    return 0 if res is not None else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv))
