"""In-graph telemetry lanes: on-device per-tick histograms for scan loops.

The bench/soak ``lax.scan`` loops used to surface last-tick point
samples (or sums/maxes) of the tick's health signals; a p99 claim needs
the DISTRIBUTION. These lanes thread a fixed-bucket histogram
accumulator through the scan carry — one ``at[i].add(1)`` per signal
per tick, ZERO host syncs inside the loop — and drain it once per scan
into the artifact's ``op_stats`` block.

Lanes (per-tick signals, from :class:`TickOutputs`):

* ``tick_ms`` — the modeled per-tick latency (see below), bucketed on
  the live metrics ladder (:data:`metrics.DEFAULT_MS_BUCKETS`) so the
  SLO verdict reads identically on- and off-device.
* ``sync_n`` / ``enter_n`` / ``leave_n`` — event volumes.
* ``over_k_rows`` / ``over_cap_cells`` — AOI saturation gauges.
* ``rebuilt`` — the Verlet rebuild bit (the skin's duty cycle).
* ``skin_slack`` — headroom before the next displacement rebuild, as a
  fraction of skin/2 (lane present only when the skin is on).

**The tick_ms model.** Wall time is not readable inside a compiled
scan, and inside one fixed-shape program the only data-dependent cost
branch is the Verlet rebuild-vs-reuse dispatch. The lane therefore
histograms ``base_ms + rebuilt_i * delta_ms`` where the constants are
HOST-MEASURED once per scan (bench's scan-marginal tick and its
aoi_rebuild/aoi_reuse phase probes) and the PER-TICK selection is the
in-graph rebuild bit — measured constants, device-resident
distribution. With no skin (or no phase probes) the lane degenerates
to the constant scan-marginal tick, which is exactly the information
available. The model is stamped next to the verdict so no reader can
mistake it for per-tick wall clock.

Bucketing uses ``bisect_left`` semantics on upper edges — identical to
:class:`goworld_tpu.utils.metrics.Histogram` — and
:func:`host_histogram` is the numpy recompute the parity tests hold
the scan accumulator bit-exact against.

**The LIVE serving path** (ISSUE 11): the same lanes also ride the real
per-tick device step of a production :class:`~goworld_tpu.entity.
manager.World` — :func:`telemetry_update_live` folds one tick's
``TickOutputs`` (single-space, vmapped S>1, mesh, or
``MegaTickOutputs``) into the carry as one small jitted call (zero host
syncs; the drain rides the tick's existing fetch-outputs transfer), and
gains a ``occupancy`` lane (per-shard/per-tile alive rows, the elastic-
mesh gauge ROADMAP item 4 needs). :func:`workload_signature` is the
jax-free reducer that folds drained lanes into the stable signature
record served at debug-http ``/workload`` and stamped into BENCH
artifacts — the exact input ROADMAP item 2's autotuning governor will
consume (this layer recommends; it does not hot-swap).
"""

from __future__ import annotations

import math

import numpy as np

from goworld_tpu.utils.metrics import DEFAULT_MS_BUCKETS

__all__ = [
    "TICK_MS_EDGES", "COUNT_EDGES", "SLACK_EDGES", "REBUILD_EDGES",
    "lane_edges", "telemetry_init", "telemetry_update",
    "telemetry_drain", "host_histogram", "TRACE_COUNTS",
    "mega_signals", "telemetry_update_mega",
    "live_signals", "telemetry_update_live",
    "lanes_delta", "workload_signature", "RECOMMENDATION_KEYS",
]

# Every [gameN] ini knob name a workload_signature recommendation can
# emit. CONTRACT (tests/test_governor.py): each of these must be a
# GameConfig field accepted by api._build_world — the strings were
# convention-only before, so a knob rename would silently break the
# autotune governor's input grammar. Extend this tuple when the
# reducer learns a new recommendation key.
RECOMMENDATION_KEYS = ("aoi_skin", "aoi_sort_impl", "aoi_cell_cap",
                       "aoi_k", "sync_delta")

# one ladder with the live metrics plane: a bench SLO and a serve-loop
# SLO bucket identically
TICK_MS_EDGES = tuple(DEFAULT_MS_BUCKETS)
# event volumes / saturation gauges: 0 and powers of 4 up past the caps
COUNT_EDGES = (0.0, 1.0, 4.0, 16.0, 64.0, 256.0, 1024.0, 4096.0,
               16384.0, 65536.0, 262144.0, 1048576.0)
# Verlet skin slack as a fraction of skin/2 (1.0 = untouched headroom)
SLACK_EDGES = (0.0, 0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0)
# the rebuild bit: buckets <=0 (reuse) and <=1 (rebuild)
REBUILD_EDGES = (0.0, 1.0)

_COUNT_LANES = ("sync_n", "enter_n", "leave_n", "over_k_rows",
                "over_cap_cells")
# megaspace comms-demand lanes (per-tick MESH maxima/sums of the
# MegaTickOutputs gauges — the halo/migrate capacity alarms as
# device-resident distributions)
_MEGA_LANES = ("halo_demand", "migrate_demand", "migrate_dropped")

# per-trace-entry counters so tests can assert the telemetry scan
# compiles ONCE per config (the scenarios/behaviors.py idiom)
TRACE_COUNTS: dict = {}


def lane_edges(skin_on: bool, mega: bool = False,
               occupancy: bool = False) -> dict[str, tuple]:
    """Static bucket edges per lane for a config (lane set depends only
    on whether the Verlet skin is live, plus the megaspace comms lanes
    when ``mega`` and the per-shard/per-tile ``occupancy`` lane carried
    by the live serving path)."""
    lanes = {"tick_ms": TICK_MS_EDGES, "rebuilt": REBUILD_EDGES}
    for nm in _COUNT_LANES:
        lanes[nm] = COUNT_EDGES
    if skin_on:
        lanes["skin_slack"] = SLACK_EDGES
    if mega:
        for nm in _MEGA_LANES:
            lanes[nm] = COUNT_EDGES
    if occupancy:
        lanes["occupancy"] = COUNT_EDGES
    return lanes


def telemetry_init(skin_on: bool, mega: bool = False,
                   occupancy: bool = False, n_tiles: int = 1):
    """Zeroed accumulator pytree: one int32 count vector per lane
    (len(edges)+1, last = +Inf) plus the tick_ms running sum. With
    ``occupancy`` the accumulator also carries ``occ_last`` — the last
    tick's per-shard/per-tile alive counts (i32[n_tiles]), the live
    skew gauge the elastic-mesh plane reads."""
    import jax.numpy as jnp

    acc = {nm: jnp.zeros(len(e) + 1, jnp.int32)
           for nm, e in lane_edges(skin_on, mega, occupancy).items()}
    acc["tick_ms_sum"] = jnp.zeros((), jnp.float32)
    if occupancy:
        acc["occ_last"] = jnp.zeros(n_tiles, jnp.int32)
    return acc


def _bucket_add(acc_vec, edges, value):
    import jax.numpy as jnp

    i = jnp.searchsorted(jnp.asarray(edges, jnp.float32),
                         value.astype(jnp.float32), side="left")
    return acc_vec.at[i].add(1)


def _bucket_add_vec(acc_vec, edges, values):
    """Vector form of :func:`_bucket_add`: every element of ``values``
    contributes one sample (scatter-add folds duplicates)."""
    import jax.numpy as jnp

    i = jnp.searchsorted(jnp.asarray(edges, jnp.float32),
                         values.astype(jnp.float32).ravel(), side="left")
    return acc_vec.at[i].add(1)


def telemetry_update(acc, out, base_ms: float, delta_ms: float,
                     half_skin: float = 0.0):
    """Fold one tick's :class:`TickOutputs` into the accumulator.
    ``base_ms``/``delta_ms`` are the host-measured tick-cost model
    constants (see module docstring) and ``half_skin`` (= skin/2, the
    slack lane's unit) normalizes ``aoi_skin_slack`` into a fraction;
    all are trace-time constants so the scan stays one compile per
    config. Runs entirely on device — callers assert that with
    ``jax.transfer_guard`` in the tests."""
    import jax.numpy as jnp

    TRACE_COUNTS["telemetry_update"] = \
        TRACE_COUNTS.get("telemetry_update", 0) + 1
    skin_on = "skin_slack" in acc
    rebuilt = out.aoi_rebuilt
    if rebuilt is None:
        rebuilt = jnp.ones((), jnp.int32)
    tick_ms = jnp.float32(base_ms) \
        + rebuilt.astype(jnp.float32) * jnp.float32(delta_ms)
    acc = dict(acc)
    acc["tick_ms"] = _bucket_add(acc["tick_ms"], TICK_MS_EDGES, tick_ms)
    acc["tick_ms_sum"] = acc["tick_ms_sum"] + tick_ms
    acc["rebuilt"] = _bucket_add(acc["rebuilt"], REBUILD_EDGES,
                                 rebuilt.astype(jnp.float32))
    signals = {
        "sync_n": out.sync_n, "enter_n": out.enter_n,
        "leave_n": out.leave_n, "over_k_rows": out.aoi_over_k_rows,
        "over_cap_cells": out.aoi_over_cap_cells,
    }
    for nm, v in signals.items():
        acc[nm] = _bucket_add(acc[nm], COUNT_EDGES,
                              v.astype(jnp.float32))
    if skin_on:
        slack = out.aoi_skin_slack
        if slack is None:
            slack = jnp.zeros((), jnp.float32)
        if half_skin > 0:
            slack = slack / jnp.float32(half_skin)
        acc["skin_slack"] = _bucket_add(acc["skin_slack"], SLACK_EDGES,
                                        slack)
    return acc


def mega_signals(mouts):
    """Reduce one tick's :class:`MegaTickOutputs` (leading [n_dev]
    leaves inside the jitted scan) to the scalar per-MESH signals the
    lanes histogram: event volumes SUM across shards (they are mesh
    totals), saturation/demand gauges take the mesh MAX (one hot tile
    is the alarm condition)."""
    import types

    import jax.numpy as jnp

    b = mouts.base
    return types.SimpleNamespace(
        sync_n=b.sync_n.sum(),
        enter_n=b.enter_n.sum(),
        leave_n=b.leave_n.sum(),
        aoi_over_k_rows=b.aoi_over_k_rows.max(),
        aoi_over_cap_cells=b.aoi_over_cap_cells.max(),
        aoi_rebuilt=jnp.ones((), jnp.int32),  # megaspace is skinless
        aoi_skin_slack=None,
        halo_demand=mouts.halo_demand.max(),
        migrate_demand=mouts.migrate_demand.max(),
        migrate_dropped=mouts.migrate_dropped.sum(),
    )


def telemetry_update_mega(acc, mouts, base_ms: float):
    """Fold one megaspace tick's outputs into the accumulator: the
    shared lanes ride :func:`telemetry_update` on the mesh-reduced
    signals; the comms lanes (halo/migrate demand, dropped arrivals)
    bucket on the count ladder. On-device like telemetry_update —
    the multichip bench asserts zero host syncs across the scan."""
    sig = mega_signals(mouts)
    acc = telemetry_update(acc, sig, base_ms, 0.0)
    for nm in _MEGA_LANES:
        acc[nm] = _bucket_add(acc[nm], COUNT_EDGES,
                              getattr(sig, nm).astype("float32"))
    return acc


def live_signals(base):
    """Reduce one tick's :class:`TickOutputs` with a leading [S] shard
    axis (the World's stacked single-device or mesh shape) to the
    scalar signals the lanes histogram — volumes SUM across shards,
    saturation gauges take the shard MAX, the rebuild bit is "any
    shard rebuilt" and the slack is the worst headroom."""
    import types

    b = base
    rebuilt = b.aoi_rebuilt
    slack = b.aoi_skin_slack
    return types.SimpleNamespace(
        sync_n=b.sync_n.sum(),
        enter_n=b.enter_n.sum(),
        leave_n=b.leave_n.sum(),
        aoi_over_k_rows=b.aoi_over_k_rows.max(),
        aoi_over_cap_cells=b.aoi_over_cap_cells.max(),
        aoi_rebuilt=None if rebuilt is None else rebuilt.max(),
        aoi_skin_slack=None if slack is None else slack.min(),
    )


def telemetry_update_live(acc, outs, *, mega: bool = False,
                          base_ms: float = 0.0, delta_ms: float = 0.0,
                          half_skin: float = 0.0):
    """Fold one PRODUCTION tick's device outputs into the live carry —
    the serving-path twin of the bench scan's telemetry_update. ``outs``
    is whatever the World's compiled step returned: TickOutputs with a
    leading [S] axis, MultiTickOutputs (mesh; its ``.base`` carries the
    shard axis), or MegaTickOutputs when ``mega``. Adds the per-shard/
    per-tile ``occupancy`` lane from the step's own ``alive_count``
    output (one sample per shard per tick) and tracks ``occ_last``.
    Entirely on device: callers assert zero host syncs with
    ``jax.transfer_guard`` in the tests."""
    import jax
    import jax.numpy as jnp

    TRACE_COUNTS["telemetry_update_live"] = \
        TRACE_COUNTS.get("telemetry_update_live", 0) + 1
    base = getattr(outs, "base", outs)
    # the fold is its own program beside the tick's: a capture names
    # it by this scope (ops/scopes.py)
    with jax.named_scope("gw.telemetry"):
        if mega:
            # the ONE mega fold (shared with the multichip bench scan)
            # so the live serving path and the bench path can never
            # diverge
            acc = telemetry_update_mega(acc, outs, base_ms)
        else:
            acc = telemetry_update(acc, live_signals(base), base_ms,
                                   delta_ms, half_skin)
        if "occupancy" in acc:
            occ = base.alive_count
            acc = dict(acc)
            acc["occupancy"] = _bucket_add_vec(acc["occupancy"],
                                               COUNT_EDGES, occ)
            acc["occ_last"] = occ.astype(jnp.int32).reshape(
                acc["occ_last"].shape)
    return acc


def telemetry_drain(acc, skin_on: bool, half_skin: float = 0.0,
                    mega: bool = False) -> dict:
    """ONE host readback for the whole scan: fetched lane counts as
    ``{lane: {"edges": [...], "counts": [...]}}`` plus the tick_ms
    mean. ``half_skin`` documents the skin_slack lane's unit (its
    edges are fractions of skin/2). Works on device arrays AND on an
    already-fetched host copy (the live World drains the carry inside
    the tick's existing fetch-outputs transfer). An ``occupancy``
    carry also exports ``per_tile`` — the last tick's per-shard alive
    counts (the live skew gauge)."""
    fetched = {k: np.asarray(v) for k, v in acc.items()}
    out: dict = {}
    for nm, edges in lane_edges(skin_on, mega,
                                occupancy="occupancy" in fetched).items():
        out[nm] = {
            "edges": [float(e) for e in edges],
            "counts": [int(c) for c in fetched[nm]],
        }
    if skin_on and half_skin > 0:
        out["skin_slack"]["unit"] = f"fraction of skin/2 ({half_skin:g})"
    if "occ_last" in fetched:
        out["occupancy"]["per_tile"] = [
            int(c) for c in fetched["occ_last"]
        ]
    n = sum(out["tick_ms"]["counts"])
    if n:
        out["tick_ms"]["mean_ms"] = round(
            float(fetched["tick_ms_sum"]) / n, 3)
    return out


def host_histogram(values, edges) -> np.ndarray:
    """Numpy recompute of the device bucketing (bisect_left on upper
    edges, +Inf tail) — the parity oracle for the scan accumulator."""
    edges = np.asarray(edges, np.float32)
    counts = np.zeros(len(edges) + 1, np.int64)
    for v in np.asarray(values, np.float32).ravel():
        counts[int(np.searchsorted(edges, v, side="left"))] += 1
    return counts


# =======================================================================
# workload signature (jax-free; the reducer ROADMAP item 2's governor
# consumes — served at /workload, stamped into BENCH artifacts)
# =======================================================================
def lanes_delta(cur: dict, prev: dict | None) -> dict:
    """Drained-lane WINDOW delta: per-lane ``cur.counts - prev.counts``
    (the lanes are cumulative; the signature wants the recent window,
    not process-lifetime averages). ``prev=None`` returns ``cur``
    as-is. Point-in-time extras (``per_tile``) come from ``cur``."""
    if prev is None:
        return cur
    out: dict = {}
    for nm, lane in cur.items():
        if not isinstance(lane, dict) or "counts" not in lane:
            out[nm] = lane
            continue
        d = dict(lane)
        pl = prev.get(nm)
        if isinstance(pl, dict) and len(pl.get("counts", ())) == \
                len(lane["counts"]):
            d["counts"] = [max(int(a) - int(b), 0) for a, b in
                           zip(lane["counts"], pl["counts"])]
        out[nm] = d
    return out


def _lane_frac_nonzero(lane: dict) -> float:
    """Fraction of samples above the first (<= 0) bucket."""
    total = sum(lane["counts"])
    if total <= 0:
        return 0.0
    return 1.0 - lane["counts"][0] / total


def _lane_q(lane: dict, q: float) -> float:
    from goworld_tpu.utils.devprof import hist_quantile

    return hist_quantile(lane["edges"], lane["counts"], q)


# event-volume ladder (p90 of per-tick enter+leave demand, bucket
# upper bounds on COUNT_EDGES)
_EVENT_CLASSES = ((1.0, "quiet"), (64.0, "low"), (4096.0, "moderate"))
# per-tile occupancy skew (max/mean) thresholds for the mesh classes
_SKEW_CLASSES = ((1.5, "balanced"), (3.0, "skewed"))


def workload_signature(lanes: dict, config: dict | None = None) -> dict:
    """Fold drained (window-delta) telemetry lanes into the stable
    workload-signature record:

    * ``churn`` — ``flock_like`` (the Verlet cache holds: rebuild rate
      < 0.5) vs ``teleport_like`` (the skin is defeated) vs
      ``skinless`` (no skin lane: every tick rebuilds by construction,
      churn is unobservable);
    * ``density`` — ``exact`` (both overflow gauges silent) /
      ``over_k`` (rows truncated to nearest-k) / ``over_cap`` (cells
      dropped candidates — the loudest degradation wins);
    * ``events`` — quiet/low/moderate/heavy by p90 per-tick
      enter+leave demand;
    * ``skew`` — per-tile occupancy max/mean for multi-shard worlds
      (balanced/skewed/hotspot), the elastic-mesh trigger gauge.

    ``recommendation`` maps the classes onto the ``[gameN]`` kernel
    knobs (the scenario matrix's measured inversions: skin=0 under
    teleport-like churn, counting sort under sustained density
    pressure) — a recommendation line, not a hot swap. Returns
    ``{"error": ...}`` when the lanes carry no samples (honest-failure
    convention of the BENCH stamps)."""
    if not isinstance(lanes, dict) or "rebuilt" not in lanes:
        return {"error": "no telemetry lanes"}
    ticks = sum(lanes["rebuilt"]["counts"])
    if ticks <= 0:
        return {"error": "no samples in window"}
    out: dict = {"ticks": int(ticks)}

    # churn: rebuild duty cycle + skin headroom
    rebuild_rate = _lane_frac_nonzero(lanes["rebuilt"])
    out["rebuild_rate"] = round(rebuild_rate, 4)
    if "skin_slack" in lanes and sum(lanes["skin_slack"]["counts"]):
        slack_p50 = _lane_q(lanes["skin_slack"], 0.5)
        # non-finite quantiles stamp as None (the slo_from_histogram
        # convention — json.dumps would emit non-RFC Infinity/NaN)
        out["skin_slack_p50"] = round(slack_p50, 4) \
            if math.isfinite(slack_p50) else None
        out["churn"] = ("flock_like" if rebuild_rate < 0.5
                        else "teleport_like")
    else:
        out["churn"] = "skinless"

    # density: overflow-gauge duty cycles (exactness preconditions of
    # the oracle suites — nonzero means interest sets degraded)
    over_k = _lane_frac_nonzero(lanes.get("over_k_rows",
                                          {"counts": [ticks]}))
    over_cap = _lane_frac_nonzero(lanes.get("over_cap_cells",
                                            {"counts": [ticks]}))
    out["over_k_frac"] = round(over_k, 4)
    out["over_cap_frac"] = round(over_cap, 4)
    out["density"] = ("over_cap" if over_cap > 0
                      else "over_k" if over_k > 0 else "exact")

    # event volume: p90 of per-tick interest-migration demand
    ev = None
    if "enter_n" in lanes and sum(lanes["enter_n"]["counts"]):
        ev = _lane_q(lanes["enter_n"], 0.9) \
            + _lane_q(lanes["leave_n"], 0.9)
        out["enter_leave_p90"] = round(ev, 1) if math.isfinite(ev) \
            else None
    out["events"] = "heavy"
    for bound, cls in _EVENT_CLASSES:
        if ev is not None and ev <= 2 * bound:
            out["events"] = cls
            break
    if ev is None:
        out["events"] = "quiet"
    if "sync_n" in lanes and sum(lanes["sync_n"]["counts"]):
        p50 = _lane_q(lanes["sync_n"], 0.5)
        out["sync_p50"] = round(p50, 1) if math.isfinite(p50) else None

    # per-tile skew (multi-shard/mesh worlds; the re-tiling trigger)
    occ = (lanes.get("occupancy") or {}).get("per_tile")
    if occ and len(occ) > 1 and sum(occ) > 0:
        mean = sum(occ) / len(occ)
        skew = max(occ) / mean if mean > 0 else 1.0
        out["tiles"] = len(occ)
        out["occupancy_per_tile"] = [int(c) for c in occ]
        out["tile_skew"] = round(skew, 3)
        out["skew"] = "hotspot"
        for bound, cls in _SKEW_CLASSES:
            if skew <= bound:
                out["skew"] = cls
                break

    # the [gameN] kernel-config recommendation (ini knob names so the
    # line is directly actionable; "keep" = no change advised)
    rec: dict = {}
    if out["churn"] == "teleport_like":
        rec["aoi_skin"] = 0
    elif out["churn"] == "flock_like":
        rec["aoi_skin"] = "keep"
    rec["aoi_sort_impl"] = ("counting" if out["density"] != "exact"
                            else "keep")
    if out["density"] == "over_cap":
        rec["aoi_cell_cap"] = "raise"
    if out["density"] in ("over_k", "over_cap") and over_k > 0:
        rec["aoi_k"] = "raise"
    # delta-compressed sync fan-out (ISSUE 12, [gameN] sync_delta):
    # pays off exactly where the dirty fraction is low — quiet worlds
    # and flock-like motion (the skin holds, few rows churn) ship
    # mostly int16 deltas against stable baselines. Gate on the sync
    # lane's p50 when it exists (the direct dirty-volume proxy).
    low_dirty = True
    if out.get("sync_p50") is not None:
        low_dirty = out["sync_p50"] <= 64.0
    if low_dirty and out["churn"] != "teleport_like" \
            and (out["churn"] == "flock_like"
                 or out["events"] == "quiet"):
        # teleport-like churn excluded: every jump overflows the int16
        # delta range, so the stream would be all keyframes anyway
        rec["sync_delta"] = 1
    out["recommendation"] = rec

    parts = [f"churn={out['churn']}", f"density={out['density']}",
             f"events={out['events']}"]
    if "skew" in out:
        parts.append(f"skew={out['skew']}")
    out["sig"] = "|".join(parts)
    if config:
        out["config"] = dict(config)
    return out
