"""Device-plane observability: XLA cost auditor, roofline audit, SLO.

Everything observable so far (metrics registry, tick timeline,
distributed tracing) lives on the HOST side of the ``jit`` boundary —
the compiled tick graph itself was a black box, and every TPU claim
rested on the hand-derived docs/ROOFLINE.md model. This module makes
the device plane legible with three pieces:

* :class:`CostReport` / :func:`cost_report` — for any jitted tick
  (single-space, vmapped, megaspace, scenario), run
  ``fn.lower(*args).compile()`` and fold ``cost_analysis()`` +
  ``memory_analysis()`` into one structured record: FLOPs, bytes
  accessed, peak HBM, output bytes, keyed by the resolved kernel
  config (sweep/topk/sort/skin stamps). XLA counts a ``while``-loop
  body ONCE, so a ``lax.scan`` probe's numbers are per-tick already.
* :func:`roofline_model_bytes` / :func:`roofline_audit` — the
  docs/ROOFLINE.md hand model, machine-readable: per-phase HBM bytes
  as a function of (n, grid knobs), diffed against the XLA-derived
  terms and the measured phase timings into the ``roofline_audit``
  block bench.py stamps into its result. The byte model is
  machine-checked on every platform; times are priced only against a
  named device's published peaks (``DEVICE_PEAKS``).
* the SLO plane — :func:`hist_quantile` / :func:`slo_from_histogram`
  turn a fixed-bucket histogram (the in-graph telemetry lanes of
  :mod:`goworld_tpu.ops.telemetry`, or the live ``tick_latency_ms``
  metric) into a {target_ms, p50/p90/p99, pass} verdict, plus a
  process-local registry served by debug_http ``/costs`` (reports,
  lazy analyze providers, the last SLO verdict).

The module is import-safe without jax (the bench parent and the
jax-free tools import the model/quantile half); jax is imported inside
the functions that need it.
"""

from __future__ import annotations

import dataclasses
import math
import threading
from typing import Any, Callable

__all__ = [
    "CostReport", "cost_report", "grid_config_key",
    "roofline_model_bytes", "roofline_audit", "DEVICE_PEAKS",
    "roofline_model_bytes_multichip", "roofline_audit_multichip",
    "device_peaks", "device_stamp", "HALO_ROW_BYTES",
    "hist_quantile", "slo_from_histogram",
    "register_report", "register_provider", "record_slo", "snapshot",
    "set_slo_target", "reset",
]

# Peak rates a roofline may be priced against, keyed by jax's
# ``device_kind``, each with its source. A device that is not here is
# an error, not a default: a share computed against another chip's
# peak is a wrong number under a right name.
DEVICE_PEAKS: dict[str, dict[str, Any]] = {
    "TPU v5 lite": {
        "hbm_gbps": 819.0,       # 16 GB of HBM2e at 819 GB/s
        "ici_gbps": 200.0,       # 1,600 Gbit/s chip-to-chip
        "hbm_gb": 16.0,
        "source": "Google Cloud documentation, \"TPU v5e\" system "
                  "architecture (per-chip figures)",
    },
}


def device_stamp() -> dict[str, Any]:
    """The device this process runs on, as JAX reports it — what every
    result, every game log and ``/vars`` carry so no number or served
    world can outlive the knowledge of its chip."""
    import jax

    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}


def device_peaks(device_kind: str) -> dict[str, Any]:
    """The peaks row for ``device_kind`` (``jax.devices()[0]
    .device_kind``); raises for a device the table does not list."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks recorded for device kind "
            f"{device_kind!r} (known: {sorted(DEVICE_PEAKS)}); add its "
            "row to devprof.DEVICE_PEAKS with a source"
        ) from None


# modeled halo payload bytes per ghost row by halo_impl
# (parallel/halo.py): the 5-lane ppermute path ships pos f32[3] +
# yaw f32 + dirty/valid bools + gid i32 = 22 B; the async packed path
# ships pos + one meta word always (16 B) and its yaw lane is zero
# unless the row is dirty, so the model charges it at dirty duty
HALO_ROW_BYTES = {"ppermute": 22.0, "async": 16.0}
HALO_ASYNC_YAW_BYTES = 4.0
# ... and under the quantized planes (precision=q16, ISSUE 12): the
# xz pair ships as ONE packed i32 lane (4 B) + y f32 (4 B), yaw as
# int16 (2 B) — ppermute 4+4+2+2+4 = 16 B/row, async packed 4+4+4
# = 12 B/row + 2 B dirty-only yaw. The wire change itself is not
# built (the model arbitrates first, the audit stamps both
# projections via ici_halo_mb_by_impl).
HALO_ROW_BYTES_Q = {"ppermute": 16.0, "async": 12.0}
HALO_ASYNC_YAW_BYTES_Q = 2.0

# the paper's AOI-sync latency target (BASELINE.md: p99 < 16 ms at the
# 1M/60 Hz headline shape) — the default SLO budget everywhere
DEFAULT_SLO_TARGET_MS = 16.0


# =======================================================================
# CostReport: compiled-artifact cost auditor
# =======================================================================
@dataclasses.dataclass
class CostReport:
    """Structured XLA cost/memory analysis of ONE compiled executable.

    ``flops``/``bytes_accessed``/``output_bytes`` come from
    ``compiled.cost_analysis()`` (None where the backend exposes no
    figure), the ``*_size`` fields from ``memory_analysis()``.
    ``peak_hbm_bytes`` is argument + output + temp — the executable's
    live-memory high-water mark. ``config`` carries the resolved
    kernel stamps (sweep/topk/sort/skin...) so a report is
    self-describing next to a BENCH headline."""

    name: str
    flops: float | None = None
    bytes_accessed: float | None = None
    output_bytes: float | None = None
    argument_size: int | None = None
    output_size: int | None = None
    temp_size: int | None = None
    peak_hbm_bytes: int | None = None
    generated_code_size: int | None = None
    # donation accounting (ISSUE 16; feeds ROADMAP item 5): bytes the
    # compiled executable ALREADY aliases input->output, and the upper
    # bound donate_argnums could still reclaim — the overlap of
    # argument and output footprints not yet aliased. temp vs arg split
    # is readable directly off temp_size/argument_size above.
    # donation_applied (ISSUE 20) is the "did reclaim" column next to
    # donation_reclaimable's "could reclaim": the actual aliased bytes
    # from the executable's input-output aliasing — 0 on a
    # non-resident world, ~= the carry footprint once donate_argnums
    # is threaded (alias_size under a different, operator-facing name
    # so /costs and the bench cost_report read as a pair).
    alias_size: int | None = None
    donation_applied: int | None = None
    donation_reclaimable: int | None = None
    n: int | None = None
    # multichip mode: device count of the mesh executable (cost figures
    # then cover the WHOLE mesh — divide by n_devices for per-chip)
    n_devices: int | None = None
    platform: str | None = None
    config: dict | None = None
    error: str | None = None

    @property
    def key(self) -> str:
        """Compact per-config key (autotune-log style)."""
        cfg = self.config or {}
        return ",".join(f"{k}={cfg[k]}" for k in sorted(cfg)) or "default"

    def as_dict(self) -> dict:
        d = {k: v for k, v in dataclasses.asdict(self).items()
             if v is not None}
        d["key"] = self.key
        return d


def grid_config_key(grid) -> dict:
    """Resolved kernel stamps for a GridSpec — the per-config key every
    CostReport and BENCH headline shares (one naming for both)."""
    return {
        "sweep_impl": grid.sweep_impl,
        "topk_impl": grid.topk_impl,
        "sort_impl": grid.sort_impl,
        "skin": grid.skin,
        "k": grid.k,
        "cell_cap": grid.cell_cap,
        "precision": getattr(grid, "precision", "off"),
    }


def cost_report(fn, *args, name: str = "tick", config: dict | None = None,
                n: int | None = None,
                n_devices: int | None = None) -> CostReport:
    """Lower + compile ``fn(*args)`` and emit its :class:`CostReport`.

    ``fn`` may be an ALREADY-COMPILED executable (has
    ``.cost_analysis`` — e.g. ``jitted.lower(x).compile()``, zero
    extra compiles), an already-jitted function (has ``.lower``), or a
    plain callable (wrapped in ``jax.jit`` here). Analysis failures
    are folded into ``report.error`` instead of raising — a cost audit
    must never kill a measurement run."""
    import jax

    rep = CostReport(name=name, config=config, n=n, n_devices=n_devices)
    try:
        rep.platform = jax.devices()[0].platform
        if hasattr(fn, "cost_analysis"):
            compiled = fn
        else:
            jfn = fn if hasattr(fn, "lower") else jax.jit(fn)
            compiled = jfn.lower(*args).compile()
    except Exception as exc:
        rep.error = f"lower/compile: {str(exc)[:200]}"
        return rep
    try:
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        ca = ca or {}
        rep.flops = float(ca["flops"]) if "flops" in ca else None
        if "bytes accessed" in ca:
            rep.bytes_accessed = float(ca["bytes accessed"])
        if "bytes accessedout{}" in ca:
            rep.output_bytes = float(ca["bytes accessedout{}"])
    except Exception as exc:
        rep.error = f"cost_analysis: {str(exc)[:200]}"
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            rep.argument_size = int(ma.argument_size_in_bytes)
            rep.output_size = int(ma.output_size_in_bytes)
            rep.temp_size = int(ma.temp_size_in_bytes)
            rep.peak_hbm_bytes = (rep.argument_size + rep.output_size
                                  + rep.temp_size)
            rep.generated_code_size = int(ma.generated_code_size_in_bytes)
            # donation headroom: what input->output aliasing could
            # still reclaim. alias_size_in_bytes is what XLA already
            # aliases (0 without donate_argnums); the bound is the
            # smaller of the two footprints minus that.
            alias = int(getattr(ma, "alias_size_in_bytes", 0) or 0)
            rep.alias_size = alias
            rep.donation_applied = alias
            rep.donation_reclaimable = max(
                0, min(rep.argument_size, rep.output_size) - alias)
    except Exception as exc:
        rep.error = (rep.error or "") + f" memory_analysis: {str(exc)[:200]}"
        rep.error = rep.error.strip()
    return rep


# =======================================================================
# roofline hand model (docs/ROOFLINE.md, machine-readable)
# =======================================================================
def _padded_cells(grid_kw: dict) -> int:
    """(cols+2) * (rows+2) padded grid rows, the table-build term."""
    radius = float(grid_kw.get("radius", 50.0))
    ex = float(grid_kw.get("extent_x", 1024.0))
    ez = float(grid_kw.get("extent_z", ex))
    cols = max(1, int(math.ceil(ex / radius)))
    rows = max(1, int(math.ceil(ez / radius)))
    return (cols + 2) * (rows + 2)


def roofline_model_bytes(n: int, grid_kw: dict) -> dict[str, float]:
    """Per-phase HBM bytes/tick of the hand model (docs/ROOFLINE.md
    table), keyed by the bench phase-probe names. ``grid_kw`` needs
    k, cell_cap, sort_impl, sweep_impl, skin (+ radius/extent for the
    table term); missing knobs take the documented bench defaults.

    These are the MODEL's coefficients — the whole point of the audit
    is that XLA's own accounting (cost_analysis) is diffed against
    them, so keep changes here in lockstep with docs/ROOFLINE.md."""
    k = int(grid_kw.get("k", 32))
    cc = int(grid_kw.get("cell_cap", 12))
    sort_impl = grid_kw.get("sort_impl", "argsort")
    sweep = grid_kw.get("sweep_impl", "ranges")
    skin = float(grid_kw.get("skin", 0.0))
    vcap = int(grid_kw.get("verlet_cap", 0)) or (k + k // 2)
    # quantized state planes (precision=q16, ISSUE 12): the per-term
    # narrowings below mirror exactly what ops/aoi.py ships — the
    # packed 2-lane "ranges" sorted view, the packed-qxz reuse gather,
    # the 21-bit-triplet cand cache, bf16 velocity, and the
    # deadbanded-dirty delta prefilter. Keep in lockstep with
    # docs/ROOFLINE.md "Quantized state planes".
    q16 = grid_kw.get("precision", "off") != "off"
    cells = _padded_cells(grid_kw)
    win = 9 * cc                      # candidate-window lanes per query

    out: dict[str, float] = {}
    out["cell_ids"] = 12.0 * n        # read pos x/z + write rows
    if sort_impl in ("counting", "pallas"):
        # two-pass counting sort: histogram + cumsum + stable scatter
        out["aoi_sort"] = 28.0 * n + 8.0 * cells
    else:
        # bitonic network: ~0.5 log^2(n) compare-exchange passes over
        # keys+payload (16 B/element/pass)
        out["aoi_sort"] = 0.5 * max(1.0, math.log2(max(n, 2))) ** 2 \
            * 16.0 * n
    if sweep in ("table", "cellrow", "shift"):
        # dense per-cell table init + 3x scatter in/out
        out["aoi_build"] = 4.0 * (3 * cc) * cells + 24.0 * n
    elif sweep == "ranges" and q16:
        # packed 2-lane sorted view ((qx,qz) pair + word = 8 B/row)
        out["aoi_build"] = 8.0 * n
    else:
        # tableless ranges/fused front half: sorted [n, 3] view write
        out["aoi_build"] = 12.0 * n
    if sweep == "fused":
        # the whole back half is ONE VMEM-resident kernel: sorted view
        # streamed once + query scalars in, ranked keys + demand out —
        # the [n, 108] window and packed keys never round-trip HBM
        # (under q16 the fused kernel keeps its f32 view — its window
        # already never touches HBM, so there is nothing left to
        # narrow)
        out["aoi_gather"] = 12.0 * n + 44.0 * n
        out["aoi_pack"] = 0.0
        out["aoi_rank"] = 4.0 * k * n + 4.0 * n
    elif sweep == "ranges" and q16:
        # 3 dynamic-slices of (2, 3*cell_cap) lanes per query — the
        # position pair rides ONE i32 lane instead of two f32 lanes
        out["aoi_gather"] = 3 * 2 * (3 * cc) * 4.0 * n
        out["aoi_pack"] = 2 * 4.0 * win * n
        out["aoi_rank"] = 4.0 * win * n + 4.0 * k * n
    else:
        # 3 dynamic-slices of (3, 3*cell_cap) f32 per query
        out["aoi_gather"] = 3 * 3 * (3 * cc) * 4.0 * n
        out["aoi_pack"] = 2 * 4.0 * win * n     # packed keys w + r
        out["aoi_rank"] = 4.0 * win * n + 4.0 * k * n
    if skin > 0:
        # Verlet reuse tick (the steady state the cache-carried probe
        # measures): candidate ids + positions + flags re-gathers plus
        # the shared ranking — front half + window fetch amortize to
        # ~1/cadence duty (cadence is workload speed, not modeled here)
        if q16:
            # 21-bit-packed cand rows (2*ceil(V/3) u32 words) + ONE
            # packed-qxz i32 gather per lane + ranked [n, k] out
            cand_words = 2 * ((vcap + 2) // 3)
            out["aoi_reuse"] = (4.0 * cand_words + 4.0 * vcap
                                + 4.0 * k) * n
        else:
            out["aoi_reuse"] = (3 * 4.0 * vcap + 4.0 * k) * n
        out["aoi_rebuild"] = (out["cell_ids"] + out["aoi_sort"]
                              + out["aoi_build"] + out["aoi_gather"]
                              + out["aoi_pack"] + out["aoi_rank"])
        out["aoi"] = out["aoi_reuse"]   # reuse-dominated steady state
    else:
        out["aoi"] = (out["cell_ids"] + out["aoi_sort"]
                      + out["aoi_build"] + out["aoi_gather"]
                      + out["aoi_pack"] + out["aoi_rank"])
    if q16:
        # pos r/w 24 + prev re-snap read 12 (the deadband compare) +
        # bf16 velocity streams 24 (half of f32's 48) + qxz mirror 4
        out["move"] = 64.0 * n
        # interest delta streams prev+new ONCE each (8k): the changed-
        # row prefilter rides the deadbanded quantized dirty lanes the
        # sweep already delivers, and the k^2 membership compare only
        # gathers the bounded changed-row set (ops/delta two_tier);
        # sync/attr masks + cap-scale value gathers ~= 24 B/row
        out["collect"] = 8.0 * k * n + 24.0 * n
    else:
        out["move"] = 96.0 * n        # pos/vel/yaw streams x ~4
        # interest delta (prev/new nbr reads x2) + sync/attr collection
        out["collect"] = 16.0 * k * n + (4.0 * k + 64.0) * n
    return out


def roofline_audit(phase_ms: dict, phase_costs: dict, n: int,
                   grid_kw: dict, platform: str | None = None,
                   device_kind: str | None = None) -> dict:
    """The ``roofline_audit`` block: per-phase modeled vs XLA-derived
    vs measured, with drift percentages.

    ``phase_ms`` is bench's measured per-phase ms; ``phase_costs`` maps
    phase name -> :class:`CostReport` (or its dict) for the SAME probe.
    ``drift_pct`` compares XLA's bytes-accessed accounting to the hand
    model (platform-lowering differences included — CPU numbers bound
    the traffic model, TPU numbers certify it). With ``device_kind``
    given, ``model_ms`` is the model's bandwidth-roofline projection
    at THAT device's HBM peak (:func:`device_peaks`; an unlisted kind
    raises); without it the block carries bytes only — no time is
    priced against a chip nobody named."""
    peaks = device_peaks(device_kind) if device_kind else None
    model = roofline_model_bytes(n, grid_kw)
    phases: dict[str, dict] = {}
    tot_model = tot_xla = 0.0
    xla_covered: list[str] = []
    for name, mbytes in model.items():
        row: dict[str, Any] = {"model_mb": round(mbytes / 1e6, 3)}
        if peaks is not None:
            row["model_ms"] = round(
                mbytes / (peaks["hbm_gbps"] * 1e6), 4)
        cr = phase_costs.get(name)
        if cr is not None:
            crd = cr.as_dict() if isinstance(cr, CostReport) else cr
            xb = crd.get("bytes_accessed")
            if xb is not None:
                row["xla_mb"] = round(xb / 1e6, 3)
                if mbytes > 0:
                    row["drift_pct"] = round(
                        (xb - mbytes) / mbytes * 100.0, 1)
            if crd.get("flops") is not None:
                row["xla_gflops"] = round(crd["flops"] / 1e9, 4)
            if crd.get("donation_reclaimable") is not None:
                # bytes input->output aliasing could still reclaim for
                # this phase's executable (ROADMAP item 5's budget)
                row["donation_reclaimable_mb"] = round(
                    crd["donation_reclaimable"] / 1e6, 3)
            if crd.get("donation_applied") is not None:
                # ...and what donation ALREADY reclaimed (ISSUE 20):
                # could-vs-did as a pair
                row["donation_applied_mb"] = round(
                    crd["donation_applied"] / 1e6, 3)
            if crd.get("error"):
                row["cost_error"] = crd["error"]
        if name in phase_ms:
            row["measured_ms"] = phase_ms[name]
        phases[name] = row
        if name in ("aoi", "move", "collect"):  # non-overlapping total
            tot_model += mbytes
            if "xla_mb" in row:
                xla_covered.append(name)
                tot_xla += row["xla_mb"] * 1e6
    out = {
        "doc": "docs/ROOFLINE.md",
        "n": n,
        "platform": platform,
        "device_kind": device_kind,
        "bandwidth_gbps": peaks["hbm_gbps"] if peaks else None,
        "phases": phases,
        "total_model_mb": round(tot_model / 1e6, 3),
    }
    # the total drift compares LIKE FOR LIKE: only stamped when every
    # top-level phase carries XLA bytes — a partial sum against the
    # full model total would read as bogus "model overestimates" rot
    if len(xla_covered) == 3:
        out["total_xla_mb"] = round(tot_xla / 1e6, 3)
        out["total_drift_pct"] = round(
            (tot_xla - tot_model) / tot_model * 100.0, 1)
    elif xla_covered:
        out["xla_coverage_partial"] = sorted(xla_covered)
    return out


def roofline_model_bytes_multichip(n_per_chip: int, grid_kw: dict,
                                   mega_kw: dict) -> dict[str, float]:
    """The multichip hand model: PER-CHIP HBM bytes/tick of the tile
    step plus the ICI halo/migrate terms (docs/ROOFLINE.md
    "Multichip"). ``mega_kw`` needs n_dev, halo_cap, migrate_cap;
    optional mesh_shape (default 1D strips), halo_impl (default
    "ppermute"), dirty_frac (fraction of ghost rows shipping a live
    yaw word — the async packed payload's dirty-only lane; default
    1.0, the conservative all-dirty bound) and hot_attrs (default 8).

    Keys: the single-chip phase terms at the EXTENDED population
    (local + ghost rows all ride the sweep), plus ``ici_halo`` and
    ``ici_migrate`` — bytes SHIPPED per chip per tick over ICI."""
    n_dev = int(mega_kw["n_dev"])
    halo_cap = int(mega_kw["halo_cap"])
    migrate_cap = int(mega_kw["migrate_cap"])
    shape = mega_kw.get("mesh_shape") or (n_dev, 1)
    halo_impl = mega_kw.get("halo_impl", "ppermute")
    dirty_frac = float(mega_kw.get("dirty_frac", 1.0))
    attrs = int(mega_kw.get("hot_attrs", 8))
    if halo_impl not in HALO_ROW_BYTES:
        raise ValueError(f"unknown halo_impl {halo_impl!r}")

    # the AOI terms price the extended local+ghost population
    strips = 4 if shape[1] > 1 else 2
    ghost_rows = strips * halo_cap
    out = roofline_model_bytes(n_per_chip + ghost_rows, grid_kw)
    # ICI halo: every inward-facing strip ships halo_cap rows each
    # way. Under the quantized planes (grid_kw precision=q16) the row
    # narrows to the packed-xz/int16-yaw layout (HALO_ROW_BYTES_Q) —
    # the halo interplay term of ISSUE 12 (wire change not built; the
    # audit stamps both projections so a chip run can arbitrate).
    q16 = grid_kw.get("precision", "off") != "off"
    row_b = (HALO_ROW_BYTES_Q if q16 else HALO_ROW_BYTES)[halo_impl]
    if halo_impl == "async":
        row_b = row_b + (HALO_ASYNC_YAW_BYTES_Q if q16
                         else HALO_ASYNC_YAW_BYTES) * dirty_frac
    out["ici_halo"] = float(strips * halo_cap) * row_b
    # ICI migrate: the all_to_all ships [n_dev, cap] rows of
    # (8 + attrs) f32 + 6 i32 each, both directions ~= one buffer out
    out["ici_migrate"] = float(n_dev * migrate_cap) \
        * ((8.0 + attrs) * 4.0 + 24.0)
    return out


def roofline_audit_multichip(tick_ms: float | None, cost, n_total: int,
                             grid_kw: dict, mega_kw: dict,
                             platform: str | None = None,
                             device_kind: str | None = None) -> dict:
    """The MULTICHIP artifact's ``roofline_audit`` block: per-chip
    modeled HBM phases + ICI halo/migrate terms (priced against
    ``device_kind``'s HBM and ICI peaks when one is named, bytes only
    otherwise), diffed against XLA's accounting of the compiled
    mesh scan where available. Same shape contract as
    :func:`roofline_audit` (a ``phases`` dict of ``model_mb`` rows) so
    tools/bench_schema.py validates both with one rule. Also stamps
    the dirty-only packing delta: modeled ICI halo bytes under each
    halo_impl at the same dirty fraction, so the async win is visible
    in the artifact."""
    peaks = device_peaks(device_kind) if device_kind else None
    n_dev = int(mega_kw["n_dev"])
    n_per_chip = max(1, n_total // n_dev)
    model = roofline_model_bytes_multichip(n_per_chip, grid_kw, mega_kw)
    phases: dict[str, dict] = {}
    hbm_total = 0.0
    for name, mbytes in model.items():
        row: dict[str, Any] = {"model_mb": round(mbytes / 1e6, 3)}
        if peaks is not None:
            gbps = peaks["ici_gbps" if name.startswith("ici_")
                         else "hbm_gbps"]
            row["model_ms"] = round(mbytes / (gbps * 1e6), 4)
        if name in ("aoi", "move", "collect"):
            hbm_total += mbytes
        phases[name] = row
    out = {
        "doc": "docs/ROOFLINE.md#multichip",
        "mode": "multichip",
        "n": n_total,
        "n_devices": n_dev,
        "n_per_chip": n_per_chip,
        "platform": platform,
        "device_kind": device_kind,
        "bandwidth_gbps": peaks["hbm_gbps"] if peaks else None,
        "ici_gbps": peaks["ici_gbps"] if peaks else None,
        "phases": phases,
        "total_model_mb_per_chip": round(hbm_total / 1e6, 3),
    }
    if tick_ms is not None:
        out["measured_tick_ms"] = tick_ms
    if cost is not None:
        crd = cost.as_dict() if isinstance(cost, CostReport) else cost
        if crd.get("bytes_accessed") is not None:
            # whole-mesh bytes: divide by n_dev for the per-chip view
            out["xla_mb_mesh"] = round(crd["bytes_accessed"] / 1e6, 3)
            out["xla_mb_per_chip"] = round(
                crd["bytes_accessed"] / n_dev / 1e6, 3)
        if crd.get("error"):
            out["cost_error"] = crd["error"]
    # the dirty-only packing delta, made visible: ICI halo bytes under
    # both impls at this config's dirty fraction — and under both
    # precision domains (the "<impl>_q16" rows are the quantized-plane
    # projection, ISSUE 12's staged halo win)
    deltas = {}
    for impl in HALO_ROW_BYTES:
        mk = dict(mega_kw)
        mk["halo_impl"] = impl
        deltas[impl] = round(
            roofline_model_bytes_multichip(
                n_per_chip, grid_kw, mk)["ici_halo"] / 1e6, 3)
        gq = dict(grid_kw)
        gq["precision"] = "q16"
        deltas[impl + "_q16"] = round(
            roofline_model_bytes_multichip(
                n_per_chip, gq, mk)["ici_halo"] / 1e6, 3)
    out["ici_halo_mb_by_impl"] = deltas
    return out


# =======================================================================
# BENCH/MULTICHIP artifact conventions (jax-free; the ONE home for the
# round-number and wrapper parsing the trajectory tools share —
# bench_trend, bench_schema and roofline_audit must never disagree
# about which rounds have headlines)
# =======================================================================
def artifact_round(path: str) -> int:
    """Round number from a BENCH_r*/MULTICHIP_r* filename; -1 when the
    name carries none."""
    import os
    import re

    m = re.search(r"_r(\d+)", os.path.basename(path))
    return int(m.group(1)) if m else -1


def artifact_headline(doc: dict) -> dict | None:
    """The stamped artifact record of one BENCH_r*.json (driver
    ``{"parsed": ...}`` wrapper or bare), or None when the round
    recorded no usable headline (failed rounds record ``parsed: null``
    honestly). Callers layer their own extra filters (e.g. the trend
    gate also drops ``timing_suspect`` headlines)."""
    rec = doc.get("parsed") if "parsed" in doc else doc
    if not isinstance(rec, dict) or not rec.get("value"):
        return None
    return rec


# =======================================================================
# histogram quantiles + SLO verdicts (jax-free; shared with the tools)
# =======================================================================
def hist_quantile(edges, counts, q: float) -> float:
    """Quantile from a fixed-bucket histogram: the UPPER edge of the
    bucket containing the q-th sample (conservative — the true value is
    <= the reported one). ``counts`` has len(edges)+1 entries (the last
    is the +Inf bucket, reported as ``inf``). NaN on an empty
    histogram."""
    total = sum(counts)
    if total <= 0:
        return float("nan")
    rank = q * total
    cum = 0.0
    for i, c in enumerate(counts):
        cum += c
        if cum >= rank:
            if i < len(edges):
                return float(edges[i])
            return float("inf")
    return float("inf")


def hist_quantile_interp(edges, counts, q: float) -> float:
    """Quantile with LINEAR INTERPOLATION inside the containing bucket
    (the Prometheus histogram_quantile estimator). The upper-edge form
    above is right for conservative SLO verdicts, but a COMPARISON of
    two quantiles (the autotune regret guard: post-swap p90 vs
    pre-swap p90) cannot live on 2x-spaced bucket edges — any
    detectable change would read as >= 2x while a within-bucket
    regression reads as 0. Interpolation keeps the estimate continuous
    as mass shifts between buckets. Still ``inf`` when the q-th sample
    sits in the +Inf bucket, NaN on an empty histogram."""
    total = sum(counts)
    if total <= 0:
        return float("nan")
    rank = q * total
    cum = 0.0
    for i, c in enumerate(counts):
        prev_cum = cum
        cum += c
        if cum >= rank:
            if i >= len(edges):
                return float("inf")
            lo = float(edges[i - 1]) if i > 0 else 0.0
            hi = float(edges[i])
            if c <= 0:
                return hi
            return lo + (hi - lo) * (rank - prev_cum) / c
    return float("inf")


def slo_from_histogram(edges, counts, target_ms: float | None = None,
                       source: str = "histogram") -> dict:
    """{target_ms, p50/p90/p99_ms, samples, pass} from a fixed-bucket
    latency histogram. ``pass`` is conservative: percentiles are bucket
    upper bounds, so a pass means the true p99 is under target too.

    Non-finite percentiles (a sample past the last edge lands in the
    +Inf bucket; an empty histogram has none at all) are stamped as
    None with ``"overflow": true`` — ``json.dumps`` would otherwise
    emit the non-RFC ``Infinity``/``NaN`` tokens straight into the
    BENCH artifacts. Either way the verdict can only be a fail."""
    if target_ms is None:
        target_ms = DEFAULT_SLO_TARGET_MS
    total = int(sum(counts))
    p50 = hist_quantile(edges, counts, 0.50)
    p90 = hist_quantile(edges, counts, 0.90)
    p99 = hist_quantile(edges, counts, 0.99)
    ok = total > 0 and p99 <= target_ms
    out = {
        "target_ms": float(target_ms),
        "p50_ms": p50, "p90_ms": p90, "p99_ms": p99,
        "samples": total,
        "pass": bool(ok),
        "source": source,
    }
    if not all(math.isfinite(out[k])
               for k in ("p50_ms", "p90_ms", "p99_ms")):
        out["overflow"] = True
        for k in ("p50_ms", "p90_ms", "p99_ms"):
            if not math.isfinite(out[k]):
                out[k] = None
    return out


# =======================================================================
# process-local registry (served by debug_http /costs)
# =======================================================================
_lock = threading.Lock()
_reports: dict[str, dict] = {}
_providers: dict[str, Callable[[], "CostReport | dict"]] = {}
_slo: dict | None = None
_slo_target_ms: float = DEFAULT_SLO_TARGET_MS


def register_report(report: CostReport | dict,
                    name: str | None = None) -> None:
    """Record a cost report for this process's ``/costs`` endpoint."""
    d = report.as_dict() if isinstance(report, CostReport) else dict(report)
    with _lock:
        _reports[name or d.get("name", "tick")] = d


def register_provider(name: str,
                      fn: Callable[[], "CostReport | dict"]) -> None:
    """Register a LAZY cost-report provider (e.g. the World's live tick
    executable). Providers run only on ``/costs?analyze=1`` — a
    lower+compile in a live process costs seconds and must be
    operator-triggered, never scrape-triggered."""
    with _lock:
        _providers[name] = fn


def record_slo(verdict: dict) -> None:
    """Record the latest SLO verdict (bench child, or a live process)."""
    global _slo
    with _lock:
        _slo = dict(verdict)


def set_slo_target(target_ms: float) -> None:
    """Set this process's SLO budget (e.g. 1000/tick_hz in a game)."""
    global _slo_target_ms
    with _lock:
        _slo_target_ms = float(target_ms)


def _live_slo() -> dict | None:
    """SLO verdict from the live ``tick_latency_ms`` metric histogram,
    when this process serves one (game serve loop)."""
    from goworld_tpu.utils import metrics

    snap = metrics.REGISTRY.histogram_snapshot("tick_latency_ms")
    if not snap:
        return None
    # merge every labeled child into one distribution
    edges: list[float] | None = None
    counts: list[int] | None = None
    for _labels, s in snap:
        e = [u for u, _c in s["buckets"]]
        c = [cnt for _u, cnt in s["buckets"]] + [s["inf"]]
        if edges is None:
            edges, counts = e, c
        elif e == edges:
            counts = [a + b for a, b in zip(counts, c)]
    if edges is None or sum(counts) == 0:
        return None
    return slo_from_histogram(edges, counts, _slo_target_ms,
                              source="tick_latency_ms")


def snapshot(analyze: bool = False) -> dict:
    """The ``/costs`` payload: recorded reports, provider names (run
    when ``analyze``), and the freshest SLO verdict (explicitly
    recorded, else derived live from ``tick_latency_ms``)."""
    if analyze:
        with _lock:
            pending = list(_providers.items())
        for name, fn in pending:
            try:
                register_report(fn(), name=name)
            except Exception as exc:  # a provider must never 500 /costs
                register_report({"name": name,
                                 "error": str(exc)[:200]}, name=name)
    with _lock:
        out: dict = {
            "reports": dict(_reports),
            "providers": sorted(_providers),
            "slo": dict(_slo) if _slo is not None else None,
            "slo_target_ms": _slo_target_ms,
        }
    if out["slo"] is None:
        out["slo"] = _live_slo()
    return out


def reset() -> None:
    """Drop all registered state (tests)."""
    global _slo, _slo_target_ms
    with _lock:
        _reports.clear()
        _providers.clear()
        _slo = None
        _slo_target_ms = DEFAULT_SLO_TARGET_MS
