"""Batched AOI (area-of-interest) neighbor search.

Reference behavior being rebuilt: each Space owns an AOI manager
(``engine/entity/Space.go:91-106`` enables a ``go-aoi`` XZList manager with a
per-space ``aoiDistance``); every entity move triggers a skip-list sweep that
fires per-entity enter/leave callbacks (``Space.go:244-252``,
``Entity.go:227-246``). Interest is Chebyshev in the XZ plane: entity B is in
A's AOI iff ``|dx| <= dist`` and ``|dz| <= dist``.

TPU-first redesign: one fixed-shape, jit-compiled **uniform-grid sweep** over
the whole Space per tick, instead of per-move incremental updates:

1. bin entities into ``radius``-sized cells over a bounded world, with one
   BORDER ring of always-empty cells around the grid (border cells stay at
   their sentinel init value, so edge queries need no bounds masking),
2. sort slot indices by cell id (one XLA sort) and compute each entity's
   rank within its cell with a segment scan,
3. scatter per-entity records into a dense per-cell table
   ``[(cells_x+2) * (cells_z+2), 3 * cell_cap]`` — px / pz / packed
   slot+flag words side by side, one row per cell,
4. for every entity, read its 3x3 neighborhood as THREE CONTIGUOUS
   3-ROW WINDOWS of that table (cells are z-minor, so the z-triple
   ``(cz-1, cz, cz+1)`` of each x-row is contiguous: one dynamic-slice of
   ``(3, 3*cell_cap)`` per x-offset). TPU gathers are descriptor-bound on
   the scalar core — 3 descriptors of 3 rows beat the 9 single-row
   descriptors of the naive layout, and both beat per-candidate scalar
   gathers by orders of magnitude at 1M entities,
5. distance-filter and keep the nearest ``k`` as a sorted neighbor list
   ``int32[N, k]`` padded with sentinel ``N``.

Per-entity **flag bits** (dirty / has_client) ride the packed slot words:
the sweep can return each neighbor's flags alongside its id, so downstream
consumers (sync collection) never re-gather per-neighbor state over the
``[N, k]`` index space — at 1M x 32 that gather alone costs more than the
whole sweep (r02 TPU profile).

Sorted fixed-width neighbor lists make the downstream enter/leave delta a
vectorized sorted-set difference (:mod:`goworld_tpu.ops.delta`) and the sync
fan-out a masked gather (:mod:`goworld_tpu.ops.sync`).

Capacity bounds (``cell_cap``, ``k``) are explicit knobs: exactness holds
while per-cell occupancy <= cell_cap and true neighbor count <= k; beyond
that the nearest neighbors win, which is the standard MMO "AOI limit"
tradeoff the reference sidesteps by being O(occupancy) per move.

Rows are processed in ``row_block``-sized chunks under ``lax.map`` so peak
memory stays ~``row_block * 9 * cell_cap`` regardless of N (1M-entity spaces
fit on one chip).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial

import jax
import jax.numpy as jnp
from flax import struct
from jax import lax

from goworld_tpu.ops.scopes import scoped
from goworld_tpu.utils import consts

# Packed candidate word layouts (n < 2^21 fast path). The top_k ranking key
# stacks a quantized distance above the word; flag bits sit BELOW the id so
# ranking is exactly (distance, id) — flags can never bias which neighbors
# survive a k-overflow (same id never appears twice, so the flag bits are
# unreachable as a tie-break):
#   with flags:    key = (qd8 << 23) | (id << 2) | flags,   qd8  in [1, 254]
#   without flags: key = (qd10 << 21) | id,                 qd10 in [0, 1023]
# Every valid key stays strictly below INT32_MAX (the invalid key). qd8
# is biased to start at 1 so that, viewed as an IEEE f32 bit pattern
# (the "f32"/"approx" top-k paths bitcast the keys), every valid key has
# a NONZERO exponent field: qd8=0 keys would be subnormal floats, which
# TPU flushes to zero — the compare would return corrupted (zeroed) key
# bits for near neighbors. Nonnegative normal floats order exactly like
# their bit patterns, so int-domain and f32-domain ranking agree.
_ID_BITS = consts.AOI_ID_BITS
_ID_MASK = (1 << _ID_BITS) - 1
_WORD_MASK = (1 << 23) - 1
_QD_MAX = 254

# The candidate structures (sorted view, cell table) are INT32 planes:
# positions ride as their f32 bit patterns and are bitcast back after
# the window fetch; slot words ride as the ints they are. Never the
# other way round — a small int viewed as f32 is a SUBNORMAL, and the
# TPU flushes subnormals to zero in a multi-column f32 row gather (the
# ``[order]`` below): every candidate word read back as 0 and every
# neighbour list collapsed to {slot 0} on the chip (PR 21's first chip
# run; the CPU keeps the bits, so no CPU test can see it). Any f32 bit
# pattern is a harmless int.
_INF_BITS = 0x7F800000          # +inf, the empty-lane coordinate


def _f32_bits(x: jax.Array) -> jax.Array:
    return lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)


def _bits_f32(x: jax.Array) -> jax.Array:
    return lax.bitcast_convert_type(x, jnp.float32)


def _log2_ceil(x: float) -> int:
    """Exact ceil(log2(x)) for positive floats (frexp, no log
    rounding): x = m * 2^e with 0.5 <= m < 1, so 2^e >= x with
    equality iff m == 0.5."""
    m, e = math.frexp(x)
    return e - 1 if m == 0.5 else e


# =======================================================================
# precision=q16 lattice quantizer (shared by the sweep, the Verlet
# reuse re-rank, core/step.py's snap, the sync codec and the snapshot
# planes — ONE quantizer so the domains can never disagree)
# =======================================================================
def quantize_positions(spec: GridSpec, pos: jax.Array) -> jax.Array:
    """Snap x/z onto the precision lattice (f32 values ON the lattice;
    y passes through untouched — AOI is XZ). Identity when precision
    is off. Idempotent: lattice points snap to themselves, so
    double-snapping along any path is harmless. All arithmetic is
    exact (multiply by a power of two, floor, multiply back)."""
    if spec.precision == "off":
        return pos
    step = spec.quant_step
    hi = float((1 << consts.PRECISION_POS_BITS) - 1)
    qx = jnp.clip(jnp.floor(pos[:, 0] * (1.0 / step)), 0.0, hi)
    qz = jnp.clip(jnp.floor(pos[:, 2] * (1.0 / step)), 0.0, hi)
    return jnp.stack([qx * step, pos[:, 1], qz * step], axis=1)


def quantize_xz_i32(spec: GridSpec, pos: jax.Array) -> jax.Array:
    """The packed int16-pair position mirror: ``(qx << 16) | qz`` as
    ONE nonnegative i32 per entity (qx, qz < 2^15). The byte-heavy
    paths gather/stream THIS plane instead of two f32 lanes."""
    step = spec.quant_step
    hi = (1 << consts.PRECISION_POS_BITS) - 1
    qx = jnp.clip(jnp.floor(pos[:, 0] * (1.0 / step)), 0, hi) \
        .astype(jnp.int32)
    qz = jnp.clip(jnp.floor(pos[:, 2] * (1.0 / step)), 0, hi) \
        .astype(jnp.int32)
    return (qx << 16) | qz


def _q16_dist(spec: GridSpec, qxz_a, qxz_b):
    """Chebyshev distance between packed lattice coordinates, as the
    EXACT f32 value ``int_diff * quant_step`` — bit-identical to
    ``max(|ax-bx|, |az-bz|)`` over the snapped f32 positions (lattice
    values and their differences are exact f32 integers times a power
    of two), so ranking and reach comparisons cannot diverge from the
    f32 path."""
    dq = jnp.maximum(
        jnp.abs((qxz_a >> 16) - (qxz_b >> 16)),
        jnp.abs((qxz_a & 0xFFFF) - (qxz_b & 0xFFFF)),
    )
    return dq.astype(jnp.float32) * spec.quant_step


# 21-bit candidate-id triplet packing (the Verlet cache's cand plane
# under precision=q16): 3 ids of <= 21 bits in 2 u32 words — the
# [N, V] i32 cache becomes [N, 2*ceil(V/3)] (33% fewer bytes streamed
# every reuse tick), losslessly (ids < 2^21 by the packed-id bound).
_ID21_MASK = (1 << 21) - 1


def packed_cand_words(v: int) -> int:
    """u32 words per row for a packed V-lane candidate cache."""
    return 2 * ((v + 2) // 3)


def pack_ids21(ids: jax.Array, pad_value: int) -> jax.Array:
    """[..., V] i32 ids -> [..., 2*ceil(V/3)] u32 (pad lanes filled
    with ``pad_value``, normally the sweep sentinel so they stay
    invalid after unpack)."""
    *lead, v = ids.shape
    pad = (-v) % 3
    if pad:
        ids = jnp.concatenate(
            [ids, jnp.full((*lead, pad), pad_value, ids.dtype)],
            axis=-1)
    t = ids.reshape(*lead, -1, 3).astype(jnp.uint32)
    a, b, c = t[..., 0], t[..., 1], t[..., 2]
    w0 = a | ((b & 0x7FF) << 21)
    w1 = (b >> 11) | (c << 10)
    return jnp.stack([w0, w1], axis=-1).reshape(*lead, -1)


def unpack_ids21(words: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_ids21` (keeps the pad lanes — they carry
    the sentinel and rank as invalid, so callers never reslice)."""
    *lead, _w = words.shape
    t = words.reshape(*lead, -1, 2)
    w0, w1 = t[..., 0], t[..., 1]
    a = w0 & _ID21_MASK
    b = ((w0 >> 21) | ((w1 & 0x3FF) << 11)) & _ID21_MASK
    c = (w1 >> 10) & _ID21_MASK
    return jnp.stack([a, b, c], axis=-1).reshape(*lead, -1) \
        .astype(jnp.int32)


@dataclasses.dataclass(frozen=True)
class GridSpec:
    """Static AOI configuration (hashable; safe to close over under jit).

    The world is the axis-aligned XZ rectangle ``[origin, origin + extent)``;
    positions outside are clamped into the border cells (the reference's
    world is unbounded, but bounded worlds are what real games configure and
    static cell counts are what XLA needs).
    """

    radius: float
    origin_x: float = 0.0
    origin_z: float = 0.0
    extent_x: float = 1024.0
    extent_z: float = 1024.0
    k: int = consts.DEFAULT_MAX_NEIGHBORS
    cell_cap: int = consts.DEFAULT_CELL_CAP
    row_block: int = consts.DEFAULT_ROW_BLOCK
    # "exact" = lax.top_k; "approx" = lax.approx_min_k over the packed
    # keys bitcast to f32 (TPU has a fast partial-reduce lowering for
    # approximate min-k). CAVEAT: on TPU the approx lowering may MISS a
    # true neighbor with small probability (recall_target=0.98 per
    # call), even without k-overflow — a lost AOI enter for that tick.
    # It is a throughput/accuracy knob for huge worlds, NOT a default;
    # exactness-critical deployments keep "exact". On CPU the lowering
    # is exact, so CPU tests only prove plumbing, not recall. The
    # approx encoding keeps every valid key finite as f32 (8-bit
    # distance quantization, +inf sentinel) — 0x7FFFFFFF would be NaN
    # and break the float ordering.
    # "sort" = full minor-dim sort of the packed keys, keep the first k:
    # EXACT (a total order over (distance, id) keys) and lowers to a
    # vectorized sorting network over the 9*cell_cap lanes — on TPU this
    # can beat lax.top_k's generic int32 lowering (r4 hardware
    # attribution: the back half of the sweep, gather+top_k, was ~95% of
    # the tick at 131K entities).
    # "f32" = exact top_k over the packed keys bitcast to f32: XLA's
    # fast TPU TopK custom-call is f32-only, so int32 keys fall back to
    # a generic (slow) expansion — but the keys are nonnegative ints,
    # and nonnegative NORMAL floats order exactly like their bit
    # patterns (the qd8 bias above keeps every valid key normal), so
    # `-top_k(-bitcast_f32(key))` ranks identically to the int domain.
    # Uses the 8-bit finite-key encoding like "approx", without the
    # recall caveat.
    # DEFAULT is "sort": exact under every workload and 2.5x faster
    # than the int32 lax.top_k on both platforms measured in r4 (the
    # generic int32 top_k lowering is the worst case everywhere);
    # autotune/benchmarks may still pick "f32" per platform. The
    # default literal lives in consts.DEFAULT_TOPK_IMPL — one source
    # of truth shared with GameConfig.aoi_topk_impl and bench.py.
    topk_impl: str = consts.DEFAULT_TOPK_IMPL
    # Candidate-fetch strategy:
    #   "table"  — scatter the sorted entities into a dense per-cell
    #              table, then read 3 strided (3, 3*cell_cap) windows
    #              per query (the r02 design).
    #   "ranges" — TABLELESS: each query's 3 z-triples are CONTIGUOUS
    #              RANGES of the cell-sorted entity array (padded border
    #              cells are never occupied, so the triple (cz-1..cz+1)
    #              of an x-row is one run). Candidates slice straight
    #              out of the sorted [N, 3] array: no dense table to
    #              init (12M elements at 1M entities), no 3M-element
    #              scatter, and every window read is CONTIGUOUS. The
    #              per-cell occupancy cap becomes a POOLED cap of
    #              3*cell_cap per z-triple — identical results while
    #              occupancy <= cell_cap, strictly fewer drops beyond
    #              (pooling only ever admits candidates the per-cell cap
    #              dropped).
    #   "cellrow" — the table impl with a CANONICAL row-gather window
    #              fetch: the 9 windows of every cell are premerged into
    #              one [cells_x*cells_z, 9*3*cell_cap] block by 9 STATIC
    #              slices of the padded table, and each query fetches
    #              its whole candidate pool as ONE contiguous row
    #              (jnp.take, 1 descriptor — vs 3 windowed
    #              dynamic-slices) indexed by its cell. BIT-IDENTICAL to
    #              "table" in every regime (same candidates, same
    #              queries, same ranking) — a pure lowering change.
    #              Costs one extra materialization (~1.3 KB/cell); built
    #              for TPU, where gather descriptors bound the sweep.
    #   "shift"  — CELL-MAJOR, gather-free: queries are the table slots
    #              themselves ([cells_x, cells_z, cell_cap]), and every
    #              one of the 9 neighbor windows is a STATIC slice of
    #              the border-padded table (query cell (i, j) sees
    #              table[i+dx, j+dz] for dx, dz in {-1,0,1} — a shift,
    #              not a gather). The only per-entity indexed ops left
    #              are the front-half build scatter (shared with
    #              "table") and ONE [N, k]-row unsort scatter of the
    #              finished lists back to slot order. Motivated by the
    #              r4 TPU measurement: the per-entity windowed
    #              dynamic-slice gather + top_k dominated the tick
    #              (~535 of 567 ms at 131K entities) while sort+build
    #              cost < 10 ms. Results are identical to "table" while
    #              per-cell occupancy <= cell_cap; beyond the cap,
    #              overflowed entities are dropped as WATCHERS too (they
    #              keep an empty neighbor list for the tick) — the cell
    #              gauge (`with_stats`) alarms in exactly that regime.
    #              Packed-id fast path only (n < 2^21); wide worlds fall
    #              back to "table".
    #   "fused"  — the "ranges" front half with the ENTIRE back half
    #              (window gather -> key pack -> top-k) as ONE Pallas
    #              kernel (_sweep_fused): per query block the 3
    #              contiguous sorted-array runs of the 9-cell window
    #              are sliced VMEM->VMEM into scratch, distances/keys
    #              are packed with the SHARED _pack_keys encoder, and
    #              the k smallest keys are selected by an unrolled
    #              min-extract loop — so the [N, 9*cell_cap] candidate
    #              window and packed-key arrays NEVER round-trip HBM
    #              (the two dominant post-r5 roofline terms,
    #              docs/ROOFLINE.md: ~1.3 GB gather + ~0.9 GB top-k at
    #              1M). Bit-identical outputs to "ranges" under every
    #              exact topk_impl (same candidates, same keys, and
    #              valid keys are unique so the k winners are the
    #              same set). Interpret-mode execution off-TPU (slow
    #              emulation — never a CPU default). REFUSED on a TPU
    #              backend by the validation below: the v5e compiler
    #              rejects the kernel's run-time-offset lane slice
    #              (ops/pallas_compat.FUSED_SWEEP_REFUSAL). Packed-id
    #              fast path only (n < 2^21); wide worlds fall back to
    #              "ranges".
    # The default literal lives in consts.DEFAULT_SWEEP_IMPL ("cellrow",
    # decided by the chip: the reason is written there) — one source of
    # truth shared with GameConfig.aoi_sweep_impl and bench.py, so
    # kernel-level GridSpec users can't silently get a slower impl than
    # the production stack.
    sweep_impl: str = consts.DEFAULT_SWEEP_IMPL
    # Front-half cell-sort lowering:
    #   "argsort"  — XLA's generic sort (a ~0.5*log2(n)^2-pass bitonic
    #                network on TPU; the roofline's worst HBM term at
    #                1M — docs/ROOFLINE.md), or the packed single-array
    #                jnp.sort fast path where the key fits (small
    #                worlds).
    #   "counting" — two-pass counting sort over the cell-row keys
    #                (ops/sort.py): histogram scatter-add + exclusive
    #                cumsum + stable chunked scatter. STABLE, so
    #                bit-identical to argsort in every regime
    #                (including which entities a cell_cap overflow
    #                drops) — a pure lowering choice, never a fidelity
    #                knob.
    #   "pallas"   — the counting sort's rank/scatter pass as a Pallas
    #                kernel (SMEM-resident fill histogram walked by the
    #                scalar core on the sequential TPU grid).
    #                Interpret-mode validated on CPU; compiles for v5e
    #                at the 131,072 shard (tests/test_tpu_compile.py).
    # Default literal in consts.DEFAULT_SORT_IMPL (one source of truth
    # with GameConfig.aoi_sort_impl and bench.py).
    sort_impl: str = consts.DEFAULT_SORT_IMPL
    # Verlet skin (classic particle-code neighbor-list reuse): bin and
    # sort at cell size ``radius + skin`` and admit candidates out to
    # ``reach + skin``; then, while every entity has moved less than
    # ``skin/2`` Chebyshev since the last rebuild, the cached candidate
    # lists are still a SUPERSET of every true neighborhood (each pair
    # approached at most ``skin``), so ticks can skip the entire front
    # half AND the 9-cell window fetch — re-ranking current distances
    # over the cached candidate ids instead (grid_neighbors_verlet;
    # core/step.py carries the cache in SpaceState). 0 disables.
    # Exactness: identical neighbor sets to a per-tick rebuild while
    # rebuild-time candidate demand <= verlet_cap_eff (the over-cap
    # gauge fires otherwise — same bounded-capacity contract as k /
    # cell_cap, never a silent approximation).
    skin: float = consts.DEFAULT_AOI_SKIN
    # cached candidate lanes per entity; 0 = auto (k + k//2)
    verlet_cap: int = 0
    # force a rebuild at least every N ticks regardless of displacement
    # (staleness backstop for float-drift paranoia and for bounding the
    # cache's worst-case age in traces); 0 = displacement-driven only
    rebuild_every_max: int = 0
    # Quantized state planes (ISSUE 12 / ROADMAP 3): "off" = today's
    # all-f32 streams, bit-identical; "q16" = AOI-visible positions
    # snap to a POWER-OF-TWO lattice (quant_step = the smallest 2^e
    # with <= 2^15 lattice points across the larger extent) and the
    # byte-heavy paths run on narrow planes — the "ranges" sorted view
    # packs (qx, qz) into ONE i32 lane (8 B/row instead of 12), the
    # Verlet reuse re-ranks int16 coordinate diffs over a 21-bit-packed
    # candidate cache, and sync/snapshot streams ship int16 deltas
    # (ops/sync.py, freeze.py). EXACTNESS IS BY CONSTRUCTION, not by
    # tolerance: the step is a power of two (scaling never rounds), the
    # cell size is rounded UP to a power-of-two multiple of the step
    # (cell index == qx >> quant_cell_shift, exactly floor(x/cell) on
    # the snapped value), and every lattice coordinate/difference is an
    # exact f32 integer — so the int16-domain sweep is BIT-IDENTICAL to
    # the f32 sweep over the snapped positions, and the brute-force
    # oracle over snapped positions gates exactness like every other
    # parity suite. The quantization itself bounds position fidelity at
    # quant_step (validated <= radius/4 below; the interest semantics
    # are then "Chebyshev over the lattice world").
    precision: str = consts.DEFAULT_PRECISION

    def __post_init__(self):
        # a typo'd knob would otherwise silently fall through every
        # impl branch to some default path
        if self.topk_impl not in ("exact", "sort", "f32", "approx"):
            raise ValueError(
                f"topk_impl must be exact|sort|f32|approx, "
                f"got {self.topk_impl!r}"
            )
        if self.sweep_impl not in ("table", "ranges", "cellrow",
                                   "shift", "fused"):
            raise ValueError(
                f"sweep_impl must be table|ranges|cellrow|shift|fused, "
                f"got {self.sweep_impl!r}"
            )
        if self.sweep_impl == "fused":
            # asked only for this value: on_tpu() touches the backend,
            # and GridSpec defaults are built at import time
            from goworld_tpu.ops import pallas_compat

            if pallas_compat.on_tpu():
                raise ValueError(pallas_compat.FUSED_SWEEP_REFUSAL)
        if self.sort_impl not in ("argsort", "counting", "pallas"):
            raise ValueError(
                f"sort_impl must be argsort|counting|pallas, "
                f"got {self.sort_impl!r}"
            )
        if not self.skin >= 0.0:
            raise ValueError(
                f"skin must be >= 0 (0 disables Verlet reuse), "
                f"got {self.skin!r}"
            )
        if self.verlet_cap < 0 or 0 < self.verlet_cap < self.k:
            # the reuse re-rank asks _rank_packed for k of the cached
            # lanes — fewer lanes than k would shape-mismatch (sort) or
            # crash lax.top_k (exact/f32) deep inside the trace
            raise ValueError(
                f"verlet_cap must be 0 (= auto k + k//2) or >= k "
                f"(={self.k}), got {self.verlet_cap!r}"
            )
        if self.rebuild_every_max < 0:
            raise ValueError(
                f"rebuild_every_max must be >= 0 (0 = displacement-"
                f"driven only), got {self.rebuild_every_max!r}"
            )
        if self.precision not in ("off", "q16"):
            raise ValueError(
                f"precision must be off|q16, got {self.precision!r}"
            )
        if self.precision != "off":
            # the lattice proofs (snap/bin/distance exactness) are
            # origin-free: qx*step must BE the coordinate, not an
            # offset a rounded f32 add would smear
            if self.origin_x != 0.0 or self.origin_z != 0.0:
                raise ValueError(
                    "precision=q16 requires origin_x == origin_z == 0 "
                    "(lattice arithmetic is origin-free; shift the "
                    f"world), got ({self.origin_x!r}, {self.origin_z!r})"
                )
            step = self.quant_step
            if not step > 0.0 or not math.isfinite(step):
                raise ValueError(
                    f"precision=q16 rejected: degenerate lattice step "
                    f"{step!r} from extents ({self.extent_x!r}, "
                    f"{self.extent_z!r})"
                )
            if step > self.radius / 4.0:
                # the sweep over the lattice is exact BY CONSTRUCTION,
                # but the snap itself moves entities by up to one step;
                # past radius/4 that slop could flip a cell assignment
                # or a reach comparison RELATIVE TO THE F32 WORLD by a
                # gameplay-visible margin — reject loudly, same style
                # as the impl-name validations above
                raise ValueError(
                    f"precision=q16 rejected: int16 lattice step "
                    f"{step!r} over extent "
                    f"{max(self.extent_x, self.extent_z)!r} exceeds "
                    f"radius/4 ({self.radius / 4.0!r}) — at 2^"
                    f"{consts.PRECISION_POS_BITS} points/axis this "
                    "resolution could flip a cell assignment or reach "
                    "comparison vs the f32 world; shrink the extent or "
                    "raise the radius"
                )
        if self.skin > 0 and self.verlet_cap_eff > 9 * self.cell_cap:
            # the rebuild sweep can admit at most the 3x3 window's
            # 9*cell_cap candidate lanes per row; asking it to keep
            # more would shape-mismatch the lax.cond branches deep in
            # the trace (the 'sort' top-k slices to the lane count)
            raise ValueError(
                f"verlet_cap (effective {self.verlet_cap_eff}) must be "
                f"<= 9*cell_cap ({9 * self.cell_cap}) — raise cell_cap "
                f"or lower verlet_cap/k"
            )

    @property
    def cell_size(self) -> float:
        """Grid cell edge. With a Verlet skin the cells grow by it so
        the 3x3 window still covers ``reach + skin`` from any query
        position (Chebyshev coverage needs reach <= cell edge). Under
        precision=q16 the edge rounds UP to a power-of-two multiple of
        the lattice step so the cell index of a snapped position is
        exactly ``qx >> quant_cell_shift`` — slightly bigger cells
        (denser occupancy; re-provision cell_cap from the gauges), same
        coverage guarantee."""
        if self.precision != "off":
            return self.quant_step * (1 << self.quant_cell_shift)
        return self.radius + self.skin

    @property
    def quant_step(self) -> float:
        """precision=q16 lattice step: the smallest power of two with
        <= 2^PRECISION_POS_BITS lattice points across the larger
        extent (power of two => scaling f32 coordinates by 1/step and
        back never rounds)."""
        ext = max(self.extent_x, self.extent_z)
        return 2.0 ** (_log2_ceil(ext) - consts.PRECISION_POS_BITS)

    @property
    def quant_cell_shift(self) -> int:
        """log2(cell edge / lattice step) under precision=q16: cell
        index = lattice coordinate >> this."""
        return max(0, _log2_ceil(
            (self.radius + self.skin) / self.quant_step))

    @property
    def quant_bits(self) -> int:
        """Lattice points/axis as bits (0 when precision is off) —
        the ``pos_scale_bits`` every artifact stamp records."""
        return consts.PRECISION_POS_BITS if self.precision != "off" \
            else 0

    @property
    def verlet_cap_eff(self) -> int:
        """``verlet_cap`` resolved: 0 = auto ``k + k//2``."""
        return self.verlet_cap if self.verlet_cap > 0 \
            else self.k + self.k // 2

    @property
    def cells_x(self) -> int:
        return max(1, int(-(-self.extent_x // self.cell_size)))

    @property
    def cells_z(self) -> int:
        return max(1, int(-(-self.extent_z // self.cell_size)))


# Profiler names of the sweep's sub-phases (ops/scopes.py). The SAME
# four names whichever sweep_impl runs, so a capture reads cells /
# index / gather / rank from any of them and a new implementation
# keeps the name of the phase it replaces.
_SCOPE_CELLS = "gw.aoi.cells"    # cell rows + cell sort
_SCOPE_INDEX = "gw.aoi.index"    # sorted view, row ranges / cell table
_SCOPE_GATHER = "gw.aoi.gather"  # the 9-cell window fetch
_SCOPE_RANK = "gw.aoi.rank"      # distances, key pack, top-k, unpack


@scoped(_SCOPE_CELLS)
def _cell_rows(spec: GridSpec, pos, alive, watch_radius):
    """Front half, stage 1: per-entity padded cell-row ids."""
    czp = spec.cells_z + 2          # padded (border) cell columns
    cxp = spec.cells_x + 2
    n_rows = cxp * czp

    if watch_radius is not None:
        # radius-0 entities leave the candidate pool here (sorted out of
        # every cell row) so they cost nothing downstream
        alive = alive & (watch_radius > 0.0)

    cx = jnp.clip(
        jnp.floor(
            (pos[:, 0] - spec.origin_x) / spec.cell_size
        ).astype(jnp.int32),
        0,
        spec.cells_x - 1,
    )
    cz = jnp.clip(
        jnp.floor(
            (pos[:, 2] - spec.origin_z) / spec.cell_size
        ).astype(jnp.int32),
        0,
        spec.cells_z - 1,
    )
    # padded row id; dead entities scatter out of bounds (dropped)
    row = (cx + 1) * czp + (cz + 1)
    srow = jnp.where(alive, row, n_rows)
    return cx, cz, srow, alive, czp, n_rows


@scoped(_SCOPE_CELLS)
def _sort_cells(n: int, n_rows: int, srow, sort_impl: str = "argsort"):
    """Front half, stage 2: entities ordered by cell row. Every impl is
    stable (ties broken by ascending slot id), so they are
    bit-interchangeable — including which entities a cell_cap overflow
    drops (see GridSpec.sort_impl)."""
    if sort_impl in ("counting", "pallas"):
        from goworld_tpu.ops.sort import (
            counting_sort_cells,
            counting_sort_cells_pallas,
        )

        fn = counting_sort_cells_pallas if sort_impl == "pallas" \
            else counting_sort_cells
        return fn(srow, n_rows)
    if n < (1 << _ID_BITS) and n_rows < (1 << 10):
        # single-array sort of (row << 21 | idx) packed keys instead of
        # a key+payload argsort: half the sorted bytes, identical result
        # (idx is unique, so ties cannot occur and within-row order is
        # ascending idx — exactly the stable argsort's). Requires
        # n < 2^21 and n_rows < 2^10 so the key fits nonneg int32;
        # bigger worlds keep the argsort. (Megaspace per-tile grids fit;
        # a 1M-entity single grid does not.)
        skey = jnp.sort(
            (srow << _ID_BITS) | jnp.arange(n, dtype=jnp.int32)
        )
        return skey & _ID_MASK, skey >> _ID_BITS
    order = jnp.argsort(srow).astype(jnp.int32)
    return order, srow[order]


@scoped(_SCOPE_INDEX)
def _sorted_src(spec: GridSpec, pos, flag_bits, order):
    """Front half, stage 3: sorted (px bits, pz bits, packed word)
    int32 triples (see ``_INF_BITS`` for why the plane is int). The
    word carries the slot id plus caller flag bits (dirty/has_client) on
    the fast path so consumers never re-gather them per neighbor.
    Returns ``(src i32[n, 3], table_sentinel, empty)`` — ``empty`` is
    the three components' empty-lane values, ``(_INF_BITS, _INF_BITS,
    table_sentinel)``."""
    n = pos.shape[0]
    sentinel = n
    idx = jnp.arange(n, dtype=jnp.int32)
    if n < (1 << _ID_BITS) and flag_bits is not None:
        word = (idx << 2) | (flag_bits.astype(jnp.int32) & 3)
        table_sentinel = sentinel << 2
    else:
        word = idx
        table_sentinel = sentinel
    src = jnp.stack(
        [_f32_bits(pos[:, 0]), _f32_bits(pos[:, 2]), word], axis=1
    )[order]
    return src, table_sentinel, (_INF_BITS, _INF_BITS, table_sentinel)


@scoped(_SCOPE_INDEX)
def _build_ranges(cc: int, n_rows: int, srow, src, pad_vals):
    """Front half, stage 4 (ranges impl): row_start offsets + padded
    component-major sorted view. row_start[r] = first sorted position of
    cell row r, from a bincount + exclusive cumsum (dead entities land
    in the n_rows bin, excluded). ``pad_vals`` gives each src component
    its sentinel-column value (int32; the precision path's 2-component
    packed view passes 2)."""
    counts = jnp.zeros(n_rows + 1, jnp.int32).at[srow].add(
        1, mode="drop"
    )
    row_start = jnp.concatenate([
        jnp.zeros((1,), jnp.int32),
        jnp.cumsum(counts[:n_rows], dtype=jnp.int32),
    ])
    # padded with 3cc sentinel columns so every window slice is in bounds
    pad = jnp.stack([
        jnp.full((3 * cc,), v, jnp.int32) for v in pad_vals
    ])
    s_t = jnp.concatenate([src.T, pad], axis=1)       # [C, n + 3cc]
    return row_start, s_t


def _init_row(comp_init, cc: int):
    """One empty table row: each component's init value repeated across
    its cc lanes. Shared by _build_table and the shift impl's x-pad so
    padded blocks can never diverge from the table's own empty lanes."""
    return jnp.repeat(jnp.asarray(comp_init, jnp.int32), cc)


@scoped(_SCOPE_INDEX)
def _build_table(cc: int, n_rows: int, sorted_row, src, comp_init):
    """Front half, stage 4 (table/shift impls): dense per-cell table.
    Ranks each sorted entity within its cell via a segment scan (no
    per-entity binary searches — those are scalar gathers on TPU), then
    scatters the C components of ``src`` ([n, C]) side by side.
    ``comp_init`` gives each component's empty-lane init value
    (int32: ``_INF_BITS`` for coordinates, the sentinel word)."""
    n, ncomp = src.shape
    idx = jnp.arange(n, dtype=jnp.int32)
    new_seg = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_row[1:] != sorted_row[:-1]]
    )
    seg_start = lax.cummax(jnp.where(new_seg, idx, 0))
    rank = idx - seg_start
    valid_src = (rank < cc) & (sorted_row < n_rows)
    base = jnp.where(
        valid_src, sorted_row * (ncomp * cc) + rank, n_rows * ncomp * cc
    )
    table = jnp.tile(_init_row(comp_init, cc), n_rows)
    for c in range(ncomp):
        table = table.at[base + c * cc].set(src[:, c], mode="drop")
    return table.reshape(n_rows, ncomp * cc)


def _invalid_key_int(topk_impl) -> int:
    """Sentinel ranking key as a plain Python int — the one source of
    truth (the fused Pallas kernel closes over it; a jnp constant
    would be a tracer under jit and uncapturable by the kernel). The
    f32-domain rankings (approx min-k and the exact "f32" top_k) run
    over the keys bitcast to f32, so their invalid key is +inf's bit
    pattern (ordered above every finite key; 0x7FFFFFFF would be a
    NaN and break the float order)."""
    return 0x7F800000 if topk_impl in ("approx", "f32") else 2**31 - 1


def _invalid_key(topk_impl):
    """:func:`_invalid_key_int` as a jnp scalar for the XLA paths."""
    return jnp.int32(_invalid_key_int(topk_impl))


def _pack_keys(spec: GridSpec, dist, valid, cand_w, want_flags,
               qmax: float | None = None):
    """Pack (quantized distance, word) into one int32 ranking key so a
    single top_k yields ids AND flags — the take_along_axis re-gather it
    replaces was the single most expensive op of the sweep (minor-axis
    dynamic indexing serializes on TPU). Distance quantization — 10
    bits on the plain int path (no flags, "exact"/"sort"), 8 bits
    whenever flags ride the word OR the ranking runs in the f32 domain
    ("f32"/"approx", whose keys must be finite normal floats) — only
    affects WHICH neighbors win when the true count exceeds k (already
    best-effort); flags sit below the id so they never influence the
    ranking. ``qmax`` is the largest representable distance (defaults
    to the interest radius; the Verlet candidate build passes
    ``radius + skin`` so skin-padded distances keep full resolution).
    Shared by the entity-major and cell-major sweeps — their bit-parity
    contract depends on one encoder."""
    invalid_key = _invalid_key(spec.topk_impl)
    if qmax is None:
        qmax = spec.radius
    if want_flags or spec.topk_impl in ("approx", "f32"):
        # 8-bit distance in [1, 254]: max key (254<<23)|word stays a
        # FINITE f32 pattern and min key (1<<23) stays a NORMAL one —
        # the f32-domain rankings require both (subnormals flush to
        # zero on TPU, corrupting returned key bits)
        qd = jnp.minimum(
            (dist * (253.0 / qmax)).astype(jnp.int32), _QD_MAX - 1
        ) + 1
        return jnp.where(valid, (qd << 23) | cand_w, invalid_key)
    qd = jnp.minimum(
        (dist * (1024.0 / qmax)).astype(jnp.int32), 1023
    )
    return jnp.where(valid, (qd << _ID_BITS) | cand_w, invalid_key)


@scoped(_SCOPE_CELLS)
def _cell_occupancy_stats(srow, n_rows: int, cc: int):
    """AOI-cap gauges' cell half: (cell_max, over_cap_cells) from the
    UNclipped per-cell occupancy bincount (overflow = members dropped
    from candidate pools; the go-aoi sweep is exact at any density,
    Space.go:244-252 — capping is the TPU tradeoff and must NEVER
    degrade silently). One [N] scatter-add; shared by every sweep
    impl so the gauges cannot skew between them."""
    occ = jnp.zeros(n_rows + 1, jnp.int32).at[srow].add(
        1, mode="drop"
    )[:n_rows]
    return occ.max().astype(jnp.int32), (occ > cc).sum().astype(jnp.int32)


def _rank_packed(packed_key, k, topk_impl, want_flags, sentinel):
    """Back-half ranking shared by the entity-major and cell-major
    sweeps: keep the k smallest packed (distance, id, flags) keys per
    row and unpack to (nbr ascending ids, cnt, flags-or-None).
    ``topk_impl``: "exact" = lax.top_k; "sort" = full minor-dim sort +
    slice (exact too — the keys are totally ordered — but lowers to a
    vectorized sorting network, which can beat the generic int32 top_k
    lowering on TPU); "f32" = exact top_k over the keys bitcast to f32
    (nonneg normal floats order like their bit patterns; rides the fast
    TPU TopK custom-call); "approx" = lax.approx_min_k over the same
    f32 view (see GridSpec.topk_impl for the recall caveat). The
    invalid key is derived here from topk_impl (the one _pack_keys
    used) so the pair can never mismatch."""
    invalid_key = _invalid_key(topk_impl)
    if topk_impl == "approx":
        fk = lax.bitcast_convert_type(packed_key, jnp.float32)
        vals, _ = lax.approx_min_k(fk, k, recall_target=0.98)
        top = lax.bitcast_convert_type(vals, jnp.int32)
    elif topk_impl == "f32":
        # exact min-k in the f32 bit-pattern domain (keys are finite
        # normal nonneg floats by construction): rides XLA's fast TPU
        # TopK custom-call instead of the generic int32 expansion
        fk = lax.bitcast_convert_type(packed_key, jnp.float32)
        top = lax.bitcast_convert_type(-lax.top_k(-fk, k)[0], jnp.int32)
    elif topk_impl == "sort":
        top = jnp.sort(packed_key, axis=-1)[..., :k]
    else:
        top = -lax.top_k(-packed_key, k)[0]  # k smallest
    return _unpack_top(top, invalid_key, want_flags, sentinel)


def _unpack_top(top, invalid_key, want_flags, sentinel):
    """Unpack ranked keys to (nbr ascending ids, cnt, flags-or-None) —
    the tail of :func:`_rank_packed`, shared with the fused Pallas
    sweep (whose kernel emits the ranked keys directly)."""
    ok = top < invalid_key
    if want_flags:
        # the (id << 2) | flags words are already id-ordered: one sort
        # restores ascending ids with flags aligned
        combo = jnp.sort(
            jnp.where(ok, top & _WORD_MASK, sentinel << 2), axis=-1
        )
        nbr = combo >> 2
        fl = jnp.where(nbr == sentinel, 0, combo & 3)
    else:
        nbr = jnp.sort(jnp.where(ok, top & _ID_MASK, sentinel), axis=-1)
        fl = None
    return nbr, ok.sum(-1).astype(jnp.int32), fl


def _sweep_shift(
    spec: GridSpec,
    pos: jax.Array,
    alive: jax.Array,
    query_rows: int | None,
    watch_radius: jax.Array | None,
    flag_bits: jax.Array | None,
    with_stats: bool = False,
    reach_pad: float = 0.0,
) -> tuple[jax.Array, jax.Array, jax.Array | None, tuple | None]:
    """Cell-major, gather-free back half (GridSpec.sweep_impl="shift").

    Queries ARE the table slots: the padded cell table is reshaped to
    [cells_x+2, cells_z+2, C*cell_cap] and each of the 9 neighbor
    windows of every query cell is one STATIC slice of it. No dynamic
    per-entity window gather exists at all — the r4 TPU attribution
    showed that gather plus top_k was ~95% of the tick. Finished
    neighbor lists are scattered back to entity-slot order in ONE
    [rows, k] scatter. Per-entity watch radii ride the table as a 4th
    component, so the query side needs no gather either."""
    n = pos.shape[0]
    q = n if query_rows is None else query_rows
    k = spec.k
    cc = spec.cell_cap
    sentinel = n
    want_flags = flag_bits is not None

    # cell-major: the per-entity cx/cz and filtered alive are never
    # needed — queries are table slots, not entity rows
    _cx, _cz, srow, _alive, czp, n_rows = _cell_rows(
        spec, pos, alive, watch_radius
    )
    if with_stats:
        cell_max, over_cap_cells = _cell_occupancy_stats(srow, n_rows, cc)
    order, sorted_row = _sort_cells(n, n_rows, srow, spec.sort_impl)
    src, _table_sentinel, empty = _sorted_src(spec, pos, flag_bits,
                                              order)
    comp_init = list(empty)
    if watch_radius is not None:
        src = jnp.concatenate(
            [src, _f32_bits(watch_radius[order])[:, None]], axis=1,
        )
        comp_init.append(0)                 # the bits of 0.0
    ncomp = src.shape[1]
    table = _build_table(cc, n_rows, sorted_row, src, comp_init)
    cxp = spec.cells_x + 2
    CZ = spec.cells_z
    t3 = table.reshape(cxp, czp, ncomp * cc)

    # x-block the CELL grid (≈ row_block query slots per block) and pad
    # x with border-init rows so every slab slice is in bounds
    xb = max(1, min(spec.cells_x, spec.row_block // max(1, CZ * cc)))
    nb = -(-spec.cells_x // xb)
    pad_x = nb * xb + 2 - cxp
    if pad_x > 0:
        t3 = jnp.concatenate(
            [
                t3,
                jnp.broadcast_to(
                    _init_row(comp_init, cc), (pad_x, czp, ncomp * cc)
                ),
            ],
            axis=0,
        )

    def do_block(bi):
        with jax.named_scope(_SCOPE_GATHER):
            slab = lax.dynamic_slice(
                t3, (bi * xb, 0, 0), (xb + 2, czp, ncomp * cc)
            )
            qs = lax.slice(slab, (1, 1, 0),
                           (1 + xb, 1 + CZ, ncomp * cc))
        qpx = _bits_f32(qs[..., :cc])
        qpz = _bits_f32(qs[..., cc:2 * cc])
        qw = qs[..., 2 * cc:3 * cc]
        qid = qw >> 2 if want_flags else qw
        if watch_radius is not None:
            reach = jnp.minimum(_bits_f32(qs[..., 3 * cc:4 * cc]),
                                spec.radius) + reach_pad
        else:
            reach = jnp.full_like(qpx, spec.radius + reach_pad)
        keys = []
        dems = []
        for dx in range(3):
            for dz in range(3):
                with jax.named_scope(_SCOPE_GATHER):
                    cs = lax.slice(
                        slab, (dx, dz, 0), (dx + xb, dz + CZ, 3 * cc)
                    )
                with jax.named_scope(_SCOPE_RANK):
                    cpx = _bits_f32(cs[..., :cc])
                    cpz = _bits_f32(cs[..., cc:2 * cc])
                    cw = cs[..., 2 * cc:3 * cc]
                    cid = cw >> 2 if want_flags else cw
                    dist = jnp.maximum(
                        jnp.abs(qpx[..., :, None] - cpx[..., None, :]),
                        jnp.abs(qpz[..., :, None] - cpz[..., None, :]),
                    )
                    valid = (
                        (cid[..., None, :] != sentinel)
                        & (dist <= reach[..., :, None])
                        & (cid[..., None, :] != qid[..., :, None])
                    )
                    keys.append(
                        _pack_keys(
                            spec, dist, valid, cw[..., None, :],
                            want_flags, qmax=spec.radius + reach_pad,
                        )
                    )
                    if with_stats:
                        dems.append(valid.sum(-1, dtype=jnp.int32))
        rows = xb * CZ * cc
        with jax.named_scope(_SCOPE_RANK):
            packed = jnp.concatenate(keys, axis=-1).reshape(rows, 9 * cc)
            nbr_b, cnt_b, fl_b = _rank_packed(
                packed, k, spec.topk_impl, want_flags, sentinel
            )
        dem_b = (
            sum(dems).reshape(rows).astype(jnp.int32)
            if with_stats else jnp.zeros((rows,), jnp.int32)
        )
        if fl_b is None:
            fl_b = jnp.zeros_like(nbr_b)
        return qid.reshape(rows), nbr_b, cnt_b, fl_b, dem_b

    if nb == 1:
        qid_f, nbr_s, cnt_s, fl_s, dem_s = do_block(jnp.int32(0))
    else:
        qid_f, nbr_s, cnt_s, fl_s, dem_s = lax.map(
            do_block, jnp.arange(nb, dtype=jnp.int32)
        )
        qid_f = qid_f.reshape(-1)
        nbr_s = nbr_s.reshape(-1, k)
        cnt_s = cnt_s.reshape(-1)
        fl_s = fl_s.reshape(-1, k)
        dem_s = dem_s.reshape(-1)

    # ONE unsort scatter back to entity-slot order; empty query lanes,
    # ghost rows (>= q) and cap-overflowed entities land in dump row n
    tgt = jnp.where(qid_f < q, qid_f, n)
    nbr = jnp.full((n + 1, k), sentinel, jnp.int32).at[tgt].set(
        nbr_s
    )[:q]
    cnt = jnp.zeros(n + 1, jnp.int32).at[tgt].set(cnt_s)[:q]
    fl = (
        jnp.zeros((n + 1, k), jnp.int32).at[tgt].set(fl_s)[:q]
        if want_flags else None
    )
    stats = None
    if with_stats:
        dem = jnp.zeros(n + 1, jnp.int32).at[tgt].set(dem_s)[:q]
        stats = (
            dem.max().astype(jnp.int32),
            (dem > k).sum().astype(jnp.int32),
            cell_max,
            over_cap_cells,
        )
    return nbr, cnt, fl, stats


# Fused-kernel query-block rows: the VMEM working set per grid step is
# ~ block * 9*cell_cap * (3 comps + keys) f32/i32 plus the whole sorted
# array (3 * (n + 3*cell_cap) f32 — resident ACROSS steps via the
# constant-index_map block, one HBM read per sweep). 512 keeps the
# per-step scratch under ~1 MB at bench cell_cap while leaving the
# descriptor-free VPU work wide enough to fill the lanes.
_FUSED_BLOCK = 512


def _sweep_fused(
    spec: GridSpec,
    pos: jax.Array,
    alive: jax.Array,
    query_rows: int | None,
    watch_radius: jax.Array | None,
    flag_bits: jax.Array | None,
    with_stats: bool = False,
    reach_pad: float = 0.0,
) -> tuple[jax.Array, jax.Array, jax.Array | None, tuple | None]:
    """One-kernel back half (GridSpec.sweep_impl="fused").

    Front half = the "ranges" impl's (cell rows -> cell sort ->
    row_start offsets + padded component-major sorted view). The back
    half — window gather, distance/key pack, top-k — is a single
    Pallas kernel over blocks of ``_FUSED_BLOCK`` query rows:

    * the sorted view ``s_t`` [3, n + 3cc] enters VMEM once (constant
      index_map — the sequential grid reuses the block, so HBM sees
      ONE streaming read of the sorted world per sweep),
    * per query, the 3 contiguous z-triple runs are VMEM->VMEM slices
      into a [3, B, 3, 3cc] scratch (the r4 killer — 3 HBM descriptor
      fetches per query — becomes on-chip addressing),
    * keys are packed by the SHARED :func:`_pack_keys` (bit parity
      with every split sweep is inherited, not re-proved),
    * the k smallest keys per row are extracted by an unrolled
      min-extract loop (valid keys are unique — the id bits differ —
      so equality-masking removes exactly one lane per pass); ranked
      keys leave the kernel as the only [Q, k]-sized output (plus a
      [Q] demand vector — but only under ``with_stats``, mirroring
      the split sweeps' gauge gating).

    The [Q, 9cc] candidate window and packed-key arrays therefore
    never exist in HBM. Outputs are bit-identical to the "ranges"
    sweep under every exact ranking (see GridSpec.sweep_impl).
    Interpret-mode execution off-TPU; GridSpec refuses the option on a
    TPU backend (ops/pallas_compat.py says why).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from goworld_tpu.ops.pallas_compat import resolve_interpret

    n = pos.shape[0]
    q = n if query_rows is None else query_rows
    k = spec.k
    cc = spec.cell_cap
    sentinel = n
    want_flags = flag_bits is not None
    # plain Python int (Pallas kernels cannot capture jnp constants),
    # from the one sentinel source so fused can never diverge from
    # what _pack_keys encodes
    invalid_key = _invalid_key_int(spec.topk_impl)

    cx, cz, srow, alive, czp, n_rows = _cell_rows(
        spec, pos, alive, watch_radius
    )
    if with_stats:
        cell_max, over_cap_cells = _cell_occupancy_stats(srow, n_rows, cc)
    order, _sorted_row = _sort_cells(n, n_rows, srow, spec.sort_impl)
    src, table_sentinel, empty = _sorted_src(spec, pos, flag_bits,
                                             order)
    row_start, s_t = _build_ranges(cc, n_rows, srow, src, empty)

    # query-side scalars ([N]-sized, trivial next to the back half)
    with jax.named_scope(_SCOPE_INDEX):
        dxs = jnp.array([-1, 0, 1], jnp.int32)
        starts = (cx[:, None] + dxs[None, :] + 1) * czp + cz[:, None]
        starts = jnp.where(alive[:, None], starts, 0)  # border rows: empty
        lo = row_start[starts]                          # [N, 3]
        hi = row_start[starts + 3]
    if watch_radius is None:
        reach = jnp.full((n,), spec.radius + reach_pad, jnp.float32)
    else:
        reach = jnp.minimum(watch_radius, spec.radius).astype(
            jnp.float32
        ) + reach_pad

    b = max(1, min(q, _FUSED_BLOCK, spec.row_block))
    nb = -(-q // b)
    padded = nb * b
    idxp = jnp.minimum(jnp.arange(padded, dtype=jnp.int32), q - 1)
    # runs-per-dx-major layouts keep the lane dim = block rows (wide)
    lo_p = lo[idxp].reshape(nb, b, 3).transpose(0, 2, 1)   # [nb, 3, B]
    hi_p = hi[idxp].reshape(nb, b, 3).transpose(0, 2, 1)
    qx_p = pos[:, 0][idxp].reshape(nb, b)
    qz_p = pos[:, 2][idxp].reshape(nb, b)
    qr_p = reach[idxp].reshape(nb, b)
    qid_p = idxp.reshape(nb, b)

    def kernel(s_ref, lo_ref, hi_ref, qx_ref, qz_ref, qr_ref, qid_ref,
               top_ref, *rest):
        # rest = (dem_ref, win_ref) under with_stats, else (win_ref,) —
        # the demand reductions + [nb, b] HBM write exist only when the
        # gauges were asked for, like every split sibling
        win_ref = rest[-1]

        def gather_one(i, carry):
            for dx in range(3):
                win_ref[:, i, dx, :] = s_ref[
                    :, pl.ds(lo_ref[0, dx, i], 3 * cc)
                ]
            return carry

        with jax.named_scope(_SCOPE_GATHER):
            lax.fori_loop(0, b, gather_one, 0)

        with jax.named_scope(_SCOPE_RANK):
            qx = qx_ref[0]
            qz = qz_ref[0]
            qreach = qr_ref[0]
            qid = qid_ref[0]
            lanes = lax.broadcasted_iota(jnp.int32, (b, 3 * cc), 1)
            keys = []
            dems = []
            for dx in range(3):
                cpx = _bits_f32(win_ref[0, :, dx, :])
                cpz = _bits_f32(win_ref[1, :, dx, :])
                cw = win_ref[2, :, dx, :]
                # out-of-range lanes of a run may hold entities of OTHER
                # cells (the sorted array is dense): hard-invalidate, same
                # as the ranges impl
                inr = lanes < (hi_ref[0, dx] - lo_ref[0, dx])[:, None]
                cpx = jnp.where(inr, cpx, jnp.inf)
                cw = jnp.where(inr, cw, table_sentinel)
                dist = jnp.maximum(
                    jnp.abs(cpx - qx[:, None]), jnp.abs(cpz - qz[:, None])
                )
                cid = cw >> 2 if want_flags else cw
                valid = (
                    (cid != sentinel)
                    & (dist <= qreach[:, None])
                    & (cid != qid[:, None])
                )
                keys.append(
                    _pack_keys(spec, dist, valid, cw, want_flags,
                               qmax=spec.radius + reach_pad)
                )
                if with_stats:
                    dems.append(valid.sum(axis=1, dtype=jnp.int32))
            packed = jnp.concatenate(keys, axis=1)        # [B, 9cc], VMEM
            # unrolled exact min-extract (k is static): ascending ranked
            # keys, exactly jnp.sort(packed)[:, :k] — valid keys are
            # unique, so each pass retires exactly one lane
            outs = []
            for _j in range(k):
                m = jnp.min(packed, axis=1)
                outs.append(m)
                packed = jnp.where(packed == m[:, None], invalid_key,
                                   packed)
        top_ref[0] = jnp.stack(outs, axis=1)
        if with_stats:
            rest[0][0] = sum(dems)

    out_specs = [pl.BlockSpec((1, b, k), lambda i: (i, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((nb, b, k), jnp.int32)]
    if with_stats:
        out_specs.append(pl.BlockSpec((1, b), lambda i: (i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((nb, b), jnp.int32))
    outs_pl = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((3, s_t.shape[1]), lambda i: (0, 0)),
            pl.BlockSpec((1, 3, b), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 3, b), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, b), lambda i: (i, 0)),
            pl.BlockSpec((1, b), lambda i: (i, 0)),
            pl.BlockSpec((1, b), lambda i: (i, 0)),
            pl.BlockSpec((1, b), lambda i: (i, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((3, b, 3, 3 * cc), jnp.int32)],
        interpret=resolve_interpret("aoi_fused_sweep"),
    )(s_t, lo_p, hi_p, qx_p, qz_p, qr_p, qid_p)
    out_top = outs_pl[0]
    out_dem = outs_pl[1] if with_stats else None

    with jax.named_scope(_SCOPE_RANK):
        top = out_top.reshape(padded, k)[:q]
        nbr, cnt, fl = _unpack_top(top, invalid_key, want_flags,
                                   sentinel)
    stats = None
    if with_stats:
        dem = out_dem.reshape(padded)[:q]
        stats = (
            dem.max().astype(jnp.int32),
            (dem > k).sum().astype(jnp.int32),
            cell_max,
            over_cap_cells,
        )
    return nbr, cnt, fl, stats


def _sweep(
    spec: GridSpec,
    pos: jax.Array,
    alive: jax.Array,
    query_rows: int | None,
    watch_radius: jax.Array | None,
    flag_bits: jax.Array | None,
    with_stats: bool = False,
    reach_pad: float = 0.0,
    _upto: str | None = None,
) -> tuple[jax.Array, jax.Array, jax.Array | None, tuple | None]:
    # ``_upto`` (sweep_phase_checksum only): stop the back half after
    # "gather" (window fetch), "pack" (key packing) or "rank" (top-k)
    # and return ONE scalar checksum instead of the normal 4-tuple —
    # the bench sub-phase probes time the real row-block code path,
    # not a reimplementation. Entity-major impls only (the caller maps
    # shift/fused onto their split siblings).
    n = pos.shape[0]
    # precision=q16: EVERY impl sweeps the snapped world (so results
    # are identical across impls by the same argument as today); the
    # "ranges" impl additionally streams the PACKED int16-pair sorted
    # view instead of two f32 position lanes — bit-identical outputs
    # (lattice arithmetic is exact in both domains; see GridSpec.
    # precision), strictly fewer bytes. The _upto probes keep the f32
    # view (they time the split stages, like fused probing ranges).
    pos = quantize_positions(spec, pos)
    if spec.sweep_impl == "shift" and n < (1 << _ID_BITS):
        return _sweep_shift(
            spec, pos, alive, query_rows, watch_radius, flag_bits,
            with_stats, reach_pad,
        )
    if spec.sweep_impl == "fused" and n < (1 << _ID_BITS):
        return _sweep_fused(
            spec, pos, alive, query_rows, watch_radius, flag_bits,
            with_stats, reach_pad,
        )
    q = n if query_rows is None else query_rows
    k = spec.k
    cc = spec.cell_cap
    sentinel = n
    packed_path = n < (1 << _ID_BITS)
    want_flags = flag_bits is not None

    cx, cz, srow, alive, czp, n_rows = _cell_rows(
        spec, pos, alive, watch_radius
    )
    if with_stats:
        cell_max, over_cap_cells = _cell_occupancy_stats(srow, n_rows, cc)
    order, sorted_row = _sort_cells(n, n_rows, srow, spec.sort_impl)
    src, table_sentinel, empty = _sorted_src(spec, pos, flag_bits,
                                             order)

    # "fused" past the packed-id bound falls back to its front-half
    # sibling "ranges" (the fused kernel packs ids into key words)
    ranges_impl = spec.sweep_impl in ("ranges", "fused")
    cellrow_impl = spec.sweep_impl == "cellrow"
    # the packed int16-pair fast path: "ranges" only (the fused kernel
    # already keeps its window in VMEM; the table impls, the default
    # "cellrow" among them, keep the shared f32 table layout and sweep
    # the snapped positions through it), real sweeps only (_upto probes
    # time the split f32 stages)
    q16 = (spec.precision != "off" and ranges_impl and packed_path
           and _upto is None)
    with jax.named_scope(_SCOPE_INDEX):
        qxz_plane = quantize_xz_i32(spec, pos) if q16 else None
    merged = None
    if ranges_impl:
        # TABLELESS (see GridSpec.sweep_impl): candidates come straight
        # out of the sorted array.
        if q16:
            # 2-component sorted view: packed (qx, qz) lattice pair +
            # flag word — 8 B/row streamed instead of 12
            with jax.named_scope(_SCOPE_INDEX):
                src = jnp.stack([qxz_plane[order], src[:, 2]], axis=1)
            row_start, s_t = _build_ranges(
                cc, n_rows, srow, src, (0, table_sentinel)
            )
        else:
            row_start, s_t = _build_ranges(cc, n_rows, srow, src, empty)
        table = None
    else:
        table = _build_table(cc, n_rows, sorted_row, src, empty)
        if cellrow_impl:
            # premerge the 9 windows of every TRUE cell into one row:
            # 9 static slices of the padded table (no gather), so the
            # per-query fetch below is ONE contiguous row
            with jax.named_scope(_SCOPE_INDEX):
                cxs, czs = spec.cells_x, spec.cells_z
                t3 = table.reshape(cxs + 2, czp, 3 * cc)
                merged = jnp.concatenate(
                    [
                        t3[dx:dx + cxs, dz:dz + czs]
                        for dx in range(3) for dz in range(3)
                    ],
                    axis=-1,
                ).reshape(cxs * czs, 9 * 3 * cc)
                # dump row: dead / radius-0 queries fetch an all-empty
                # window (the table impl reads border rows for them; cell
                # (0, 0) would hold real candidates)
                merged = jnp.concatenate(
                    [
                        merged,
                        jnp.tile(_init_row(empty, cc), 9)[None],
                    ],
                    axis=0,
                )

    dxs = jnp.array([-1, 0, 1], jnp.int32)
    px = pos[:, 0]
    pz = pos[:, 2]

    def row_block(rows: jax.Array):
        # rows: int32[B] entity slot indices (may include padding = n-1
        # dupes; harmless, outputs for them are overwritten consistently).
        b = rows.shape[0]
        # z-triple windows: for each x-offset, rows ((cx+dx+1)*czp + cz)
        # .. +2 are the contiguous (cz-1, cz, cz+1) padded cells. Dead
        # query rows read window 0 — border rows, all sentinel/empty.
        with jax.named_scope(_SCOPE_GATHER):
            starts = (cx[rows][:, None] + dxs[None, :] + 1) * czp \
                + cz[rows][:, None]
            starts = jnp.where(alive[rows][:, None], starts, 0)

            if cellrow_impl:
                rq = cx[rows] * spec.cells_z + cz[rows]
                rq = jnp.where(alive[rows], rq,
                               spec.cells_x * spec.cells_z)
                win = jnp.take(merged, rq, axis=0).reshape(b, 9, 3 * cc)
                cand_px = _bits_f32(win[:, :, :cc]).reshape(b, 9 * cc)
                cand_pz = _bits_f32(win[:, :, cc:2 * cc]).reshape(b, 9 * cc)
                cand_w = win[:, :, 2 * cc:].reshape(b, 9 * cc)
            elif ranges_impl:
                lo = row_start[starts]                   # [B, 3]
                hi = row_start[starts + 3]
                ncmp = 2 if q16 else 3
                win = jax.vmap(
                    jax.vmap(
                        lambda s: lax.dynamic_slice(
                            s_t, (0, s), (ncmp, 3 * cc)
                        ),
                    )
                )(lo)                                    # [B, 3, C, 3cc]
                if q16:
                    cand_qxz = win[:, :, 0, :].reshape(b, 9 * cc)
                    cand_px = cand_pz = None
                    cand_w = win[:, :, 1, :].reshape(b, 9 * cc)
                else:
                    cand_px = _bits_f32(win[:, :, 0, :]).reshape(b, 9 * cc)
                    cand_pz = _bits_f32(win[:, :, 1, :]).reshape(b, 9 * cc)
                    cand_w = win[:, :, 2, :].reshape(b, 9 * cc)
                lanes3 = jnp.arange(3 * cc, dtype=jnp.int32)
                in_range = (
                    lanes3[None, None, :] < (hi - lo)[:, :, None]
                ).reshape(b, 9 * cc)
                # out-of-range lanes may hold entities of OTHER cells (the
                # sorted array is dense): hard-invalidate them — admitting
                # one for some watchers but not others would make interest
                # asymmetric. (The q16 path needs only the word kill: its
                # validity never consults coordinates.)
                if not q16:
                    cand_px = jnp.where(in_range, cand_px, jnp.inf)
                cand_w = jnp.where(in_range, cand_w, table_sentinel)
            else:
                win = jax.vmap(
                    jax.vmap(
                        lambda s: lax.dynamic_slice(
                            table, (s, 0), (3, 3 * cc)
                        ),
                    )
                )(starts)                                # [B, 3, 3, 3cc]
                win = win.reshape(b, 9, 3 * cc)
                cand_px = _bits_f32(win[:, :, :cc]).reshape(b, 9 * cc)
                cand_pz = _bits_f32(win[:, :, cc:2 * cc]).reshape(b, 9 * cc)
                cand_w = win[:, :, 2 * cc:].reshape(b, 9 * cc)

        if _upto == "gather":
            return (
                jnp.where(jnp.isfinite(cand_px), cand_px, 0.0).sum()
                + jnp.where(jnp.isfinite(cand_pz), cand_pz, 0.0).sum()
                + cand_w.sum().astype(jnp.float32)
            )
        with jax.named_scope(_SCOPE_RANK):
            if q16:
                # int16-pair domain: |int diff| * step is the EXACT f32
                # distance over lattice positions (see _q16_dist), so
                # everything downstream — reach compare, key pack, top-k —
                # is bit-identical to the f32 branch below
                dist = _q16_dist(spec, cand_qxz, qxz_plane[rows][:, None])
            else:
                ddx = jnp.abs(cand_px - px[rows][:, None])
                ddz = jnp.abs(cand_pz - pz[rows][:, None])
                dist = jnp.maximum(ddx, ddz)             # Chebyshev XZ
            if watch_radius is None:
                reach = spec.radius + reach_pad
            else:  # per-watcher view distance, bounded by the cell size
                reach = (jnp.minimum(watch_radius[rows], spec.radius)
                         + reach_pad)[:, None]

            if packed_path:
                cand_id = cand_w >> 2 if want_flags else cand_w
                valid = (
                    (cand_id != sentinel)
                    & (dist <= reach)
                    & (cand_id != rows[:, None])
                )
                packed_key = _pack_keys(spec, dist, valid, cand_w, want_flags,
                                        qmax=spec.radius + reach_pad)
                if _upto == "pack":
                    return packed_key.sum().astype(jnp.float32)
                nbr_b, cnt_b, fl_b = _rank_packed(
                    packed_key, k, spec.topk_impl, want_flags, sentinel
                )
                if _upto == "rank":
                    return nbr_b.sum().astype(jnp.float32) \
                        + cnt_b.sum().astype(jnp.float32)
                dem_b = (
                    valid.sum(axis=1).astype(jnp.int32) if with_stats else None
                )
                return nbr_b, cnt_b, fl_b, dem_b

            valid = (
                (cand_w != sentinel)
                & (dist <= reach)
                & (cand_w != rows[:, None])
            )
            key = jnp.where(valid, dist, jnp.inf)
            if _upto == "pack":
                return jnp.where(jnp.isfinite(key), key, 0.0).sum()
            top_val, top_idx = lax.top_k(-key, k)        # k nearest
            nbr_b = jnp.take_along_axis(cand_w, top_idx, axis=1)
            ok = jnp.isfinite(top_val)
            nbr_b = jnp.where(ok, nbr_b, sentinel).astype(jnp.int32)
            nbr_b = jnp.sort(nbr_b, axis=1)              # ascending ids
            if _upto == "rank":
                return nbr_b.sum().astype(jnp.float32)
            fl_b = None
            if want_flags:
                # wide-id fallback: flags can't ride the word; one bounded
                # gather over [B, k] recovers them (megaspace-scale only)
                nbr_c = jnp.minimum(nbr_b, n - 1)
                fl_b = jnp.where(
                    nbr_b == sentinel, 0,
                    flag_bits[nbr_c].astype(jnp.int32) & 3,
                )
            dem_b = valid.sum(axis=1).astype(jnp.int32) if with_stats else None
            return nbr_b, ok.sum(axis=1).astype(jnp.int32), fl_b, dem_b

    # never let the block exceed the query count: a small space with the
    # default row_block would otherwise pad up to a full block and do
    # row_block/q times the work
    rb = min(spec.row_block, q)
    nblocks = -(-q // rb)
    padded = nblocks * rb
    all_rows = jnp.minimum(jnp.arange(padded, dtype=jnp.int32), q - 1)
    blocks = all_rows.reshape(nblocks, rb)
    if _upto is not None:
        # sub-phase probe: row_block returned ONE scalar per block
        if nblocks == 1:
            return row_block(blocks[0])
        return lax.map(row_block, blocks).sum()
    if nblocks == 1:
        nbr, cnt, fl, dem = row_block(blocks[0])
    else:
        nbr, cnt, fl, dem = lax.map(row_block, blocks)
        nbr = nbr.reshape(padded, k)
        cnt = cnt.reshape(padded)
        if fl is not None:
            fl = fl.reshape(padded, k)
        if dem is not None:
            dem = dem.reshape(padded)
    if fl is not None:
        fl = fl[:q]
    stats = None
    if with_stats:
        dem = dem[:q]
        # demand is measured WITHIN the candidate pool: if cells
        # overflowed (over_cap_cells > 0) it is itself a lower bound —
        # but then the cell gauge already fires, so "both gauges zero"
        # still proves the sweep was exact this tick
        stats = (
            dem.max().astype(jnp.int32),              # aoi_demand_max
            (dem > k).sum().astype(jnp.int32),        # aoi_over_k_rows
            cell_max,                                 # aoi_cell_max
            over_cap_cells,                           # aoi_over_cap_cells
        )
    return nbr[:q], cnt[:q], fl, stats


@partial(jax.jit, static_argnums=(0, 3))
def grid_neighbors(
    spec: GridSpec,
    pos: jax.Array,
    alive: jax.Array,
    query_rows: int | None = None,
    watch_radius: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Compute AOI neighbor lists for every entity.

    Args:
      spec: static grid configuration.
      pos: float32[N, 3] positions (x, y, z); AOI uses x and z only,
        matching the reference's XZList manager.
      alive: bool[N] slot-occupied mask.
      query_rows: if set, only rows [0, query_rows) get neighbor lists while
        all N entities remain candidates — megaspaces append ghost rows at
        the end that must be visible but never watch
        (:mod:`goworld_tpu.parallel.megaspace`).
      watch_radius: optional f32[N] per-entity AOI distance (reference
        ``EntityTypeDesc.aoiDistance``, ``EntityManager.go:24-101``). An
        entity with radius <= 0 is excluded from AOI entirely — invisible
        to every watcher AND blind itself (the reference's aoiDistance=0 /
        useAOI=false service-entity case); radius > 0 watches within
        ``min(watch_radius, spec.radius)`` (the grid cell size bounds the
        reachable range). None = uniform ``spec.radius`` for all.

    Returns:
      nbr: int32[Q, k] neighbor slot ids, ascending, padded with sentinel N.
      cnt: int32[Q] number of valid neighbors per row. (Q = query_rows or N)
    """
    nbr, cnt, _, _ = _sweep(spec, pos, alive, query_rows, watch_radius,
                            None)
    return nbr, cnt


@partial(jax.jit, static_argnums=(0, 3, 6))
def grid_neighbors_flags(
    spec: GridSpec,
    pos: jax.Array,
    alive: jax.Array,
    query_rows: int | None = None,
    watch_radius: jax.Array | None = None,
    flag_bits: jax.Array | None = None,
    with_stats: bool = False,
) -> tuple:
    """:func:`grid_neighbors` plus per-neighbor flag propagation.

    ``flag_bits`` is int32/uint32[N] with 2 meaningful low bits per entity
    (bit 0 = dirty, bit 1 = has_client by convention of the callers). The
    extra return value ``flags`` is int32[Q, k], aligned with ``nbr``: each
    neighbor's flag bits as of sweep time (0 on sentinel lanes). This costs
    nothing on the packed fast path (n < 2^21) — the bits ride the packed
    candidate words through top_k — and one bounded [Q, k] gather on the
    wide-id fallback.

    ``with_stats=True`` additionally returns 4 i32 scalars
    ``(demand_max, over_k_rows, cell_max, over_cap_cells)`` — true
    neighbor demand vs ``k`` and cell occupancy vs ``cell_cap``, the
    AOI-cap overflow gauges (both zero <=> this tick's sweep was exact;
    see GridSpec's capacity-bounds note). Cost: one [N] scatter-add and
    a few reductions.
    """
    if flag_bits is None:
        raise ValueError("grid_neighbors_flags requires flag_bits")
    nbr, cnt, fl, stats = _sweep(
        spec, pos, alive, query_rows, watch_radius, flag_bits,
        with_stats=with_stats,
    )
    if with_stats:
        return nbr, cnt, fl, stats
    return nbr, cnt, fl


def sweep_phase_checksum(spec: GridSpec, pos, alive, phase: str):
    """Sub-phase probe for on-chip attribution (bench.py phase harness):
    runs the sweep UP TO ``phase`` and reduces to one scalar. Front-half
    phases: "sort" = cell ids + cell sort; "build" = sort plus the
    candidate structure (table scatter or ranges row_start/padded view,
    per ``spec.sweep_impl``). Back-half phases (cumulative on top of
    "build"): "gather" = the 9-cell window fetch, "pack" = plus the
    distance/key pack, "rank" = plus the top-k — these run the REAL
    ``_sweep`` row-block path with an early ``_upto`` exit, so the
    fused-vs-split win is attributable stage by stage. Entity-major
    impls only for the back half: "fused" probes its split sibling
    "ranges" (identical front half and candidates — the delta between
    the probed split stages and the fused "aoi" phase IS the fusion
    win) and "shift" probes "table" (same structure, cell-major
    execution). Calls the exact helpers the real sweep uses, so timings
    attribute the real code — NOT a reimplement. Un-jitted; callers
    wrap in their own jit/scan with loop-carried inputs (see
    bench.measure_phases)."""
    n = pos.shape[0]
    cc = spec.cell_cap
    if phase in ("gather", "pack", "rank"):
        sibling = {"fused": "ranges", "shift": "table"}.get(
            spec.sweep_impl, spec.sweep_impl
        )
        return _sweep(
            dataclasses.replace(spec, sweep_impl=sibling),
            pos, alive, None, None, None, _upto=phase,
        )
    cx, cz, srow, alive2, czp, n_rows = _cell_rows(spec, pos, alive, None)
    order, sorted_row = _sort_cells(n, n_rows, srow, spec.sort_impl)
    if phase == "sort":
        return order.sum() + sorted_row.sum()
    src, _ts, empty = _sorted_src(spec, pos, None, order)
    if spec.sweep_impl in ("ranges", "fused"):
        row_start, s_t = _build_ranges(cc, n_rows, srow, src, empty)
        return (row_start.sum() + s_t.sum()).astype(jnp.float32)
    table = _build_table(cc, n_rows, sorted_row, src, empty)
    return table.sum().astype(jnp.float32)


# ==================================================================
# Verlet skin reuse (GridSpec.skin > 0)
# ==================================================================

@struct.dataclass
class VerletCache:
    """Carried AOI front-half products (one per Space, in SpaceState).

    ``cand`` holds, per entity, every candidate within
    ``min(watch_radius, radius) + skin`` Chebyshev AT REBUILD TIME
    (ascending ids, sentinel N). By the standard Verlet bound it stays
    a superset of the true neighborhood while no entity has moved more
    than ``skin/2`` since the rebuild — so reuse ticks re-rank current
    distances over these ids and skip cell binning, sorting, structure
    build and the 9-cell window fetch entirely."""

    cand: jax.Array        # i32[N, V] candidate ids (sentinel N)
    ref_x: jax.Array       # f32[N] x at last rebuild
    ref_z: jax.Array       # f32[N] z at last rebuild
    ref_alive: jax.Array   # bool[N] alive set at last rebuild
    ref_radius: jax.Array  # f32[N] watch radii at last rebuild
    age: jax.Array         # i32 scalar: ticks since rebuild
    valid: jax.Array       # bool scalar: False until the first rebuild
    # last-rebuild overflow gauges, carried so reuse ticks keep
    # reporting the regime the cache was built in
    cell_max: jax.Array        # i32 max cell occupancy at rebuild
    over_cap_cells: jax.Array  # i32 cells past cell_cap at rebuild
    over_v_rows: jax.Array     # i32 rows whose candidate demand
                               # exceeded verlet_cap_eff at rebuild
                               # (nonzero = this cache may be inexact)


def init_verlet_cache(spec: GridSpec, n: int) -> VerletCache:
    """Empty (invalid) cache: the first tick always rebuilds. Under
    precision=q16 the cand plane is 21-bit-triplet packed
    (:func:`pack_ids21`) — [n, 2*ceil(V/3)] u32 instead of [n, V] i32,
    33% fewer bytes streamed every reuse tick, losslessly."""
    v = spec.verlet_cap_eff
    zi = jnp.zeros((), jnp.int32)
    if spec.precision != "off":
        return VerletCache(
            cand=pack_ids21(jnp.full((n, v), n, jnp.int32), n),
            ref_x=jnp.zeros((n,), jnp.float32),
            ref_z=jnp.zeros((n,), jnp.float32),
            ref_alive=jnp.zeros((n,), bool),
            ref_radius=jnp.zeros((n,), jnp.float32),
            age=zi,
            valid=jnp.zeros((), bool),
            cell_max=zi,
            over_cap_cells=zi,
            over_v_rows=zi,
        )
    return VerletCache(
        cand=jnp.full((n, v), n, jnp.int32),
        ref_x=jnp.zeros((n,), jnp.float32),
        ref_z=jnp.zeros((n,), jnp.float32),
        ref_alive=jnp.zeros((n,), bool),
        ref_radius=jnp.zeros((n,), jnp.float32),
        age=zi,
        valid=jnp.zeros((), bool),
        cell_max=zi,
        over_cap_cells=zi,
        over_v_rows=zi,
    )


def _rank_candidates(
    spec: GridSpec,
    pos: jax.Array,
    watch_radius: jax.Array | None,
    flag_bits: jax.Array | None,
    cand: jax.Array,
    with_stats: bool,
):
    """Back half over CACHED candidate ids (the Verlet reuse path):
    gather each candidate's current position (and flag bits) by id,
    re-test exact ``dist <= reach`` and re-rank with the shared
    packed-key machinery. V lanes per row instead of the grid path's
    ``9 * cell_cap`` — and no cell structure or window fetch at all.
    Produces the same lists a full rebuild would (the cached pool is a
    superset of every true neighborhood under the skin bound)."""
    n = pos.shape[0]
    k = spec.k
    sentinel = n
    want_flags = flag_bits is not None
    px = pos[:, 0]
    pz = pos[:, 2]
    # precision=q16 reuse path: ONE packed (qx, qz) i32 gather per
    # candidate instead of two f32 gathers, candidate ids unpacked
    # from the 21-bit-triplet cache rows — the two byte levers of the
    # steady-state AOI term (docs/ROOFLINE.md "Quantized state
    # planes"). Distances are exact (_q16_dist), so ranking is
    # bit-identical to the f32 gathers over the snapped world.
    q16 = spec.precision != "off"
    qxz_plane = quantize_xz_i32(spec, pos) if q16 else None

    def row_block(rows: jax.Array):
        # the reuse path's "window fetch" is the by-id gather of each
        # cached candidate's current position (and flag bits); the
        # scopes alternate so the traced op order stays what it was
        gather = jax.named_scope(_SCOPE_GATHER)
        rank = jax.named_scope(_SCOPE_RANK)
        with gather:
            if q16:
                cb = unpack_ids21(cand[rows])          # [B, >=V]
            else:
                cb = cand[rows]                        # [B, V]
            cbc = jnp.minimum(cb, n - 1)
            cand_x = qxz_plane[cbc] if q16 else px[cbc]
        if q16:
            with rank:
                dist = _q16_dist(spec, cand_x, qxz_plane[rows][:, None])
        else:
            with rank:
                ddx = jnp.abs(cand_x - px[rows][:, None])
            with gather:
                cand_z = pz[cbc]
            with rank:
                dist = jnp.maximum(
                    ddx, jnp.abs(cand_z - pz[rows][:, None])
                )
        with rank:
            if watch_radius is None:
                reach = spec.radius
            else:
                reach = jnp.minimum(watch_radius[rows],
                                    spec.radius)[:, None]
            valid = (cb != sentinel) & (dist <= reach)
            w = cb << 2 if want_flags else cb
        if want_flags:
            with gather:
                cand_fl = flag_bits[cbc]
            with rank:
                w = w | (cand_fl.astype(jnp.int32) & 3)
        with rank:
            packed = _pack_keys(spec, dist, valid, w, want_flags)
            nbr_b, cnt_b, fl_b = _rank_packed(
                packed, k, spec.topk_impl, want_flags, sentinel
            )
        dem_b = valid.sum(axis=1).astype(jnp.int32) if with_stats \
            else jnp.zeros(rows.shape, jnp.int32)
        if fl_b is None:
            fl_b = jnp.zeros_like(nbr_b)
        return nbr_b, cnt_b, fl_b, dem_b

    rb = min(spec.row_block, n)
    nblocks = -(-n // rb)
    padded = nblocks * rb
    all_rows = jnp.minimum(jnp.arange(padded, dtype=jnp.int32), n - 1)
    if nblocks == 1:
        nbr, cnt, fl, dem = row_block(all_rows)
    else:
        nbr, cnt, fl, dem = lax.map(
            row_block, all_rows.reshape(nblocks, rb)
        )
        nbr = nbr.reshape(padded, k)[:n]
        cnt = cnt.reshape(padded)[:n]
        fl = fl.reshape(padded, k)[:n]
        dem = dem.reshape(padded)[:n]
    return nbr[:n], cnt[:n], fl if want_flags else None, dem[:n]


@partial(jax.jit, static_argnums=(0, 6))
def grid_neighbors_verlet(
    spec: GridSpec,
    pos: jax.Array,
    alive: jax.Array,
    cache: VerletCache,
    watch_radius: jax.Array | None = None,
    flag_bits: jax.Array | None = None,
    with_stats: bool = False,
) -> tuple:
    """:func:`grid_neighbors_flags` with Verlet-skin front-half reuse.

    The rebuild decision is IN-GRAPH (``lax.cond``), a pure function of
    the carried cache and this tick's state, so the whole tick still
    scans on device:

      rebuild iff  cache invalid
               or  max alive Chebyshev displacement since rebuild
                   > skin/2                       (the Verlet bound)
               or  the alive set changed          (spawn/despawn)
               or  any alive watch radius changed
               or  age >= rebuild_every_max       (if > 0)

    Rebuild ticks run the configured sweep front half once with reach
    padded by ``skin`` and keep the ``verlet_cap_eff`` nearest
    candidates per entity; every tick (rebuild or not) then ranks the
    cached candidates at CURRENT positions/flags — so results are
    exactly a per-tick rebuild's while candidate demand fits the cap
    (``over_v_rows`` gauges the only divergence regime, like k /
    cell_cap).

    Returns ``(nbr, cnt, flags, stats-or-None, cache', rebuilt,
    skin_slack)``: ``rebuilt`` is i32 0/1; ``skin_slack`` is
    ``skin/2 - displacement`` (f32; headroom left when positive,
    trigger overshoot when negative). ``stats`` (when requested) keeps
    the 4-gauge contract — cell gauges are as of the last rebuild, and
    ``over_k_rows`` folds in the rebuild's over-cap candidate rows so
    "all gauges zero" still certifies an exact tick.

    Constraints: packed-id fast path only (n < 2^21); no megaspace
    ghost ``query_rows`` (the megaspace step keeps the stateless
    sweep).
    """
    n = pos.shape[0]
    if spec.skin <= 0.0:
        raise ValueError(
            "grid_neighbors_verlet requires spec.skin > 0 "
            f"(got {spec.skin!r}); use grid_neighbors_flags instead"
        )
    if n >= (1 << _ID_BITS):
        raise ValueError(
            "Verlet reuse needs the packed-id fast path (n < 2^21); "
            f"got n={n}"
        )
    want_flags = flag_bits is not None
    # precision=q16: the whole Verlet machinery (displacement check,
    # refs, rebuild sweep, reuse re-rank) runs in the snapped domain —
    # the standard Verlet bound holds verbatim there (movement,
    # candidates and reach all measured on the same lattice)
    pos = quantize_positions(spec, pos)

    disp = jnp.max(
        jnp.where(
            alive,
            jnp.maximum(
                jnp.abs(pos[:, 0] - cache.ref_x),
                jnp.abs(pos[:, 2] - cache.ref_z),
            ),
            0.0,
        )
    )
    need = (
        ~cache.valid
        | (2.0 * disp > spec.skin)
        | jnp.any(alive != cache.ref_alive)
    )
    if watch_radius is not None:
        need = need | jnp.any(
            jnp.where(alive, watch_radius != cache.ref_radius, False)
        )
    age = cache.age + 1
    if spec.rebuild_every_max > 0:
        need = need | (age >= spec.rebuild_every_max)
    # against an invalid cache the zero ref positions make disp ~ the
    # world extent — report full headroom instead of a ~-extent spike
    # in the aoi_skin_slack gauge on every (re)start
    slack = jnp.where(
        cache.valid,
        jnp.float32(0.5 * spec.skin) - disp,
        jnp.float32(0.5 * spec.skin),
    )

    spec_v = dataclasses.replace(spec, k=spec.verlet_cap_eff)

    def rebuild(c: VerletCache) -> VerletCache:
        cand, _cnt, _fl, cstats = _sweep(
            spec_v, pos, alive, None, watch_radius, None,
            with_stats=True, reach_pad=spec.skin,
        )
        return VerletCache(
            cand=(pack_ids21(cand, n) if spec.precision != "off"
                  else cand),
            ref_x=pos[:, 0],
            ref_z=pos[:, 2],
            ref_alive=alive,
            ref_radius=(watch_radius if watch_radius is not None
                        else c.ref_radius),
            age=jnp.zeros((), jnp.int32),
            valid=jnp.ones((), bool),
            cell_max=cstats[2],
            over_cap_cells=cstats[3],
            over_v_rows=cstats[1],
        )

    def reuse(c: VerletCache) -> VerletCache:
        return c.replace(age=age)

    cache = lax.cond(need, rebuild, reuse, cache)
    nbr, cnt, fl, dem = _rank_candidates(
        spec, pos, watch_radius, flag_bits, cache.cand, with_stats
    )
    stats = None
    if with_stats:
        stats = (
            dem.max().astype(jnp.int32),
            (dem > spec.k).sum().astype(jnp.int32) + cache.over_v_rows,
            cache.cell_max,
            cache.over_cap_cells,
        )
    return (nbr, cnt, fl if want_flags else None, stats, cache,
            need.astype(jnp.int32), slack)


def neighbors_oracle(pos, alive, radius, watch_radius=None):
    """NumPy reference implementation (unbounded, uncapped) for tests.

    ``watch_radius`` (optional f32[N]) applies the per-entity AOI
    semantics of :func:`grid_neighbors`: radius <= 0 excludes the
    entity from AOI entirely (invisible AND blind); otherwise watcher
    ``i`` sees participants within ``min(watch_radius[i], radius)``.
    The scenario oracle gates (scenarios/runner.py, the mixed-radius
    workloads) compare World interest sets against exactly this."""
    import numpy as np

    pos = np.asarray(pos)
    alive = np.asarray(alive)
    n = pos.shape[0]
    if watch_radius is None:
        participates = alive
        reach = np.full(n, radius, np.float64)
    else:
        wr = np.asarray(watch_radius, np.float64)
        participates = alive & (wr > 0)
        reach = np.minimum(wr, radius)
    out = []
    for i in range(n):
        if not participates[i]:
            out.append(set())
            continue
        dx = np.abs(pos[:, 0] - pos[i, 0])
        dz = np.abs(pos[:, 2] - pos[i, 2])
        mask = (np.maximum(dx, dz) <= reach[i]) & participates
        mask[i] = False
        out.append(set(np.nonzero(mask)[0].tolist()))
    return out
