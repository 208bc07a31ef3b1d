"""Benchmark: entity ticks/sec/chip at 1M entities (BASELINE.md metric).

Runs the full single-shard world tick — client-input scatter, random-walk
behavior, movement integration, grid AOI sweep, interest deltas, sync-record
+ attr-delta collection — on one chip at 1M entities (the reference's CI
soak tops out at 200 bots over 9 processes; it publishes no benchmark
numbers, see BASELINE.md).

Orchestration: the PARENT process (this file, no ``--child``) never
imports jax — a chip belongs to one process at a time — and runs the
measurement in ONE child (``--child``) that owns it. The child runs
STAGED (an 8K-entity smoke first, then the full-N run, then the
per-plane blocks), one JSON line per stage, streamed, so the parent's
one stdout line carries every stage that completed even if a later one
dies. Every result is stamped with the device it ran on (platform,
``device_kind``, count). There is no fallback: a child that finds no
accelerator refuses to measure, the parent prints an error record (no
metric, no value) and exits non-zero — a CPU timing is never written
under a device metric's name. The functions tests import from here
(``build``, ``measure*``, ...) run on whatever backend the caller has.

The timed region is a ``lax.scan`` over BENCH_TICKS ticks entirely on
device with ONE host readback at the end, so the figure is the scan's
marginal tick, not the served loop's. Per-tick outputs are reduced to
checksums inside the scan so XLA cannot dead-code-eliminate the
collection kernels.

vs_baseline: the driver-set north star is 1M entities @ 60 ticks/s on a
v5e-8 => 7.5M entity-ticks/sec/chip. value/7.5e6 > 1.0 beats it.

Env knobs: BENCH_N (default 1_048_576), BENCH_TICKS (default 20),
BENCH_CLIENT_FRAC (default 0.01), BENCH_PHASES=1 (add per-phase timing:
separately-jitted AOI / behavior+integrate / collect variants),
BENCH_CHILD_TIMEOUT seconds (default 1200), BENCH_BACKHALF_AB=0 to skip the fused-vs-split back-half A/B record
(BENCH_BACKHALF_AB_N shapes it; default the 131K per-chip shard).

`--multichip` (ISSUE 10) runs the MESH headline instead: the megaspace
tick (parallel/megaspace.py) under the real device mesh, driven by one
on-device ``lax.scan`` (zero host syncs per tick), stamped in the
MULTICHIP_r*.json shape — ``entity_ticks_per_sec_mesh``,
``per_chip_efficiency`` vs the same-capacity 1-chip number, comms
gauges, a hotspot-driven ``border_churn`` phase and the multichip
roofline audit. Knobs: BENCH_MULTI_N (default 1M; capacity/chip x
n_dev auto-derived), BENCH_MULTI_TICKS,
BENCH_HALO_IMPL (ppermute|async), BENCH_HALO_CAP, BENCH_MIGRATE_CAP,
BENCH_CHURN_SCENARIO/BENCH_CHURN_SPEED.

Device-plane observability (ISSUE 8): BENCH_DEVPROF=0 skips the
compiled-tick CostReport + roofline_audit stamps (XLA cost_analysis vs
the docs/ROOFLINE.md hand model, per phase); BENCH_SLO=0 skips the
in-graph telemetry scan + slo stamp; BENCH_SLO_MS (default 16.0, the
paper's p99 target) sets the budget; BENCH_SLO_TICKS (default 64) the
histogram scan length. `--check-slo` turns the stamped verdict into
the exit code.

End-to-end sync-age block (ISSUE 15): every round stamps a
``sync_age`` block — the device-tick-epoch -> gate-delivery age
measured through a REAL game -> dispatcher -> gate loopback over
localhost sockets (utils/syncage.py), per-hop p50/p90/p99 + an e2e
verdict vs BENCH_SLO_MS, plus the micro-measured overhead of the
always-on stamp (< 1% of the 60 Hz budget is the criterion).
BENCH_SYNC_AGE=0 skips (recorded honestly); BENCH_SYNC_AGE_RECORDS
(default 32768) / _CLIENTS (16) / _TICKS (64) / _HZ (50) shape it;
BENCH_SYNC_AGE_DELTA=1 runs the 1505 delta-codec leg instead.

Correctness-audit block (ISSUE 17): every round stamps an ``audit``
block — the entity-ownership ledger census + conservation verdict and
the sampled live AOI oracle measured on a REAL churning World
(utils/audit.py), by-kind violation totals (the zero-violation gate)
plus the strict A/B overhead of the plane vs the 60 Hz budget (< 1%
is the criterion). BENCH_AUDIT=0 skips (recorded honestly);
BENCH_AUDIT_ENTITIES (default 192) / _TICKS (96) shape it.

Hot-standby failover block (ISSUE 18): every round stamps a
``failover`` block — a REAL primary streaming SnapshotChain frames
through the bounded replication worker into a live standby world,
killed at a deterministic tick and promoted through the
kvreg-arbitrated claim (goworld_tpu/replication/). Reports
replication bytes/tick NEXT TO the client-sync bytes/tick the same
workload ships, standby apply ms/tick, and the promotion latency in
ticks; the gate is zero lost/duplicated EntityIDs, a clean stream, a
byte-replayable decision log, and a window inside the lag budget.
BENCH_FAILOVER=0 skips (recorded honestly); BENCH_FAILOVER_ENTITIES
(default 128) / _TICKS (48) shape it.

Self-healing rebalance block (ISSUE 19): every round stamps a
``rebalance`` block — a REAL donor world under pose churn trips the
sustained-DEGRADED proxy and the production rebalance stack
(goworld_tpu/rebalance/) hands a space-affine cohort to an
underloaded receiver through the migration protocol. Reports the
donor's tick p99 BEFORE and AFTER the handoff, entities moved vs the
batch cap, abort count, and the donor recovery latency in observation
windows (bench_trend's lower-is-better series); the gate is zero
lost/duplicated EntityIDs across the move and a byte-identical
DecisionLog replay. BENCH_REBALANCE=0 skips (recorded honestly);
BENCH_REBALANCE_ENTITIES (default 96) / _TICKS (32) shape it.

Resident-world A/B block (ISSUE 20): every round stamps a
``resident_ab`` block — two REAL instrumented Worlds on the same
config, the ON arm resident (carry donation via ``donate_argnums``)
plus the double-buffered output drain, the OFF arm the legacy
copy-mode serve loop, ticked in interleaved windows so host noise
lands on both arms. The residency census runs on BOTH arms: the gate
is 0 re-allocated carry lanes on the donated arm (the worklist ISSUE
16 measured, consumed), >= 1 on the copy arm (or the A/B measures
nothing), and on_ms_per_tick strictly below off_ms_per_tick.
BENCH_RESIDENT_AB=0 skips (recorded honestly);
BENCH_RESIDENT_ENTITIES (default 192) / _WINDOWS (6) / _TICKS (24)
shape it.
"""

import argparse
import json
import os
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# jax-free (verified: pure constants) — safe in the no-jax parent
from goworld_tpu.utils import consts as _consts
# jax-free scenario registry (goworld_tpu/scenarios/spec.py): the ONE
# place the accepted BENCH_BEHAVIOR set, the --scenario names and their
# error messages live (ISSUE 7 satellite — new scenarios are
# bench-selectable for free)
from goworld_tpu.scenarios import spec as _sspec  # noqa: E402
from goworld_tpu.scenarios.spec import (  # noqa: E402
    get_scenario,
    resolve_bench_behavior,
    scenario_names,
)
BASELINE_ENTITY_TICKS_PER_CHIP = 7.5e6
# packed-id bound shared with ops/aoi.py: the Verlet reuse path (and
# its phase probes below) only exists for n below it
_AOI_ID_BITS = _consts.AOI_ID_BITS

# grid knob -> env var pinning it (shared by _grid_kw_from_env's
# consumers, autotune's pin detection, and the variant forwarding)
GRID_ENV = {
    "k": "BENCH_K",
    "cell_cap": "BENCH_CELL_CAP",
    "row_block": "BENCH_ROW_BLOCK",
    "topk_impl": "BENCH_TOPK",
    "sweep_impl": "BENCH_SWEEP",
    "sort_impl": "BENCH_SORT",
    "skin": "BENCH_SKIN",
    "verlet_cap": "BENCH_VERLET_CAP",
    "precision": "BENCH_PRECISION",
}

# Bench-default Verlet skin (world units). The bench movers advance
# npc_speed * dt = 5/60 ~ 0.083/tick, so skin 4 rebuilds the AOI front
# half every ~ (skin/2) / 0.083 ~ 24 ticks and every other tick
# re-ranks cached candidates instead of re-sorting the world — exact by
# the Verlet bound (ops/aoi.py GridSpec.skin). The LIBRARY default
# stays 0 (consts.DEFAULT_AOI_SKIN): a skin must be sized to movement
# speed, which the bench knows and a generic deploy doesn't. Pin
# BENCH_SKIN=0 to A/B the skinless path.
BENCH_SKIN_DEFAULT = 4.0

# autotune_sweep's candidate pool: (selectable, grid overrides).
# Module-level so tests can assert the fidelity contract directly:
# selectable=False marks DIAGNOSTICS — configs whose fidelity at the
# bench workload can be WORSE than the default's, which autotune must
# never pick on its own (tests/test_impl_defaults.py locks this in).
AUTOTUNE_CANDIDATES = [
    (True, {}),
    (True, {"row_block": 32768}),
    # dense-table sweep (pre-r4 default; "ranges" won the r4 CPU A/B
    # by 18% and is never-worse on fidelity, so it is the default
    # now) — kept so autotune can pick table back on TPU. Front-half
    # A/Bs (sweep_impl / sort_impl) pin skin=0: under the skin-on
    # default the structure build + cell sort only run on the ONE
    # rebuild tick the scan-marginal cancels, so their timing would be
    # pure reuse-tick noise measuring no front half at all.
    (True, {"sweep_impl": "table", "skin": 0.0}),
    # table with premerged windows + one canonical row-gather per
    # query (bit-identical to table ALWAYS; built for TPU where
    # gather descriptors bound the sweep)
    (True, {"sweep_impl": "cellrow", "skin": 0.0}),
    # the generic int32 lax.top_k (pre-r4 default; "sort" is the
    # default now) — kept so autotune can still detect a platform
    # where it wins
    (True, {"topk_impl": "exact"}),
    # exact top-k in the f32 bit-pattern domain: rides the fast TPU
    # TopK custom-call instead of the generic int32 expansion
    (True, {"topk_impl": "f32"}),
    # skinless Verlet A/B: strictly never-worse fidelity than the
    # skin-on bench default (no candidate cache to overflow), so
    # autotune may select it wherever the reuse doesn't pay
    (True, {"skin": 0.0}),
    # two-pass counting sort front half (ops/sort.py): stable, hence
    # bit-identical results to argsort in every regime — a pure
    # lowering A/B targeting the roofline's dominant bitonic term
    # (skin pinned 0 so the sort actually runs every measured tick)
    (True, {"sort_impl": "counting", "skin": 0.0}),
    # the counting sort's Pallas kernel: interpret-mode (CPU) runs are
    # emulation — meaningless to time off-TPU; diagnostic until a chip
    # run measures it (its v5e compile: tests/test_tpu_compile.py)
    (False, {"sort_impl": "pallas", "skin": 0.0}),
    # the fused Pallas back half (ops/aoi.py _sweep_fused: window
    # gather -> key pack -> top-k in one VMEM-resident kernel — the
    # r6 lever on the two dominant post-r5 roofline terms). Results
    # are bit-identical to ranges, but off-TPU it executes in
    # interpret mode (emulation — meaningless to time, ~2x the split
    # sweep on CPU), and on a TPU backend GridSpec REFUSES it (the v5e
    # compiler rejects the kernel: ops/pallas_compat.py) — so
    # DIAGNOSTIC; child_main's backhalf_ab records the A/B, or the
    # refusal, into the artifact regardless. Skin pinned 0 per the
    # front/back-half A/B convention above. The second row is the
    # full-Pallas pipeline (fused back half over the counting-sort
    # front half).
    (False, {"sweep_impl": "fused", "skin": 0.0}),
    (False, {"sweep_impl": "fused", "sort_impl": "counting",
             "skin": 0.0}),
    # cell-major gather-free sweep: DIAGNOSTIC despite its speed
    # potential — beyond cell_cap it drops overflowed entities as
    # watchers (strictly worse than table, unlike ranges' pooling),
    # and at 1M/cc=12 the occupancy tail gives a small but nonzero
    # per-run chance of that regime. Selecting it would need the
    # headline run to verify the over-cap gauge stayed zero on the
    # measured workload; pin BENCH_SWEEP=shift to A/B by hand.
    (False, {"sweep_impl": "shift", "skin": 0.0}),
    (False, {"sweep_impl": "shift", "topk_impl": "sort", "skin": 0.0}),
    (False, {"cell_cap": 8}),           # diagnostic: drop risk at 1M
    (False, {"topk_impl": "approx"}),   # diagnostic: recall < 1
]

N = int(os.environ.get("BENCH_N", 1_048_576))
BEHAVIOR = os.environ.get("BENCH_BEHAVIOR", "random_walk")  # a legacy
# behavior (random_walk|mlp|btree) OR any scenario registry name —
# validation and the (cfg.behavior, ScenarioSpec) resolution both live
# in goworld_tpu/scenarios/spec.py, so the accepted set has one home
try:
    BEHAVIOR_RESOLVED = resolve_bench_behavior(BEHAVIOR)
except ValueError as exc:
    raise SystemExit(str(exc))
# per-scenario headline blocks (ISSUE 7): "all" = every registry
# scenario; a comma list selects; "0"/"none" skips. The parent's
# --scenario flag writes this env for the children.
SCENARIOS_SEL = os.environ.get("BENCH_SCENARIOS", "all")
if SCENARIOS_SEL.strip().lower() not in ("0", "none", "", "all"):
    # a typo'd env selection must fail fast pre-spawn with the registry
    # list (same contract as BENCH_BEHAVIOR above), not as a KeyError
    # inside the child minutes into the headline measurement
    for _nm in (s.strip() for s in SCENARIOS_SEL.split(",") if s.strip()):
        try:
            get_scenario(_nm)
        except KeyError as exc:
            raise SystemExit(f"BENCH_SCENARIOS: {exc.args[0]}")
SCENARIO_N = int(os.environ.get("BENCH_SCENARIO_N", 65536))
SCENARIO_TICKS = int(os.environ.get("BENCH_SCENARIO_TICKS", 4))
T = int(os.environ.get("BENCH_TICKS", 20))
# --multichip knobs: the megaspace mesh bench (ISSUE 10). Total
# entities target (capacity/chip x n_dev is auto-derived from it), scan
# length, halo impl ("" = the MegaConfig default), and the border-churn
# scenario.
MULTI_N = int(os.environ.get("BENCH_MULTI_N", 1_048_576))
MULTI_TICKS = int(os.environ.get("BENCH_MULTI_TICKS", 8))
MULTI_HALO_IMPL = os.environ.get("BENCH_HALO_IMPL", "")
MULTI_CHURN = os.environ.get("BENCH_CHURN_SCENARIO", "hotspot")
CLIENT_FRAC = float(os.environ.get("BENCH_CLIENT_FRAC", 0.01))
SMOKE_N = int(os.environ.get("BENCH_SMOKE_N", 8192))
SMOKE_T = int(os.environ.get("BENCH_SMOKE_TICKS", 5))
CHILD_TIMEOUT = float(os.environ.get("BENCH_CHILD_TIMEOUT", 1200))
PHASES = os.environ.get("BENCH_PHASES", "1") == "1"  # default ON: the
# per-phase decomposition is the round's main diagnostic and costs ~3
# extra compiles inside the same child


def log(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------- child ----

def _grid_kw_from_env(n: int, overrides: dict | None = None) -> dict:
    """The bench grid knobs, env-defaulted then override-patched — the
    ONE place build() and autotune_sweep() both draw from, so autotune
    always times exactly the config family the headline run will use."""
    grid_kw = dict(
        # ~1.3 entities/cell at bench density: cap 12 is ~9x headroom
        # (overflow drops are the documented AOI-cap tradeoff)
        k=int(os.environ.get("BENCH_K", 32)),
        cell_cap=int(os.environ.get("BENCH_CELL_CAP", 12)),
        row_block=min(n, int(os.environ.get("BENCH_ROW_BLOCK", 65536))),
        topk_impl=os.environ.get("BENCH_TOPK", _consts.DEFAULT_TOPK_IMPL),
        sweep_impl=os.environ.get("BENCH_SWEEP",
                                  _consts.DEFAULT_SWEEP_IMPL),
        sort_impl=os.environ.get("BENCH_SORT",
                                 _consts.DEFAULT_SORT_IMPL),
        skin=float(os.environ.get("BENCH_SKIN", BENCH_SKIN_DEFAULT)),
        verlet_cap=int(os.environ.get("BENCH_VERLET_CAP", 0)),
        # quantized state planes (ISSUE 12): off by default — the
        # headline stays bit-identical to prior rounds; the
        # precision_ab block A/Bs on-vs-off every run
        precision=os.environ.get("BENCH_PRECISION",
                                 _consts.DEFAULT_PRECISION),
    )
    grid_kw.update(overrides or {})
    grid_kw["row_block"] = min(n, grid_kw["row_block"])
    if n >= (1 << _AOI_ID_BITS):
        # the Verlet path needs the packed-id fast path; past the
        # bound keep the grid geometry identical to the stateless
        # config instead of binning at radius+skin with no reuse to
        # show for it (api.py zeroes the skin the same way)
        grid_kw["skin"] = 0.0
    return grid_kw


def build(n: int, client_frac: float, grid_overrides: dict | None = None,
          scenario=None, force_behavior: str | None = None):
    import jax
    import jax.numpy as jnp

    from goworld_tpu.core.state import SpaceState, WorldConfig
    from goworld_tpu.core.step import TickInputs
    from goworld_tpu.ops.aoi import GridSpec, init_verlet_cache

    # ~12 avg Chebyshev neighbors at radius 50 (north-star AOI density)
    extent = float(int((n * 10000 / 12) ** 0.5))
    grid_kw = _grid_kw_from_env(n, grid_overrides)
    if force_behavior is not None:
        # caller pins the workload regardless of BENCH_BEHAVIOR (the
        # multichip 1-chip reference must measure the SAME motion the
        # mesh headline ran, or per_chip_efficiency compares apples
        # to oranges)
        behavior, scenario = force_behavior, None
    elif scenario is None:
        # BENCH_BEHAVIOR may itself name a scenario (the headline then
        # measures that workload); an explicit scenario arg overrides
        # (the per-scenario block harness passes each registry spec)
        behavior, scenario = BEHAVIOR_RESOLVED
    else:
        behavior = "random_walk"
    cfg = WorldConfig(
        capacity=n,
        grid=GridSpec(
            radius=50.0, extent_x=extent, extent_z=extent, **grid_kw
        ),
        npc_speed=5.0,
        behavior=behavior,  # "mlp" = config 5 (fused NPC behavior kernel)
        scenario=scenario,
        enter_cap=65536, leave_cap=65536,
        sync_cap=65536, attr_sync_cap=4096, input_cap=4096,
        delta_rows_cap=65536,  # sized with enter/leave caps: 1M movers at
                               # 60 Hz churn tens of thousands of rows/tick
    )
    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    pos = jnp.stack(
        [
            jax.random.uniform(k1, (n,), maxval=extent),
            jnp.zeros(n),
            jax.random.uniform(k2, (n,), maxval=extent),
        ],
        axis=1,
    )
    st = SpaceState(
        pos=pos,
        yaw=jnp.zeros(n),
        vel=jnp.zeros((n, 3)),
        alive=jnp.ones(n, bool),
        npc_moving=jnp.ones(n, bool),
        has_client=jax.random.uniform(k3, (n,)) < client_frac,
        client_gate=jnp.zeros(n, jnp.int32),
        type_id=jnp.zeros(n, jnp.int32),
        gen=jnp.zeros(n, jnp.int32),
        hot_attrs=jnp.zeros((n, 8)),
        attr_dirty=jnp.zeros(n, jnp.uint32),
        nbr=jnp.full((n, cfg.grid.k), n, jnp.int32),
        nbr_cnt=jnp.zeros(n, jnp.int32),
        nbr_client_cnt=jnp.zeros(n, jnp.int32),
        nbr_mean_off=jnp.zeros((n, 3), jnp.float32),
        aoi_radius=(jnp.asarray(_sspec.assign_watch_radii(scenario, n))
                    if scenario is not None
                    else jnp.full(n, jnp.inf, jnp.float32)),
        dirty=jnp.zeros(n, bool),
        rng=jax.random.PRNGKey(1),
        tick=jnp.zeros((), jnp.int32),
        aoi_cache=(init_verlet_cache(cfg.grid, n)
                   if cfg.grid.skin > 0 and n < (1 << _AOI_ID_BITS)
                   else None),
        behavior_id=(jnp.asarray(_sspec.assign_behavior_ids(scenario, n))
                     if scenario is not None else None),
    )
    # steady stream of client position syncs (input-scatter path stays hot)
    inputs = TickInputs(
        pos_sync_idx=jax.random.randint(k4, (cfg.input_cap,), 0, n),
        pos_sync_vals=jnp.concatenate(
            [
                jax.random.uniform(k4, (cfg.input_cap, 3), maxval=extent),
                jnp.zeros((cfg.input_cap, 1)),
            ],
            axis=1,
        ),
        pos_sync_n=jnp.asarray(cfg.input_cap, jnp.int32),
    )
    return cfg, st, inputs


def autotune_sweep(ticks: int = 8) -> tuple[dict, dict]:
    """On-chip knob pick for the AOI sweep: time the sweep ALONE at the
    131K per-chip shard and return (grid overrides for the winner,
    per-config ms log). SELECTABLE candidates are those whose fidelity
    at the bench workload is identical-or-better than the default
    (which since r4 is ranges/sort — the r4 CPU winners): row_block
    variants (pure execution blocking — cannot change which neighbors
    are found), the dense-table sweep and its cellrow row-gather form
    (cellrow is bit-identical to table always; both are bit-identical
    to ranges while per-cell occupancy <= cell_cap, a 9x margin at
    bench density, and the default ranges impl only ever ADDS neighbors
    beyond that), the exact/f32 top-k lowerings (same total key
    order as sort), the counting-sort front half (stable — bit-
    identical to argsort everywhere), and skin=0 (strictly never-worse
    fidelity than the skin-on default: no candidate cache to
    overflow). cell_cap=8, the approx top-k, the pallas sort (CPU runs
    are interpret-mode emulation) and shift are DIAGNOSTICS only:
    cap 8 drops neighbors in overflowing cells at 1M density and approx
    trades ~2% recall — autotune must never make the headline measure
    LESS than the documented default does. Knobs the caller pinned via
    env are never overridden. Bounded cost: 8 selectable candidates x 2
    jitted scan lengths = 16 sweep-only compiles at 131K (plus the
    diagnostic pairs with BENCH_AUTOTUNE_DIAG=1); any failure falls
    back to defaults."""
    import jax
    from jax import lax

    from goworld_tpu.ops.aoi import (
        GridSpec,
        grid_neighbors_flags,
        grid_neighbors_verlet,
        init_verlet_cache,
    )

    n = int(os.environ.get("BENCH_AUTOTUNE_N", 131072))
    extent, pos, alive, flags = _ab_world(n, seed=2)
    candidates = AUTOTUNE_CANDIDATES
    if os.environ.get("BENCH_AUTOTUNE_DIAG", "0") != "1":
        # diagnostics cost 2 compiles each at 131K (most of a minute
        # apiece) and can never be selected — skip them unless asked
        candidates = [c for c in candidates if c[0]]
    env_pins = GRID_ENV
    log_d: dict = {}
    best_ms, best_ov = None, {}
    for selectable, ov in candidates:
        gk = _grid_kw_from_env(n, ov)
        spec = GridSpec(radius=50.0, extent_x=extent, extent_z=extent,
                        **gk)

        def mk(length, spec=spec):
            if spec.skin > 0 and n < (1 << _AOI_ID_BITS):
                # verlet specs carry the candidate cache through the
                # scan like the real tick does. The ~static positions
                # mean one rebuild (tick 0) then pure reuse, and the
                # 2x-minus-1x marginal cancels that rebuild — this
                # times the REUSE tick; the rebuild amortization shows
                # up in the headline run's real movement.
                cache0 = init_verlet_cache(spec, n)

                @jax.jit
                def run(p):
                    def body(carry, _):
                        c, cache = carry
                        nbr, cnt, fl, _st, cache, _rb, _sl = \
                            grid_neighbors_verlet(
                                spec, c, alive, cache, flag_bits=flags
                            )
                        c = c + (cnt[:, None] % 2).astype(c.dtype) * 1e-6
                        return (c, cache), cnt.sum() + fl.sum()
                    (pp, _), s = lax.scan(
                        body, (p, cache0), None, length=length
                    )
                    return s.sum() + pp.sum()
                return run

            @jax.jit
            def run(p):
                def body(c, _):
                    nbr, cnt, fl = grid_neighbors_flags(
                        spec, c, alive, flag_bits=flags
                    )
                    c = c + (cnt[:, None] % 2).astype(c.dtype) * 1e-6
                    return c, cnt.sum() + fl.sum()
                pp, s = lax.scan(body, p, None, length=length)
                return s.sum() + pp.sum()
            return run

        ms = _scan_marginal_ms(mk, pos, ticks)
        name = ",".join(f"{kk}={vv}" for kk, vv in ov.items()) or "default"
        log_d[name] = round(ms, 3)
        pinned = any(env_pins[kk] in os.environ for kk in ov)
        if selectable and not pinned \
                and (best_ms is None or ms < best_ms):
            best_ms, best_ov = ms, ov
    # only deviate from defaults for a clear (>5%) win
    if best_ov and log_d.get("default") \
            and best_ms > 0.95 * log_d["default"]:
        best_ov = {}
    log(f"autotune sweep@{n}: {log_d} -> {best_ov or 'default'}")
    return best_ov, log_d


def _ab_world(n: int, seed: int):
    """Synthetic sweep-A/B world shared by autotune_sweep and
    backhalf_ab: uniform XZ positions at the bench density formula,
    all alive, ~half flagged. One synthesis so the A/B harnesses can
    never drift apart in workload."""
    import jax
    import jax.numpy as jnp

    extent = float(int((n * 10000 / 12) ** 0.5))
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    pos = jnp.stack(
        [jax.random.uniform(k1, (n,), maxval=extent),
         jnp.zeros(n),
         jax.random.uniform(k2, (n,), maxval=extent)], axis=1)
    alive = jnp.ones(n, bool)
    flags = (jax.random.uniform(k3, (n,)) < 0.5).astype(jnp.int32)
    return extent, pos, alive, flags


def _scan_marginal_ms(mk, pos, ticks: int) -> float:
    """The 2x-minus-1x scan-marginal timing protocol shared by every
    sweep A/B (autotune_sweep, backhalf_ab): compile + warm T- and
    2T-tick scans, then ms/tick = (wall_2T - wall_T) / ticks so
    constant costs (dispatch, transfer, result caching — the r01
    mismeasurement mode) cancel. ``mk(length)`` must return a jitted
    fn of the position array whose scan body is perturbed by its own
    output (anti-LICM)."""
    import numpy as np

    r1, r2 = mk(ticks), mk(2 * ticks)
    float(np.asarray(r1(pos)))           # compile + warm
    float(np.asarray(r2(pos + 0.001)))
    t0 = time.perf_counter()
    float(np.asarray(r1(pos + 0.002)))
    e1 = time.perf_counter() - t0
    t0 = time.perf_counter()
    float(np.asarray(r2(pos + 0.003)))
    e2 = time.perf_counter() - t0
    return 1000.0 * max(e2 - e1, 1e-9) / ticks


def backhalf_ab(n: int, ticks: int = 4) -> dict:
    """Fused-vs-split back-half A/B: sweep-only scan-marginal ms/tick
    for ``sweep_impl="fused"`` against the resolved split default at
    the same shape, skin pinned 0 (the front/back-half A/B convention —
    under a skin the back half only runs on rebuild ticks and the
    marginal would time reuse noise). Stamped into the artifact: off-TPU
    the fused kernel executes in interpret mode, and recording that
    losing number next to ``"interpret": true`` is exactly what
    documents why fused stays non-default off-TPU; on a TPU backend
    GridSpec refuses the option and the record is that refusal
    (``"refused"``, with the compiler's reason)."""
    import jax
    from jax import lax

    from goworld_tpu.ops.aoi import GridSpec, grid_neighbors_flags
    from goworld_tpu.ops.pallas_compat import on_tpu

    extent, pos, alive, flags = _ab_world(n, seed=3)
    split_impl = _grid_kw_from_env(n, {"skin": 0.0})["sweep_impl"]
    if split_impl == "fused":        # env pinned fused: A/B vs ranges,
        split_impl = "ranges"        # the fused front half's sibling
    out: dict = {"n": n, "split_impl": split_impl,
                 "interpret": not on_tpu()}
    if on_tpu():
        from goworld_tpu.ops.pallas_compat import FUSED_SWEEP_REFUSAL

        out["refused"] = FUSED_SWEEP_REFUSAL
        return out
    for label, impl in (("split_ms", split_impl), ("fused_ms", "fused")):
        gk = _grid_kw_from_env(n, {"sweep_impl": impl, "skin": 0.0})
        spec = GridSpec(radius=50.0, extent_x=extent, extent_z=extent,
                        **gk)

        def mk(length, spec=spec):
            @jax.jit
            def run(p):
                def body(c, _):
                    _nbr, cnt, fl = grid_neighbors_flags(
                        spec, c, alive, flag_bits=flags
                    )
                    c = c + (cnt[:, None] % 2).astype(c.dtype) * 1e-6
                    return c, cnt.sum() + fl.sum()
                pp, s = lax.scan(body, p, None, length=length)
                return s.sum() + pp.sum()
            return run

        try:
            out[label] = round(_scan_marginal_ms(mk, pos, ticks), 3)
        except Exception as exc:
            out["error"] = f"{label}: {str(exc)[:200]}"
            break
    log(f"backhalf_ab@{n}: {out}")
    return out


def precision_ab(n: int, ticks: int = 4) -> dict:
    """Precision on/off A/B (ISSUE 12): full-sweep scan-marginal
    ms/tick with the quantized planes off vs on at the same shape and
    workload (skin pinned 0, the front/back-half A/B convention), plus
    the MODELED bytes/tick both ways at this shape AND the 1M
    north-star shape — so every artifact carries the measured marginal
    next to the roofline claim the plane exists to cash. Runs on every
    platform (the q16 path is plain XLA — no interpret-mode caveat);
    failures fold into {"error": ...} like backhalf_ab."""
    import jax
    from jax import lax

    from goworld_tpu.ops.aoi import GridSpec, grid_neighbors_flags
    from goworld_tpu.utils import devprof

    extent, pos, alive, flags = _ab_world(n, seed=7)
    out: dict = {"n": n}
    for label, prec in (("off_ms", "off"), ("q16_ms", "q16")):
        gk = _grid_kw_from_env(n, {"precision": prec, "skin": 0.0})
        spec = GridSpec(radius=50.0, extent_x=extent, extent_z=extent,
                        **gk)

        def mk(length, spec=spec):
            @jax.jit
            def run(p):
                def body(c, _):
                    _nbr, cnt, fl = grid_neighbors_flags(
                        spec, c, alive, flag_bits=flags
                    )
                    c = c + (cnt[:, None] % 2).astype(c.dtype) * 1e-6
                    return c, cnt.sum() + fl.sum()
                pp, s = lax.scan(body, p, None, length=length)
                return s.sum() + pp.sum()
            return run

        try:
            out[label] = round(_scan_marginal_ms(mk, pos, ticks), 3)
        except Exception as exc:
            out["error"] = f"{label}: {str(exc)[:200]}"
            break
        if prec == "q16":
            out["pos_scale_bits"] = spec.quant_bits
            out["quant_step"] = spec.quant_step
    # the modeled claim, stamped both ways at this shape and at 1M
    # (sum of the non-overlapping aoi/move/collect phase terms) —
    # once for the RESOLVED env config, and once at the ROOFLINE
    # headline config (fused + counting, the TPU production stack the
    # "~1.5 GB -> under 0.8 GB" claim is made at)
    try:
        def _tot(nn, gk):
            m = devprof.roofline_model_bytes(nn, gk)
            return round(sum(m[p] for p in ("aoi", "move", "collect"))
                         / 1e9, 3)

        for tag, nn in (("", n), ("_1m", 1 << 20)):
            for label, prec in (("model_off", "off"),
                                ("model_q16", "q16")):
                out[f"{label}_gb{tag}"] = _tot(
                    nn, _grid_kw_from_env(nn, {"precision": prec}))
        head = {"k": 32, "cell_cap": 12, "sort_impl": "counting",
                "sweep_impl": "fused", "skin": 0.0}
        for label, prec in (("model_off", "off"), ("model_q16", "q16")):
            out[f"{label}_gb_1m_headline"] = _tot(
                1 << 20, dict(head, precision=prec))
    except Exception as exc:
        out.setdefault("error", f"model: {str(exc)[:200]}")
    log(f"precision_ab@{n}: {out}")
    return out


# Per-scenario kernel A/B pool (the per-scenario kernel table ISSUE 7
# feeds autotune): one candidate per knob family the scenarios stress —
# the Verlet skin (teleport/hotspot thrash it, flock loves it), the
# sweep impl and the front-half sort. The canonical list now lives in
# goworld_tpu/autotune/policy.py (the governor decides between exactly
# these labels, so the table stamps and the policy share one home);
# re-exported here so tests and tooling keep pinning the bench name.
from goworld_tpu.autotune.policy import (  # noqa: E402
    DEFAULT_CANDIDATES as _GOV_CANDIDATES,
)

SCENARIO_KERNEL_CANDIDATES = [
    (label, dict(ov)) for label, ov in _GOV_CANDIDATES
]


def scenario_selection() -> list:
    """BENCH_SCENARIOS -> registry names ("all" | comma list | 0/none)."""
    sel = SCENARIOS_SEL.strip().lower()
    if sel in ("0", "none", ""):
        return []
    if sel == "all":
        return list(scenario_names())
    names = [s.strip() for s in SCENARIOS_SEL.split(",") if s.strip()]
    for nm in names:
        get_scenario(nm)  # unknown names fail here with the registry list
    return names


def _marginal_full_tick_ms(mk, variant, ticks: int, aot_first: bool):
    """The ONE 2x-minus-1x full-tick protocol shared by the scenario
    blocks and the multichip mesh headline (per_chip_efficiency
    divides one by the other, so they MUST measure identically):
    compile + warm T- and 2T-tick scans, time each min-of-2 with a
    DISTINCT anti-cache input per call, marginal per-tick = (2T - T)/T.
    ``mk(length)`` returns a jitted scan of one state arg; ``variant(i)``
    produces the distinct inputs. With ``aot_first`` the T-scan is
    AOT-compiled and returned so the caller's devprof audit costs zero
    extra compiles. Returns (per_tick_s, scale_2x, compiled_or_None)."""
    import numpy as np

    r1, r2 = mk(ticks), mk(2 * ticks)
    r1c = r1.lower(variant(0)).compile() if aot_first else r1
    float(np.asarray(r1c(variant(0))))       # compile + warm
    float(np.asarray(r2(variant(1))))
    es = []
    for i in range(2):
        t0 = time.perf_counter()
        float(np.asarray(r1c(variant(2 + 2 * i))))
        e1 = time.perf_counter() - t0
        t0 = time.perf_counter()
        float(np.asarray(r2(variant(3 + 2 * i))))
        e2 = time.perf_counter() - t0
        es.append((e1, e2))
    e1 = min(e[0] for e in es)
    e2 = min(e[1] for e in es)
    per_tick = max(e2 - e1, 1e-9) / ticks
    return per_tick, e2 / max(e1, 1e-9), (r1c if aot_first else None)


def _scenario_tick_ms(cfg, st, inputs, policy, ticks: int):
    """Scan-marginal full-tick timing for a scenario config — the same
    protocol as the headline (2x-minus-1x, min-of-2 repeats, distinct
    anti-cache inputs per timed call). Returns (per_tick_s, scale_2x)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from goworld_tpu.core.step import tick_body

    def mk(length):
        @jax.jit
        def run(state):
            def body(s, _):
                s2, out = tick_body(cfg, s, inputs, policy)
                chk = (out.enter_n + out.leave_n + out.sync_n).astype(
                    jnp.float32) + out.sync_vals.sum()
                return s2, chk
            st2, checks = lax.scan(body, state, None, length=length)
            return checks.sum() + st2.pos.sum()
        return run

    def variant(i):
        return st.replace(
            rng=jax.random.PRNGKey(500 + i),
            pos=st.pos + jnp.float32(0.001 * (i + 1)),
        )

    per_tick, scale, _ = _marginal_full_tick_ms(mk, variant, ticks,
                                                aot_first=False)
    return per_tick, scale


def _scenario_gauges(cfg, st, inputs, policy, ticks: int) -> dict:
    """One on-device scan aggregating the scenario-relevant gauges
    (overflow/rebuild/migration stats the headline block stamps)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import lax

    from goworld_tpu.core.step import tick_body

    @jax.jit
    def run(state):
        acc0 = (
            jnp.zeros((), jnp.int32),   # rebuilds
            jnp.zeros((), jnp.int32),   # over_k max
            jnp.zeros((), jnp.int32),   # over_cap max
            jnp.zeros((), jnp.int32),   # demand max
            jnp.full((), jnp.inf, jnp.float32),  # slack min
            jnp.zeros((), jnp.int32),   # enter events
            jnp.zeros((), jnp.int32),   # leave events
        )

        def body(carry, _):
            s, acc = carry
            s2, out = tick_body(cfg, s, inputs, policy)
            acc = (
                acc[0] + out.aoi_rebuilt,
                jnp.maximum(acc[1], out.aoi_over_k_rows),
                jnp.maximum(acc[2], out.aoi_over_cap_cells),
                jnp.maximum(acc[3], out.aoi_demand_max),
                jnp.minimum(acc[4], out.aoi_skin_slack),
                acc[5] + out.enter_n,
                acc[6] + out.leave_n,
            )
            return (s2, acc), 0
        (s2, acc), _ = lax.scan(body, (state, acc0), None,
                                length=ticks)
        return acc
    acc = [np.asarray(x) for x in run(st)]
    return {
        "aoi_rebuild_total": int(acc[0]),
        "aoi_over_k_rows_max": int(acc[1]),
        "aoi_over_cap_cells_max": int(acc[2]),
        "aoi_demand_max": int(acc[3]),
        "aoi_skin_slack_min": round(float(acc[4]), 3),
        "aoi_enter_events": int(acc[5]),
        "aoi_leave_events": int(acc[6]),
    }


def measure_scenarios(n: int, grid_overrides: dict | None = None) -> dict:
    """Per-scenario headline blocks (ISSUE 7): for every selected
    registry scenario, the full-tick scan-marginal throughput at the
    scenario shape with resolved kernel stamps + overflow/rebuild
    gauges, plus (BENCH_SCENARIO_AUTOTUNE=1, the default) the
    per-scenario kernel table over SCENARIO_KERNEL_CANDIDATES — the
    measured input the autotuner has been missing: kernel choice is now
    per WORKLOAD, not just per platform."""
    import jax

    ns = min(n, SCENARIO_N)
    ticks = SCENARIO_TICKS
    kernels = os.environ.get("BENCH_SCENARIO_AUTOTUNE", "1") == "1"
    out: dict = {"n": ns, "ticks": ticks, "scenarios": {}}
    for name in scenario_selection():
        spec = get_scenario(name)
        block: dict = {"behaviors": list(spec.behavior_names)}
        try:
            cfg, st, inputs = build(ns, CLIENT_FRAC, grid_overrides,
                                    scenario=spec)
            policy = None
            if spec.needs_policy:
                from goworld_tpu.models.npc_policy import init_policy

                policy = init_policy(jax.random.PRNGKey(5))
            per_tick, scale = _scenario_tick_ms(cfg, st, inputs, policy,
                                                ticks)
            block.update(
                value=round(ns / per_tick, 1),
                tick_ms=round(1000.0 * per_tick, 3),
                entities=ns,
                ticks_timed=ticks,
                scale_2x=round(scale, 2),
                # resolved kernel stamps, headline-style (skin stamped
                # EFFECTIVE past the packed-id bound like measure())
                sweep_impl=cfg.grid.sweep_impl,
                topk_impl=cfg.grid.topk_impl,
                sort_impl=cfg.grid.sort_impl,
                skin=(cfg.grid.skin if ns < (1 << _AOI_ID_BITS)
                      else 0.0),
            )
            if not (1.5 <= scale <= 3.0):
                block["timing_suspect"] = (
                    f"2x scan took {scale:.2f}x the 1x time"
                )
            block["gauges"] = _scenario_gauges(cfg, st, inputs, policy,
                                               max(ticks, 4))
            if kernels:
                table: dict = {}
                for label, ov in SCENARIO_KERNEL_CANDIDATES:
                    if label == "default":
                        table[label] = block["tick_ms"]
                        continue
                    try:
                        kcfg, kst, kin = build(
                            ns, CLIENT_FRAC,
                            {**(grid_overrides or {}), **ov},
                            scenario=spec)
                        kms, _ = _scenario_tick_ms(kcfg, kst, kin,
                                                   policy, ticks)
                        table[label] = round(1000.0 * kms, 3)
                    except Exception as exc:
                        table[label] = f"error: {str(exc)[:120]}"
                block["kernels_ms"] = table
                numeric = {k: v for k, v in table.items()
                           if isinstance(v, (int, float))}
                if numeric:
                    block["best_kernel"] = min(numeric, key=numeric.get)
        except Exception as exc:  # one broken scenario must not zero
            block["error"] = str(exc)[:200]  # out the whole stage
        out["scenarios"][name] = block
        log(f"scenario {name}@{ns}: "
            f"{block.get('tick_ms', block.get('error'))} ms/tick")
    return out


# --governor knobs: the phase-switching schedule (registry scenario
# names; single-behavior, uniform-radius specs only — the evolving
# population carries across phases), the signature-window length in
# ticks and the windows per phase
GOVERNOR_PHASES = os.environ.get("BENCH_GOVERNOR_PHASES",
                                 "flock,teleport,hotspot")
GOVERNOR_WINDOW = int(os.environ.get("BENCH_GOVERNOR_WINDOW", 8))
GOVERNOR_WINDOWS = int(os.environ.get("BENCH_GOVERNOR_WINDOWS", 6))


def measure_governor(n: int, grid_overrides: dict | None = None) -> dict:
    """The governor acceptance run (ISSUE 13): ONE evolving population
    driven through a phase-switching workload schedule
    (BENCH_GOVERNOR_PHASES, default flock -> teleport -> hotspot) while
    the autotune policy hot-swaps the kernel config from the drained
    telemetry-signature windows — exactly the production loop, minus
    the network.

    Every (phase, candidate) window scan is AOT-compiled UP FRONT
    (prewarm, wall time stamped separately), so the measured schedule
    never pays a compile: the run executes pre-compiled executables
    under ``jax.transfer_guard("disallow")`` and asserts the telemetry
    TRACE_COUNTS stay frozen. The mapping table is derived from warm
    probe windows on THIS machine by default (``probe_ms``;
    BENCH_GOVERNOR_TABLE=artifacts pins the checked-in seeding — see
    the probe-pass comment), the static candidate pins run the same
    schedule INTERLEAVED with the governed run window-by-window (so
    machine drift lands on every config equally), and the block stamps
    the governor's end-to-end throughput against the best and worst
    static config plus each phase's chosen config + swap latency in
    ticks."""
    import jax
    import jax.numpy as jnp
    import numpy as np  # noqa: F401 (drain consumers)
    from jax import lax

    from goworld_tpu.autotune.policy import (
        SCENARIO_CLASS_MAP,
        GovernorPolicy,
        seed_table,
    )
    from goworld_tpu.autotune.warmset import carry_state
    from goworld_tpu.core.step import tick_body
    from goworld_tpu.ops import telemetry as telem

    ns = min(n, SCENARIO_N)
    W, P = GOVERNOR_WINDOW, GOVERNOR_WINDOWS
    phases = [s.strip() for s in GOVERNOR_PHASES.split(",") if s.strip()]
    specs = {}
    for nm in phases:
        spec = get_scenario(nm)  # KeyError lists the registry
        if len(spec.behavior_names) != 1 or not spec.uniform_radius:
            raise ValueError(
                f"--governor phase {nm!r} must be a single-behavior, "
                "uniform-radius scenario (the population's behavior "
                "lanes carry across the phase switch)"
            )
        specs[nm] = spec
    table = seed_table()
    labels = [lbl for lbl, _ in SCENARIO_KERNEL_CANDIDATES]
    out: dict = {
        "schedule": phases, "window_ticks": W,
        "windows_per_phase": P, "n": ns, "table": dict(table),
    }

    # ---- prewarm: one AOT window-scan executable per (phase, label) --
    t_warm = time.perf_counter()
    cfgs: dict = {}
    exes: dict = {}
    acc0s: dict = {}
    st0 = None
    mlp_policy = None
    if any(specs[nm].needs_policy for nm in phases):
        from goworld_tpu.models.npc_policy import init_policy

        mlp_policy = init_policy(jax.random.PRNGKey(5))

    def mk_window(cfg):
        skin_flag = cfg.grid.skin > 0 and ns < (1 << _AOI_ID_BITS)
        half_skin = cfg.grid.skin / 2.0 if skin_flag else 0.0

        @jax.jit
        def run(state, acc):
            def body(carry, _):
                s, a = carry
                s2, o = tick_body(cfg, s, TB_INPUTS, mlp_policy)
                a2 = telem.telemetry_update(a, o, 0.0, 0.0, half_skin)
                return (s2, a2), 0

            (s2, a2), _ = lax.scan(body, (state, acc), None, length=W)
            return s2, a2

        return run, skin_flag

    TB_INPUTS = None
    probe_states: dict = {}
    for nm in phases:
        for lbl, ov in SCENARIO_KERNEL_CANDIDATES:
            cfg, st, inp = build(
                ns, CLIENT_FRAC, {**(grid_overrides or {}), **ov},
                scenario=specs[nm])
            cfgs[(nm, lbl)] = cfg
            if TB_INPUTS is None:
                # the headline's steady random client-sync stream is a
                # workload of its own (it re-randomizes positions and
                # would erase every phase's character at small n) —
                # the governor schedule runs the SCENARIO's motion
                # with the input-scatter path present but empty
                TB_INPUTS = inp.replace(
                    pos_sync_n=jnp.zeros((), jnp.int32))
            if st0 is None and lbl == "default":
                st0 = st  # the ONE evolving population (phase-0 shape)
            probe_states[(nm, lbl)] = st
            run, skin_flag = mk_window(cfg)
            acc0 = telem.telemetry_init(skin_flag)
            # lower at the CONCRETE build-time avals (the live state
            # keeps them: scan carries pin input==output avals, and
            # the Verlet carry reallocates through the same
            # init_verlet_cache) — AOT compile, jit cache untouched
            exes[(nm, lbl)] = run.lower(st, acc0).compile()
            acc0s[(nm, lbl)] = (acc0, skin_flag,
                                cfg.grid.skin / 2.0 if skin_flag
                                else 0.0)
    out["prewarm_s"] = round(time.perf_counter() - t_warm, 1)
    out["warm_executables"] = len(exes)

    # per-phase entry layouts — the phase change is the production
    # analog of a flash crowd / event teleport, which is exactly the
    # shift the governor exists to chase. Attractor-driven scenarios
    # (hotspot/shrink) drop into their CONVERGED late-game layout
    # (scenario_layout's fast-forward, the A/B tools' adversarial-
    # density trick — a 48-tick phase at bench extent contracts ~4
    # units of a 2000+-unit world otherwise, so the density signature
    # never forms); diffuse scenarios redraw a fresh uniform cloud
    # (their own converged layout under the fast-forward dt is a blob
    # too — cohesion compounds — which would misclassify every phase
    # as density pressure). Computed at prewarm, applied OUTSIDE the
    # timed windows.
    from goworld_tpu.scenarios.runner import scenario_layout

    extent = cfgs[(phases[0], "default")].grid.extent_x
    layouts = {}
    for pi, nm in enumerate(phases):
        if {"hotspot", "shrink"} & set(specs[nm].behavior_names):
            layouts[nm] = jnp.asarray(
                scenario_layout(specs[nm], ns, extent, ticks=64,
                                seed=7))
        else:
            k1, k2 = jax.random.split(jax.random.PRNGKey(40 + pi))
            layouts[nm] = jnp.stack([
                jax.random.uniform(k1, (ns,), maxval=extent),
                jnp.zeros(ns),
                jax.random.uniform(k2, (ns,), maxval=extent),
            ], axis=1)

    # ---- probe pass: the mapping table from THIS machine's truth ----
    # The checked-in best_kernel stamps are measured on another
    # machine (and under the headline's client-sync stream); chasing a
    # stale table caps the governor at that table's quality — which in
    # production the regret guard corrects from measured latency. The
    # bench's acceptance is about the MACHINERY (convergence latency,
    # warm-swap cost, compile-freedom), so by default the schedule's
    # table is derived from one warm min-of-2 probe window per
    # (phase, candidate) on this machine (stamped as probe_ms;
    # BENCH_GOVERNOR_TABLE=artifacts pins the checked-in seeding
    # instead — the production default).
    table_source = os.environ.get("BENCH_GOVERNOR_TABLE", "measured")
    probe_ms: dict = {}
    for nm in phases:
        for lbl in labels:
            stp = probe_states[(nm, lbl)].replace(
                pos=layouts[nm],
                vel=jnp.zeros_like(probe_states[(nm, lbl)].vel))
            acc0, _sf, _hs = acc0s[(nm, lbl)]
            best = float("inf")
            for _rep in range(2):
                t0 = time.perf_counter()
                s2, _a = exes[(nm, lbl)](stp, acc0)
                jax.block_until_ready(s2.pos)
                best = min(best, time.perf_counter() - t0)
            probe_ms[f"{nm}/{lbl}"] = round(best * 1e3, 1)
    probe_states.clear()  # free 3x4 full populations
    if table_source == "measured":
        for nm in phases:
            cls = SCENARIO_CLASS_MAP.get(nm, "default")
            table[cls] = min(
                labels, key=lambda l: probe_ms[f"{nm}/{l}"])
    out["table"] = dict(table)
    out["table_source"] = table_source
    out["probe_ms"] = probe_ms

    trace_before = dict(telem.TRACE_COUNTS)

    # The governed run and every static pin drive the SAME schedule
    # over their own copies of the population, INTERLEAVED window by
    # window: all five configs time window wdx back-to-back before any
    # of them runs window wdx+1. Sequential whole-schedule passes were
    # measurably biased by machine drift between passes (a noisy CPU
    # box swings 2x across minutes); interleaving lands the noise on
    # every config equally, which is what a throughput COMPARISON
    # needs. Positions evolve identically across configs (the kernel
    # config never changes motion), so the runs stay apples-to-apples.
    base_cfg0 = cfgs[(phases[0], "default")]
    policy_obj = GovernorPolicy(table=table, up_windows=2,
                                down_windows=2, cooldown_windows=2)
    runners = ["governor"] + labels
    states = {"governor": st0}
    cur = {"governor": "default"}
    for lbl in labels:
        states[lbl] = (st0 if lbl == "default" else carry_state(
            st0, base_cfg0, cfgs[(phases[0], lbl)], stacked=False))
        cur[lbl] = lbl
    wall = dict.fromkeys(runners, 0.0)
    gov_recs: list = []
    for nm in phases:
        # phase entry: every population snaps to the scenario's
        # converged/uniform layout (unmeasured — the workload shock,
        # not the serving cost). A position jump this large trips the
        # Verlet displacement rebuild by construction, so a skin-on
        # config stays exact without special-casing.
        for k in runners:
            states[k] = states[k].replace(
                pos=layouts[nm],
                vel=jnp.zeros_like(states[k].vel),
            )
        expected = table.get(SCENARIO_CLASS_MAP.get(nm, "default"),
                             "default")
        rec: dict = {"scenario": nm, "expected": expected,
                     "swaps": [], "window_ms": []}
        converged = None
        for wdx in range(P):
            for k in runners:
                lbl = cur[k]
                exe = exes[(nm, lbl)]
                acc0, skin_flag, half_skin = acc0s[(nm, lbl)]
                t0 = time.perf_counter()
                with jax.transfer_guard("disallow"):
                    state2, acc = exe(states[k], acc0)
                    jax.block_until_ready(state2.pos)
                dt = time.perf_counter() - t0
                wall[k] += dt
                states[k] = state2
                if k != "governor":
                    continue
                rec["window_ms"].append(round(dt * 1e3, 2))
                lanes = telem.telemetry_drain(
                    jax.device_get(acc), skin_flag, half_skin)
                sig = telem.workload_signature(lanes)
                want = policy_obj.observe(sig)
                if want is not None and want != lbl:
                    # the swap itself: the target executable is warm
                    # by construction, only the Verlet-cache carry
                    # happens here (tick-free, between windows — the
                    # production commit point)
                    states[k] = carry_state(
                        states[k], cfgs[(nm, lbl)], cfgs[(nm, want)],
                        stacked=False)
                    rec["swaps"].append(
                        {"window": wdx, "from": lbl, "to": want,
                         "sig": sig.get("sig")})
                    cur[k] = want
                if converged is None and cur[k] == expected:
                    converged = wdx
        rec["chosen"] = cur["governor"]
        rec["converged_window"] = converged
        rec["swap_latency_ticks"] = (
            None if converged is None else (converged + 1) * W
        )
        gov_recs.append(rec)
    gov_s = wall["governor"]
    statics = {lbl: round(wall[lbl], 3) for lbl in labels}
    trace_after = dict(telem.TRACE_COUNTS)

    ticks_total = len(phases) * P * W
    out["phases"] = gov_recs
    out["ticks"] = ticks_total
    out["wall_s"] = round(gov_s, 3)
    out["throughput"] = round(ns * ticks_total / max(gov_s, 1e-9), 1)
    out["static_wall_s"] = statics
    numeric = {k: v for k, v in statics.items()
               if isinstance(v, (int, float))}
    if numeric:
        best = min(numeric, key=numeric.get)
        worst = max(numeric, key=numeric.get)
        out["best_static"] = {
            "label": best,
            "throughput": round(ns * ticks_total / numeric[best], 1),
        }
        out["worst_static"] = {
            "label": worst,
            "throughput": round(ns * ticks_total / numeric[worst], 1),
        }
        out["vs_best_static"] = round(
            out["throughput"] / out["best_static"]["throughput"], 3)
    out["swaps_total"] = sum(len(r["swaps"]) for r in gov_recs)
    out["converged_all"] = all(
        r["converged_window"] is not None and r["converged_window"] <= 3
        for r in gov_recs
    )
    # the compile-free contract: AOT executables under a transfer
    # guard, telemetry trace counters frozen across the measured run
    out["trace_counts_stable"] = trace_before == trace_after
    out["transfer_guard"] = "disallow"
    log(f"governor@{ns}: {out['throughput']} et/s over {ticks_total} "
        f"ticks, {out['swaps_total']} swaps, vs_best_static="
        f"{out.get('vs_best_static')}")
    return out


def measure_sync_age() -> dict:
    """End-to-end sync-age block (ISSUE 15): the paper's REAL SLO —
    device-tick epoch to gate delivery — measured through a live
    game -> dispatcher -> gate loopback over real localhost sockets
    (the production wire, codec, stamp and flush paths; nothing
    simulated). Per tick the game fans out BENCH_SYNC_AGE_RECORDS
    stamped records (default 32768 — the sync volume scale of the
    131K bench shape at the default client fraction, shape stamped
    honestly) to BENCH_SYNC_AGE_CLIENTS connected bot clients; the
    gate ages every delivered record (utils/syncage.py) and this
    block reduces the histograms to per-hop p50/p90/p99 plus ONE e2e
    verdict vs BENCH_SLO_MS.

    Also stamps the measured overhead of the always-on stamp: the
    per-tick work the plane adds (wall reads + 45 B trailer pack on
    the game, unpack + 6 weighted histogram inserts on the gate) is
    micro-timed and reported as a fraction of the 1/60 s tick budget
    — the acceptance criterion is < 1%."""
    import threading as _threading

    import numpy as np

    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.entity.entity import Entity
    from goworld_tpu.entity.manager import World
    from goworld_tpu.net.botclient import BotClient
    from goworld_tpu.net.game import GameServer
    from goworld_tpu.net.standalone import ClusterHarness
    from goworld_tpu.ops.aoi import GridSpec
    from goworld_tpu.utils import syncage

    records = int(os.environ.get("BENCH_SYNC_AGE_RECORDS", 32768))
    n_clients = int(os.environ.get("BENCH_SYNC_AGE_CLIENTS", 16))
    ticks = int(os.environ.get("BENCH_SYNC_AGE_TICKS", 64))
    target_ms = float(os.environ.get("BENCH_SLO_MS", 16.0))
    tick_hz = float(os.environ.get("BENCH_SYNC_AGE_HZ", 50.0))
    use_delta = os.environ.get("BENCH_SYNC_AGE_DELTA") == "1"

    class _BenchAccount(Entity):
        ATTRS: dict = {}

    harness = ClusterHarness(n_dispatchers=1, n_gates=1,
                             desired_games=1)
    harness.start()
    gs = None
    stop = _threading.Event()
    loop_thread = None
    try:
        cfg = WorldConfig(
            capacity=256,
            grid=GridSpec(radius=50.0, extent_x=200.0,
                          extent_z=200.0),
            input_cap=256,
        )
        world = World(cfg, n_spaces=1)
        world.register_entity("Account", _BenchAccount)
        world.create_nil_space()
        gs = GameServer(1, world, list(harness.dispatcher_addrs),
                        boot_entity="Account",
                        gc_freeze_on_boot=False,
                        tick_interval=1.0 / tick_hz,
                        sync_delta=use_delta)
        gs.start_network()
        # injection armed by the main thread once the bots are in;
        # the fan-out is staged ON the logic thread (the production
        # threading model — _sync_sink is a logic-thread edge)
        inject: dict = {"batch": None, "ticks_left": 0}

        def run_loop() -> None:
            while not stop.is_set():
                gs.pump()
                if inject["ticks_left"] > 0 and \
                        inject["batch"] is not None:
                    gs._sync_sink(1, *inject["batch"])
                    inject["ticks_left"] -= 1
                gs.tick()
                time.sleep(1.0 / tick_hz)

        loop_thread = _threading.Thread(target=run_loop, daemon=True)
        loop_thread.start()
        if not gs.ready_event.wait(30):
            return {"error": "loopback deployment never became ready"}

        host, port = harness.gate_addrs[0]
        bots = [BotClient(host, port, bot_id=i)
                for i in range(n_clients)]

        async def drain(bot) -> None:
            await bot.connect()
            try:
                await bot._recv_loop()
            except Exception:
                pass

        for b in bots:
            harness.submit(drain(b))
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            live = [e for e in world.entities.values()
                    if e.client is not None]
            if len(live) >= n_clients:
                break
            time.sleep(0.05)
        live = [e for e in world.entities.values()
                if e.client is not None]
        if not live:
            return {"error": "no bot client reached the game"}
        # synthetic fan-out at the bench record volume through the
        # REAL flush: cids resolve to the live bot connections, so
        # every record travels game -> dispatcher -> gate -> socket
        per_client = max(1, records // len(live))
        cids = np.repeat(
            np.asarray([e.client.client_id for e in live], "S16"),
            per_client)
        eids = np.asarray(
            [(b"E%015d" % (i % 1000)) for i in range(len(cids))],
            "S16")
        rng = np.random.default_rng(0)
        vals = rng.random((len(cids), 4), dtype=np.float32)
        tracker = harness.gates[0].syncage
        base_batches = int(tracker.snapshot()["batches"])
        inject["batch"] = (cids, eids, vals)
        inject["ticks_left"] = ticks
        deadline = time.monotonic() + max(30.0, 4.0 * ticks / tick_hz)
        while time.monotonic() < deadline and (
                inject["ticks_left"] > 0
                or int(tracker.snapshot()["batches"])
                < base_batches + ticks // 2):
            time.sleep(0.1)
        snap = tracker.snapshot()
        if not snap["e2e"].get("samples"):
            # every degraded path records an honest error (the schema
            # contract): a zero-delivery run must not stamp a block
            # with no percentile shape
            return {"error": "no stamped deliveries reached the gate "
                             f"({len(live)} clients, {ticks} ticks)"}
        out: dict = {
            "target_ms": target_ms,
            "records_per_tick": int(len(cids)),
            "clients": len(live),
            "ticks": ticks,
            "tick_hz": tick_hz,
            "sync_delta": use_delta,
            "e2e": snap["e2e"],
            "hops": {h: snap["hops"][h] for h in syncage.HOPS},
            "clock_warp_total": snap["clock_warp_total"],
        }
        p99 = snap["e2e"].get("p99_ms")
        out["pass"] = bool(isinstance(p99, (int, float))
                           and p99 <= target_ms)
        # measured overhead of the always-on stamp: everything the
        # plane adds per tick (game-side wall reads + pack, the
        # dispatcher patch, gate-side unpack + 6 weighted inserts),
        # micro-timed over the REAL tracker at this batch size
        stamp = syncage.SyncAgeStamp(1, syncage.now_us(),
                                     syncage.now_us())
        reps = 2000
        t0 = time.perf_counter()
        for _ in range(reps):
            stamp.t_stage_us = syncage.now_us()
            stamp.t_send_us = syncage.now_us()
            wire = stamp.pack()
            back = syncage.SyncAgeStamp.unpack(wire)
            back.t_disp_us = syncage.now_us()
            tracker.observe(back, syncage.now_us(), len(cids))
        per_tick_us = (time.perf_counter() - t0) / reps * 1e6
        budget_us = 1e6 / 60.0  # the paper's 60 Hz frame
        out["stamp_overhead_us_per_tick"] = round(per_tick_us, 2)
        out["stamp_overhead_pct_of_budget"] = round(
            100.0 * per_tick_us / budget_us, 4)
        log(f"sync_age: e2e {snap['e2e']} over {len(cids)} rec/tick "
            f"x {ticks} ticks, stamp overhead "
            f"{out['stamp_overhead_pct_of_budget']}% of 16.7 ms")
        return out
    finally:
        stop.set()
        if loop_thread is not None:
            loop_thread.join(timeout=5)
        if gs is not None:
            gs.stop()
        harness.stop()


def measure_residency(n: int) -> dict:
    """Serve-loop residency block (ISSUE 16): the three taxes the
    scan-marginal headline never sees — the host bubble between device
    dispatches, allocator churn plus the donation-readiness buffer
    census on the SpaceState carry, and the scan-marginal -> serve-loop
    gap as ONE ratio — measured on a REAL instrumented World ticking a
    paced serve-like loop (utils/residency.py marks riding
    World.tick_dispatch and tick_land; zero added device syncs).

    The serve_gap reference is measured HERE: a device-only
    back-to-back ``_step`` marginal on the same compiled executable and
    state shape the serve loop runs (2x-minus-1x, the shared protocol),
    pinned via ``set_scan_marginal_ms`` so the stamped ratio compares
    like against like and ``serve_gap_ref`` records that it was. Also
    stamps the measured overhead of the always-on marks as a fraction
    of the 1/60 s budget — the acceptance criterion is < 1%."""
    import jax
    import numpy as np

    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.entity.entity import Entity
    from goworld_tpu.entity.manager import World
    from goworld_tpu.entity.space import Space
    from goworld_tpu.ops.aoi import GridSpec
    from goworld_tpu.utils import residency

    ents = min(int(n),
               int(os.environ.get("BENCH_RESIDENCY_ENTITIES", 192)))
    ticks = int(os.environ.get("BENCH_RESIDENCY_TICKS", 96))
    tick_hz = float(os.environ.get("BENCH_RESIDENCY_HZ", 60.0))
    sample_every = max(1, min(residency.DEFAULT_SAMPLE_EVERY,
                              ticks // 6))

    class _BenchMob(Entity):
        ATTRS = {"hp": "allclients hot:0"}

    capacity = 64
    while capacity < 2 * ents:
        capacity *= 2
    cfg = WorldConfig(
        capacity=capacity,
        grid=GridSpec(radius=20.0, extent_x=200.0, extent_z=200.0),
        input_cap=256,
    )
    world = World(cfg, n_spaces=1, game_id=90,
                  residency=True, residency_sample_every=sample_every)
    rt = world.residency
    try:
        world.register_entity("Mob", _BenchMob)
        world.register_space("Arena", Space)
        world.create_nil_space()
        sp = world.create_space("Arena")
        rng = np.random.default_rng(7)
        for _ in range(ents):
            x, z = rng.uniform(10.0, 190.0, 2)
            sp.create_entity("Mob", pos=(float(x), 0.0, float(z)))
        # warmup outside the plane: the first ticks pay jit compile and
        # the spawn flush — seconds that must not pollute the gap stats
        world.residency = None
        for _ in range(3):
            world.tick()
        world.residency = rt

        # device-only serve_gap reference: back-to-back _step on the
        # SAME executable and state shape, 2x-minus-1x so the constant
        # dispatch/fetch overhead cancels (the shared protocol)
        inputs = world._flush_staging()

        def dev_run(reps: int) -> float:
            # COPY the carry first: the resident world's _step donates
            # its state argument, so running the marginal directly on
            # world.state would delete the serve loop's live carry
            s = jax.tree.map(jax.numpy.copy, world.state)
            jax.block_until_ready(s)
            t0 = time.perf_counter()
            for _ in range(reps):
                s, _o = world._step(s, inputs, world.policy)
            jax.block_until_ready(s)
            return time.perf_counter() - t0

        reps = max(8, min(64, ticks // 2))
        dev_run(4)
        t_1x = dev_run(reps)
        t_2x = dev_run(2 * reps)
        marginal_ms = max(t_2x - t_1x, 1e-6) / reps * 1e3
        rt.set_scan_marginal_ms(marginal_ms)

        # the paced serve-like loop the plane exists to measure: tick,
        # then sleep off the remaining frame budget, DECLARED as idle
        # (measured sleep, not requested — oversleep must not hide in
        # the declared lane and undersleep must not inflate it)
        interval = 1.0 / tick_hz
        for _ in range(ticks):
            t0 = time.perf_counter()
            world.tick()
            delay = interval - (time.perf_counter() - t0)
            if delay > 0:
                t_s = time.perf_counter()
                time.sleep(delay)
                rt.add_idle(time.perf_counter() - t_s)

        snap = rt.snapshot()
        if not snap["tick"].get("samples"):
            return {"error": "no inter-dispatch gaps recorded "
                             f"({ticks} ticks requested)"}
        out: dict = {
            "entities": ents,
            "capacity": capacity,
            "ticks": snap["ticks"],
            "tick_hz": tick_hz,
            "sample_every": sample_every,
            "scan_marginal_ms": round(marginal_ms, 3),
            "tick": snap["tick"],
            "bubble": snap["bubble"],
            "bubble_budget_ms": snap["bubble_budget_ms"],
            "phases": snap["phases"],
            "gc": snap["gc"],
            "alloc": snap["alloc"],
            "census": snap["census"],
        }
        for k in ("serve_ms_per_tick", "serve_gap", "serve_gap_ref",
                  "serve_gap_ref_ms", "pass"):
            if k in snap:
                out[k] = snap[k]
        # measured overhead of the always-on marks: everything the
        # plane adds per tick (the 5 tick marks + the serve loop's
        # declare calls — perf_counter reads + histogram inserts),
        # micro-timed over a real tracker
        mt = residency.ResidencyTracker("bench_overhead",
                                        sample_every=1 << 30)
        reps_o = 2000
        t0 = time.perf_counter()
        for _ in range(reps_o):
            mt.tick_begin()
            mt.mark_dispatch()
            mt.mark_fetch()
            mt.mark_visible()
            mt.add_host(1e-4)
            mt.add_idle(1e-4)
            mt.observe_device_step(1e-3)
            mt.mark_decode_done()
        per_tick_us = (time.perf_counter() - t0) / reps_o * 1e6
        mt.close()
        budget_us = 1e6 / 60.0  # the paper's 60 Hz frame
        out["mark_overhead_us_per_tick"] = round(per_tick_us, 2)
        out["mark_overhead_pct_of_budget"] = round(
            100.0 * per_tick_us / budget_us, 4)
        cen = snap["census"]
        log(f"residency: bubble p99 {snap['bubble'].get('p99_ms')} ms "
            f"serve_gap {out.get('serve_gap')} "
            f"(ref {out.get('serve_gap_ref')}), census "
            f"{len(cen['realloc'])}/{cen['lanes']} lanes realloc, "
            f"mark overhead {out['mark_overhead_pct_of_budget']}% "
            f"of 16.7 ms")
        return out
    finally:
        residency.unregister("game90")
        if rt is not None:
            rt.close()


def measure_audit(n: int) -> dict:
    """Correctness-audit block (ISSUE 17): the entity-ownership
    ledger + sampled AOI oracle measured on a REAL World ticking a
    churning workload (creates + destroys every few ticks so the
    ledger actually works), with the plane's cost measured as the
    marginal duration of sampled over unsampled ticks interleaved in
    ONE run, amortized at the production sampling cadence and stamped
    as a fraction of the 60 Hz frame budget (the acceptance criterion
    is < 1%).

    The zero-violation gate: a clean soak must record NO violations
    and a passing conservation verdict; any recorded kind fails the
    block (and bench_trend gates it unconditionally)."""
    import numpy as np

    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.entity.entity import Entity
    from goworld_tpu.entity.manager import World
    from goworld_tpu.entity.space import Space
    from goworld_tpu.ops.aoi import GridSpec
    from goworld_tpu.utils import audit as audit_mod

    ents = min(int(n),
               int(os.environ.get("BENCH_AUDIT_ENTITIES", 192)))
    ticks = int(os.environ.get("BENCH_AUDIT_TICKS", 96))
    # >= 2 so every run has BOTH sampled and unsampled ticks (the A/B
    # below compares the two buckets within one run)
    sample_every = max(2, min(8, ticks // 12))

    class _AuditMob(Entity):
        ATTRS = {"hp": "allclients hot:0"}

    capacity = 64
    while capacity < 2 * ents:
        capacity *= 2

    cfg = WorldConfig(
        capacity=capacity,
        grid=GridSpec(radius=20.0, extent_x=200.0, extent_z=200.0),
        input_cap=256,
    )
    world = World(cfg, n_spaces=1, game_id=91,
                  audit=True,
                  audit_sample_every=sample_every,
                  audit_cohort=64)
    world.register_entity("Mob", _AuditMob)
    world.register_space("Arena", Space)
    world.create_nil_space()
    sp = world.create_space("Arena")
    rng = np.random.default_rng(17)
    pool = []
    for _ in range(ents):
        x, z = rng.uniform(10.0, 190.0, 2)
        pool.append(sp.create_entity(
            "Mob", pos=(float(x), 0.0, float(z))))
    ap = world.audit
    if ap is None:
        return {"error": "audit plane disabled itself at build"}

    try:
        # warmup outside the clock: jit compile + the spawn flush
        for _ in range(3):
            world.tick()
        # The A/B rides ONE run with the plane attached throughout:
        # sampled and unsampled ticks INTERLEAVE, so clock drift, GC
        # pressure, and allocator warm-up hit both buckets equally —
        # separate on/off worlds (and even detach/reattach windows on
        # a shared world) proved unmeasurable, with between-arm drift
        # 10x the plane's real cost. Churn is deferred onto unsampled
        # ticks: a spawn/despawn flush costs ~5x a plain tick with
        # the plane OFF too (it dispatches the staging scatters), so
        # letting it land on a sampled tick would bill workload cost
        # to the plane.
        d_sampled, d_base, d_churn = [], [], []
        churn_due = 0
        for _ in range(ticks):
            want = ap.want_sample(world.tick_count)
            churn_due += 1
            churned = False
            if churn_due >= 4 and not want and pool:
                # churn so the ledger has work: destroy + recreate
                # one entity (conservation must still balance)
                world.destroy_entity(pool.pop(0))
                x, z = rng.uniform(10.0, 190.0, 2)
                pool.append(sp.create_entity(
                    "Mob", pos=(float(x), 0.0, float(z))))
                churn_due = 0
                churned = True
            t1 = time.perf_counter()
            world.tick()
            d = time.perf_counter() - t1
            if churned:
                d_churn.append(d)
            elif want:
                d_sampled.append(d)
            else:
                d_base.append(d)
        ap.drain()
        snap = ap.snapshot(tick=world.tick_count)
        conservation = audit_mod.conservation_verdict([snap])
        if not d_sampled or not d_base:
            return {"error": "degenerate tick buckets "
                             f"(sampled={len(d_sampled)}, "
                             f"base={len(d_base)})"}
        import statistics

        sampled_ms = statistics.median(d_sampled) * 1e3
        base_ms = statistics.median(d_base) * 1e3
        # marginal cost of ONE sample, amortized at the production
        # cadence (the config default, not the bench's compressed
        # sample_every — the bench samples often only so the oracle
        # is exercised enough times in a short run)
        sampled_extra_ms = max(0.0, sampled_ms - base_ms)
        import dataclasses as _dc

        from goworld_tpu import config as server_config
        prod_every = next(
            f.default for f in _dc.fields(server_config.GameConfig)
            if f.name == "audit_sample_every")
        budget_ms = 1e3 / 60.0
        overhead_ms = sampled_extra_ms / prod_every
        overhead_pct = round(100.0 * overhead_ms / budget_ms, 4)
        oracle = snap["oracle"]
        viol = snap["violations_total"]
        out = {
            "entities": ents,
            "capacity": capacity,
            "ticks": ticks,
            "sample_every": sample_every,
            "prod_sample_every": int(prod_every),
            "ledger": {
                "entities": snap["entities"],
                "crc": snap["crc"],
                "created": snap["created"],
                "destroyed": snap["destroyed"],
                "migrated_out": snap["migrated_out"],
                "migrated_in": snap["migrated_in"],
            },
            "oracle": oracle,
            "violations_total": viol,
            "conservation": {
                k: conservation[k]
                for k in ("ok", "live", "in_flight", "created",
                          "destroyed", "problems")
                if k in conservation
            },
            "base_tick_ms": round(base_ms, 3),
            "sampled_tick_ms": round(sampled_ms, 3),
            "sampled_extra_ms": round(sampled_extra_ms, 3),
            "overhead_ms_per_tick": round(overhead_ms, 4),
            "overhead_pct_of_budget": overhead_pct,
            # the acceptance gate: violation-free, conserving, and
            # cheaper than 1% of the 16.7 ms frame at the production
            # sampling cadence
            "pass": (not any(viol.values())
                     and bool(conservation.get("ok"))
                     and overhead_pct < 1.0),
        }
        log(f"audit: {oracle['samples']} oracle samples "
            f"({oracle['entities_checked']} entities, "
            f"{oracle['mismatches']} mismatches), "
            f"{sum(viol.values())} violations, "
            f"+{sampled_extra_ms:.3f} ms/sample = {overhead_pct}% "
            f"of 16.7 ms at 1/{prod_every} cadence "
            f"({'PASS' if out['pass'] else 'FAIL'})")
        return out
    finally:
        audit_mod.unregister("game91")


def measure_failover(n: int) -> dict:
    """Hot-standby failover block (ISSUE 18): a REAL primary world
    under pose churn streams SnapshotChain frames through the bounded
    off-thread :class:`ReplicationWorker` into a live
    :class:`StandbyApplier` world, then dies at a deterministic tick
    and the standby promotes through the kvreg-arbitrated claim. The
    block reports the replication stream's wire cost NEXT TO the
    client-sync wire volume the same workload generates (the
    paper-facing contrast: continuous replication rides the same
    order of magnitude as what the primary already ships to clients),
    the standby's per-tick apply cost, and the promotion latency in
    TICKS (staleness behind the dead primary at the kill + the one
    resume tick).

    The gate: zero lost / zero duplicated EntityIDs across promotion,
    no torn frames, an arbitrated single winner whose decision log
    replays byte-for-byte, and a promotion window within the standby
    lag budget."""
    import shutil
    import statistics
    import tempfile

    import numpy as np

    from goworld_tpu import freeze as freeze_mod
    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.entity.entity import Entity, GameClient
    from goworld_tpu.entity.manager import World
    from goworld_tpu.entity.space import Space
    from goworld_tpu.net import codec as net_codec
    from goworld_tpu.ops.aoi import GridSpec
    from goworld_tpu.replication.promote import (
        DecisionLog, adjudicate, claim_key, claim_value,
        replay_decisions)
    from goworld_tpu.replication.standby import (
        StandbyApplier, StandbyTracker)
    from goworld_tpu.replication.worker import ReplicationWorker
    from goworld_tpu.utils import audit as audit_mod

    ents = min(int(n),
               int(os.environ.get("BENCH_FAILOVER_ENTITIES", 128)))
    ticks = int(os.environ.get("BENCH_FAILOVER_TICKS", 48))
    keyframe_every = 8

    class _FoMob(Entity):
        ATTRS = {"hp": "allclients hot:0"}

    capacity = 64
    while capacity < 2 * ents:
        capacity *= 2

    cfg = WorldConfig(
        capacity=capacity,
        grid=GridSpec(radius=20.0, extent_x=200.0, extent_z=200.0),
        input_cap=256,
    )
    primary = World(cfg, n_spaces=1, game_id=93)
    primary.register_entity("Mob", _FoMob)
    primary.register_space("Arena", Space)
    primary.create_nil_space()
    sp = primary.create_space("Arena")
    rng = np.random.default_rng(23)
    pool = []
    for i in range(ents):
        x, z = rng.uniform(10.0, 190.0, 2)
        e = sp.create_entity("Mob", pos=(float(x), 0.0, float(z)))
        e.attrs["hp"] = i
        pool.append(e)
    # a client cohort so the primary generates REAL downstream sync
    # wire bytes — the denominator of the replication-cost contrast
    n_clients = max(1, ents // 4)
    for i in range(n_clients):
        pool[i].set_client(GameClient(1, f"fo-c{i}", primary))
    sync_acc = {"bytes": 0}

    def _client_sync_sink(gate_id, cids, eids, vals) -> None:
        # the exact full-wire body the game server ships per gate per
        # tick (net/game.py _flush_sync_out, non-delta leg)
        cid_b = np.asarray(cids, "S16")
        if cid_b.size == 0:
            return
        body = net_codec.encode_client_sync_batch(
            cid_b, np.asarray(eids, "S16"),
            np.asarray(vals, np.float32).reshape(-1, 4))
        sync_acc["bytes"] += len(body)

    primary.sync_sink = _client_sync_sink

    # the standby: a bare world sharing the type registry, pre-warmed
    # the way net/game.py _standby_tick does — compile the jit'd tick
    # program on the still-empty world (SoA shapes are capacity-static,
    # so it is the same program the promoted tick runs; without it the
    # "warm" promotion pays seconds of compile)
    standby = World(cfg, n_spaces=1, game_id=94)
    standby.register_entity("Mob", _FoMob)
    standby.register_space("Arena", Space)
    standby.tick()
    standby.tick_count = 0
    tracker = StandbyTracker(94, 93, tick_hz=60.0)
    applier = StandbyApplier(standby, 93, tracker=tracker)

    tmpdir = tempfile.mkdtemp(prefix="bench_failover_")
    frames: list = []

    def send_fn(blob: bytes, kind: str, tick: int) -> None:
        frames.append((blob, kind, tick))

    chain = freeze_mod.SnapshotChain(primary, tmpdir,
                                     keyframe_every=keyframe_every)
    worker = ReplicationWorker(chain, game_id=93, queue_max=4,
                               send_fn=send_fn)

    def _census(w) -> set:
        out = {e.id for e in w.entities.values() if not e.destroyed}
        if w.nil_space is not None:
            out.discard(w.nil_space.id)
        return out

    census_by_tick: dict[int, set] = {}
    try:
        # warmup outside the clock: jit compile + the spawn flush
        for _ in range(3):
            primary.tick()
        sync_acc["bytes"] = 0
        repl_bytes = 0
        applied = rejected = keyframes = 0
        apply_ms: list[float] = []
        tick_ms: list[float] = []
        for _ in range(ticks):
            for e in pool:
                if e.destroyed:
                    continue
                x, z = rng.uniform(10.0, 190.0, 2)
                primary.stage_pose(e, (float(x), 0.0, float(z)),
                                   yaw=float(rng.uniform(0.0, 6.28)))
            t1 = time.perf_counter()
            primary.tick()
            tick_ms.append((time.perf_counter() - t1) * 1e3)
            census_by_tick[primary.tick_count] = _census(primary)
            worker.submit(chain.capture(), to_disk=True,
                          to_stream=True)
            worker.drain()  # deterministic measurement: no drops
            batch, frames[:] = frames[:], []
            for blob, kind, _tk in batch:
                repl_bytes += len(blob)
                if kind == "key":
                    keyframes += 1
                t2 = time.perf_counter()
                out = applier.apply(blob)
                apply_ms.append((time.perf_counter() - t2) * 1e3)
                if out["ok"]:
                    applied += 1
                else:
                    rejected += 1
        if applied == 0:
            return {"error": "no frames reached the standby"}

        # deterministic kill at the last streamed tick; the standby
        # claims through the dispatcher's exact first-writer-wins kvreg
        # semantics (net/dispatcher.py _h_kvreg), emulated locally
        kill_tick = primary.tick_count
        applied_tick = applier.decoder.applied_tick
        applied_seq = applier.decoder.applied_seq
        kvreg: dict[str, str] = {}

        def kv_register(key: str, val: str, force: bool = False) -> str:
            if key not in kvreg or force:
                kvreg[key] = val
            return kvreg[key]

        key = claim_key(93)
        mine = claim_value(94, 1, applied_seq)
        dlog = DecisionLog()
        dlog.note("claim", key=key, value=mine, epoch=1,
                  applied_seq=applied_seq, applied_tick=applied_tick)
        t_warm0 = time.perf_counter()
        winner = kv_register(key, mine)
        verdict = adjudicate(winner, mine)
        dlog.note("adjudicate", winner=winner, mine=mine,
                  verdict=verdict)
        promote_ok = verdict == "won"
        standby.tick_count = max(standby.tick_count, applied_tick)
        standby.tick()  # first served tick from the mirrored state
        warm_secs = time.perf_counter() - t_warm0
        promotion_latency_ticks = (kill_tick - max(0, applied_tick)) + 1
        tracker.note_promoted(1, applied_tick)
        replay_ok = replay_decisions(dlog.inputs) == dlog.dump()

        # conservation across promotion: the promoted census must equal
        # the primary's census at the last APPLIED frame
        want = census_by_tick.get(applied_tick, set())
        got = _census(standby)
        lost = len(want - got)
        dup = len(got - want)

        repl_per_tick = repl_bytes / max(1, ticks)
        sync_per_tick = sync_acc["bytes"] / max(1, ticks)
        budget = tracker.lag_budget_ticks
        out = {
            "entities": ents,
            "capacity": capacity,
            "ticks": ticks,
            "keyframe_every": keyframe_every,
            "clients": n_clients,
            "frames_applied": applied,
            "frames_rejected": rejected,
            "keyframes": keyframes,
            "replication_bytes_per_tick": round(repl_per_tick, 1),
            "client_sync_bytes_per_tick": round(sync_per_tick, 1),
            "replication_vs_client_sync": (
                round(repl_per_tick / sync_per_tick, 3)
                if sync_per_tick > 0 else None),
            "standby_apply_ms_per_tick": round(
                sum(apply_ms) / max(1, ticks), 3),
            "primary_tick_ms": round(statistics.median(tick_ms), 3),
            "promotion_latency_ticks": promotion_latency_ticks,
            "promotion_secs": round(warm_secs, 4),
            "lag_budget_ticks": budget,
            "entities_expected": len(want),
            "entities_promoted": len(got),
            "entities_lost": lost,
            "entities_duplicated": dup,
            "decision_log_replay_ok": replay_ok,
            "worker": worker.stats(),
            # the acceptance gate: conservation across promotion, a
            # clean stream, a single arbitrated winner with a
            # byte-replayable log, inside the lag budget
            "pass": (lost == 0 and dup == 0 and rejected == 0
                     and promote_ok and replay_ok
                     and promotion_latency_ticks <= budget),
        }
        log(f"failover: {applied} frames ({keyframes} keys) "
            f"{out['replication_bytes_per_tick']} repl B/tick vs "
            f"{out['client_sync_bytes_per_tick']} sync B/tick, "
            f"apply {out['standby_apply_ms_per_tick']} ms/tick, "
            f"promoted in {promotion_latency_ticks} ticks "
            f"({lost} lost, {dup} dup) "
            f"({'PASS' if out['pass'] else 'FAIL'})")
        return out
    finally:
        worker.close()
        audit_mod.unregister("game93")
        audit_mod.unregister("game94")
        shutil.rmtree(tmpdir, ignore_errors=True)


def measure_rebalance(n: int) -> dict:
    """Self-healing rebalance block (ISSUE 19): a REAL donor world
    under pose churn trips the sustained-DEGRADED occupancy proxy and
    the production rebalance stack (:class:`RebalancePolicy` +
    :class:`HandoffExecutor` + :class:`RebalanceController`) hands a
    space-affine cohort to an underloaded receiver world through the
    migration protocol. The block reports the donor's tick p99 BEFORE
    and AFTER the handoff (the self-healing claim is that shedding a
    cohort buys the donor tick time back), the entities moved vs the
    batch cap, the abort count, and the donor recovery latency in
    observation windows — the lower-is-better series bench_trend
    gates.

    The gate: zero lost / zero duplicated EntityIDs across the move
    (census partition: donor_final and moved_final must partition the
    original set exactly) and a byte-identical DecisionLog replay."""
    import numpy as np

    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.entity.entity import Entity
    from goworld_tpu.entity.manager import World
    from goworld_tpu.entity.space import Space
    from goworld_tpu.ops.aoi import GridSpec
    from goworld_tpu.rebalance.controller import RebalanceController
    from goworld_tpu.rebalance.executor import HandoffExecutor
    from goworld_tpu.rebalance.policy import RebalancePolicy
    from goworld_tpu.utils import audit as audit_mod

    ents = min(int(n),
               int(os.environ.get("BENCH_REBALANCE_ENTITIES", 96)))
    m_ticks = int(os.environ.get("BENCH_REBALANCE_TICKS", 32))
    batch = max(4, min(24, ents // 4))
    hold_windows, cooldown_windows = 2, 8
    windows_budget = 24

    class _RbMob(Entity):
        ATTRS = {"hp": "allclients hot:0"}

    capacity = 64
    while capacity < 2 * ents:
        capacity *= 2
    cfg = WorldConfig(
        capacity=capacity,
        grid=GridSpec(radius=20.0, extent_x=200.0, extent_z=200.0),
        input_cap=256,
    )
    donor = World(cfg, n_spaces=1, game_id=95)
    donor.register_entity("Mob", _RbMob)
    donor.register_space("Arena", Space)
    donor.create_nil_space()
    dsp = donor.create_space("Arena")
    rng = np.random.default_rng(29)
    pool = []
    for _i in range(ents):
        x, z = rng.uniform(10.0, 190.0, 2)
        pool.append(dsp.create_entity(
            "Mob", pos=(float(x), 0.0, float(z))))
    # the receiver: an underloaded mirror world sharing the registry,
    # jit-warmed off the measured path
    recv = World(cfg, n_spaces=1, game_id=96)
    recv.register_entity("Mob", _RbMob)
    recv.register_space("Arena", Space)
    recv.create_nil_space()
    rsp = recv.create_space("Arena")
    recv.tick()
    recv.tick_count = 0

    def _census(w) -> set:
        out = {e.id for e in w.entities.values() if not e.destroyed}
        if w.nil_space is not None:
            out.discard(w.nil_space.id)
        return out

    def _churn() -> None:
        for e in pool:
            if e.destroyed:
                continue
            x, z = rng.uniform(10.0, 190.0, 2)
            donor.stage_pose(e, (float(x), 0.0, float(z)),
                             yaw=float(rng.uniform(0.0, 6.28)))

    def _measured_ticks(k: int) -> list[float]:
        out = []
        for _ in range(k):
            _churn()
            t1 = time.perf_counter()
            donor.tick()
            out.append((time.perf_counter() - t1) * 1e3)
        return out

    try:
        for _ in range(3):  # warmup outside the clock: jit compile
            donor.tick()
        before_ms = _measured_ticks(m_ticks)

        original = _census(donor)
        recv_base = _census(recv)
        c0 = len(original)
        # occupancy-proxy overload stage, same construction as the
        # chaos_soak rebalance scenario: DEGRADED while the census
        # holds at least (c0 - batch/2), so the COMPLETED handoff of
        # `batch` flips the donor NORMAL
        hot_threshold = c0 - batch // 2

        def stage_of(w, base: set) -> str:
            return ("DEGRADED"
                    if len(_census(w) - base) >= hot_threshold
                    else "NORMAL")

        policy = RebalancePolicy(hold_windows=hold_windows,
                                 batch=batch,
                                 cooldown_windows=cooldown_windows)
        agent = HandoffExecutor(donor, game_id=donor.game_id,
                                batch=batch)

        def transport(action):
            # zero-latency wire: the bench measures the donor's tick
            # cost around the handoff, not transport in-flight windows
            # (chaos_soak owns that) — deliver and ack inline
            def send(eid, data) -> None:
                recv.restore_from_migration(data, space=rsp)
                agent.ack(eid)
            return send

        ctl = RebalanceController(
            policy, agents={"game95": agent}, transport=transport,
            rate=max(1, batch // 2), timeout_windows=4)

        commit_window = recovered_window = None
        windows_used = 0
        for w_i in range(1, windows_budget + 1):
            windows_used = w_i
            _churn()
            donor.tick()
            recv.tick()
            obs = {
                "game95": {"stage": stage_of(donor, set()),
                           "entities": len(_census(donor)),
                           "present": True},
                "game96": {"stage": stage_of(recv, recv_base),
                           "entities":
                               len(_census(recv) - recv_base),
                           "present": True},
            }
            if (commit_window is not None
                    and recovered_window is None
                    and obs["game95"]["stage"] == "NORMAL"):
                recovered_window = w_i
            action = ctl.step(obs)
            if action is not None and commit_window is None:
                commit_window = w_i
            if recovered_window is not None \
                    and agent.completed + agent.aborted > 0:
                break

        after_ms = _measured_ticks(m_ticks)

        donor_final = _census(donor)
        moved_final = _census(recv) - recv_base
        lost = len(original - (donor_final | moved_final))
        dup = (len(donor_final & moved_final)
               + len((donor_final | moved_final) - original))
        replay_ok = RebalancePolicy.replay(
            policy.log.inputs, hold_windows=hold_windows,
            batch=batch, cooldown_windows=cooldown_windows,
        ) == policy.log.dump()
        recovery = (None if commit_window is None
                    or recovered_window is None
                    else recovered_window - commit_window)
        p99 = (lambda xs:
               round(float(np.percentile(np.asarray(xs), 99)), 3))
        out = {
            "entities": ents,
            "capacity": capacity,
            "measure_ticks": m_ticks,
            "donor_p50_before_ms": round(
                float(np.percentile(np.asarray(before_ms), 50)), 3),
            "donor_p99_before_ms": p99(before_ms),
            "donor_p50_after_ms": round(
                float(np.percentile(np.asarray(after_ms), 50)), 3),
            "donor_p99_after_ms": p99(after_ms),
            "batch": batch,
            "commit_window": commit_window,
            "windows_used": windows_used,
            "entities_moved": len(moved_final),
            "aborts": agent.aborted,
            "donor_recovery_windows": recovery,
            "entities_lost": lost,
            "entities_duplicated": dup,
            "decision_log_replay_ok": replay_ok,
            # the acceptance gate: one clean committed handoff of the
            # full batch, conservation across the move, a
            # byte-replayable decision log, a recovered donor
            "pass": (commit_window is not None
                     and len(moved_final) == batch
                     and agent.aborted == 0
                     and lost == 0 and dup == 0
                     and replay_ok and recovery is not None),
        }
        log(f"rebalance: moved {out['entities_moved']}/{batch} at "
            f"window {commit_window}, donor p99 "
            f"{out['donor_p99_before_ms']} -> "
            f"{out['donor_p99_after_ms']} ms, recovered in "
            f"{recovery} window(s) ({lost} lost, {dup} dup) "
            f"({'PASS' if out['pass'] else 'FAIL'})")
        return out
    finally:
        audit_mod.unregister("game95")
        audit_mod.unregister("game96")


def measure_resident_ab(n: int) -> dict:
    """Resident-world A/B block (ISSUE 20): two REAL instrumented
    Worlds on the same config — the ON arm resident (carry donation)
    plus the double-buffered output drain (``pipeline_decode``), the
    OFF arm the legacy copy-mode serve loop — ticked in INTERLEAVED
    PACED windows (on/off, off/on alternating, each window sleeping
    off the frame budget like a real 60 Hz server) so ambient host
    noise lands on both arms symmetrically and neither arm's in-flight
    async compute bleeds into the other's clock. The residency census runs on BOTH
    arms: the ON arm's acceptance verdict is 0 re-allocated carry
    lanes (the worklist PR 16 measured, consumed), the OFF arm must
    still show the churn (>= 1) or the A/B is not measuring what it
    claims. Allocator churn per tick rides along where the backend
    serves memory_stats (honest ``None`` on CPU, never a fake zero).

    BENCH_RESIDENT_AB=0 skips (recorded honestly);
    BENCH_RESIDENT_ENTITIES (default 192) / _WINDOWS (6) / _TICKS
    (24 per window) shape it."""
    import jax
    import numpy as np

    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.entity.entity import Entity
    from goworld_tpu.entity.manager import World
    from goworld_tpu.entity.space import Space
    from goworld_tpu.ops.aoi import GridSpec

    ents = min(int(n),
               int(os.environ.get("BENCH_RESIDENT_ENTITIES", 192)))
    windows = int(os.environ.get("BENCH_RESIDENT_WINDOWS", 6))
    w_ticks = int(os.environ.get("BENCH_RESIDENT_TICKS", 24))
    # 30 Hz default: at the provisioned 4x-capacity shape a CPU
    # backend's compute exceeds a 60 Hz frame, which would starve the
    # sleep and degenerate the paced protocol into back-to-back ticks
    tick_hz = float(os.environ.get("BENCH_RESIDENT_HZ", 30.0))

    class _ResMob(Entity):
        ATTRS = {"hp": "allclients hot:0"}

    # capacity provisions 4x headroom (a serving world admits churn
    # without re-compiling): the carry donation saves buffer traffic
    # proportional to CAPACITY, so the A/B measures the provisioned
    # shape a resident server actually runs, not a tightly-packed one
    capacity = 64
    while capacity < 4 * ents:
        capacity *= 2
    cfg = WorldConfig(
        capacity=capacity,
        grid=GridSpec(radius=20.0, extent_x=200.0, extent_z=200.0),
        input_cap=256,
    )

    def _mk(game_id: int, resident: bool) -> World:
        w = World(cfg, n_spaces=1, game_id=game_id,
                  resident=resident, pipeline_decode=resident,
                  residency=True,
                  residency_sample_every=max(2, w_ticks // 8))
        w.register_entity("Mob", _ResMob)
        w.register_space("Arena", Space)
        w.create_nil_space()
        sp = w.create_space("Arena")
        rng = np.random.default_rng(13)  # same layout on both arms
        for _ in range(ents):
            x, z = rng.uniform(10.0, 190.0, 2)
            sp.create_entity("Mob", pos=(float(x), 0.0, float(z)))
        rt = w.residency
        w.residency = None  # warmup outside the census: jit compile
        for _ in range(3):  # and spawn flush must not pollute it
            w.tick()
        w.residency = rt
        return w

    on = _mk(91, True)
    off = _mk(92, False)

    def _window(w: World) -> float:
        """Median serve-loop BUSY ms/tick over one PACED window — the
        real serving pattern (tick, then sleep off the frame budget),
        not a back-to-back throughput loop. Pacing is load-bearing
        twice over: (1) it is where the overlap claim lives — the
        resident arm's device compute runs during the sleep, so its
        busy time is the host work alone, while the copy arm blocks
        in-frame on its own-tick fetch; (2) an unpaced loop leaves the
        pipelined arm's async compute in flight when the OTHER arm
        ticks, so the two arms fight over the shared backend and the
        A/B measures contention, not the knob."""
        interval = 1.0 / tick_hz
        busy = []
        for _ in range(w_ticks):
            t0 = time.perf_counter()
            w.tick()
            b = time.perf_counter() - t0
            busy.append(b * 1e3)
            if interval - b > 0:
                time.sleep(interval - b)
        if w.pipeline_decode:
            w.flush_pending_outputs()
        jax.block_until_ready(w.state)
        return float(np.median(np.asarray(busy)))

    on_ms: list[float] = []
    off_ms: list[float] = []
    for w_i in range(windows):
        # alternate the order inside each window pair so slow-drift
        # host noise (thermal, page cache) cancels across arms
        arms = (on, off) if w_i % 2 == 0 else (off, on)
        for arm in arms:
            (on_ms if arm is on else off_ms).append(_window(arm))

    def _arm(w: World) -> tuple[dict, float | None]:
        snap = w.residency.snapshot()
        census = snap.get("census", {}) or {}
        allocs = (snap.get("alloc", {}) or {}).get("allocs_per_tick")
        return ({
            "samples": int(census.get("samples", 0)),
            "realloc": len(census.get("realloc", [])),
            "aliased": len(census.get("aliased", [])),
            "skipped_deleted": int(census.get("skipped_deleted", 0)),
        }, allocs)

    on_census, on_allocs = _arm(on)
    off_census, off_allocs = _arm(off)
    med = lambda xs: round(float(np.median(np.asarray(xs))), 3)
    on_med, off_med = med(on_ms), med(off_ms)
    out = {
        "entities": ents,
        "capacity": capacity,
        "windows": windows,
        "ticks_per_window": w_ticks,
        "tick_hz": tick_hz,
        "on_ms_per_tick": on_med,
        "off_ms_per_tick": off_med,
        "ratio": round(on_med / max(off_med, 1e-9), 4),
        "on_allocs_per_tick": on_allocs,
        "off_allocs_per_tick": off_allocs,
        "on_census": on_census,
        "off_census": off_census,
        # the acceptance gate: the donated arm re-allocates ZERO carry
        # lanes while the copy arm still shows the churn, each census
        # actually sampled, and the resident arm is not slower
        "pass": (on_census["samples"] >= 2
                 and off_census["samples"] >= 2
                 and on_census["realloc"] == 0
                 and off_census["realloc"] >= 1
                 and on_med < off_med),
    }
    log(f"resident_ab: on {on_med} ms/tick vs off {off_med} "
        f"(ratio {out['ratio']}), census realloc "
        f"on={on_census['realloc']} off={off_census['realloc']} "
        f"({'PASS' if out['pass'] else 'FAIL'})")
    return out


def measure(n: int, ticks: int, client_frac: float, phases: bool,
            grid_overrides: dict | None = None) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax

    from goworld_tpu.core.step import tick_body

    cfg, st, inputs = build(n, client_frac, grid_overrides)

    policy = None
    if cfg.behavior == "mlp" or (
            cfg.scenario is not None and cfg.scenario.needs_policy):
        from goworld_tpu.models.npc_policy import init_policy

        policy = init_policy(jax.random.PRNGKey(5))

    def one_tick(state, _):
        state, out = tick_body(cfg, state, inputs, policy)
        checks = (
            out.enter_n + out.leave_n + out.sync_n + out.attr_n,
            out.sync_vals.sum(),
            out.alive_count,
        )
        return state, checks

    def make_run(length):
        @jax.jit
        def run(state):
            st2, checks = lax.scan(one_tick, state, None, length=length)
            # ONE scalar depending on every tick's outputs AND the final
            # state: fetching it (np.asarray below) ends the timed
            # region in a fetched value, like measure_p99
            return (
                checks[0].sum().astype(jnp.float32)
                + checks[1].sum()
                + checks[2].sum().astype(jnp.float32)
                + st2.pos.sum()
            )
        return run

    run = make_run(ticks)
    run2 = make_run(2 * ticks)

    # Every timed call gets a DISTINCT input state (fresh rng + position
    # jitter): identical (executable, args) pairs returned suspiciously
    # fast in r01-era measurements (0.01 ms/tick for a 1M-entity sweep —
    # physically impossible), consistent with result caching somewhere in
    # the remote-backend path. Distinct inputs force real execution.
    def variant(i: int):
        return st.replace(
            rng=jax.random.PRNGKey(1000 + i),
            pos=st.pos + jnp.float32(0.001 * (i + 1)),
        )

    import numpy as _np

    def force(x):
        return float(_np.asarray(x))

    t0 = time.perf_counter()
    # AOT lower+compile: the SAME executable serves the timed calls
    # below AND the devprof cost audit (cost_analysis needs the
    # compiled artifact; going through .lower here means the audit
    # costs zero extra compiles)
    run_compiled = run.lower(variant(0)).compile()
    run = lambda s: run_compiled(s)  # noqa: E731
    force(run(variant(0)))
    compile_s = time.perf_counter() - t0
    log(f"n={n}: compile+warmup {compile_s:.1f}s")
    t0 = time.perf_counter()
    force(run2(variant(1)))
    compile2_s = time.perf_counter() - t0

    # Time each scan length REPEATS times and take the min (the standard
    # noise-robust estimator: system-load spikes only ever ADD time).
    # r03 shipped scale_2x=2.63 from single-shot timings — one slow run2
    # inflated the marginal tick by ~63% and made the robust 64-sample
    # p99 median look "impossibly fast" (p50 < 0.7x tick), tripping the
    # consistency gate on a healthy harness. Min-of-k on both lengths
    # makes the marginal estimate comparable to a median in robustness.
    repeats = int(os.environ.get("BENCH_TIME_REPEATS", 3))
    times_t, times_2t = [], []
    for r_i in range(repeats):
        t0 = time.perf_counter()
        force(run(variant(2 + 2 * r_i)))
        times_t.append(time.perf_counter() - t0)
        # a 2x-length scan on fresh input must take ~2x: if it doesn't,
        # the harness is NOT measuring execution and the number can't be
        # trusted (the marginal per-tick figure below also cancels the
        # constant scalar-readback roundtrip these force() calls add)
        t0 = time.perf_counter()
        force(run2(variant(3 + 2 * r_i)))
        times_2t.append(time.perf_counter() - t0)
    elapsed_t = min(times_t)
    elapsed_2t = min(times_2t)
    scale = elapsed_2t / max(elapsed_t, 1e-9)
    # marginal per-tick cost cancels constant dispatch/transfer overhead
    per_tick = max(elapsed_2t - elapsed_t, 1e-9) / ticks

    ticks_per_sec = 1.0 / per_tick
    result = {
        "value": round(n * ticks_per_sec, 1),
        "entities": n,
        "ticks_per_sec": round(ticks_per_sec, 2),
        "tick_ms": round(1000.0 * per_tick, 3),
        "ticks_timed": ticks,
        "wall_t_s": round(elapsed_t, 3),
        "wall_2t_s": round(elapsed_2t, 3),
        "wall_t_s_all": [round(x, 3) for x in times_t],
        "wall_2t_s_all": [round(x, 3) for x in times_2t],
        "time_repeats": repeats,
        "scale_2x": round(scale, 2),
        "compile_s": round(compile_s, 1),
        "compile2_s": round(compile2_s, 1),
        "behavior": cfg.behavior,
        # the RESOLVED kernel choices this number was produced with
        # (env defaults + autotune overrides), so trajectory files
        # (BENCH_*.json) record which kernels made each headline
        "sweep_impl": cfg.grid.sweep_impl,
        "topk_impl": cfg.grid.topk_impl,
        "sort_impl": cfg.grid.sort_impl,
        # skin stamped as EFFECTIVE: past the packed-id bound the tick
        # statically falls back to the stateless sweep, and the stamp
        # must record what actually produced the number
        "skin": (cfg.grid.skin
                 if n < (1 << _AOI_ID_BITS) else 0.0),
        "verlet_cap": (cfg.grid.verlet_cap_eff
                       if cfg.grid.skin > 0
                       and n < (1 << _AOI_ID_BITS) else 0),
        # resolved quantized-plane config (ISSUE 12; bench_schema
        # requires the block from r12): plane on/off, the lattice
        # scale, and the delta-sync knobs a serving deploy would run
        "precision": {
            "plane": cfg.grid.precision,
            "pos_scale_bits": cfg.grid.quant_bits,
            "quant_step": cfg.grid.quant_step,
            "sync_delta": os.environ.get("BENCH_SYNC_DELTA",
                                         "0") == "1",
            "sync_keyframe_every": int(os.environ.get(
                "BENCH_SYNC_KEYFRAME_EVERY", 16)),
        },
        "device": _device_stamp(),
        "platform": jax.devices()[0].platform,
    }
    if not (1.5 <= scale <= 3.0):
        result["timing_suspect"] = (
            f"2x-tick scan took {scale:.2f}x the 1x time; "
            "per-tick figure may not reflect real execution"
        )
    phase_costs: dict = {}
    if phases:
        result["phase_ms"], phase_costs = measure_phases(
            cfg, st, inputs, ticks)
    # Device-plane stamps (ISSUE 8). EVERY path stamps each block —
    # real, {"error": ...} (an exception must never cost a headline;
    # each stamp records its OWN failure so a cost_report error is
    # never misfiled under roofline_audit) or {"skipped": ...} (the
    # documented BENCH_DEVPROF=0/BENCH_SLO=0/phases-off knobs) — so a
    # deliberately-thinner run still produces a schema-valid artifact
    # (tools/bench_schema.py accepts error/skipped records).
    if os.environ.get("BENCH_DEVPROF", "1") == "1":
        try:
            from goworld_tpu.utils import devprof

            result["cost_report"] = devprof.cost_report(
                run_compiled, name="tick_scan",
                config=devprof.grid_config_key(cfg.grid), n=n,
            ).as_dict()
        except Exception as exc:
            result["cost_report"] = {"error": str(exc)[:200]}
        if phases:
            try:
                from goworld_tpu.utils import devprof

                result["roofline_audit"] = devprof.roofline_audit(
                    result["phase_ms"], phase_costs, n,
                    _model_grid_kw(cfg, n),
                    platform=result["platform"],
                )
            except Exception as exc:
                result["roofline_audit"] = {"error": str(exc)[:200]}
        else:
            result["roofline_audit"] = {
                "skipped": "phases disabled (BENCH_PHASES=0 or "
                           "smoke stage)"}
    else:
        result["cost_report"] = {"skipped": "BENCH_DEVPROF=0"}
        result["roofline_audit"] = {"skipped": "BENCH_DEVPROF=0"}
    if phases and os.environ.get("BENCH_SLO", "1") == "1":
        # in-graph telemetry lanes + the SLO verdict (ISSUE 8): one
        # extra on-device scan, zero per-tick host syncs, drained once
        try:
            result["op_stats"], result["slo"] = measure_telemetry(
                cfg, variant(6), inputs, policy,
                int(os.environ.get("BENCH_SLO_TICKS", 64)),
                result["tick_ms"], result.get("phase_ms") or {},
            )
        except Exception as exc:
            result["slo"] = {"error": str(exc)[:200]}
            result["op_stats"] = {"error": str(exc)[:200]}
    else:
        why = ("BENCH_SLO=0" if phases
               else "phases disabled (BENCH_PHASES=0 or smoke stage)")
        result["slo"] = {"skipped": why}
        result["op_stats"] = {"skipped": why}
    # the workload-signature block (ISSUE 11): the SAME jax-free
    # reducer the live /workload endpoint serves, applied to the
    # just-drained lanes — bench and serving cross-validate one
    # signature grammar (required by bench_schema from r11)
    result["workload_signature"] = _signature_stamp(
        result["op_stats"], _model_grid_kw(cfg, n))
    # hand the caller what it needs to run the p99 pass AFTER the
    # headline line is safely on stdout (a hang mid-p99 must not discard
    # the already-measured result)
    result["_p99_args"] = (cfg, variant(4), inputs, policy)
    return result


def _skin_effective(grid, n: int) -> bool:
    """Whether the Verlet skin is LIVE at this shape: configured on AND
    inside the packed-id bound (past it the tick statically falls back
    to the stateless sweep — api.py/tick_body mirror this predicate).
    The one helper for the device-plane stamp sites, so the roofline
    model, the slo constants and the headline skin stamp can never
    describe different kernels for the same run."""
    return grid.skin > 0 and n < (1 << _AOI_ID_BITS)


def _model_grid_kw(cfg, n: int) -> dict:
    """The grid-knob dict the roofline hand model prices (devprof.
    roofline_model_bytes), with skin stamped EFFECTIVE like the
    headline stamps."""
    g = cfg.grid
    skin_on = _skin_effective(g, n)
    return {
        "radius": g.radius, "extent_x": g.extent_x,
        "extent_z": g.extent_z, "k": g.k, "cell_cap": g.cell_cap,
        "sort_impl": g.sort_impl, "sweep_impl": g.sweep_impl,
        "skin": g.skin if skin_on else 0.0,
        "verlet_cap": g.verlet_cap_eff if skin_on else 0,
        "precision": g.precision,
    }


def measure_telemetry(cfg, st, inputs, policy, ticks: int,
                      tick_ms: float, phase_ms: dict) -> tuple[dict, dict]:
    """The in-graph telemetry scan (ops/telemetry.py): fixed-bucket
    histograms of per-tick signals accumulated ON DEVICE through one
    ``lax.scan`` — zero host syncs per tick, one drain at the end —
    plus the SLO verdict evaluated from the tick_ms lane.

    The tick_ms lane's per-tick latency model: ``base + rebuilt_i *
    delta`` with host-measured constants (the scan-marginal tick and
    the aoi_rebuild/aoi_reuse phase probes) selected per tick by the
    in-graph Verlet rebuild bit; with no skin the lane is the constant
    scan-marginal tick. The model constants are stamped into the slo
    block so the figure is never mistaken for per-tick wall clock."""
    import jax
    from jax import lax

    from goworld_tpu.core.step import tick_body
    from goworld_tpu.ops import telemetry
    from goworld_tpu.utils import devprof

    n = cfg.capacity
    skin_on = (_skin_effective(cfg.grid, n)
               and getattr(st, "aoi_cache", None) is not None)
    base_ms, delta_ms = tick_ms, 0.0
    if skin_on and {"aoi", "aoi_rebuild", "aoi_reuse"} <= set(phase_ms):
        delta_ms = max(phase_ms["aoi_rebuild"] - phase_ms["aoi_reuse"],
                       0.0)
        base_ms = max(tick_ms - phase_ms["aoi"], 0.0) \
            + phase_ms["aoi_reuse"]
    half_skin = cfg.grid.skin / 2.0 if skin_on else 0.0

    @jax.jit
    def run(state):
        acc0 = telemetry.telemetry_init(skin_on)

        def body(carry, _):
            s, acc = carry
            s2, out = tick_body(cfg, s, inputs, policy)
            acc = telemetry.telemetry_update(acc, out, base_ms,
                                             delta_ms, half_skin)
            return (s2, acc), 0
        (_s2, acc), _ = lax.scan(body, (state, acc0), None,
                                 length=ticks)
        return acc

    op_stats = telemetry.telemetry_drain(run(st), skin_on, half_skin)
    target = float(os.environ.get("BENCH_SLO_MS",
                                  devprof.DEFAULT_SLO_TARGET_MS))
    lane = op_stats["tick_ms"]
    slo = devprof.slo_from_histogram(lane["edges"], lane["counts"],
                                     target,
                                     source="in-graph-histogram")
    slo["model"] = {"base_ms": round(base_ms, 3),
                    "rebuild_delta_ms": round(delta_ms, 3)}
    devprof.record_slo(slo)
    log(f"slo@{n}: p50={slo['p50_ms']} p90={slo['p90_ms']} "
        f"p99={slo['p99_ms']} target={target} "
        f"-> {'PASS' if slo['pass'] else 'FAIL'}")
    return op_stats, slo


def _signature_stamp(op_stats, grid_kw: dict | None) -> dict:
    """The artifact's ``workload_signature`` block: the jax-free
    reducer of ops/telemetry.py over the drained lanes (the exact
    reduction the live ``/workload`` endpoint serves, so bench rounds
    and production processes speak one signature grammar), or an
    honest error/skip mirroring the op_stats block's own status."""
    from goworld_tpu.ops import telemetry

    if not isinstance(op_stats, dict) \
            or "error" in op_stats or "skipped" in op_stats:
        src = op_stats if isinstance(op_stats, dict) else {}
        if "skipped" in src:
            return {"skipped": str(src["skipped"])[:200]}
        return {"error": str(src.get("error", "no op_stats"))[:200]}
    try:
        return telemetry.workload_signature(op_stats, config=grid_kw)
    except Exception as exc:
        return {"error": str(exc)[:200]}


def measure_p99(cfg, st, inputs, policy, samples: int | None = None) -> dict:
    """Per-tick latency distribution (BASELINE's second metric: AOI-sync
    p99 < 16 ms).

    Anti-fake-latency design (an early artifact reported
    tick_p99_ms=3.2 next to a scan-measured tick_ms=776 — the fetch
    did not serialize with execution): every tick takes the PREVIOUS
    tick's FETCHED
    scalar as a live input (folded into positions through a dynamic
    argument), so tick i+1 cannot produce its output until the host has
    read tick i's. Caching, pipelining, or early readback returns would
    all leave the feedback value wrong for the next dispatch — the chain
    forces one real round trip per sample. The figure therefore includes
    one host<->device scalar roundtrip — an upper bound on tick time.

    The sanity cross-check against the scan-marginal tick_ms lives in the
    parent (p99 must be >= ~tick_ms; see parent_main)."""
    import jax
    import jax.numpy as jnp

    from goworld_tpu.core.step import tick_body

    if samples is None:
        samples = int(os.environ.get("BENCH_P99_SAMPLES", 64))

    @jax.jit
    def tick_fb(state, feedback, ins, pol):
        # fold the host-fetched scalar into the positions so this tick's
        # AOI sweep (and thus sync_n) depends on it; the perturbation is
        # sub-micrometer so it cannot change the measured workload
        state = state.replace(pos=state.pos + feedback)
        return tick_body(cfg, state, ins, pol)

    fb = jnp.zeros((), jnp.float32)
    st, out = tick_fb(st, fb, inputs, policy)
    v = int(out.sync_n)  # compile + force
    lat = []
    for i in range(samples):
        fb = jnp.float32(((v + i) % 7 + 1) * 1e-7)
        t0 = time.perf_counter()
        st, out = tick_fb(st, fb, inputs, policy)
        v = int(out.sync_n)  # next tick's feedback depends on this fetch
        lat.append(time.perf_counter() - t0)
    lat.sort()
    return {
        "tick_p50_ms": round(1000.0 * lat[len(lat) // 2], 3),
        "tick_p99_ms": round(1000.0 * lat[int(len(lat) * 0.99)], 3),
        "p99_includes_host_roundtrip": True,
        "p99_loop_carried_fetch": True,
        "p99_samples": samples,
    }


def measure_phases(cfg, st, inputs, ticks: int) -> tuple[dict, dict]:
    """Per-phase timings via separately-jitted partial ticks: aoi (grid
    sweep only), move (inputs+behavior+integrate), collect (changed-row
    interest pairs + sync + attr extraction, AOI held fixed). Sum != whole
    tick (XLA fuses across phases in the real program); it localizes where
    the time goes. Returns ``(phase_ms, phase_cost_reports)`` — the
    second dict maps phase name -> devprof CostReport of the SAME
    AOT-compiled probe (empty with BENCH_DEVPROF=0). Each phase
    reduces to ONE scalar which is fetched with np.asarray — a result
    left lazily on the device would time as ~0 ms."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import lax

    from goworld_tpu.models.random_walk import random_walk_step
    from goworld_tpu.ops.aoi import (
        grid_neighbors,
        grid_neighbors_flags,
        grid_neighbors_verlet,
        init_verlet_cache,
    )
    from goworld_tpu.ops.delta import interest_pairs
    from goworld_tpu.ops.integrate import apply_pos_inputs, integrate
    from goworld_tpu.ops.sync import collect_attr_deltas, collect_sync

    n = cfg.capacity
    # mirror tick_body's use_verlet guard: past the packed-id bound the
    # real tick falls back to the stateless sweep, so the phase probes
    # must too (grid_neighbors_verlet raises there)
    verlet = cfg.grid.skin > 0 and getattr(st, "aoi_cache", None) \
        is not None and n < (1 << _AOI_ID_BITS)

    if verlet:
        # skin sub-phases: "aoi" is the REAL configured path (cache
        # carried through the scan — one rebuild at tick 0, reuse
        # after, like the live tick at low displacement);
        # "aoi_rebuild" forces the front half every iteration (the
        # rebuild-tick cost); "aoi_reuse" starts from a warmed cache
        # (the steady-state reuse tick). Amortized truth at cadence C:
        # (reuse*(C-1) + rebuild) / C.
        cache0 = init_verlet_cache(cfg.grid, n)

        def make_verlet(init_cache, force_rebuild):
            @jax.jit
            def probe(state):
                def body(carry, _):
                    pos, cache = carry
                    _nbr, cnt, _fl, _s, cache2, _rb, _sl = \
                        grid_neighbors_verlet(
                            cfg.grid, pos, state.alive,
                            cache0 if force_rebuild else cache,
                        )
                    pos = pos + (cnt[:, None] % 2).astype(pos.dtype) \
                        * 1e-6
                    return (pos, cache2), cnt.sum()
                (pos, _c), s = lax.scan(
                    body, (state.pos, init_cache), None, length=ticks
                )
                return s.sum() + pos.sum()
            return probe

        aoi_only = make_verlet(cache0, False)
        aoi_rebuild_only = make_verlet(cache0, True)
        warm_cache = grid_neighbors_verlet(
            cfg.grid, st.pos, st.alive, cache0
        )[4]
        aoi_reuse_only = make_verlet(warm_cache, False)
    else:
        @jax.jit
        def aoi_only(state):
            def body(carry, _):
                pos = carry
                nbr, cnt = grid_neighbors(cfg.grid, pos, state.alive)
                # feed a nbr-dependent perturbation back so scan
                # iterations cannot be collapsed by the compiler
                pos = pos + (cnt[:, None] % 2).astype(pos.dtype) * 1e-6
                return pos, cnt.sum()
            pos, s = lax.scan(body, state.pos, None, length=ticks)
            return s.sum() + pos.sum()

    def make_sweep_probe(phase):
        from goworld_tpu.ops.aoi import sweep_phase_checksum

        @jax.jit
        def probe(state):
            def body(carry, _):
                pos = carry
                s = sweep_phase_checksum(cfg.grid, pos, state.alive,
                                         phase)
                pos = pos + (s.astype(pos.dtype) % 2) * 1e-7
                return pos, s
            pos, ss = lax.scan(body, state.pos, None, length=ticks)
            return ss.astype(jnp.float32).sum() + pos.sum()
        return probe

    @jax.jit
    def move_only(state):
        def body(carry, _):
            pos, yaw, vel, rng = carry
            pos, yaw, touched = apply_pos_inputs(
                pos, yaw, inputs.pos_sync_idx, inputs.pos_sync_vals,
                inputs.pos_sync_n,
            )
            rng, k = jax.random.split(rng)
            vel = random_walk_step(
                k, vel, state.npc_moving, cfg.npc_speed, cfg.turn_prob
            )
            pos, moved = integrate(
                pos, vel, state.npc_moving, cfg.dt,
                cfg.bounds_min, cfg.bounds_max,
            )
            return (pos, yaw, vel, rng), moved.sum()
        carry, s = lax.scan(
            body, (state.pos, state.yaw, state.vel, state.rng),
            None, length=ticks,
        )
        return s.sum() + carry[0].sum()

    @jax.jit
    def collect_only(state, nbr, fl):
        def body(carry, _):
            prev_dirty, dirty = carry
            # prev list derived from the loop-carried dirty vector so
            # NOTHING here is loop-invariant (XLA LICM would otherwise
            # hoist a whole phase out of the scan and under-report it —
            # the r01/r02 mismeasurement failure mode). ~6% of rows
            # differ from nbr: realistic steady-state churn.
            prev_nbr = jnp.where(
                prev_dirty[:, None], jnp.roll(nbr, 1, axis=0), nbr
            )
            ew, ej, en, lw, lj, ln, drn = interest_pairs(
                prev_nbr, nbr, n, cfg.enter_cap, cfg.leave_cap,
                min(cfg.delta_rows_cap_eff, n),
            )
            sw, sj, sv, sn = collect_sync(
                nbr, dirty, state.has_client, state.pos, state.yaw,
                cfg.sync_cap,
                nbr_dirty=(fl & 1).astype(bool) & dirty[: nbr.shape[0],
                                                        None],
            )
            ae, ai, av, an = collect_attr_deltas(
                state.hot_attrs, state.attr_dirty, cfg.attr_sync_cap
            )
            return (
                (jnp.roll(prev_dirty, 1), jnp.roll(dirty, 3)),
                en + ln + sn + an + drn + ew.sum() + sv.sum(),
            )
        init_prev = (jnp.arange(n) % 16) == 0      # ~6% churn rows
        init_dirty = jnp.ones((n,), bool)
        carry, s = lax.scan(
            body, (init_prev, init_dirty), None, length=ticks
        )
        return s.sum()

    out = {}
    nbr, cnt, fl = grid_neighbors_flags(
        cfg.grid, st.pos, st.alive, flag_bits=st.dirty.astype(jnp.int32)
    )
    phase_list = [
        ("aoi", aoi_only, (st,)),
        # sweep sub-phases (cumulative: sort ⊂ build ⊂ gather ⊂ pack ⊂
        # rank ⊂ aoi): where the AOI milliseconds go — cell sort vs
        # candidate-structure build vs the BACK half staged (9-cell
        # window fetch, + distance/key pack, + top-k). The back-half
        # probes run the real split row-block path (sweep_impl="fused"
        # probes its split sibling "ranges"), so at a fused config the
        # delta between these split stages and the fused "aoi" phase IS
        # the fusion win — the attribution ISSUE 6 asks for. With a
        # skin these attribute the REBUILD tick.
        ("aoi_sort", make_sweep_probe("sort"), (st,)),
        ("aoi_build", make_sweep_probe("build"), (st,)),
        ("aoi_gather", make_sweep_probe("gather"), (st,)),
        ("aoi_pack", make_sweep_probe("pack"), (st,)),
        ("aoi_rank", make_sweep_probe("rank"), (st,)),
    ]
    if verlet:
        phase_list += [
            ("aoi_rebuild", aoi_rebuild_only, (st,)),
            ("aoi_reuse", aoi_reuse_only, (st,)),
        ]
    phase_list += [
        ("move", move_only, (st,)),
        ("collect", collect_only, (st, nbr, fl)),
    ]
    devprof_on = os.environ.get("BENCH_DEVPROF", "1") == "1"
    costs: dict = {}
    for name, fn, args in phase_list:
        # AOT-compile so the SAME executable is timed and cost-audited
        # (XLA counts a while-loop body ONCE, so a scan probe's
        # cost_analysis is per-tick already)
        try:
            fnc = fn.lower(*args).compile()
        except Exception:
            fnc = fn  # fall back to the plain jit path
        float(np.asarray(fnc(*args)))  # compile + force
        t0 = time.perf_counter()
        r = float(np.asarray(fnc(*args)))
        dt = time.perf_counter() - t0
        out[name] = round(1000.0 * dt / ticks, 3)
        if devprof_on and hasattr(fnc, "cost_analysis"):
            from goworld_tpu.utils import devprof

            costs[name] = devprof.cost_report(
                fnc, name=f"phase:{name}", n=cfg.capacity)
        log(f"phase {name}: {out[name]} ms/tick")
    return out, costs


# ---------------------------------------------------------- multichip ----

def _mega_factor(n_dev: int) -> tuple[int, int]:
    """Most-square (tx, tz) tiling of n_dev (the dryrun convention:
    8 -> 4x2, 16 -> 4x4; primes fall back to 1D x-strips)."""
    tz = max(d for d in range(1, int(n_dev ** 0.5) + 1)
             if n_dev % d == 0)
    return n_dev // tz, tz


def build_mega(n_total: int, scenario=None, halo_impl: str | None = None,
               grid_overrides: dict | None = None, seed: int = 0,
               npc_speed: float = 5.0):
    """The megaspace bench world: n_total entities tiled over EVERY
    visible device at the headline density formula (~12 Chebyshev
    neighbors at radius 50). Returns (mc, mesh, state, inputs, policy).

    Capacity/chip is auto-derived (alive rows + 1/8 headroom for
    migration imbalance); positions start uniform inside each tile's
    owned rectangle so tick 0 needs no cross-tile migration storm.
    The megaspace sweep is stateless (no Verlet cache to carry), so
    the grid kw pins skin=0 whatever the env says."""
    import jax
    import jax.numpy as jnp

    from goworld_tpu.core.state import SpaceState, WorldConfig
    from goworld_tpu.core.step import TickInputs
    from goworld_tpu.ops.aoi import GridSpec
    from goworld_tpu.parallel.megaspace import MegaConfig, make_mega_tick
    from goworld_tpu.parallel.mesh import make_mesh, shard_state
    from goworld_tpu.parallel.step import MultiTickInputs

    n_dev = len(jax.devices())
    tx, tz = _mega_factor(n_dev)
    alive_per = max(64, n_total // n_dev)
    cap = alive_per + max(64, alive_per // 8)
    radius = 50.0
    extent = float(int((n_total * 10000 / 12) ** 0.5))
    tile_w = extent / tx
    tile_d = extent / tz if tz > 1 else 0.0
    if radius > min(tile_w, tile_d if tz > 1 else tile_w):
        raise ValueError(
            f"tiles {tile_w:.0f}x{tile_d:.0f} thinner than AOI radius "
            f"{radius} at n_total={n_total}, n_dev={n_dev}; raise "
            "BENCH_MULTI_N or use fewer devices"
        )
    # worst-strip occupancy estimate x4 safety (hotspot churn piles
    # entities onto borders), clamped to sane pow2-ish bounds
    strip_frac = radius / min(tile_w, tile_d or tile_w)
    halo_cap = int(os.environ.get(
        "BENCH_HALO_CAP",
        max(512, min(16384, 1 << int(4 * alive_per * strip_frac)
                     .bit_length()))))
    migrate_cap = int(os.environ.get("BENCH_MIGRATE_CAP", 256))
    gk = _grid_kw_from_env(cap, {**(grid_overrides or {}),
                                 "skin": 0.0, "verlet_cap": 0})
    gk["row_block"] = min(cap, gk["row_block"])
    cfg = WorldConfig(
        capacity=cap,
        grid=GridSpec(
            radius=radius,
            extent_x=tile_w + 2 * radius,
            extent_z=(tile_d + 2 * radius) if tz > 1 else extent,
            **gk,
        ),
        npc_speed=npc_speed,
        behavior="random_walk",
        scenario=scenario,
        enter_cap=65536, leave_cap=65536,
        sync_cap=65536, attr_sync_cap=4096, input_cap=4096,
        delta_rows_cap=65536,
    )
    mc = MegaConfig(
        cfg=cfg, n_dev=n_dev, tile_w=tile_w,
        halo_cap=halo_cap, migrate_cap=migrate_cap,
        mesh_shape=(tx, tz) if tz > 1 else None, tile_d=tile_d,
        halo_impl=halo_impl or MULTI_HALO_IMPL or "ppermute",
    )
    mesh = make_mesh(n_dev)

    key = jax.random.PRNGKey(seed)
    k1, k2, k3, k4, k5, k6 = jax.random.split(key, 6)
    # per-tile owned rectangles in GLOBAL coords
    dix = (jnp.arange(n_dev, dtype=jnp.int32) // tz).astype(jnp.float32)
    diz = (jnp.arange(n_dev, dtype=jnp.int32) % tz).astype(jnp.float32)
    px = dix[:, None] * tile_w \
        + jax.random.uniform(k1, (n_dev, cap), maxval=tile_w)
    if tz > 1:
        pz = diz[:, None] * tile_d \
            + jax.random.uniform(k2, (n_dev, cap), maxval=tile_d)
    else:
        pz = jax.random.uniform(k2, (n_dev, cap), maxval=extent)
    pos = jnp.stack([px, jnp.zeros_like(px), pz], axis=-1)
    alive = jnp.arange(cap) < alive_per
    alive = jnp.broadcast_to(alive, (n_dev, cap))
    if scenario is not None:
        bid = jnp.stack([
            jnp.asarray(_sspec.assign_behavior_ids(scenario, cap,
                                                   seed * n_dev + d))
            for d in range(n_dev)
        ])
        wr = jnp.stack([
            jnp.asarray(_sspec.assign_watch_radii(scenario, cap,
                                                  seed * n_dev + d))
            for d in range(n_dev)
        ])
    else:
        bid = None
        wr = jnp.full((n_dev, cap), jnp.inf, jnp.float32)
    st = SpaceState(
        pos=pos,
        yaw=jnp.zeros((n_dev, cap)),
        vel=jnp.zeros((n_dev, cap, 3)),
        alive=alive,
        npc_moving=alive,
        has_client=(jax.random.uniform(k3, (n_dev, cap)) < CLIENT_FRAC)
        & alive,
        client_gate=jnp.zeros((n_dev, cap), jnp.int32),
        type_id=jnp.zeros((n_dev, cap), jnp.int32),
        gen=jnp.zeros((n_dev, cap), jnp.int32),
        hot_attrs=jnp.zeros((n_dev, cap, 8)),
        attr_dirty=jnp.zeros((n_dev, cap), jnp.uint32),
        nbr=jnp.full((n_dev, cap, cfg.grid.k), mc.gid_sentinel,
                     jnp.int32),
        nbr_cnt=jnp.zeros((n_dev, cap), jnp.int32),
        nbr_client_cnt=jnp.zeros((n_dev, cap), jnp.int32),
        nbr_mean_off=jnp.zeros((n_dev, cap, 3), jnp.float32),
        aoi_radius=wr,
        dirty=jnp.zeros((n_dev, cap), bool),
        rng=jax.vmap(jax.random.PRNGKey)(
            jnp.arange(1, n_dev + 1) + seed * n_dev),
        tick=jnp.zeros((n_dev,), jnp.int32),
        aoi_cache=None,
        behavior_id=bid,
    )
    st = shard_state(st, mesh)
    # steady client-sync stream, like the single-chip headline — but
    # TILE-LOCAL positions: a client correction lands near the entity,
    # it does not teleport it across the world (a world-uniform stream
    # here was measured turning every tick into a migration storm that
    # overflowed arrival slots — that load case is the border_churn
    # phase's job, driven by the scenario kernels, not the input path)
    n_sync = min(cfg.input_cap, max(16, alive_per // 16))
    sx = dix[:, None] * tile_w \
        + jax.random.uniform(k4, (n_dev, n_sync), maxval=tile_w)
    if tz > 1:
        sz = diz[:, None] * tile_d \
            + jax.random.uniform(k5, (n_dev, n_sync), maxval=tile_d)
    else:
        sz = jax.random.uniform(k5, (n_dev, n_sync), maxval=extent)
    sync_vals = jnp.zeros((n_dev, cfg.input_cap, 4))
    sync_vals = sync_vals.at[:, :n_sync, 0].set(sx)
    sync_vals = sync_vals.at[:, :n_sync, 2].set(sz)
    base = TickInputs(
        pos_sync_idx=jax.random.randint(k6, (n_dev, cfg.input_cap),
                                        0, alive_per),
        pos_sync_vals=sync_vals,
        pos_sync_n=jnp.full((n_dev,), n_sync, jnp.int32),
    )
    inputs = MultiTickInputs(
        base=base,
        migrate_target=jnp.full((n_dev, cap), -1, jnp.int32),
        migrate_tag=jnp.full((n_dev, cap), -1, jnp.int32),
    )
    policy = None
    if scenario is not None and scenario.needs_policy:
        from goworld_tpu.models.npc_policy import init_policy

        policy = init_policy(jax.random.PRNGKey(5))
    return mc, mesh, st, inputs, policy


def _mega_variant(st, i: int):
    import jax
    import jax.numpy as jnp

    n_dev = st.pos.shape[0]
    return st.replace(
        rng=jax.vmap(jax.random.PRNGKey)(
            jnp.arange(n_dev) + 1000 + 31 * i),
        pos=st.pos + jnp.float32(0.001 * (i + 1)),
    )


def _mega_tick_ms(tick, st, inputs, policy, ticks: int):
    """Scan-marginal mesh tick timing: the SHARED 2x-minus-1x protocol
    (``_marginal_full_tick_ms`` — one harness with the single-chip
    side, so per_chip_efficiency compares identical measurements),
    driving the shard_map'd mega step through ``lax.scan`` with zero
    host syncs per tick. Returns (per_tick_s, scale_2x, compiled_run —
    AOT-compiled, so the devprof audit costs zero extra compiles)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def mk(length):
        @jax.jit
        def run(state):
            def body(s, _):
                s2, outs = tick(s, inputs, policy)
                b = outs.base
                chk = (b.enter_n.sum() + b.leave_n.sum()
                       + b.sync_n.sum()).astype(jnp.float32) \
                    + b.sync_vals.sum() \
                    + outs.global_alive[0].astype(jnp.float32)
                return s2, chk
            st2, checks = lax.scan(body, state, None, length=length)
            return checks.sum() + st2.pos.sum()
        return run

    return _marginal_full_tick_ms(
        mk, lambda i: _mega_variant(st, i), ticks, aot_first=True)


def _mega_gauges(tick, st, inputs, policy, ticks: int,
                 base_ms: float) -> tuple[dict, dict]:
    """One on-device scan over the mega tick accumulating (a) the
    in-graph telemetry lanes (ops/telemetry.py mega set — zero host
    syncs, one drain) and (b) scalar comms gauges: halo/migrate demand
    maxima, dropped/migrated totals, mesh event volumes. Returns
    (gauges, op_stats)."""
    import numpy as np

    import jax
    import jax.numpy as jnp
    from jax import lax

    from goworld_tpu.ops import telemetry

    @jax.jit
    def run(state):
        acc0 = telemetry.telemetry_init(False, mega=True)
        g0 = (jnp.zeros((), jnp.int32),   # halo demand max
              jnp.zeros((), jnp.int32),   # migrate demand max
              jnp.zeros((), jnp.int32),   # migrate dropped total
              jnp.zeros((), jnp.int32),   # arrivals (migrations) total
              jnp.zeros((), jnp.int32),   # enter events total
              jnp.zeros((), jnp.int32))   # leave events total

        def body(carry, _):
            s, acc, g = carry
            s2, outs = tick(s, inputs, policy)
            acc = telemetry.telemetry_update_mega(acc, outs, base_ms)
            g = (jnp.maximum(g[0], outs.halo_demand.max()),
                 jnp.maximum(g[1], outs.migrate_demand.max()),
                 g[2] + outs.migrate_dropped.sum(),
                 g[3] + outs.arr_n.sum(),
                 g[4] + outs.base.enter_n.sum(),
                 g[5] + outs.base.leave_n.sum())
            return (s2, acc, g), 0
        (s2, acc, g), _ = lax.scan(body, (state, acc0, g0), None,
                                   length=ticks)
        return acc, g

    acc, g = run(_mega_variant(st, 9))
    op_stats = telemetry.telemetry_drain(acc, False, mega=True)
    gv = [int(np.asarray(x)) for x in g]
    gauges = {
        "halo_demand_max": gv[0],
        "migrate_demand_max": gv[1],
        "migrate_dropped_total": gv[2],
        "migrated_total": gv[3],
        "aoi_enter_events": gv[4],
        "aoi_leave_events": gv[5],
        "ticks": ticks,
    }
    return gauges, op_stats


def measure_multichip(n_total: int, ticks: int) -> dict:
    """The mesh headline (ISSUE 10): `entity_ticks_per_sec_mesh` from a
    scan-driven megaspace tick across every visible device, with
    per-chip efficiency vs the same-capacity 1-chip number, a
    border_churn phase (hotspot drift forcing sustained tile
    crossings), comms-demand gauges, and the device-plane stamps
    (cost_report + multichip roofline_audit)."""
    import jax

    from goworld_tpu.parallel.megaspace import make_mega_tick
    from goworld_tpu.utils import devprof

    mc, mesh, st, inputs, policy = build_mega(n_total)
    n_dev = mc.n_dev
    alive_total = int(jax.numpy.asarray(st.alive).sum())
    tick = make_mega_tick(mc, mesh)
    per_tick, scale, run_compiled = _mega_tick_ms(
        tick, st, inputs, policy, ticks)
    value = alive_total / per_tick
    grid_kw = _model_grid_kw(mc.cfg, mc.cfg.capacity)
    mega_kw = {
        "n_dev": n_dev, "halo_cap": mc.halo_cap,
        "migrate_cap": mc.migrate_cap, "mesh_shape": mc.mesh_shape,
        "halo_impl": mc.halo_impl, "dirty_frac": 1.0,
    }
    result: dict = {
        "headline": {
            "metric": "entity_ticks_per_sec_mesh",
            "entity_ticks_per_sec_mesh": round(value, 1),
            "per_chip": round(value / n_dev, 1),
            "n_entities": alive_total,
            "n_devices": n_dev,
            "capacity_per_chip": mc.cfg.capacity,
            "mesh_shape": list(mc.mesh_shape or (n_dev, 1)),
            "tick_ms": round(1000.0 * per_tick, 3),
            "ticks_timed": ticks,
            "scale_2x": round(scale, 2),
            "halo_impl": mc.halo_impl,
            "halo_cap": mc.halo_cap,
            "migrate_cap": mc.migrate_cap,
            # resolved kernel stamps (headline convention; megaspace
            # is statically skinless)
            "sweep_impl": mc.cfg.grid.sweep_impl,
            "topk_impl": mc.cfg.grid.topk_impl,
            "sort_impl": mc.cfg.grid.sort_impl,
            "skin": 0.0,
            "platform": jax.devices()[0].platform,
            "device": _device_stamp(),
        },
    }
    if not (1.5 <= scale <= 3.0):
        result["headline"]["timing_suspect"] = (
            f"2x scan took {scale:.2f}x the 1x time"
        )
    # same-capacity 1-chip reference: the single-space tick at the
    # per-chip alive count, same resolved kernels (skin pinned 0 to
    # match the stateless mega sweep), same scan-marginal protocol
    try:
        ref_n = max(64, alive_total // n_dev)
        rcfg, rst, rinputs = build(ref_n, CLIENT_FRAC, {"skin": 0.0},
                                   force_behavior="random_walk")
        ref_tick, ref_scale = _scenario_tick_ms(rcfg, rst, rinputs,
                                                None, ticks)
        ref_value = ref_n / ref_tick
        result["headline"]["one_chip_value"] = round(ref_value, 1)
        result["headline"]["one_chip_n"] = ref_n
        result["headline"]["per_chip_efficiency"] = round(
            (value / n_dev) / ref_value, 4)
        if not (1.5 <= ref_scale <= 3.0):
            result["headline"]["one_chip_timing_suspect"] = round(
                ref_scale, 2)
    except Exception as exc:
        result["headline"]["per_chip_efficiency"] = None
        result["headline"]["one_chip_error"] = str(exc)[:200]
    log(f"multichip@{alive_total}x{n_dev}dev: "
        f"{result['headline']['tick_ms']} ms/tick, "
        f"mesh={value:.0f}, eff="
        f"{result['headline'].get('per_chip_efficiency')}")

    # comms gauges + telemetry lanes at rest (the headline workload)
    try:
        result["gauges"], result["op_stats"] = _mega_gauges(
            tick, st, inputs, policy, max(ticks, 4),
            result["headline"]["tick_ms"])
    except Exception as exc:
        result["gauges"] = {"error": str(exc)[:200]}
        result["op_stats"] = {"error": str(exc)[:200]}
    # the mesh round's workload-signature block (same grammar as the
    # BENCH stamp and the live /workload endpoint; the mega lanes add
    # halo/migrate demand to the reduction's inputs)
    result["workload_signature"] = _signature_stamp(
        result["op_stats"], None)

    # border_churn phase: hotspot-style drift (scenarios/behaviors.py
    # kernels — megaspace honors the scenario knob now) pulls the whole
    # population toward an orbiting attractor, forcing sustained tile
    # crossings, so all_to_all migration + ghost traffic are measured
    # under load, not at rest
    try:
        churn_spec = get_scenario(MULTI_CHURN)
        # drift speed raised (the dryrun's border-crossing speed, 5x
        # the headline movers) so crossings SUSTAIN inside the
        # measured window instead of needing thousands of ticks to
        # reach a border — the phase exists to price comms under load
        churn_speed = float(os.environ.get("BENCH_CHURN_SPEED", 25.0))
        cmc, cmesh, cst, cin, cpol = build_mega(
            n_total, scenario=churn_spec, npc_speed=churn_speed)
        ctick = make_mega_tick(cmc, cmesh)
        cper, cscale, _ = _mega_tick_ms(ctick, cst, cin, cpol, ticks)
        churn: dict = {
            "scenario": MULTI_CHURN,
            "npc_speed": churn_speed,
            "tick_ms": round(1000.0 * cper, 3),
            "entity_ticks_per_sec_mesh": round(alive_total / cper, 1),
            "scale_2x": round(cscale, 2),
        }
        cg, _cop = _mega_gauges(ctick, cst, cin, cpol, max(ticks, 16),
                                churn["tick_ms"])
        churn["gauges"] = cg
        result["phases"] = {"border_churn": churn}
        log(f"border_churn@{alive_total}: {churn['tick_ms']} ms/tick, "
            f"migrated={cg.get('migrated_total')}, "
            f"halo_max={cg.get('halo_demand_max')}")
    except Exception as exc:
        result["phases"] = {"border_churn": {"error": str(exc)[:200]}}

    # device-plane stamps (PR 8 convention: real, or an honest error)
    if os.environ.get("BENCH_DEVPROF", "1") == "1":
        try:
            cr = devprof.cost_report(
                run_compiled, name="mega_tick_scan",
                config={**devprof.grid_config_key(mc.cfg.grid),
                        "halo_impl": mc.halo_impl},
                n=alive_total, n_devices=n_dev,
            )
            result["cost_report"] = cr.as_dict()
        except Exception as exc:
            cr = None
            result["cost_report"] = {"error": str(exc)[:200]}
        try:
            result["roofline_audit"] = devprof.roofline_audit_multichip(
                result["headline"]["tick_ms"], cr, alive_total,
                grid_kw, mega_kw,
                platform=result["headline"]["platform"],
            )
        except Exception as exc:
            result["roofline_audit"] = {"error": str(exc)[:200]}
    else:
        result["cost_report"] = {"skipped": "BENCH_DEVPROF=0"}
        result["roofline_audit"] = {"skipped": "BENCH_DEVPROF=0"}
    return result


def _device_stamp() -> dict:
    """devprof.device_stamp(), imported late (the parent is jax-free)."""
    from goworld_tpu.utils import devprof

    return devprof.device_stamp()


def _child_setup() -> dict:
    """What every chip-owning bench child does first: refuse to run
    without an accelerator (a CPU timing must never land under a device
    metric's name — tests call the measure functions directly instead),
    point JAX at the shared compile cache, and return the device stamp
    every result carries."""
    from goworld_tpu.utils import compile_cache

    compile_cache.setup()
    stamp = _device_stamp()
    if stamp["platform"] == "cpu":
        log(f"no accelerator: JAX found {stamp}; refusing to measure")
        print(json.dumps({"stage": "refused", "device": stamp,
                          "error": "no accelerator"}), flush=True)
        raise SystemExit(2)
    return stamp


def multichip_child_main(args) -> int:
    _child_setup()
    res = measure_multichip(args.n, args.ticks)
    res["stage"] = "multichip"
    print(json.dumps(res), flush=True)
    return 0


def multichip_parent_main() -> int:
    """--multichip: one child that owns the mesh; emits ONE JSON line in
    the MULTICHIP artifact shape. No accelerator, or no completed
    stage: an error record and a non-zero exit."""
    stages, note = run_child(MULTI_N, CHILD_TIMEOUT,
                             extra_args=["--multichip"],
                             ticks=MULTI_TICKS)
    child = next((s for s in stages if s.get("stage") == "multichip"),
                 None)
    artifact: dict = {
        "n_devices": 0,
        "rc": 0 if child is not None else 1,
        "ok": False,
        "skipped": False,
        "tail": "",
    }
    if child is not None:
        child.pop("stage", None)
        hl = child.get("headline", {})
        artifact["n_devices"] = hl.get("n_devices", 0)
        artifact["ok"] = bool(hl.get("entity_ticks_per_sec_mesh", 0)
                              and "timing_suspect" not in hl)
        artifact["tail"] = (
            f"multichip({hl.get('n_devices')}): "
            f"{hl.get('entity_ticks_per_sec_mesh')} entity-ticks/s/mesh "
            f"at {hl.get('n_entities')} entities "
            f"({hl.get('tick_ms')} ms/tick, per_chip_efficiency="
            f"{hl.get('per_chip_efficiency')}, "
            f"halo_impl={hl.get('halo_impl')}, "
            f"platform={hl.get('platform')})"
        )
        artifact.update(child)
    else:
        artifact["tail"] = "no multichip stage completed"
        artifact["error"] = note or "no multichip stage completed"
    artifact["attempts"] = [{
        "attempt": 1, "stages": [s.get("stage") for s in stages],
        "error": note or None}]
    print(json.dumps(artifact), flush=True)
    return 0 if child is not None else 1


def child_main(args) -> int:
    """Staged measurement: smoke first, then full. One JSON line per stage
    on stdout; the parent harvests whatever stages completed."""
    _child_setup()
    if os.environ.get("BENCH_RNG"):
        # opt-in PRNG impl for the behavior kernels ("rbg" rides the
        # TPU hardware RNG instead of ~20 threefry rounds per draw);
        # affects only WHICH random walk is taken, never its statistics
        import jax

        jax.config.update("jax_default_prng_impl", os.environ["BENCH_RNG"])
    stages = [("smoke", min(SMOKE_N, args.n), SMOKE_T, False)]
    if args.n > SMOKE_N:
        stages.append(("full", args.n, args.ticks, args.phases))
    else:
        stages[0] = ("full", args.n, args.ticks, args.phases)
    overrides: dict = {}
    atlog = None
    for name, n, ticks, phases in stages:
        if name == "full" and os.environ.get("BENCH_AUTOTUNE", "1") == "1":
            if n > int(os.environ.get("BENCH_AUTOTUNE_N", 131072)):
                # a candidate the chip's compiler refuses is a finding:
                # it stops the run, it is not replaced by the defaults
                overrides, atlog = autotune_sweep()
        t0 = time.perf_counter()
        r = measure(n, ticks, args.client_frac, phases,
                    overrides if name == "full" else None)
        p99_args = r.pop("_p99_args", None)
        r["stage"] = name
        r["stage_wall_s"] = round(time.perf_counter() - t0, 1)
        if name == "full" and atlog is not None:
            r["autotune_sweep_ms"] = atlog
            if overrides:
                r["autotuned_grid"] = overrides
        if name == "full" \
                and os.environ.get("BENCH_BACKHALF_AB", "1") == "1":
            # fused-vs-split back half A/B, recorded into the round
            # artifact on every platform (ISSUE 6: the CPU interpret
            # number documents why fused stays non-default off-TPU;
            # the TPU number is the round's headline lever). Runs at
            # the 131K per-chip shard, never the full 1M (interpret
            # mode at 1M would eat the child timeout).
            ab_n = min(n, int(os.environ.get("BENCH_BACKHALF_AB_N",
                                             131072)))
            try:
                r["backhalf_ab"] = backhalf_ab(ab_n)
            except Exception as exc:  # belt over backhalf_ab's braces
                r["backhalf_ab"] = {"error": str(exc)[:200]}
        if name == "full" \
                and os.environ.get("BENCH_PRECISION_AB", "1") == "1":
            # quantized-plane on/off A/B (ISSUE 12): measured marginal
            # + modeled bytes both ways, every platform, every round
            ab_n = min(n, int(os.environ.get("BENCH_PRECISION_AB_N",
                                             131072)))
            try:
                r["precision_ab"] = precision_ab(ab_n)
            except Exception as exc:
                r["precision_ab"] = {"error": str(exc)[:200]}
        print(json.dumps(r), flush=True)
        if name == "full" and scenario_selection():
            # per-scenario headline blocks, AFTER the headline line is
            # safely on stdout (same contract as p99: an adversarial-
            # workload wedge must never zero out the measured number)
            try:
                sc = measure_scenarios(n, overrides)
                sc["stage"] = "scenarios"
                print(json.dumps(sc), flush=True)
            except Exception as exc:
                log(f"scenario stage failed: {exc}")
        if name == "full" \
                and os.environ.get("BENCH_GOVERNOR") == "1":
            # the governor acceptance schedule (ISSUE 13), AFTER the
            # headline line is safely on stdout (the p99/scenario
            # contract: an autotune wedge must never zero the round)
            try:
                g = measure_governor(n, overrides)
            except Exception as exc:
                log(f"governor stage failed: {exc}")
                g = {"error": str(exc)[:300]}
            g["stage"] = "governor"
            print(json.dumps(g), flush=True)
        if name == "full" \
                and os.environ.get("BENCH_SYNC_AGE", "1") == "1":
            # the end-to-end sync-age loopback (ISSUE 15), AFTER the
            # headline line is safely on stdout (the p99/scenario
            # contract: a host-harness wedge must never zero the round)
            try:
                sa = measure_sync_age()
            except Exception as exc:
                log(f"sync_age stage failed: {exc}")
                sa = {"error": str(exc)[:300]}
            sa["stage"] = "sync_age"
            print(json.dumps(sa), flush=True)
        if name == "full" \
                and os.environ.get("BENCH_RESIDENCY", "1") == "1":
            # the serve-loop residency plane (ISSUE 16), AFTER the
            # headline line is safely on stdout (same contract: an
            # instrumented-World wedge must never zero the round)
            try:
                resid = measure_residency(n)
            except Exception as exc:
                log(f"residency stage failed: {exc}")
                resid = {"error": str(exc)[:300]}
            resid["stage"] = "residency"
            print(json.dumps(resid), flush=True)
        if name == "full" \
                and os.environ.get("BENCH_AUDIT", "1") == "1":
            # the correctness-audit plane (ISSUE 17), AFTER the
            # headline line is safely on stdout (same contract: a
            # ledger/oracle wedge must never zero the round)
            try:
                aud = measure_audit(n)
            except Exception as exc:
                log(f"audit stage failed: {exc}")
                aud = {"error": str(exc)[:300]}
            aud["stage"] = "audit"
            print(json.dumps(aud), flush=True)
        if name == "full" \
                and os.environ.get("BENCH_FAILOVER", "1") == "1":
            # the hot-standby failover plane (ISSUE 18), AFTER the
            # headline line is safely on stdout (same contract: a
            # replication/promotion wedge must never zero the round)
            try:
                fov = measure_failover(n)
            except Exception as exc:
                log(f"failover stage failed: {exc}")
                fov = {"error": str(exc)[:300]}
            fov["stage"] = "failover"
            print(json.dumps(fov), flush=True)
        if name == "full" \
                and os.environ.get("BENCH_REBALANCE", "1") == "1":
            # the self-healing rebalance plane (ISSUE 19), AFTER the
            # headline line is safely on stdout (same contract: a
            # handoff wedge must never zero the round)
            try:
                rbl = measure_rebalance(n)
            except Exception as exc:
                log(f"rebalance stage failed: {exc}")
                rbl = {"error": str(exc)[:300]}
            rbl["stage"] = "rebalance"
            print(json.dumps(rbl), flush=True)
        if name == "full" \
                and os.environ.get("BENCH_RESIDENT_AB", "1") == "1":
            # the resident-world A/B (ISSUE 20), AFTER the headline
            # line is safely on stdout (same contract: a two-world
            # wedge must never zero the round)
            try:
                rab = measure_resident_ab(n)
            except Exception as exc:
                log(f"resident_ab stage failed: {exc}")
                rab = {"error": str(exc)[:300]}
            rab["stage"] = "resident_ab"
            print(json.dumps(rab), flush=True)
        if name == "full" and p99_args is not None \
                and os.environ.get("BENCH_SKIP_P99") != "1":
            # separate stage AFTER the headline line is on stdout: a
            # failure in these 64 per-tick roundtrips can no longer
            # zero out the measured throughput
            try:
                p = measure_p99(*p99_args)
                p["stage"] = "p99"
                p["p99_n"] = n
                print(json.dumps(p), flush=True)
            except Exception as exc:
                log(f"p99 measurement failed: {exc}")
            # the north-star p99 claim is at the PER-CHIP shard of the
            # 1M/v5e-8 target (131072 entities), not the full single-chip
            # 1M load — measure it on a fresh shard-sized world too
            shard_n = int(os.environ.get("BENCH_P99_SHARD_N", 131072))
            if shard_n and shard_n < n:
                try:
                    # same grid config as the headline full stage (incl.
                    # any autotuned overrides): the two claims in one
                    # report must describe the same config
                    scfg, sst, sinputs = build(shard_n, args.client_frac,
                                               overrides)
                    spolicy = None
                    if scfg.behavior == "mlp" or (
                            scfg.scenario is not None
                            and scfg.scenario.needs_policy):
                        from goworld_tpu.models.npc_policy import init_policy
                        import jax as _jax

                        spolicy = init_policy(_jax.random.PRNGKey(5))
                    p = measure_p99(scfg, sst, sinputs, spolicy)
                    p["stage"] = "p99_shard"
                    p["p99_n"] = shard_n
                    print(json.dumps(p), flush=True)
                except Exception as exc:
                    log(f"shard p99 measurement failed: {exc}")
    return 0


# --------------------------------------------------------------- parent ----

def run_child(n: int, timeout: float, phases: bool | None = None,
              live: list | None = None,
              extra_args: list | None = None,
              ticks: int | None = None) -> tuple[list, str]:
    """Run THE child; returns (parsed stage dicts, failure note).

    Child stdout is STREAMED (reader thread), not buffered until exit:
    stages the child already printed are visible immediately — in
    particular to the parent's signal handler, so a driver-side kill
    mid-child still ships every completed stage. ``live`` (optional) is
    a shared list the parsed stages are also appended to for exactly
    that consumer."""
    import collections
    import threading

    cmd = [
        sys.executable, os.path.abspath(__file__), "--child",
        "--n", str(n), "--ticks", str(T if ticks is None else ticks),
        "--client-frac", str(CLIENT_FRAC),
    ]
    cmd.extend(extra_args or [])
    if PHASES if phases is None else phases:
        cmd.append("--phases")
    log(f"spawn child: n={n} timeout={timeout:.0f}s")
    proc = subprocess.Popen(
        cmd, cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
    )
    stages: list = []
    err_tail: collections.deque = collections.deque(maxlen=12)

    def read_out() -> None:
        for line in proc.stdout:
            line = line.strip()
            if line.startswith("{"):
                try:
                    s = json.loads(line)
                except json.JSONDecodeError:
                    continue
                stages.append(s)
                if live is not None:
                    live.append(s)

    def read_err() -> None:
        for line in proc.stderr:
            err_tail.append(line.rstrip())

    t_out = threading.Thread(target=read_out, daemon=True)
    t_err = threading.Thread(target=read_err, daemon=True)
    t_out.start()
    t_err.start()
    note = ""
    try:
        rc = proc.wait(timeout=timeout)
        if rc != 0:
            last = err_tail[-1][:300] if err_tail else "no stderr"
            note = f"rc={rc}: {last}"
    except subprocess.TimeoutExpired:
        proc.kill()
        proc.wait()
        note = f"timeout after {timeout:.0f}s"
    t_out.join(timeout=10)
    t_err.join(timeout=10)
    for line in list(err_tail):
        log(f"  child# {line[:240]}")
    return stages, note


# plane blocks the full stage's artifact ALWAYS carries (the
# bench_schema contract): stage name -> the env knob that skips it
# ("" = on by default; governor is opt-in through --governor)
_BLOCK_KNOBS = {
    "governor": "BENCH_GOVERNOR", "sync_age": "BENCH_SYNC_AGE",
    "residency": "BENCH_RESIDENCY", "audit": "BENCH_AUDIT",
    "failover": "BENCH_FAILOVER", "rebalance": "BENCH_REBALANCE",
    "resident_ab": "BENCH_RESIDENT_AB",
}


def compose(stages: list, note: str) -> dict:
    """The parent's single stdout line, from whatever stage lines the
    child completed. A headline exists only if a FULL stage ran on an
    accelerator; the latency/scenario/plane blocks attach to it, a
    smoke-only run is flagged ``partial``, and with no stage at all —
    or a child that refused for want of a chip — the record is an
    error with no metric and no value."""
    by = {s.get("stage"): s for s in stages}
    full = by.get("full")
    chosen = full or by.get("smoke")
    attempts = [{"attempt": 1, "stages": [s.get("stage") for s in stages],
                 "error": note or None}]
    if chosen is None:
        refused = by.get("refused", {})
        return {"error": refused.get("error")
                or f"no stage completed ({note or 'child printed nothing'})",
                "device": refused.get("device"),
                "attempts": attempts}
    chosen = dict(chosen)
    cp99, cp99s, csc = by.get("p99"), by.get("p99_shard"), \
        by.get("scenarios")
    if full is not None and cp99 is not None:
        for k in ("tick_p50_ms", "tick_p99_ms",
                  "p99_includes_host_roundtrip",
                  "p99_loop_carried_fetch", "p99_samples"):
            if k in cp99:
                chosen[k] = cp99[k]
        # consistency gate: with the loop-carried fetch each sample
        # covers a full tick plus a host roundtrip, so p50 below ~70%
        # of the scan-marginal tick cost means the fetch chain did not
        # serialize — flag it, never report it silently
        tick_ms = chosen.get("tick_ms")
        if tick_ms and cp99.get("tick_p50_ms", 0) < 0.7 * tick_ms:
            chosen["p99_suspect"] = (
                f"p50 {cp99['tick_p50_ms']} ms < 0.7x scan-marginal "
                f"tick {tick_ms} ms; latency chain did not serialize"
            )
    if full is not None and cp99s is not None:
        chosen["shard_p99"] = {
            k: cp99s[k]
            for k in ("p99_n", "tick_p50_ms", "tick_p99_ms",
                      "p99_samples")
            if k in cp99s
        }
    if full is not None and csc is not None:
        # the per-scenario headline blocks ride the artifact next to
        # the single-workload headline (ISSUE 7)
        chosen["scenarios"] = csc.get("scenarios", {})
        chosen["scenario_n"] = csc.get("n")
        chosen["scenario_ticks"] = csc.get("ticks")
    if full is not None:
        for block, knob in _BLOCK_KNOBS.items():
            wanted = os.environ.get(
                knob, "0" if block == "governor" else "1") == "1"
            if block in by:
                chosen[block] = {k: v for k, v in by[block].items()
                                 if k != "stage"}
            elif wanted:
                chosen[block] = {
                    "error": f"{block} stage never completed"}
            else:
                chosen[block] = {
                    "skipped": "--governor not requested"
                    if block == "governor" else f"{knob}=0"}
    value = chosen.pop("value")
    result = {
        "metric": "entity_ticks_per_sec_per_chip",
        "value": value,
        "unit": "entity-ticks/s/chip",
        "vs_baseline": round(value / BASELINE_ENTITY_TICKS_PER_CHIP, 3),
        **chosen,
    }
    if full is None:
        result["partial"] = True  # the full run never landed
    result["attempts"] = attempts
    return result


def parent_main() -> int:
    live_stages: list = []   # the child's streamed stages
    emitted: list = []
    final: dict = {}

    def emit_once(note: str) -> None:
        if emitted:
            return
        emitted.append(True)
        final.update(compose(list(live_stages), note))
        print(json.dumps(final), flush=True)

    def on_term(signum, frame):
        log(f"signal {signum}: emitting best-so-far result before exit")
        try:
            emit_once(f"signal {signum}")
        finally:
            os._exit(3)

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)

    _stages, note = run_child(N, CHILD_TIMEOUT, live=live_stages)
    emit_once(note)
    if "error" in final or final.get("partial") \
            or final.get("timing_suspect"):
        return 1
    if os.environ.get("BENCH_CHECK_SLO") == "1":
        # --check-slo: the stamped verdict becomes a GATE — rc != 0
        # when the measured p99 misses the budget
        slo = final.get("slo")
        if not isinstance(slo, dict) or "skipped" in slo \
                or "error" in slo:
            # the gate is UNSATISFIABLE, not failed: no verdict was
            # measured (BENCH_PHASES=0 / BENCH_SLO=0 skip the
            # telemetry scan, or it errored) — still rc != 0, but say
            # why instead of an opaque FAIL
            log(f"--check-slo: no slo verdict measured ({slo}); "
                "BENCH_PHASES=0/BENCH_SLO=0 skip the telemetry scan, "
                "and only a full-stage headline carries one")
            return 4
        if not slo.get("pass"):
            log(f"--check-slo: FAIL ({slo})")
            return 4
        log("--check-slo: PASS")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--child", action="store_true")
    ap.add_argument(
        "--multichip", action="store_true",
        help="mesh headline: the scan-driven megaspace tick across "
             "every visible device (entity_ticks_per_sec_mesh + "
             "per_chip_efficiency + border_churn, stamped in the "
             "MULTICHIP artifact shape)")
    ap.add_argument("--n", type=int, default=N)
    ap.add_argument("--ticks", type=int, default=T)
    ap.add_argument("--client-frac", type=float, default=CLIENT_FRAC)
    ap.add_argument("--phases", action="store_true", default=PHASES)
    ap.add_argument(
        "--check-slo", action="store_true",
        help="gate the exit code on the stamped slo verdict (the "
             "in-graph tick_ms histogram vs BENCH_SLO_MS, default "
             "16 ms p99 — the paper target)")
    ap.add_argument(
        "--governor", action="store_true",
        help="run the online kernel-governor acceptance schedule "
             "(ISSUE 13): one evolving population through "
             f"{GOVERNOR_PHASES} while the autotune policy hot-swaps "
             "the kernel config from drained signature windows; "
             "stamps a `governor` block (per-phase chosen config, "
             "swap latency in ticks, throughput vs best/worst static) "
             "into the round artifact")
    ap.add_argument(
        "--scenario", default=None, metavar="NAME|all|none",
        help="per-scenario headline blocks to stamp (scenario registry "
             f"names: {'|'.join(scenario_names())}; comma list, 'all' "
             "(the default via BENCH_SCENARIOS), or 'none')")
    args = ap.parse_args()
    if args.check_slo:
        # children + parent share the knob through the env (like
        # --scenario); the gate itself is applied in parent_main after
        # the artifact is safely on stdout
        os.environ["BENCH_CHECK_SLO"] = "1"
    if args.governor:
        # children inherit through the env, like --scenario; the
        # phase names fail fast pre-spawn with the registry list
        os.environ["BENCH_GOVERNOR"] = "1"
        for _nm in (s.strip() for s in GOVERNOR_PHASES.split(",")
                    if s.strip()):
            try:
                get_scenario(_nm)
            except KeyError as exc:
                raise SystemExit(f"--governor: {exc.args[0]}")
    if args.scenario is not None:
        # children inherit the selection through the env (one knob for
        # both the CLI and env-driven invocations)
        os.environ["BENCH_SCENARIOS"] = args.scenario
        global SCENARIOS_SEL
        SCENARIOS_SEL = args.scenario
        try:
            scenario_selection()  # unknown names fail fast, pre-spawn
        except KeyError as exc:
            raise SystemExit(f"--scenario: {exc.args[0]}")
    if args.multichip:
        return (multichip_child_main(args) if args.child
                else multichip_parent_main())
    if args.child:
        return child_main(args)
    return parent_main()


if __name__ == "__main__":
    sys.exit(main())
