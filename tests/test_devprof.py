"""Device-plane observability suites (ISSUE 8).

Covers the three tentpole pieces end to end on CPU:

* the XLA cost auditor (`utils/devprof.py`): CostReport smoke for
  EVERY bench autotune candidate shape plus the single-space, vmapped
  and scenario tick forms, and the live World provider behind
  debug_http ``/costs``;
* the in-graph telemetry lanes (`ops/telemetry.py`): bucket-count
  parity against a host-side recompute over the SAME tick series
  (bit-exact, skin on/off, scenario on/off), zero host syncs asserted
  via ``jax.transfer_guard`` and one-trace-per-config asserted via the
  TRACE_COUNTS counter;
* the roofline audit + SLO math (`hist_quantile`,
  ``slo_from_histogram``, ``roofline_audit``).
"""

import importlib.util
import json
import os
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from goworld_tpu.core.step import tick_body
from goworld_tpu.ops import telemetry
from goworld_tpu.utils import devprof

pytestmark = pytest.mark.devprof

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_bench():
    spec = importlib.util.spec_from_file_location(
        "bench_devprof_under_test", os.path.join(REPO, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


BENCH = _load_bench()


# =======================================================================
# histogram quantiles + SLO verdicts (pure math)
# =======================================================================
def test_hist_quantile_bucket_uppers():
    edges = (1.0, 2.0, 4.0)
    assert devprof.hist_quantile(edges, [1, 1, 1, 0], 0.50) == 2.0
    assert devprof.hist_quantile(edges, [1, 1, 1, 0], 0.99) == 4.0
    assert devprof.hist_quantile(edges, [3, 0, 0, 0], 0.99) == 1.0
    # +Inf tail reports inf (conservative: true value unknown upward)
    assert devprof.hist_quantile(edges, [0, 0, 0, 2], 0.50) \
        == float("inf")
    assert np.isnan(devprof.hist_quantile(edges, [0, 0, 0, 0], 0.5))


def test_slo_from_histogram_pass_and_fail():
    edges = (1.0, 2.0, 16.0, 33.0)
    ok = devprof.slo_from_histogram(edges, [50, 49, 1, 0, 0], 16.0)
    # rank 99 of 100 falls in the <=2ms bucket; the 1 outlier at
    # <=16ms is the p100 tail
    assert ok["pass"] and ok["p99_ms"] == 2.0 and ok["samples"] == 100
    bad = devprof.slo_from_histogram(edges, [0, 0, 0, 5, 0], 16.0)
    assert not bad["pass"] and bad["p99_ms"] == 33.0
    # an empty histogram can never pass
    empty = devprof.slo_from_histogram(edges, [0, 0, 0, 0, 0], 16.0)
    assert not empty["pass"] and empty["samples"] == 0


def test_slo_overflow_and_empty_are_json_safe():
    """Samples in the +Inf bucket (a 1M CPU tick past the last edge)
    and empty histograms must stamp None, never the non-RFC
    Infinity/NaN tokens, into the BENCH artifacts."""
    edges = (1.0, 2.0)
    over = devprof.slo_from_histogram(edges, [0, 0, 4], 16.0)
    assert over["p99_ms"] is None and over["overflow"]
    assert not over["pass"] and over["samples"] == 4
    empty = devprof.slo_from_histogram(edges, [0, 0, 0], 16.0)
    assert empty["p50_ms"] is None and empty["overflow"]
    for blob in (json.dumps(over), json.dumps(empty)):
        assert "Infinity" not in blob and "NaN" not in blob


# =======================================================================
# CostReport: every autotune candidate shape + tick forms
# =======================================================================
N = 256


def _candidate_ids():
    return [
        ",".join(f"{k}={v}" for k, v in ov.items()) or "default"
        for _sel, ov in BENCH.AUTOTUNE_CANDIDATES
    ]


@pytest.mark.parametrize(
    "selectable,overrides", BENCH.AUTOTUNE_CANDIDATES,
    ids=_candidate_ids(),
)
def test_cost_report_every_autotune_candidate(selectable, overrides,
                                              monkeypatch):
    """cost_analysis + memory_analysis succeed for the FULL tick at
    every autotune candidate config (a candidate whose compiled
    artifact can't be audited would hide from the device plane)."""
    for var in BENCH.GRID_ENV.values():
        monkeypatch.delenv(var, raising=False)
    cfg, st, inputs = BENCH.build(N, 0.02, overrides)

    def tick(state):
        s2, out = tick_body(cfg, state, inputs, None)
        return s2.pos.sum() + out.sync_n

    rep = devprof.cost_report(
        tick, st, name=f"tick:{_key(overrides)}",
        config=devprof.grid_config_key(cfg.grid), n=N)
    assert rep.error is None, rep.error
    assert rep.flops and rep.flops > 0
    assert rep.bytes_accessed and rep.bytes_accessed > 0
    assert rep.peak_hbm_bytes and rep.peak_hbm_bytes > 0
    d = rep.as_dict()
    # the per-config key carries the resolved kernel stamps
    for stamp in ("sweep_impl", "sort_impl", "skin"):
        assert stamp in d["key"]
    assert d["platform"] == "cpu"


def _key(ov):
    return ",".join(f"{k}={v}" for k, v in ov.items()) or "default"


def test_cost_report_vmapped_and_scenario_ticks():
    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.entity.manager import _make_local_tick
    from goworld_tpu.ops.aoi import GridSpec
    from goworld_tpu.parallel.mesh import create_multi_state
    from goworld_tpu.core.step import TickInputs
    from goworld_tpu.scenarios.spec import get_scenario

    # vmapped multi-space form (the production n_spaces > 1 local step)
    cfg = WorldConfig(capacity=64, grid=GridSpec(
        radius=10.0, extent_x=40.0, extent_z=40.0))
    step = _make_local_tick(cfg, 2)
    state = create_multi_state(cfg, 2, seed=0)
    inputs = jax.tree.map(
        lambda x: jnp.broadcast_to(x, (2,) + x.shape),
        TickInputs.empty(cfg))
    rep = devprof.cost_report(step, state, inputs, None,
                              name="vmapped.tick", n=128)
    assert rep.error is None and rep.bytes_accessed > 0

    # scenario form (heterogeneous vmapped lax.switch behaviors)
    spec = get_scenario("hotspot")
    cfg2, st2, in2 = BENCH.build(64, 0.02, scenario=spec)
    policy = None
    if spec.needs_policy:
        from goworld_tpu.models.npc_policy import init_policy

        policy = init_policy(jax.random.PRNGKey(0))

    def tick(state):
        s2, out = tick_body(cfg2, state, in2, policy)
        return s2.pos.sum() + out.sync_n

    rep2 = devprof.cost_report(tick, st2, name="scenario.tick", n=64)
    assert rep2.error is None and rep2.flops > 0


def test_cost_report_accepts_precompiled_executable():
    @jax.jit
    def f(x):
        return (x * 2).sum()

    x = jnp.ones((32, 32))
    compiled = f.lower(x).compile()
    rep = devprof.cost_report(compiled, name="precompiled")
    assert rep.error is None and rep.bytes_accessed > 0


def test_cost_report_folds_failures_instead_of_raising():
    def broken(x):
        raise RuntimeError("boom")

    rep = devprof.cost_report(broken, jnp.ones(4), name="broken")
    assert rep.error is not None and "boom" in rep.error


def test_world_registers_costs_provider():
    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.entity.manager import World
    from goworld_tpu.ops.aoi import GridSpec

    devprof.reset()
    try:
        w = World(WorldConfig(capacity=32, grid=GridSpec(
            radius=10.0, extent_x=40.0, extent_z=40.0)), n_spaces=1)
        snap = devprof.snapshot()
        assert "world.tick" in snap["providers"]
        assert snap["reports"] == {}  # lazy: nothing ran yet
        rep = w.cost_report()
        assert rep.error is None, rep.error
        assert rep.flops > 0 and rep.config["sweep_impl"]
    finally:
        devprof.reset()


# =======================================================================
# /costs endpoint
# =======================================================================
def _get_json(url):
    with urllib.request.urlopen(url, timeout=5) as resp:
        return resp.status, json.loads(resp.read())


def test_costs_endpoint_reports_providers_and_slo():
    from goworld_tpu.utils import debug_http

    devprof.reset()
    srv = debug_http.start(0, process_name="devproftest")
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        devprof.register_report(
            devprof.CostReport(name="tick_scan", flops=1e9,
                               bytes_accessed=2e9, n=1024))
        ran = []

        def provider():
            ran.append(1)
            return devprof.CostReport(name="lazy", flops=5.0)

        devprof.register_provider("lazy", provider)
        devprof.record_slo({"target_ms": 16.0, "p99_ms": 3.0,
                            "pass": True})

        code, body = _get_json(base + "/costs")
        assert code == 200
        assert body["reports"]["tick_scan"]["flops"] == 1e9
        assert body["providers"] == ["lazy"]
        assert not ran  # providers NEVER run on a plain scrape
        assert body["slo"]["pass"] is True

        code, body = _get_json(base + "/costs?analyze=1")
        assert ran == [1]
        assert body["reports"]["lazy"]["flops"] == 5.0
    finally:
        srv.shutdown()
        srv.server_close()
        devprof.reset()


def test_costs_live_slo_falls_back_to_tick_latency_histogram():
    from goworld_tpu.utils import metrics

    devprof.reset()
    try:
        h = metrics.histogram("tick_latency_ms")
        before = h.count
        for v in (1.0, 2.0, 3.0, 900.0):
            h.observe(v)
        devprof.set_slo_target(16.0)
        slo = devprof.snapshot()["slo"]
        assert slo is not None
        assert slo["source"] == "tick_latency_ms"
        assert slo["samples"] >= before + 4
        assert slo["target_ms"] == 16.0
    finally:
        devprof.reset()


def test_registry_histogram_snapshot_accessor():
    from goworld_tpu.utils import metrics

    reg = metrics.Registry()
    assert reg.histogram_snapshot("nope") is None
    reg.counter("a_total").inc()
    assert reg.histogram_snapshot("a_total") is None  # wrong kind
    h = reg.histogram("lat_ms", buckets=(1.0, 2.0))
    h.observe(1.5)
    snap = reg.histogram_snapshot("lat_ms")
    assert len(snap) == 1
    labels, s = snap[0]
    assert labels == {} and s["count"] == 1
    assert s["buckets"] == [(1.0, 0), (2.0, 1)]


def test_scrape_metrics_costs_and_slo_lines():
    """tools/scrape_metrics.py learns /costs: per-process SLO verdict
    lines next to the metric table (ISSUE 8 satellite; cli.py status
    goes through the same two helpers)."""
    import importlib.util as _ilu

    from goworld_tpu.utils import debug_http

    spec = _ilu.spec_from_file_location(
        "scrape_under_test",
        os.path.join(REPO, "tools", "scrape_metrics.py"))
    scraper = _ilu.module_from_spec(spec)
    spec.loader.exec_module(scraper)

    devprof.reset()
    srv = debug_http.start(0, process_name="scrapetest")
    base = f"http://127.0.0.1:{srv.server_address[1]}"
    try:
        devprof.record_slo({"target_ms": 16.0, "p50_ms": 1.0,
                            "p90_ms": 2.0, "p99_ms": 3.0,
                            "samples": 10, "pass": True,
                            "source": "in-graph-histogram"})
        costs = scraper.scrape_costs([("game1", base + "/metrics")])
        assert "game1" in costs
        lines = scraper.slo_lines(costs)
        assert len(lines) == 1
        assert "game1" in lines[0] and "PASS" in lines[0] \
            and "p99=3.0" in lines[0]
        # unreachable targets are skipped silently (the metric scrape
        # already reports reachability)
        assert scraper.scrape_costs(
            [("dead", "http://127.0.0.1:9/metrics")]) == {}
    finally:
        srv.shutdown()
        srv.server_close()
        devprof.reset()


# =======================================================================
# in-graph telemetry lanes: parity + zero-sync + one-trace
# =======================================================================
def _telemetry_scan(cfg, st, inputs, policy, ticks, skin_on,
                    base_ms, delta_ms, half_skin):
    """One jitted scan returning BOTH the on-device accumulator and
    the raw per-tick signal series (device truth) — the parity oracle
    histograms the series host-side and must match bit-exactly."""

    @jax.jit
    def run(state):
        acc0 = telemetry.telemetry_init(skin_on)

        def body(carry, _):
            s, acc = carry
            s2, out = tick_body(cfg, s, inputs, policy)
            acc = telemetry.telemetry_update(acc, out, base_ms,
                                             delta_ms, half_skin)
            rebuilt = out.aoi_rebuilt
            if rebuilt is None:
                rebuilt = jnp.ones((), jnp.int32)
            slack = out.aoi_skin_slack
            if slack is None:
                slack = jnp.zeros((), jnp.float32)
            series = {
                "tick_ms": jnp.float32(base_ms)
                + rebuilt.astype(jnp.float32) * jnp.float32(delta_ms),
                "rebuilt": rebuilt.astype(jnp.float32),
                "sync_n": out.sync_n.astype(jnp.float32),
                "enter_n": out.enter_n.astype(jnp.float32),
                "leave_n": out.leave_n.astype(jnp.float32),
                "over_k_rows":
                    out.aoi_over_k_rows.astype(jnp.float32),
                "over_cap_cells":
                    out.aoi_over_cap_cells.astype(jnp.float32),
                "skin_slack": (slack / jnp.float32(half_skin)
                               if half_skin > 0 else slack),
            }
            return (s2, acc), series
        (_s2, acc), series = lax.scan(body, (state, acc0), None,
                                      length=ticks)
        return acc, series
    return run


@pytest.mark.parametrize("skin,scenario", [
    (0.0, None), (4.0, None), (0.0, "hotspot"), (4.0, "teleport"),
], ids=["skinless", "skin", "scenario", "skin+scenario"])
def test_telemetry_histogram_parity_vs_host_recompute(skin, scenario,
                                                      monkeypatch):
    for var in BENCH.GRID_ENV.values():
        monkeypatch.delenv(var, raising=False)
    from goworld_tpu.scenarios.spec import get_scenario

    spec = get_scenario(scenario) if scenario else None
    cfg, st, inputs = BENCH.build(
        128, 0.05, {"skin": skin},
        scenario=spec if spec is not None else None)
    policy = None
    if spec is not None and spec.needs_policy:
        from goworld_tpu.models.npc_policy import init_policy

        policy = init_policy(jax.random.PRNGKey(0))
    skin_on = cfg.grid.skin > 0 and st.aoi_cache is not None
    base_ms, delta_ms = 3.0, (2.5 if skin_on else 0.0)
    half_skin = cfg.grid.skin / 2.0 if skin_on else 0.0
    ticks = 12
    run = _telemetry_scan(cfg, st, inputs, policy, ticks, skin_on,
                          base_ms, delta_ms, half_skin)
    acc, series = run(st)
    drained = telemetry.telemetry_drain(acc, skin_on, half_skin)
    for lane, edges in telemetry.lane_edges(skin_on).items():
        host = telemetry.host_histogram(np.asarray(series[lane]),
                                        edges)
        assert drained[lane]["counts"] == [int(c) for c in host], \
            f"lane {lane}: device {drained[lane]['counts']} " \
            f"!= host {host.tolist()}"
        assert sum(drained[lane]["counts"]) == ticks
    # the distribution is over REAL per-tick variation: with a skin,
    # the rebuild lane must show both a rebuild and reuse ticks
    if skin_on and scenario is None:
        rb = drained["rebuilt"]["counts"]
        assert rb[1] >= 1 and rb[0] >= 1, rb
    if scenario == "teleport":
        # every teleport tick defeats the skin: rebuilds dominate
        assert drained["rebuilt"]["counts"][1] >= ticks - 1


def test_telemetry_zero_host_syncs_and_single_trace(monkeypatch):
    """The accumulator scan runs with host<->device transfers DISALLOWED
    (zero per-tick syncs — the drain is the one readback, outside the
    guard) and traces exactly once per config across repeat calls."""
    for var in BENCH.GRID_ENV.values():
        monkeypatch.delenv(var, raising=False)
    cfg, st, inputs = BENCH.build(64, 0.05, {"skin": 0.0})

    @jax.jit
    def run(state):
        acc0 = telemetry.telemetry_init(False)

        def body(carry, _):
            s, acc = carry
            s2, out = tick_body(cfg, s, inputs, None)
            acc = telemetry.telemetry_update(acc, out, 1.0, 0.0)
            return (s2, acc), 0
        (_s2, acc), _ = lax.scan(body, (state, acc0), None, length=4)
        return acc

    st_dev = jax.device_put(st)
    in_dev = jax.device_put(inputs)  # noqa: F841 (closed over above)
    traces0 = telemetry.TRACE_COUNTS.get("telemetry_update", 0)
    run(st_dev)  # trace + compile outside the guard
    with jax.transfer_guard("disallow"):
        acc = run(jax.tree.map(lambda x: x, st_dev))
    drained = telemetry.telemetry_drain(acc, False)  # the ONE drain
    assert sum(drained["tick_ms"]["counts"]) == 4
    # one trace per config: the second (guarded) call hit the cache
    assert telemetry.TRACE_COUNTS["telemetry_update"] == traces0 + 1


# =======================================================================
# roofline model + audit block
# =======================================================================
@pytest.mark.parametrize("grid_kw", [
    {"sort_impl": "argsort", "sweep_impl": "ranges", "skin": 0.0},
    {"sort_impl": "counting", "sweep_impl": "table", "skin": 0.0},
    {"sort_impl": "argsort", "sweep_impl": "fused", "skin": 0.0},
    {"sort_impl": "counting", "sweep_impl": "ranges", "skin": 4.0,
     "verlet_cap": 48},
], ids=["ranges", "table+counting", "fused", "verlet"])
def test_roofline_model_bytes_shapes(grid_kw):
    kw = dict(grid_kw, k=32, cell_cap=12, radius=50.0,
              extent_x=10000.0, extent_z=10000.0)
    model = devprof.roofline_model_bytes(131072, kw)
    for phase in ("cell_ids", "aoi_sort", "aoi_build", "aoi_gather",
                  "aoi_rank", "aoi", "move", "collect"):
        assert phase in model and model[phase] >= 0.0
    if grid_kw.get("skin", 0) > 0:
        assert {"aoi_reuse", "aoi_rebuild"} <= set(model)
        assert model["aoi_rebuild"] > model["aoi_reuse"]
    if grid_kw["sweep_impl"] == "fused":
        # the fusion deletes the window-gather + packed-key HBM terms
        split = devprof.roofline_model_bytes(
            131072, dict(kw, sweep_impl="ranges"))
        assert model["aoi"] < 0.5 * split["aoi"]
    if grid_kw["sort_impl"] == "counting":
        bitonic = devprof.roofline_model_bytes(
            131072, dict(kw, sort_impl="argsort"))
        assert model["aoi_sort"] < 0.2 * bitonic["aoi_sort"]


def test_roofline_audit_block_shape():
    kw = {"k": 32, "cell_cap": 12, "sort_impl": "argsort",
          "sweep_impl": "ranges", "skin": 0.0, "radius": 50.0,
          "extent_x": 3000.0, "extent_z": 3000.0}
    phase_ms = {"aoi": 10.0, "move": 1.0, "collect": 2.0}
    costs = {"aoi": devprof.CostReport(name="phase:aoi",
                                       bytes_accessed=5e6, flops=1e6),
             "move": {"bytes_accessed": 2e6},
             "collect": {"bytes_accessed": 3e6}}
    block = devprof.roofline_audit(phase_ms, costs, 4096, kw,
                                   platform="tpu",
                                   device_kind="TPU v5 lite")
    assert block["doc"] == "docs/ROOFLINE.md" and block["n"] == 4096
    aoi = block["phases"]["aoi"]
    assert aoi["measured_ms"] == 10.0
    assert aoi["xla_mb"] == 5.0
    assert "drift_pct" in aoi
    # the time projection is priced against the NAMED device's peak
    assert block["bandwidth_gbps"] == 819.0
    assert aoi["model_ms"] == round(
        aoi["model_mb"] * 1e6 / (819.0 * 1e6), 4)
    # no device named: bytes only, no time under any chip's name
    bare = devprof.roofline_audit(phase_ms, costs, 4096, kw,
                                  platform="cpu")
    assert "model_ms" not in bare["phases"]["aoi"]
    assert bare["bandwidth_gbps"] is None
    # an unlisted device is an error, not a default
    with pytest.raises(ValueError, match="no published peaks"):
        devprof.roofline_audit(phase_ms, costs, 4096, kw,
                               device_kind="TPU v99")
    assert block["phases"]["move"]["xla_mb"] == 2.0
    assert "total_drift_pct" in block

    # PARTIAL XLA coverage (a probe whose lower failed) must never
    # stamp a like-for-unlike total drift — it flags coverage instead
    partial = devprof.roofline_audit(
        phase_ms, {k: costs[k] for k in ("aoi", "move")}, 4096, kw,
        platform="cpu")
    assert "total_drift_pct" not in partial
    assert partial["xla_coverage_partial"] == ["aoi", "move"]
    # phases with no cost report still carry the model columns
    assert "model_mb" in partial["phases"]["collect"]
    assert "xla_mb" not in partial["phases"]["collect"]
