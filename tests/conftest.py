"""Test env: force CPU with 8 virtual devices so mesh/sharding tests run
without TPU hardware (the multi-node-without-a-cluster capability noted in
SURVEY.md#4). Must run before jax is imported anywhere."""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # tier-1 is a CPU suite, whatever
# the ambient environment says
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "soak: long-running load tests (the reload-under-load soak)",
    )
    config.addinivalue_line(
        "markers",
        "slow: multi-process / long tests (multihost mesh, soak)",
    )
    config.addinivalue_line(
        "markers",
        "soak_full: the reference CI's 200-bot/300s profile "
        "(RUN_SOAK_FULL=1 to enable; ~7 min)",
    )
    config.addinivalue_line(
        "markers",
        "chaos: seeded fault-injection tests (tests/test_chaos.py); the "
        "fast smoke runs in tier-1, the full soak is also marked slow",
    )
    config.addinivalue_line(
        "markers",
        "overload: overload-protection ladder tests "
        "(tests/test_overload.py); the live smoke runs in tier-1, the "
        "chaos_soak overload scenario is also marked slow",
    )
    config.addinivalue_line(
        "markers",
        "pallas: interpret-mode Pallas kernel suites (the fused AOI "
        "back half and the counting-sort fill kernel); all run in "
        "tier-1 on CPU — the marker selects exactly the "
        "kernel-parity set (their v5e compiles live in "
        "tests/test_tpu_compile.py)",
    )
    config.addinivalue_line(
        "markers",
        "scenarios: adversarial-workload suites (tests/test_scenarios"
        ".py + the scenario-driven AOI regressions); the small-N "
        "oracle gates run in tier-1, long soaks are also marked slow",
    )
    config.addinivalue_line(
        "markers",
        "multichip: megaspace mesh suites (the scan-driven multichip "
        "bench path, halo_impl async/ppermute parity, mesh "
        "schema/trend gates — tests/test_multichip_bench.py, "
        "test_halo_async.py); tier-1 on 8 fake CPU devices at small N "
        "— the marker selects exactly the mesh set",
    )
    config.addinivalue_line(
        "markers",
        "devprof: device-plane observability suites (XLA cost auditor, "
        "in-graph telemetry lanes, roofline audit, bench trend/schema "
        "gates — tests/test_devprof.py, test_bench_trend.py, "
        "test_bench_schema.py); all run in tier-1 on CPU",
    )
    config.addinivalue_line(
        "markers",
        "precision: quantized state-plane suites (the q16 lattice "
        "sweep/Verlet parity vs the snapped oracle, the delta-sync "
        "codec, the delta snapshot chain — tests/test_precision.py + "
        "the precision rows in test_aoi_parity.py); all run in tier-1 "
        "on CPU — the marker selects exactly the quantized-plane set",
    )
    config.addinivalue_line(
        "markers",
        "flightrec: live workload-signature + incident flight-recorder "
        "suites (the production telemetry carry, /workload + "
        "/incidents, trigger/dedup/replay determinism — "
        "tests/test_flightrec.py, tests/test_telemetry_live.py); all "
        "run in tier-1 on CPU",
    )
    config.addinivalue_line(
        "markers",
        "governor: online kernel-governor suites (goworld_tpu/autotune "
        "— policy hysteresis/replay determinism, warm-set AOT "
        "executables, live mid-churn swap oracle exactness, the "
        "regret guard, /governor, the recommendation-key contract — "
        "tests/test_governor.py); all run in tier-1 on CPU "
        "(docs/AUTOTUNE.md)",
    )
    config.addinivalue_line(
        "markers",
        "syncage: end-to-end sync-age plane suites (the per-batch "
        "stamp trailer, gate age-at-delivery histograms, the "
        "deployment aggregator, the sync_age_breach trigger — "
        "tests/test_syncage.py); all run in tier-1 on CPU "
        "(docs/OBSERVABILITY.md \"End-to-end sync age\")",
    )
    config.addinivalue_line(
        "markers",
        "residency: serve-loop residency plane suites (host-sync "
        "bubble accounting, alloc-churn census, the scan-marginal vs "
        "serve gap, /residency, the residency_regression trigger — "
        "tests/test_residency.py); all run in tier-1 on CPU "
        "(docs/OBSERVABILITY.md \"Serve-loop residency\")",
    )
    config.addinivalue_line(
        "markers",
        "audit: correctness audit plane suites (entity-ownership "
        "ledger census/seq semantics, deployment conservation "
        "verdicts, the sampled live AOI oracle, mirror probes, "
        "/audit, the audit_violation trigger, the trailer "
        "coexistence wire contract — tests/test_audit.py); all run "
        "in tier-1 on CPU (docs/OBSERVABILITY.md \"Correctness "
        "audit plane\")",
    )
    config.addinivalue_line(
        "markers",
        "replication: hot-standby replication suites (stream frame "
        "CRC chaining + torn-stream classes, double-apply lattice "
        "determinism, the bounded replication worker's "
        "never-block-the-tick contract, standby apply/mirror "
        "semantics, kvreg promotion arbitration incl. both "
        "stale-claim race orders, /standby — "
        "tests/test_replication.py); all run in tier-1 on CPU "
        "(docs/ROBUSTNESS.md \"Hot-standby & promotion\")",
    )
    config.addinivalue_line(
        "markers",
        "resident: resident-world runtime suites (carry donation "
        "deleted-buffer fencing on freeze/census/governor paths, "
        "donation on/off bit-parity across the skin/precision/vmap "
        "matrix, mid-churn governor swap exactness under donation, "
        "the 0-realloc census verdict, the resident_ab trend gate — "
        "tests/test_resident.py); all run in tier-1 on CPU "
        "(docs/OBSERVABILITY.md \"Serve-loop residency\")",
    )
    config.addinivalue_line(
        "markers",
        "rebalance: self-healing deployment rebalance suites "
        "(goworld_tpu/rebalance — sustained-DEGRADED hold/hysteresis "
        "policy, ping-pong cooldown suppression, plan-window "
        "cancellation, byte-identical decision-log replay, bounded "
        "cohort handoff + abort restore through the migration "
        "protocol, admission pause, the burst-aware conservation "
        "grace, /rebalance, the rebalance_action trigger — "
        "tests/test_rebalance.py); all run in tier-1 on CPU "
        "(docs/ROBUSTNESS.md \"Elastic rebalancing\")",
    )


def spawn_on(states, dev, slot, **kw):
    """Spawn into one device's shard of a stacked [n_dev, ...] state
    (shared by the parallel/megaspace/multihost tests)."""
    import jax

    from goworld_tpu.core.state import spawn

    one = jax.tree.map(lambda x: x[dev], states)
    one = spawn(one, slot, **kw)
    return jax.tree.map(
        lambda full, new: full.at[dev].set(new), states, one
    )
