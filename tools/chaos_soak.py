#!/usr/bin/env python
"""Chaos soak: run a standalone gate->dispatcher->game cluster under a
seeded fault schedule and report convergence + the deterministic fault
log.

One invocation = one full chaos scenario against a throwaway server
dir. Two scenarios share the harness (``--scenario``):

``kill`` (default):

1. build a 1-dispatcher/1-game/1-gate cluster (persistent Vault entity,
   1 s crash-recovery checkpoints, gate /faults endpoint),
2. start it with ``GOWORLD_FAULTS`` armed (wire faults on the
   gate->dispatcher edge + a deterministic ``crash:game.tick@n=...``
   game kill),
3. drive deposits through a bot, wait for a post-deposit checkpoint,
4. let the kill fire, supervise the cluster back to health
   (``cli.cmd_supervise`` machinery), audit the Vault from a fresh
   client,
5. scrape the gate's ``/faults`` log and write a JSON report.

``overload`` (ISSUE 4): flood the cluster with slow RPCs + position
spam at ``--msg-rate`` msg/s for ``--flood-secs`` while seeded delay
faults are active, then scrape the game's ``/overload`` ladder and the
``shed_total`` counters; ``converged`` means the ladder ENGAGED
(reached SHEDDING), the critical/rpc classes shed nothing, and the
process RETURNED to NORMAL after the flood stopped.

``governor`` (ISSUE 13), ``audit`` (ISSUE 17) and ``failover``
(ISSUE 18) run IN-PROCESS (no cluster): the governor soak hot-swaps
kernel configs under a scenario-switching schedule; the audit soak
proves the correctness plane — a clean churn + migration-storm phase
must record ZERO violations, then an injected entity drop
(migrate-out, restore suppressed) must be detected by the
conservation verdict within <= 8 ticks, naming the EntityID and
freezing an ``audit_violation`` flight-recorder bundle
(``run_audit``); the failover soak streams a primary under
churn-and-migration into a hot standby, kills the primary at a
deterministic tick, promotes through the kvreg-arbitrated protocol
(both stale-claim race orders replayed and refused, decision log
byte-replayable), proves ZERO lost/duplicated EntityIDs by census +
conservation verdict, and times the warm promotion against a cold
chain restore of the same crash (must be >= 10x faster —
``run_failover``).

``rebalance`` (ISSUE 19) also runs IN-PROCESS: a donor world under
sustained-DEGRADED load and an underloaded receiver are watched by the
real :class:`RebalancePolicy` + :class:`HandoffExecutor` stack; one
run proves BOTH variants — the clean handoff (fires after
``hold_windows`` sustained windows, rate-limited cohort moves through
the production migration hooks, donor recovers to NORMAL within the
report's window budget, zero entities lost or duplicated, the
deployment conservation verdict green EVERY window including
mid-batch, the decision log byte-replayable) and the target-kill abort
(the receiver dies mid-handoff with a batch in flight; the timeout
abort must restore every unacked entity LIVE on the source and the
census must account for every original EntityID) — ``run_rebalance``.

Running either scenario TWICE with the same ``--seed`` must produce
byte-identical fault/transition behavior — the seeded-replay guarantee
(tests/test_chaos.py::test_chaos_soak_same_seed_replays_identical_log
automates the kill double run behind ``-m slow``;
tests/test_overload.py covers the overload scenario).

Usage::

    python tools/chaos_soak.py --dir /tmp/chaos --seed 77 \
        --deposits 25 --out chaos_report.json
    python tools/chaos_soak.py --scenario overload --dir /tmp/ov \
        --seed 77 --flood-secs 6 --msg-rate 120 --out ov_report.json
    python tools/chaos_soak.py --dir /tmp/chaos --seed 77 \
        --workload teleport   # faults under adversarial NPC motion
"""

from __future__ import annotations

import argparse
import asyncio
import json
import os
import socket
import sys
import threading
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from goworld_tpu.net import proto  # noqa: E402 (after sys.path insert)

SERVER_PY = '''\
import goworld_tpu as gw

VAULT_EID = "Vault00000000001"


@gw.register_entity("Vault")
class Vault(gw.Entity):
    ATTRS = {"gold": "persistent"}


@gw.register_entity("Account")
class Account(gw.Entity):
    ATTRS = {"status": "client", "audit": "client"}

    def OnClientConnected(self):
        self.attrs["status"] = "online"

    def Deposit_Client(self, amount):
        v = gw.get_entity(VAULT_EID)
        if v is None:
            v = gw.create_entity("Vault", eid=VAULT_EID)
        v.attrs["gold"] = v.attrs.get("gold", 0) + amount
        v.save()
        self.attrs["audit"] = v.attrs["gold"]

    def Audit_Client(self):
        v = gw.get_entity(VAULT_EID)
        self.attrs["audit"] = -1 if v is None else v.attrs.get("gold", 0)

    def Stress_Client(self, ms):
        # overload scenario: a deliberately slow handler — the flood's
        # tick-budget hog (never shed: RPCs are a protected class)
        import time as _t
        _t.sleep(ms / 1000.0)


if __name__ == "__main__":
    gw.run()
'''

RPC_MT = proto.MT_CALL_ENTITY_METHOD_FROM_CLIENT
KILL_TICK = 900   # ~15 s of serve loop at 60 Hz: past the deposit
                  # phase, deterministic regardless of boot-compile time


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def build_server_dir(path: str,
                     overload_knobs: bool = False,
                     workload: str = "") -> tuple[str, int, int]:
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "server.py"), "w") as f:
        f.write(SERVER_PY)
    dport, gport, hport = _free_port(), _free_port(), _free_port()
    ghport = _free_port()  # game debug-http (/overload scrapes)
    extra = ""
    if workload:
        # --workload <scenario>: the game tick runs the adversarial
        # behavior mix (goworld_tpu/scenarios registry) instead of the
        # homogeneous random_walk, so faults/overload land under
        # adversarial motion (ISSUE 7). Validated jax-free up front —
        # a typo must not surface as a mid-soak game crash.
        from goworld_tpu.scenarios.spec import get_scenario

        get_scenario(workload)  # KeyError lists the registry
        extra += f"scenario = {workload}\n"
    if overload_knobs:
        # aggressive ladder so a short flood engages it, a fast
        # descent so the report's recovery wait stays bounded, and a
        # 10 Hz tick budget a loaded CI box can actually hold when
        # idle (the governor judges wall time against 1/tick_hz — on a
        # budget the host can never meet, NORMAL is unreachable)
        extra += ("tick_hz = 10\n"
                  "overload_up_ticks = 3\noverload_down_ticks = 30\n"
                  "degraded_sync_stride = 2\n")
    with open(os.path.join(path, "goworld_tpu.ini"), "w") as f:
        f.write(
            f"[dispatcher1]\nhost = 127.0.0.1\nport = {dport}\n"
            "[game_common]\nboot_entity = Account\ncapacity = 256\n"
            "n_spaces = 1\ncheckpoint_interval = 1\n"
            f"http_port = {ghport}\n{extra}"
            "[game1]\n"
            f"[gate1]\nhost = 127.0.0.1\nport = {gport}\n"
            f"http_port = {hport}\n"
            "[storage]\nkind = filesystem\ndirectory = entity_storage\n"
            "[kvdb]\nkind = memory\n"
        )
    return path, gport, hport


def spec_for(kill_tick: int = KILL_TICK) -> str:
    return (
        f"drop:gate->dispatcher:mt={RPC_MT}:0.25,"
        f"dup:gate->dispatcher:mt={RPC_MT}:0.25,"
        f"delay:gate->dispatcher:mt={RPC_MT}:0.5:5ms,"
        f"crash:game.tick@n={kill_tick}"
    )


async def _session(gport: int, actions):
    from goworld_tpu.net.botclient import BotClient

    bot = BotClient("127.0.0.1", gport)
    await bot.connect()
    recv = asyncio.ensure_future(bot._recv_loop())
    try:
        await asyncio.wait_for(bot.player_ready.wait(), 90)
        for _ in range(200):
            if bot.player.attrs.get("status") == "online":
                break
            await asyncio.sleep(0.05)
        return await actions(bot)
    finally:
        recv.cancel()
        await bot.conn.close()


def run_soak(server_dir: str, seed: int, deposits: int,
             kill_tick: int = KILL_TICK) -> dict:
    from goworld_tpu import cli
    from goworld_tpu.utils import faults as faults_mod

    spec = spec_for(kill_tick)
    report: dict = {"seed": seed, "spec": spec, "converged": False}
    os.environ["GOWORLD_FAULTS"] = spec
    os.environ["GOWORLD_FAULTS_SEED"] = str(seed)
    stop = threading.Event()
    sup = None
    try:
        if cli.cmd_start(server_dir) != 0:
            report["error"] = "initial start failed"
            return report
        os.environ.pop("GOWORLD_FAULTS")
        os.environ.pop("GOWORLD_FAULTS_SEED")
        _, gport, hport = (
            server_dir,
            _ini_port(server_dir, "gate1", "port"),
            _ini_port(server_dir, "gate1", "http_port"),
        )
        game_pid = cli._read_pid(server_dir, "game", 1)

        async def deposit(bot):
            for _ in range(deposits):
                bot.call_server("Deposit_Client", 1)
                await asyncio.sleep(0.02)
            deadline = time.time() + 20
            while time.time() < deadline:
                a = bot.player.attrs.get("audit")
                if a is not None:
                    await asyncio.sleep(1.0)
                    return bot.player.attrs.get("audit")
                await asyncio.sleep(0.1)
            return None

        gold = asyncio.run(asyncio.wait_for(_session(gport, deposit),
                                            180))
        t_gold = time.time()
        report["gold"] = gold
        if not gold:
            report["error"] = "no deposit survived"
            return report

        # poll until every deposit passed the gate's decision point
        # (ordered client stream: the first rule's trial count grows to
        # exactly the RPC count)
        def _scrape():
            with urllib.request.urlopen(
                f"http://127.0.0.1:{hport}/faults", timeout=5
            ) as r:
                return json.loads(r.read())

        snap = _scrape()
        deadline = time.time() + 30
        while time.time() < deadline \
                and snap["rules"][0]["trials"] < deposits:
            time.sleep(0.2)
            snap = _scrape()
        report["fault_log"] = snap["log"]
        report["injected_total"] = snap["injected_total"]
        # sanity: the live log IS the seeded pure function
        expected = faults_mod.FaultPlane(
            faults_mod.parse_schedule(spec), seed)
        for _ in range(deposits):
            expected.wire_fault("gate->dispatcher", RPC_MT)
        report["replay_matches"] = snap["log"] == expected.log_lines()

        ckpt = os.path.join(server_dir, "game1_checkpoint.dat")
        deadline = time.time() + 60
        while time.time() < deadline and (
            not os.path.exists(ckpt)
            or os.path.getmtime(ckpt) < t_gold + 0.5
        ):
            time.sleep(0.2)

        deadline = time.time() + 120
        while time.time() < deadline and cli._alive(game_pid):
            time.sleep(0.2)
        if cli._alive(game_pid):
            report["error"] = "kill never fired"
            return report
        report["killed"] = True

        sup = threading.Thread(
            target=cli.cmd_supervise, args=(server_dir,),
            kwargs=dict(interval=0.5, stop=stop), daemon=True,
        )
        sup.start()
        deadline = time.time() + 240
        while time.time() < deadline:
            pid = cli._read_pid(server_dir, "game", 1)
            if pid != game_pid and cli._alive(pid):
                break
            time.sleep(0.3)
        else:
            report["error"] = "supervisor never recovered the game"
            return report
        report["restarted"] = True

        async def audit(bot):
            bot.call_server("Audit_Client")
            deadline = time.time() + 30
            while time.time() < deadline:
                a = bot.player.attrs.get("audit")
                if a is not None:
                    return a
                await asyncio.sleep(0.1)
            return None

        seen = asyncio.run(asyncio.wait_for(_session(gport, audit), 240))
        report["audited"] = seen
        report["converged"] = bool(
            seen == gold and report.get("replay_matches")
        )
        return report
    finally:
        stop.set()
        if sup is not None:
            sup.join(timeout=60)
        from goworld_tpu import cli as _cli

        _cli.cmd_stop(server_dir)


OVERLOAD_STRESS_MS = 30   # per-RPC handler sleep: ~12 per 100 ms tick
                          # (tick_hz = 10) at 120 msg/s -> tick latency
                          # ratio ~3.6, severely pressured while the
                          # flood lasts, drainable within seconds after


def overload_spec() -> str:
    return "delay:gate->dispatcher:0.5:5ms"


def run_overload(server_dir: str, seed: int, flood_secs: float,
                 msg_rate: float) -> dict:
    """The ISSUE-4 overload scenario: bot flood + delay faults, then
    judge the ladder from /overload and the shed counters from
    /metrics. Same report shape as the kill scenario (seed / spec /
    converged + scenario fields)."""
    from goworld_tpu import cli
    from goworld_tpu.utils import metrics as metrics_mod

    spec = overload_spec()
    report: dict = {"scenario": "overload", "seed": seed, "spec": spec,
                    "flood_secs": flood_secs, "msg_rate": msg_rate,
                    "converged": False}
    os.environ["GOWORLD_FAULTS"] = spec
    os.environ["GOWORLD_FAULTS_SEED"] = str(seed)
    try:
        if cli.cmd_start(server_dir) != 0:
            report["error"] = "initial start failed"
            return report
        os.environ.pop("GOWORLD_FAULTS")
        os.environ.pop("GOWORLD_FAULTS_SEED")
        gport = _ini_port(server_dir, "gate1", "port")
        game_hport = _ini_port(server_dir, "game_common", "http_port")

        def _scrape(path: str, port: int):
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=5
            ) as r:
                return r.read()

        def _game_gov() -> dict | None:
            try:
                snap = json.loads(_scrape("/overload", game_hport))
            except OSError:
                return None
            for n, g in snap.get("governors", {}).items():
                if n.startswith("game"):
                    g["_shed"] = snap.get("shed", {})
                    return g
            return None

        def _wait_state(want: str, secs: float) -> dict | None:
            deadline = time.monotonic() + secs
            gov = None
            while time.monotonic() < deadline:
                gov = _game_gov()
                if gov is not None and gov["state"] == want:
                    return gov
                time.sleep(0.5)
            return gov

        # phase 0: warm the compile paths (boot + the FIRST position
        # sync batch each re-jit the step; on a CI box that is a
        # multi-second mega-tick that would swallow the whole flood
        # window), then let the spike decay — engagement must be
        # judged against a calm baseline, not startup transients
        async def warmup(bot):
            for i in range(10):
                bot.send_position(float(i), 0.0, 1.0, 0.0)
                await asyncio.sleep(0.05)
            bot.call_server("Stress_Client", 1)
            await asyncio.sleep(1.0)
            return True

        asyncio.run(asyncio.wait_for(_session(gport, warmup), 180))
        gov = _wait_state("NORMAL", 120)
        if gov is None or gov["state"] != "NORMAL":
            report["error"] = "never settled to NORMAL after boot"
            report["transitions"] = (gov or {}).get("transitions")
            return report
        n0 = len(gov["transitions"])

        async def flood(bot):
            interval = 1.0 / max(1.0, msg_rate)
            end = time.monotonic() + flood_secs
            sent = 0
            while time.monotonic() < end:
                bot.call_server("Stress_Client", OVERLOAD_STRESS_MS)
                bot.send_position(float(sent % 9), 0.0,
                                  float(sent % 7), 0.0)
                sent += 1
                await asyncio.sleep(interval)
            return sent

        report["sent"] = asyncio.run(
            asyncio.wait_for(_session(gport, flood), flood_secs + 180)
        )

        # recovery: the ladder must walk back to NORMAL after the flood
        gov = _wait_state("NORMAL", 120)
        state = None if gov is None else gov["state"]
        flood_transitions = (gov or {}).get("transitions", [])[n0:]
        report["final_state"] = state
        report["transitions"] = flood_transitions
        report["shed"] = (gov or {}).get("_shed", {})
        report["engaged"] = any(
            "->SHEDDING" in t for t in flood_transitions
        )
        report["returned_normal"] = state == "NORMAL"
        report["cheap_shed"] = sum(
            v for k, v in report["shed"].items()
            if not (k.startswith("critical/") or k.startswith("rpc/"))
        )

        # zero sheds in the protected classes, cluster-wide (game /
        # gate /metrics both carry shed_total)
        critical_shed = 0.0
        for port in (game_hport,
                     _ini_port(server_dir, "gate1", "http_port")):
            try:
                series = metrics_mod.parse_prometheus_text(
                    _scrape("/metrics", port).decode())
            except OSError:
                continue
            for name, val in series.items():
                if name.startswith("shed_total") and (
                    'class="critical"' in name or 'class="rpc"' in name
                ):
                    critical_shed += val
        report["critical_shed"] = critical_shed
        report["converged"] = bool(
            report["engaged"] and report["returned_normal"]
            and critical_shed == 0 and report["cheap_shed"] > 0
        )
        return report
    finally:
        from goworld_tpu import cli as _cli

        _cli.cmd_stop(server_dir)


# governor soak knobs: boosted teleport churn so the skinless event
# proxy reads "moderate" at soak scale (the registry default's handful
# of jumps/tick is indistinguishable from flock at n~100)
GOV_SOAK_N = 96
GOV_SOAK_WINDOW = 16
GOV_SOAK_WINDOWS = 4


def run_governor(seed: int, phases: tuple = ("flock", "teleport",
                                             "flock", "teleport"),
                 n: int = GOV_SOAK_N,
                 window: int = GOV_SOAK_WINDOW,
                 windows_per_phase: int = GOV_SOAK_WINDOWS) -> dict:
    """The ISSUE-13 governor scenario: ONE live in-process World driven
    through a scenario-switching schedule while the autotune policy
    hot-swaps its kernel config from the real drained signature
    windows. In-process (no cluster) because the assertions need
    direct World access: ``check_oracle`` exactness (interest sets +
    client mirrors, both overflow gauges zero) after EVERY swap and on
    a cadence, zero entity loss across the whole run, >= 3 live swaps,
    and a deterministic decision log (the recorded signature stream
    replayed through a fresh policy must reproduce it byte-identically
    — the seeded-replay guarantee of the kill/overload scenarios)."""
    import dataclasses

    from goworld_tpu.autotune import GovernorPolicy, WarmSet, seed_table
    from goworld_tpu.scenarios.spec import get_scenario
    from goworld_tpu.scenarios.runner import build_world, check_oracle

    _specs: dict = {}

    def spec_of(name: str):
        if name not in _specs:
            if name == "teleport":
                # boosted jump rate: the event-volume churn proxy must
                # read moderate/heavy even at soak n (see module knob)
                _specs[name] = dataclasses.replace(
                    get_scenario("teleport"), name="teleport_soak",
                    teleport_prob=0.2)
            else:
                _specs[name] = get_scenario(name)
        return _specs[name]

    report: dict = {"scenario": "governor", "seed": seed,
                    "phases": list(phases), "n": n,
                    "window_ticks": window,
                    "windows_per_phase": windows_per_phase,
                    "converged": False}
    w, ents, clients = build_world(
        spec_of(phases[0]), n=n, skin=4.0, client_frac=0.15, seed=seed)
    w.SIG_WINDOW_TICKS = window  # one signature window per decision
    eids0 = set(w.entities)
    boot_cfg = w.cfg
    policy = GovernorPolicy(table=seed_table(), up_windows=1,
                            down_windows=1, cooldown_windows=0)
    label = "default"
    warmsets: dict = {}
    sig_stream: list = []
    swaps: list = []
    oracle_checks = 0
    mismatches: list = []

    def warm(spec, lbl: str):
        ws = warmsets.get(spec.name)
        if ws is None:
            base = dataclasses.replace(boot_cfg, scenario=spec)
            ws = warmsets[spec.name] = WarmSet(
                base, 1, w.policy, telemetry=w.telemetry_live)
        ws.ensure(lbl, block=True)
        e = ws.entry(lbl)
        if e is None or not e.warm:
            raise RuntimeError(
                f"candidate {lbl} failed to warm: "
                f"{getattr(e, 'error', 'missing')}")
        return e

    def commit(e) -> None:
        w.apply_tick_config(
            e.cfg, e.exe, telem_fold=e.fold_exe, telem_acc0=e.acc0,
            telem_skin_on=e.skin_on, telem_half_skin=e.half_skin)

    try:
        for nm in phases:
            spec = spec_of(nm)
            if w.cfg.scenario is not spec:
                # the WORKLOAD switch (production analog: the
                # population's behavior turns) — same swap machinery,
                # same kernel label, new scenario trace
                commit(warm(spec, label))
            for _w in range(windows_per_phase):
                for _t in range(window):
                    w.tick()
                # judge COMPLETED rotation windows like the production
                # _drive_governor (window_signature); the running
                # delta can cover ~0 ticks right after a rotation or a
                # swap's window reset and would misclassify. Fall back
                # to the running delta only before the first rotation.
                sig = w.window_signature() or w.workload_signature()
                sig_stream.append(sig)
                want = policy.observe(sig)
                if want is not None and want != label:
                    commit(warm(spec, want))
                    swaps.append({
                        "phase": nm, "window": policy.window,
                        "from": label, "to": want,
                        "sig": (sig or {}).get("sig"),
                    })
                    label = want
                    # the acceptance tick: a swap mid-churn must keep
                    # the full interest contract exact IMMEDIATELY
                    w.tick()
                    bad = check_oracle(w, clients)
                    oracle_checks += 1
                    mismatches.extend(
                        f"post-swap {label}: {m}" for m in bad[:8])
            bad = check_oracle(w, clients)
            oracle_checks += 1
            mismatches.extend(f"phase {nm}: {m}" for m in bad[:8])
    except Exception as exc:
        report["error"] = f"{type(exc).__name__}: {str(exc)[:300]}"
        return report

    report["swaps"] = swaps
    report["decision_log"] = policy.log_lines()
    report["oracle_ticks_checked"] = oracle_checks
    report["mismatches"] = mismatches[:16]
    report["entities_before"] = len(eids0)
    report["entities_after"] = len(
        [e for e in w.entities.values() if not e.destroyed])
    report["entity_ids_stable"] = set(w.entities) == eids0
    # determinism: the recorded signature stream through a FRESH
    # policy reproduces the decision log byte-identically
    replay = GovernorPolicy(table=seed_table(), up_windows=1,
                            down_windows=1, cooldown_windows=0)
    for sig in sig_stream:
        replay.observe(sig)
    report["replay_matches"] = (replay.log_lines()
                                == report["decision_log"])
    report["converged"] = bool(
        len(swaps) >= 3
        and not mismatches
        and report["entity_ids_stable"]
        and report["replay_matches"]
    )
    return report


# audit soak knobs: clean-churn length, migration-storm cadence, and
# the verdict's in-flight grace — 6 ticks so the injected drop is
# judged lost at age 7, inside the <= 8-tick detection criterion
AUDIT_SOAK_N = 96
AUDIT_SOAK_CLEAN_TICKS = 48
AUDIT_SOAK_GRACE = 6


def run_audit(seed: int, n: int = AUDIT_SOAK_N,
              clean_ticks: int = AUDIT_SOAK_CLEAN_TICKS,
              grace_ticks: int = AUDIT_SOAK_GRACE) -> dict:
    """The ISSUE-17 audit scenario, in-process like the governor soak
    (the assertions need direct World + ledger access). Two phases:

    1. CLEAN soak: a live world with the audit plane sampling the AOI
       oracle EVERY tick, under create/destroy churn plus a
       migration storm (full out->in round-trips through the real
       ``get_migrate_data``/``remove_for_migration``/
       ``restore_from_migration`` protocol). Must end with ZERO
       violations of any kind, zero oracle mismatches and a passing
       conservation verdict — the plane must not cry wolf.
    2. INJECTED drop: one more migrate-out whose restore is
       deliberately suppressed (the lost update every list of
       migration bugs fears). The conservation verdict must name the
       dropped EntityID within <= 8 ticks, and routing the finding
       back through the ledger's violation path must freeze an
       ``audit_violation`` flight-recorder bundle carrying the ledger
       tail.

    ``converged`` = both phases held. Same-seed reruns replay the same
    world evolution (the seeded-replay guarantee)."""
    from goworld_tpu.scenarios.runner import build_world
    from goworld_tpu.scenarios.spec import get_scenario
    from goworld_tpu.utils import audit as audit_mod
    from goworld_tpu.utils import flightrec

    report: dict = {"scenario": "audit", "seed": seed, "n": n,
                    "clean_ticks": clean_ticks,
                    "grace_ticks": grace_ticks, "converged": False}
    w, ents, clients = build_world(
        get_scenario("mixed"), n=n, skin=4.0, client_frac=0.15,
        seed=seed)
    ap = w.audit
    if ap is None:
        report["error"] = "world built without an audit plane"
        return report
    ap.sample_every = 1  # soak-grade scrutiny: oracle every tick
    rec = flightrec.FlightRecorder(ring=64,
                                   context_fn=ap.incident_context)
    incidents: list = []

    def tick_and_record() -> None:
        w.tick()
        frame = {"tick": w.tick_count}
        av = ap.take_violation()
        if av is not None:
            frame["audit_violation"] = av
        incidents.extend(rec.record(frame))

    def verdict() -> dict:
        ap.drain()
        return audit_mod.conservation_verdict(
            [ap.snapshot(tick=w.tick_count)], grace_ticks=grace_ticks)

    try:
        # ---- phase 1: clean churn + migration storm ------------------
        alive = [e for e in ents if not e.destroyed]
        storm = 0
        for t in range(clean_ticks):
            if t % 4 == 2 and alive:
                # one full migration round-trip through the real
                # protocol: out-record opened, in-record retires it
                e = alive[t % len(alive)]
                if not e.destroyed and e._migrating is None:
                    data = w.get_migrate_data(e)
                    w.remove_for_migration(e)
                    moved = w.restore_from_migration(data)
                    alive[t % len(alive)] = moved
                    storm += 1
            tick_and_record()
        clean = verdict()
        snap = ap.snapshot(tick=w.tick_count)
        report["migration_round_trips"] = storm
        report["oracle"] = snap["oracle"]
        report["violations_total"] = snap["violations_total"]
        report["clean_verdict"] = {
            k: clean.get(k) for k in ("ok", "live", "in_flight",
                                      "created", "destroyed",
                                      "problems")
        }
        clean_ok = (
            clean.get("ok") is True
            and not any(snap["violations_total"].values())
            and snap["oracle"]["mismatches"] == 0
            and snap["oracle"]["samples"] > 0
            and not incidents
        )
        report["clean_ok"] = clean_ok

        # ---- phase 2: injected entity drop ---------------------------
        victim = next(e for e in alive
                      if not e.destroyed and e._migrating is None)
        report["dropped_eid"] = victim.id
        w.get_migrate_data(victim)        # stamps the outgoing seq
        w.remove_for_migration(victim)    # ... and the restore never
        drop_tick = w.tick_count          # happens: the entity is lost
        detected_at = None
        problem = ""
        for _ in range(grace_ticks + 4):
            tick_and_record()
            v = verdict()
            named = [p for p in v.get("problems", [])
                     if victim.id in p]
            if not v.get("ok") and named:
                detected_at = w.tick_count - drop_tick
                problem = named[0]
                break
        report["detected_after_ticks"] = detected_at
        report["problem"] = problem
        detect_ok = detected_at is not None and detected_at <= 8
        report["detect_ok"] = detect_ok

        # the finding routes back through the ledger's violation path
        # (the aggregator's role in production): counter bumped, tail
        # annotated, and the flightrec trigger freezes the bundle
        bundle_ok = False
        if detect_ok:
            ap.ledger.note_violation("lost_entity", problem,
                                     w.tick_count)
            tick_and_record()
            frozen = [i for i in incidents
                      if i.get("trigger") == "audit_violation"]
            bundle_ok = bool(
                frozen and victim.id in frozen[-1].get("detail", "")
                and "tail" in (frozen[-1].get("context") or {}))
            report["incident"] = {
                "trigger": frozen[-1]["trigger"],
                "detail": frozen[-1]["detail"],
                "tick": frozen[-1]["tick"],
            } if frozen else None
        report["bundle_ok"] = bundle_ok
        report["converged"] = bool(clean_ok and detect_ok and bundle_ok)
        return report
    except Exception as exc:
        report["error"] = f"{type(exc).__name__}: {str(exc)[:300]}"
        return report
    finally:
        audit_mod.unregister(f"game{w.game_id}")


FAILOVER_SOAK_N = 96
FAILOVER_SOAK_TICKS = 40
FAILOVER_KEYFRAME_EVERY = 8


def _mirror_world(spec, cfg, game_id: int, seed: int):
    """A bare world sharing the primary's type registry (the shape a
    standby process boots with: classes registered, no population)."""
    from goworld_tpu.entity.entity import Entity
    from goworld_tpu.entity.manager import World
    from goworld_tpu.entity.space import Space

    _INF = float("inf")
    w = World(cfg, n_spaces=1, seed=seed, game_id=game_id)
    w.register_space("ScnSpace", type("ScnSpace", (Space,), {}))
    for i, (r, _f) in enumerate(spec.radius_mix):
        tname = f"Scn{i}"
        w.register_entity(
            tname, type(tname, (Entity,), {}),
            aoi_distance=0.0 if r == _INF else float(r))
    return w


def _census(w) -> set:
    """Live EntityIDs minus the world's OWN nil space (each game's nil
    space id is deterministic from ITS game_id and never replicated)."""
    out = {e.id for e in w.entities.values() if not e.destroyed}
    if w.nil_space is not None:
        out.discard(w.nil_space.id)
    return out


def run_failover(seed: int, n: int = FAILOVER_SOAK_N,
                 ticks: int = FAILOVER_SOAK_TICKS,
                 keyframe_every: int = FAILOVER_KEYFRAME_EVERY) -> dict:
    """The ISSUE-18 failover scenario, in-process like the audit soak
    (the conservation assertions need direct World + ledger access on
    BOTH sides). One run proves the whole hot-standby story:

    1. STREAM: a primary world under churn + a migration storm
       replicates through the real path — ``SnapshotChain.capture`` on
       the tick thread, the bounded :class:`ReplicationWorker` building
       key/delta records off-thread (disk chain riding the same jobs),
       ``StreamEncoder`` framing, ``StandbyApplier`` reconciling every
       frame into a live standby world with per-frame ledger resync.
    2. KILL: the primary dies at a deterministic tick (mid-churn,
       mid-migration — the worst case).
    3. PROMOTE: the standby claims through the kvreg-arbitrated
       protocol (first-writer-wins + epoch guard, emulated with the
       dispatcher's exact register semantics), wins, resumes ticking
       from its last applied frame. Both stale-claim race orders are
       replayed against the arbitration and must be refused, and the
       decision log must replay byte-for-byte
       (:func:`goworld_tpu.replication.promote.replay_decisions`).
    4. VERDICT: the promoted census must equal the primary's census at
       the last applied frame — zero lost, zero duplicated EntityIDs —
       and the standby's own conservation verdict must pass.
    5. A/B: the same crash recovered COLD (fresh World + chain restore
       from the disk records the worker wrote) is timed against the
       warm promotion; the paper's claim is >= 10x. The cold time is a
       LOWER bound (a real cold restore also pays process boot).

    Same-seed reruns replay the same world evolution and the same
    decision log (the seeded-replay guarantee)."""
    from goworld_tpu import freeze as freeze_mod
    from goworld_tpu.replication.promote import (
        DecisionLog, adjudicate, claim_key, claim_value,
        replay_decisions)
    from goworld_tpu.replication.standby import (
        StandbyApplier, StandbyTracker)
    from goworld_tpu.replication.worker import ReplicationWorker
    from goworld_tpu.scenarios.runner import build_world
    from goworld_tpu.scenarios.spec import get_scenario
    from goworld_tpu.utils import audit as audit_mod
    from goworld_tpu.utils import snapfiles

    import tempfile

    report: dict = {"scenario": "failover", "seed": seed, "n": n,
                    "ticks": ticks, "keyframe_every": keyframe_every,
                    "converged": False}
    spec = get_scenario("mixed")
    kill_tick = ticks  # deterministic: the last streamed tick
    tmpdir = tempfile.mkdtemp(prefix="failover_soak_")
    primary, ents, _clients = build_world(
        spec, n=n, skin=4.0, client_frac=0.15, seed=seed)
    standby = _mirror_world(spec, primary.cfg, game_id=2, seed=seed)
    # the standby's attach-time warmup (net/game.py _standby_tick):
    # compile the jit'd tick program on the still-empty world — SoA
    # shapes are capacity-static, so this is the same program the
    # promoted tick runs; without it the "warm" promotion would pay
    # seconds of compile, the exact cost hot standby exists to avoid
    standby.tick()
    standby.tick_count = 0
    tracker = StandbyTracker(2, primary.game_id, tick_hz=60.0)
    applier = StandbyApplier(standby, primary.game_id,
                             tracker=tracker)
    frames: list = []
    lock = threading.Lock()

    def send_fn(blob: bytes, kind: str, tick: int) -> None:
        with lock:
            frames.append((blob, kind, tick))

    chain = freeze_mod.SnapshotChain(primary, tmpdir,
                                     keyframe_every=keyframe_every)
    worker = ReplicationWorker(chain, game_id=primary.game_id,
                               queue_max=4, send_fn=send_fn)
    census_by_tick: dict[int, set] = {}
    try:
        # ---- phase 1: stream under churn + migration storm -----------
        alive = [e for e in ents if not e.destroyed]
        storm = 0
        applied = rejected = 0
        bytes_stream = 0
        apply_ms = 0.0
        for t in range(ticks):
            if t % 4 == 2 and alive:
                e = alive[t % len(alive)]
                if not e.destroyed and e._migrating is None:
                    data = primary.get_migrate_data(e)
                    primary.remove_for_migration(e)
                    moved = primary.restore_from_migration(data)
                    alive[t % len(alive)] = moved
                    storm += 1
            primary.tick()
            census_by_tick[primary.tick_count] = _census(primary)
            worker.submit(chain.capture(), to_disk=True,
                          to_stream=True)
            worker.drain()  # deterministic soak: no backlog drops
            with lock:
                batch, frames[:] = frames[:], []
            for blob, _kind, _tick in batch:
                t0 = time.perf_counter()
                out = applier.apply(blob)
                apply_ms += (time.perf_counter() - t0) * 1e3
                bytes_stream += len(blob)
                if out["ok"]:
                    applied += 1
                else:
                    rejected += 1
        report["migration_round_trips"] = storm
        report["frames_applied"] = applied
        report["frames_rejected"] = rejected
        report["replication_bytes_per_tick"] = round(
            bytes_stream / max(1, ticks), 1)
        report["standby_apply_ms_per_tick"] = round(
            apply_ms / max(1, ticks), 3)
        report["worker"] = worker.stats()
        stream_ok = applied > 0 and rejected == 0
        report["stream_ok"] = stream_ok

        # ---- phase 2: deterministic kill + arbitrated promotion ------
        # the primary is dead from here on: nothing submits, nothing
        # streams. The standby promotes from its last APPLIED frame.
        applied_tick = applier.decoder.applied_tick
        applied_seq = applier.decoder.applied_seq
        report["kill_tick"] = kill_tick
        report["applied_tick_at_kill"] = applied_tick

        kvreg: dict[str, str] = {}

        def kv_register(key: str, val: str, force: bool = False) -> str:
            # the dispatcher's exact first-writer-wins semantics
            # (net/dispatcher.py _h_kvreg): a later non-force register
            # gets the existing value broadcast back
            if key not in kvreg or force:
                kvreg[key] = val
            return kvreg[key]

        key = claim_key(primary.game_id)
        epoch = 1
        mine = claim_value(2, epoch, applied_seq)
        log = DecisionLog()
        log.note("claim", key=key, value=mine, epoch=epoch,
                 applied_seq=applied_seq, applied_tick=applied_tick)
        t_warm0 = time.perf_counter()
        winner = kv_register(key, mine)
        verdict = adjudicate(winner, mine)
        log.note("adjudicate", winner=winner, mine=mine,
                 verdict=verdict)
        promote_ok = verdict == "won"
        standby.tick_count = max(standby.tick_count, applied_tick)
        log.note("promoted", epoch=epoch, tick=standby.tick_count,
                 seq=applied_seq, entities=len(_census(standby)))
        standby.tick()  # first served tick: staged mirror state
        warm_secs = time.perf_counter() - t_warm0  # flushes to device
        # promotion latency in TICKS: staleness at the kill (frames
        # behind the dead primary) + the one resume tick
        promotion_latency_ticks = (kill_tick - max(0, applied_tick)) + 1
        tracker.note_promoted(epoch, applied_tick)
        report["promotion_latency_ticks"] = promotion_latency_ticks
        report["promotion_secs"] = round(warm_secs, 4)
        report["promote_ok"] = promote_ok

        # both stale-claim race orders must be refused:
        # (a) stale-second — a zombie replays an OLD claim after the
        #     live winner registered: first-writer-wins broadcasts the
        #     live winner; the zombie adjudicates "lost"
        stale = claim_value(7, 0, 3)
        zl = DecisionLog()
        zl.note("claim", key=key, value=stale, epoch=0, applied_seq=3,
                applied_tick=-1)
        zw = kv_register(key, stale)
        zv = adjudicate(zw, stale)
        zl.note("adjudicate", winner=zw, mine=stale, verdict=zv)
        stale_second_refused = zv == "lost" and kvreg[key] == mine
        # (b) stale-first — the replay lands BEFORE the live claim on a
        #     fresh key: the live claimant sees a lower-epoch winner
        #     ("stale_winner"), force-re-registers (legitimate exactly
        #     then), and wins the next broadcast
        key2 = claim_key(99)
        kv_register(key2, claim_value(7, 0, 3))  # zombie lands first
        mine2 = claim_value(2, 1, applied_seq)
        fl = DecisionLog()
        w1 = kv_register(key2, mine2)
        v1 = adjudicate(w1, mine2)
        fl.note("adjudicate", winner=w1, mine=mine2, verdict=v1)
        stale_first_named = v1 == "stale_winner"
        w2 = kv_register(key2, mine2, force=True)
        v2 = adjudicate(w2, mine2)
        fl.note("force_reregister", winner=w2, mine=mine2, verdict=v2)
        stale_first_recovered = v2 == "won"
        arbitration_ok = bool(stale_second_refused and stale_first_named
                              and stale_first_recovered)
        report["arbitration"] = {
            "stale_second_refused": stale_second_refused,
            "stale_first_named": stale_first_named,
            "stale_first_recovered": stale_first_recovered,
        }
        report["arbitration_ok"] = arbitration_ok
        # the decision logs must replay byte-for-byte from their inputs
        replay_ok = all(
            replay_decisions(d.inputs) == d.dump()
            for d in (log, zl, fl))
        report["decision_log_replay_ok"] = replay_ok
        report["decision_log"] = log.lines

        # ---- phase 3: conservation verdict ---------------------------
        want = census_by_tick.get(applied_tick, set())
        got = _census(standby)
        lost = sorted(want - got)
        extra = sorted(got - want)
        report["entities_expected"] = len(want)
        report["entities_promoted"] = len(got)
        report["entities_lost"] = len(lost)
        report["entities_duplicated"] = len(extra)
        report["lost_eids"] = lost[:8]
        report["duplicated_eids"] = extra[:8]
        ap2 = standby.audit
        conservation_ok = False
        if ap2 is not None:
            ap2.drain()
            v = audit_mod.conservation_verdict(
                [ap2.snapshot(tick=standby.tick_count)])
            report["conservation_verdict"] = {
                k: v.get(k) for k in ("ok", "live", "in_flight",
                                      "created", "destroyed",
                                      "problems")}
            conservation_ok = v.get("ok") is True
        census_ok = not lost and not extra
        report["census_ok"] = census_ok
        report["conservation_ok"] = conservation_ok

        # ---- phase 4: cold-restore A/B -------------------------------
        # the SAME crash recovered the pre-standby way: fresh World,
        # chain records resolved from disk (the worker wrote them),
        # restore_world, first tick. A real cold restore ALSO pays
        # process boot + jit warmup, so this is a conservative floor.
        t_cold0 = time.perf_counter()
        snap_path = snapfiles.latest_snapshot_path(
            primary.game_id, tmpdir)
        cold_ok = False
        if snap_path is not None:
            data = freeze_mod.read_freeze_file(snap_path)
            cold = _mirror_world(spec, primary.cfg, game_id=3,
                                 seed=seed)
            try:
                freeze_mod.restore_world(cold, data)
                cold.tick()
                cold_ok = True
            finally:
                audit_mod.unregister("game3")
        cold_secs = time.perf_counter() - t_cold0
        report["cold_restore_secs"] = round(cold_secs, 4)
        report["cold_restore_ok"] = cold_ok
        speedup = cold_secs / max(warm_secs, 1e-9)
        report["warm_vs_cold_speedup"] = round(speedup, 1)
        ab_ok = cold_ok and speedup >= 10.0
        report["ab_ok"] = ab_ok

        report["standby"] = tracker.snapshot()
        report["converged"] = bool(
            stream_ok and promote_ok and arbitration_ok and replay_ok
            and census_ok and conservation_ok and ab_ok)
        return report
    except Exception as exc:
        report["error"] = f"{type(exc).__name__}: {str(exc)[:300]}"
        return report
    finally:
        worker.close()
        audit_mod.unregister(f"game{primary.game_id}")
        audit_mod.unregister("game2")
        import shutil

        shutil.rmtree(tmpdir, ignore_errors=True)


REBALANCE_SOAK_N = 96
REBALANCE_SOAK_BATCH = 24
REBALANCE_SOAK_WINDOWS = 20
REBALANCE_HOLD_WINDOWS = 3
REBALANCE_COOLDOWN_WINDOWS = 12
REBALANCE_TIMEOUT_WINDOWS = 4
# windows from the commit to the donor OBSERVING NORMAL again — the
# report's recovery budget (clean variant)
REBALANCE_RECOVERY_BUDGET = 6


def _run_rebalance_variant(seed: int, kill_target: bool,
                           n: int = REBALANCE_SOAK_N,
                           batch: int = REBALANCE_SOAK_BATCH,
                           windows: int = REBALANCE_SOAK_WINDOWS
                           ) -> dict:
    """One donor/receiver pair driven through the REAL rebalance stack
    (:class:`RebalancePolicy` + :class:`HandoffExecutor` +
    :class:`RebalanceController`): the donor world holds a
    sustained-DEGRADED occupancy proxy, the receiver is an underloaded
    mirror world, and the transport delivers each pump window's sends
    one window later (a one-window wire). ``kill_target=False`` proves
    the clean handoff; ``kill_target=True`` kills the receiver after
    the first delivered sub-batch — the remaining sends vanish into
    the dead target, the executor's idle-window timeout must abort,
    and every unacked entity must come back LIVE on the source with
    the deployment conservation verdict green the whole way."""
    from goworld_tpu.rebalance.controller import RebalanceController
    from goworld_tpu.rebalance.executor import HandoffExecutor
    from goworld_tpu.rebalance.policy import RebalancePolicy
    from goworld_tpu.scenarios.runner import build_world
    from goworld_tpu.scenarios.spec import get_scenario
    from goworld_tpu.utils import audit as audit_mod
    from goworld_tpu.utils import flightrec

    variant = "target_kill" if kill_target else "clean"
    rep: dict = {"variant": variant, "seed": seed, "n": n,
                 "batch": batch, "windows": windows,
                 "converged": False}
    spec = get_scenario("mixed")
    donor, _ents, _clients = build_world(
        spec, n=n, skin=4.0, client_frac=0.15, seed=seed)
    recv = _mirror_world(spec, donor.cfg, game_id=2, seed=seed)
    recv.create_nil_space()
    recv_space = recv.create_space("ScnSpace")
    recv.tick()  # jit warmup off the measured path
    recv.tick_count = 0
    try:
        dap, rap = donor.audit, recv.audit
        if dap is None or rap is None:
            rep["error"] = "world built without an audit plane"
            return rep
        original = _census(donor)
        recv_base = _census(recv)  # the receiver's own space entities
        c0 = len(original)
        # occupancy-proxy overload stage: DEGRADED while the census
        # holds at least (c0 - batch/2) entities, so a COMPLETED
        # handoff of `batch` flips the donor NORMAL and an aborted one
        # (half the cohort restored) does not — the stage is a pure
        # deterministic function of world state, seeded-replay safe
        hot_threshold = c0 - batch // 2
        rep["hot_threshold"] = hot_threshold

        def stage_of(w) -> str:
            return ("DEGRADED" if len(_census(w)) >= hot_threshold
                    else "NORMAL")

        policy = RebalancePolicy(
            hold_windows=REBALANCE_HOLD_WINDOWS, batch=batch,
            cooldown_windows=REBALANCE_COOLDOWN_WINDOWS)
        agent = HandoffExecutor(donor, game_id=donor.game_id,
                                batch=batch)
        donor_name = f"game{donor.game_id}"
        mailbox: list = []
        receiver_alive = True
        recv_dead_snap: dict | None = None
        dropped = delivered = 0

        def transport(action):
            # the committed action's send callable: one-window wire
            return lambda eid, data: mailbox.append((eid, data))

        ctl = RebalanceController(
            policy, agents={donor_name: agent}, transport=transport,
            rate=max(1, batch // 2),
            timeout_windows=REBALANCE_TIMEOUT_WINDOWS)

        def deliver() -> None:
            nonlocal dropped, delivered
            arriving, mailbox[:] = mailbox[:], []
            for eid, data in arriving:
                if not receiver_alive:
                    dropped += 1  # the dead target never acks
                    continue
                recv.restore_from_migration(data, space=recv_space)
                agent.ack(eid)
                delivered += 1

        def recv_snapshot() -> dict:
            # a dead game's planes stop answering; the aggregator (and
            # this verdict) judges from its LAST scrape
            if recv_dead_snap is not None:
                return recv_dead_snap
            rap.drain()
            return rap.snapshot(tick=recv.tick_count)

        def verdict() -> dict:
            dap.drain()
            return audit_mod.conservation_verdict(
                [dap.snapshot(tick=donor.tick_count), recv_snapshot()])

        rec = flightrec.FlightRecorder(
            ring=64, context_fn=dap.incident_context)
        incidents: list = []
        verdict_ok_all = True
        max_in_flight = 0
        commit_window = recovered_window = None
        for w_i in range(1, windows + 1):
            deliver()  # last window's sends arrive on the wire
            if kill_target and receiver_alive and delivered > 0:
                # the receiver dies with a sub-batch still queued on
                # the donor: the worst case — mid-handoff, after acks
                recv_dead_snap = rap.snapshot(tick=recv.tick_count)
                receiver_alive = False
                rep["killed_at_window"] = w_i
                rep["acked_before_kill"] = delivered
            donor.tick()
            if receiver_alive:
                recv.tick()
            obs = {
                donor_name: {"stage": stage_of(donor),
                             "entities": len(_census(donor)),
                             "present": True},
                "game2": {"stage": stage_of(recv),
                          "entities":
                              len(_census(recv) - recv_base),
                          "present": receiver_alive},
            }
            if (commit_window is not None and recovered_window is None
                    and obs[donor_name]["stage"] == "NORMAL"):
                recovered_window = w_i  # donor OBSERVED healthy again
            action = ctl.step(obs)
            if action is not None and commit_window is None:
                commit_window = w_i
            v = verdict()
            max_in_flight = max(max_in_flight, int(v["in_flight"]))
            if not v["ok"]:
                verdict_ok_all = False
                rep.setdefault("verdict_problems", v["problems"])
            frame = {"tick": donor.tick_count}
            note = agent.take_action_note()
            if note is not None:
                frame["rebalance"] = note
            incidents.extend(rec.record(frame))

        # ---- the verdicts --------------------------------------------
        results = [dict(f) for ev, f in policy.log.inputs
                   if ev == "result"]
        aborts = [r for r in results if r.get("kind") == "abort"]
        dones = [r for r in results if r.get("kind") == "done"]
        donor_final = _census(donor)
        moved_final = _census(recv) - recv_base
        lost = sorted(original - (donor_final | moved_final))
        dup = sorted(donor_final & moved_final)
        ghosts = sorted((donor_final | moved_final) - original)
        replay_ok = RebalancePolicy.replay(
            policy.log.inputs,
            hold_windows=REBALANCE_HOLD_WINDOWS, batch=batch,
            cooldown_windows=REBALANCE_COOLDOWN_WINDOWS,
        ) == policy.log.dump()
        trigger_fired = sum(
            1 for i in incidents
            if i.get("trigger") == "rebalance_action")
        rep.update({
            "handoff_fired": commit_window is not None,
            "commit_window": commit_window,
            "committed": policy.committed,
            "entities_moved": len(moved_final),
            "entities_lost": len(lost),
            "entities_duplicated": len(dup) + len(ghosts),
            "lost_eids": lost[:8],
            "duplicated_eids": (dup + ghosts)[:8],
            "sends_dropped": dropped,
            "conservation_ok_all_windows": verdict_ok_all,
            "max_in_flight_seen": max_in_flight,
            "decision_log_replay_ok": replay_ok,
            "rebalance_action_triggers": trigger_fired,
            "moves_total": agent.snapshot()["moves_total"],
            "aborts_total": dict(agent.aborts_total),
            "decision_log": list(policy.log.lines),
        })
        zero_loss = not lost and not dup and not ghosts
        if kill_target:
            abort = aborts[0] if aborts else {}
            rep["abort_cause"] = abort.get("cause")
            rep["entities_restored"] = int(abort.get("restored", 0))
            rep["converged"] = bool(
                commit_window is not None
                and agent.aborted == 1 and not dones
                and abort.get("cause") == "timeout"
                # mid-batch: some of the cohort was acked before the
                # kill, the rest must be restored live on the source
                and 0 < len(moved_final) < batch
                and rep["entities_restored"] == batch
                - len(moved_final)
                and zero_loss and verdict_ok_all and replay_ok
                and trigger_fired > 0)
        else:
            rep["donor_recovery_windows"] = (
                None if recovered_window is None or commit_window
                is None else recovered_window - commit_window)
            rep["converged"] = bool(
                commit_window is not None
                and policy.committed == 1 and agent.completed == 1
                and not aborts
                and len(moved_final) == batch
                and rep["donor_recovery_windows"] is not None
                and rep["donor_recovery_windows"]
                <= REBALANCE_RECOVERY_BUDGET
                and zero_loss and verdict_ok_all
                # the verdict judged a window with a batch in flight
                and max_in_flight > 0
                and replay_ok and trigger_fired > 0)
        return rep
    except Exception as exc:
        rep["error"] = f"{type(exc).__name__}: {str(exc)[:300]}"
        return rep
    finally:
        from goworld_tpu.utils import audit as audit_mod

        audit_mod.unregister(f"game{donor.game_id}")
        audit_mod.unregister("game2")


def run_rebalance(seed: int) -> dict:
    """The ISSUE-19 self-healing rebalance scenario, in-process like
    the audit and failover soaks. ONE run proves BOTH halves of the
    story on the same seed:

    - ``clean``: sustained DEGRADED fires exactly one rate-limited
      cohort handoff through the production migration machinery, the
      donor recovers to NORMAL within the recovery budget, zero
      entities are lost or duplicated, the deployment conservation
      verdict is green EVERY window (including mid-batch, with the
      cohort in flight), and the decision log replays byte-for-byte.
    - ``target_kill``: the receiver dies mid-handoff with a sub-batch
      unacked; the executor's timeout abort must restore every unacked
      entity LIVE on the source (ledger out-record/seq machinery —
      the self-round-trip retires the record), already-acked entities
      stay moved, and the donor + receiver censuses still partition
      the original entity set exactly.

    Same-seed reruns replay the same observation stream and therefore
    the same decision log (the seeded-replay guarantee)."""
    report: dict = {"scenario": "rebalance", "seed": seed,
                    "converged": False}
    report["clean"] = _run_rebalance_variant(seed, kill_target=False)
    report["target_kill"] = _run_rebalance_variant(
        seed, kill_target=True)
    report["converged"] = bool(
        report["clean"].get("converged")
        and report["target_kill"].get("converged"))
    return report


def _ini_port(server_dir: str, section: str, key: str) -> int:
    import configparser

    cp = configparser.ConfigParser()
    cp.read(os.path.join(server_dir, "goworld_tpu.ini"))
    return int(cp[section][key])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--dir", default=None,
                    help="throwaway server dir (created); required for "
                         "the cluster scenarios (kill, overload), "
                         "unused by the in-process ones "
                         "(governor, audit, failover, rebalance)")
    ap.add_argument("--scenario",
                    choices=("kill", "overload", "governor", "audit",
                             "failover", "rebalance"),
                    default="kill")
    ap.add_argument("--seed", type=int, default=77)
    ap.add_argument("--deposits", type=int, default=25)
    ap.add_argument("--kill-tick", type=int, default=KILL_TICK)
    ap.add_argument("--flood-secs", type=float, default=6.0,
                    help="overload scenario: bot flood duration")
    ap.add_argument("--msg-rate", type=float, default=120.0,
                    help="overload scenario: flood messages per second")
    ap.add_argument("--workload", default="",
                    help="adversarial NPC workload for the game under "
                         "test (goworld_tpu/scenarios registry name, "
                         "e.g. hotspot|teleport|mixed); default: the "
                         "homogeneous random_walk")
    ap.add_argument("--out", default="chaos_report.json")
    args = ap.parse_args()
    if args.scenario in ("governor", "audit", "failover",
                         "rebalance"):
        # in-process (no cluster dir needed): the oracle + entity
        # audits need direct World access; --dir is accepted but
        # unused for symmetry with the other scenarios
        if args.scenario == "governor":
            report = run_governor(args.seed)
            report["workload"] = "governor-schedule"
        elif args.scenario == "failover":
            report = run_failover(args.seed)
            report["workload"] = "failover-churn"
        elif args.scenario == "rebalance":
            report = run_rebalance(args.seed)
            report["workload"] = "rebalance-handoff"
        else:
            report = run_audit(args.seed)
            report["workload"] = "audit-churn"
        with open(args.out, "w") as f:
            json.dump(report, f, indent=2)
        print(json.dumps(report, indent=2))
        return 0 if report.get("converged") else 1
    if not args.dir:
        ap.error(f"--dir is required for the {args.scenario} scenario")
    server_dir, _, _ = build_server_dir(
        args.dir, overload_knobs=args.scenario == "overload",
        workload=args.workload)
    if args.scenario == "overload":
        report = run_overload(server_dir, args.seed, args.flood_secs,
                              args.msg_rate)
    else:
        report = run_soak(server_dir, args.seed, args.deposits,
                          kill_tick=args.kill_tick)
    report["workload"] = args.workload or "random_walk"
    with open(args.out, "w") as f:
        json.dump(report, f, indent=2)
    print(json.dumps(report, indent=2))
    return 0 if report.get("converged") else 1


if __name__ == "__main__":
    sys.exit(main())
