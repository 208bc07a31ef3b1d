"""One clock for host and device (ISSUE 25): the tick's phases carry
``gw.`` names in the compiled program and change nothing else in it;
the serve loop's spans go to the profiler through a hook only the game
process sets, and into ``tick_phase_ms``; the wait at the game's queue
is counted; ``benchmark/phase_reduce.py`` reads all of it back from a
capture by name."""

import contextlib
import importlib.util
import os
import re
import subprocess
import sys
import threading
import time

import jax
import numpy as np
import pytest

from goworld_tpu.core.state import WorldConfig, create_state
from goworld_tpu.core.step import TickInputs, make_tick
from goworld_tpu.entity.entity import Entity, GameClient
from goworld_tpu.entity.manager import World
from goworld_tpu.entity.space import Space
from goworld_tpu.ops.aoi import GridSpec
from goworld_tpu.utils import metrics, overload

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmark")

PHASES = ("gw.inputs", "gw.behave", "gw.integrate", "gw.aoi", "gw.delta",
          "gw.sync", "gw.attrs")
SUB_PHASES = ("gw.aoi.cells", "gw.aoi.index", "gw.aoi.gather",
              "gw.aoi.rank")


# =======================================================================
# (1) device: the names are in the program, and nothing else changed
# =======================================================================
def _tick_text(grid_kw: dict) -> tuple[str, str]:
    """The tick lowered at a small capacity, traced from scratch: its
    program text without and with the operations' names (jax prints
    them as ``loc("<name stack>/<primitive>")``)."""
    jax.clear_caches()      # inner jitted helpers keep their first trace
    cfg = WorldConfig(capacity=256, grid=GridSpec(
        radius=10.0, extent_x=80.0, extent_z=80.0, row_block=64,
        **grid_kw))
    lowered = make_tick(cfg).lower(
        create_state(cfg), TickInputs.empty(cfg), None)
    return lowered.as_text(), lowered.as_text(debug_info=True)


def _strip_metadata(hlo: str) -> str:
    """Without ``metadata={...}``, and with every instruction's label
    numbered by first appearance: the compiler forms a label from the
    last part of ``op_name`` (``%tile.14``), so labels are names too."""
    hlo = re.sub(r",? ?metadata=\{[^{}]*\}", "", hlo)
    seen: dict[str, str] = {}
    return re.sub(
        r"%[\w.\-]+",
        lambda m: seen.setdefault(m.group(0), f"%v{len(seen)}"), hlo)


@pytest.mark.parametrize("grid_kw", [
    {"sweep_impl": "ranges"},
    {"sweep_impl": "table"},
    {"sweep_impl": "cellrow"},
    {"sweep_impl": "shift"},
    {"sweep_impl": "fused"},
    {"sweep_impl": "ranges", "skin": 4.0},
    {"sweep_impl": "ranges", "skin": 4.0, "precision": "q16"},
], ids=lambda kw: "-".join(str(v) for v in kw.values()))
def test_tick_holds_every_scope_and_nothing_else_changed(
        grid_kw, monkeypatch):
    program, named = _tick_text(grid_kw)
    names = set(re.findall(r'loc\("([^"]+)"', named))
    for scope in PHASES + SUB_PHASES:
        assert any(scope in n.split("/") for n in names), \
            f"{scope} names no operation of the tick ({grid_kw})"
    assert "gw." not in program          # names are not the program

    monkeypatch.setattr(
        jax, "named_scope", lambda name: contextlib.nullcontext())
    bare_program, bare_named = _tick_text(grid_kw)
    jax.clear_caches()      # leave no scope-less trace behind
    assert "gw." not in bare_named
    assert program == bare_program


def test_compiled_tick_is_the_same_program_without_its_metadata(
        monkeypatch):
    """The served default (``cellrow``), through the compiler."""
    def compiled() -> str:
        jax.clear_caches()
        cfg = WorldConfig(capacity=256, grid=GridSpec(
            radius=10.0, extent_x=80.0, extent_z=80.0, row_block=64))
        return make_tick(cfg).lower(
            create_state(cfg), TickInputs.empty(cfg), None
        ).compile().as_text()

    texts = []
    for bare in (False, True):     # one call site: one stack-frame table
        if bare:
            monkeypatch.setattr(
                jax, "named_scope", lambda name: contextlib.nullcontext())
        texts.append(compiled())
    scoped, bare = texts
    jax.clear_caches()
    names = set(re.findall(r'op_name="([^"]*)"', scoped))
    # the sub-phases sit under the sweep's own scope, under the jit's
    assert any(n.startswith("jit(tick)/gw.aoi/") and "/gw.aoi.gather/"
               in n for n in names)
    assert "gw." not in bare
    assert _strip_metadata(scoped) != scoped
    assert _strip_metadata(scoped) == _strip_metadata(bare)


def test_telemetry_fold_is_named():
    import jax.numpy as jnp

    from goworld_tpu.ops import telemetry as telem

    cfg = WorldConfig(capacity=64, grid=GridSpec(
        radius=10.0, extent_x=40.0, extent_z=40.0))
    _st, outs = make_tick(cfg)(create_state(cfg), TickInputs.empty(cfg),
                               None)
    outs = jax.tree.map(lambda x: jnp.asarray(x)[None], outs)
    acc = telem.telemetry_init(False, occupancy=True, n_tiles=1)
    text = jax.jit(telem.telemetry_update_live).lower(acc, outs).as_text(
        debug_info=True)
    assert "gw.telemetry" in text


# =======================================================================
# (2) host: the timeline's spans go to the profiler through the hook
# =======================================================================
class _Recorder:
    """A fake ``TraceAnnotation`` class: logs enter/exit in order."""

    def __init__(self):
        self.log: list[tuple[str, str, dict]] = []

    def __call__(self, name, **kw):
        rec = self

        class _Ann:
            def __enter__(self):
                rec.log.append(("enter", name, kw))
                return self

            def __exit__(self, *exc):
                rec.log.append(("exit", name, kw))

        return _Ann()


class _Mob(Entity):
    pass


class _Arena(Space):
    pass


def _world(capacity=64, **kw) -> World:
    w = World(WorldConfig(
        capacity=capacity,
        grid=GridSpec(radius=10.0, extent_x=40.0, extent_z=40.0,
                      k=8, cell_cap=16, row_block=capacity),
        npc_speed=0.0, turn_prob=0.0), n_spaces=1, **kw)
    w.register_entity("Mob", _Mob)
    w.register_space("Arena", _Arena)
    w.create_nil_space()
    return w


def _serve(gs, ticks: int) -> None:
    """Run the real serve loop for exactly ``ticks`` iterations."""
    tick, left = gs.tick, [ticks]

    def counted():
        tick()
        left[0] -= 1
        if left[0] <= 0:
            gs._stop.set()

    gs.tick = counted
    t = threading.Thread(target=gs.serve_forever, daemon=True)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive(), "the serve loop did not stop"


@pytest.fixture
def recorder():
    rec = _Recorder()
    metrics.set_annotation(rec)
    yield rec
    metrics.set_annotation(None)


def test_one_frame_annotates_every_span_once_nested_in_the_frame(recorder):
    from goworld_tpu.net.game import GameServer

    w = _world()
    w.tick()                       # compile outside the recorded frame
    gs = GameServer(1, w, [], tick_interval=0.05,
                    gc_freeze_on_boot=False)
    recorder.log.clear()
    _serve(gs, 1)
    # (the audit worker's thread is not the frame's: left out here)
    recorder.log[:] = [e for e in recorder.log if e[1] != "gw.audit_judge"]
    names = [n for kind, n, _kw in recorder.log if kind == "enter"]
    # each once, but the two the wait for the device is made of: a
    # gw.fetch_outputs around each wait on the queue and a
    # gw.drain_inputs around each burst handled meanwhile
    once = [n for n in names
            if n not in ("gw.fetch_outputs", "gw.drain_inputs")]
    assert sorted(once) == sorted(set(once)), names
    want = {"gw.frame", "gw.drain_inputs", "gw.flush_staging",
            "gw.device_step", "gw.fetch_outputs", "gw.decode_fanout",
            "gw.fan_out", "gw.pacing_sleep"}
    assert want <= set(names), sorted(want - set(names))
    # world tick 1 of a world that samples every 64: no audit sample;
    # what else a frame may hold is named here and nowhere unknown
    assert set(names) - want <= {
        "gw.flightrec", "gw.overload_observe", "gw.audit_sample",
        "gw.rebalance", "gw.governor", "gw.restore_reconcile",
        "gw.mh_exchange"}
    frame = next(kw for kind, n, kw in recorder.log
                 if kind == "enter" and n == "gw.frame")
    assert frame == {"tick": 1}
    # nesting: every span opens and closes inside gw.frame, one at a
    # time, except the two lone ones, which follow the frame's close
    depth, inside = 0, []
    for kind, n, _kw in recorder.log:
        if kind == "enter":
            depth += 1
            if n != "gw.frame":
                inside.append((n, depth))
        else:
            depth -= 1
    assert depth == 0
    lone = {"gw.pacing_sleep", "gw.overload_observe"}
    assert all(d == (1 if n in lone else 2) for n, d in inside), inside
    order = [n for kind, n, _kw in recorder.log if kind == "exit"]
    assert order.index("gw.frame") < order.index("gw.pacing_sleep")


def test_audit_sample_and_worker_are_annotated(recorder):
    w = _world(audit_sample_every=2)
    arena = w.create_space("Arena")
    for i in range(3):
        w.create_entity("Mob", space=arena, pos=(5.0 + i, 0.0, 5.0))
    w.tick()                       # tick 0: the spawns are still staged
    w.tick()
    w.audit.drain()                # nothing of tick 0's is still judged
    recorder.log.clear()
    w.tick()                       # tick 2 samples a cohort and judges it
    w.audit.drain()
    names = [n for kind, n, _kw in recorder.log if kind == "enter"]
    assert names.count("gw.audit_sample") == 1
    # (the hook is the process's: another test's world may judge too)
    assert names.count("gw.audit_judge") >= 1
    assert ("tick_phase_ms", "audit_sample") in {
        ("tick_phase_ms", lb.get("phase")) for lb, _s in
        metrics.REGISTRY.histogram_snapshot("tick_phase_ms")}


def test_metrics_module_needs_no_jax_and_no_hook():
    """The gate and the dispatcher import utils.metrics: it must load
    and record with jax nowhere in the process."""
    code = (
        "import sys\n"
        "from goworld_tpu.utils import metrics\n"
        "tl = metrics.TickTimeline()\n"
        "tl.begin_tick(7)\n"
        "with tl.span('a'):\n    pass\n"
        "with metrics.annotation('gw.x'):\n    pass\n"
        "assert tl.end_tick() is not None\n"
        "with tl.lone_span('pacing_sleep'):\n    pass\n"
        "assert [s[0] for s in tl.records()[0][2]] == ['a']\n"
        "text = metrics.REGISTRY.expose_text()\n"
        "assert 'tick_phase_ms_count{phase=\"a\"} 1' in text, text\n"
        "assert 'phase=\"unspanned\"' in text\n"
        "assert 'phase=\"pacing_sleep\"' in text\n"
        "bad = [m for m in sys.modules if m == 'jax' "
        "or m.startswith(('jax.', 'jaxlib', 'libtpu'))]\n"
        "assert not bad, bad\n"
        "print('ok')\n")
    r = subprocess.run([sys.executable, "-c", code], cwd=REPO,
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, PYTHONPATH=REPO))
    assert r.returncode == 0 and r.stdout.strip() == "ok", \
        r.stdout + r.stderr


def test_timeline_overhead_with_the_hook_set(recorder):
    """tests/test_metrics.py's bound, with an annotation class set (the
    real one where jax has it: no profiler session runs here)."""
    from jax.profiler import TraceAnnotation

    metrics.set_annotation(TraceAnnotation)
    tl = metrics.TickTimeline(capacity=16)
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        tl.begin_tick(i)
        for name in ("a", "b", "c", "d", "e", "f"):
            with tl.span(name):
                pass
        tl.end_tick()
    per_tick = (time.perf_counter() - t0) / n
    assert per_tick < 160e-6, f"{per_tick * 1e6:.1f}us per tick"


# =======================================================================
# (3) tick_phase_ms adds up to tick_latency_ms
# =======================================================================
def _phase_sums() -> dict:
    return {lb["phase"]: snap["sum"] for lb, snap in
            metrics.REGISTRY.histogram_snapshot("tick_phase_ms") or []}


def test_phase_sums_equal_the_tick_histogram_over_ten_ticks():
    from goworld_tpu.net.game import GameServer

    w = _world()
    w.tick()
    gs = GameServer(1, w, [], tick_interval=0.01,
                    gc_freeze_on_boot=False)
    before, frames0 = _phase_sums(), gs._m_tick_hist.snapshot()
    _serve(gs, 10)
    after, frames1 = _phase_sums(), gs._m_tick_hist.snapshot()
    assert frames1["count"] - frames0["count"] == 10
    lone = ("pacing_sleep", "overload_observe")
    spans = sum(v - before.get(k, 0.0) for k, v in after.items()
                if k not in lone)
    total = frames1["sum"] - frames0["sum"]
    assert "unspanned" in after and "drain_inputs" in after
    assert abs(spans - total) < 1e-3, (spans, total)     # ms: 1 us
    assert after["pacing_sleep"] > before.get("pacing_sleep", 0.0)


# =======================================================================
# (4) the wait at the game's queue
# =======================================================================
def test_queue_wait_reads_the_sleep_per_class():
    q = overload.ClassQueues(stage="t_wait_q")

    def wait(cls):
        return metrics.histogram(
            "t_wait_q_wait_ms",
            **{"class": overload.CLASS_NAMES[cls]}).snapshot()

    q.offer(overload.CLASS_SYNC, "s")
    time.sleep(0.03)
    q.offer(overload.CLASS_RPC, "r")
    time.sleep(0.02)
    assert q.pop() == "r" and q.pop() == "s"     # priority order kept
    rpc, sync = wait(overload.CLASS_RPC), wait(overload.CLASS_SYNC)
    assert rpc["count"] == 1 and sync["count"] == 1
    # (a loaded machine oversleeps: the upper bounds only say "ms")
    assert 20.0 <= rpc["sum"] < 1000.0, rpc
    assert 50.0 <= sync["sum"] < 1000.0, sync
    assert sync["sum"] >= rpc["sum"] + 30.0
    assert wait(overload.CLASS_EVENTS)["count"] == 0
    # drain() observes too, with one clock read for all of it
    q.offer(overload.CLASS_EVENTS, "e")
    time.sleep(0.01)
    assert q.drain() == ["e"]
    assert wait(overload.CLASS_EVENTS)["count"] == 1
    assert wait(overload.CLASS_EVENTS)["sum"] >= 10.0


def test_game_queue_wait_is_in_the_games_metrics():
    from goworld_tpu.net import proto
    from goworld_tpu.net.game import GameServer
    from goworld_tpu.net.packet import Packet

    gs = GameServer(1, _world(), [], gc_freeze_on_boot=False)
    gs._handle_packet = lambda d, mt, p: None
    gs._on_packet_netthread(
        0, proto.MT_CALL_ENTITY_METHOD_FROM_CLIENT, Packet(b""))
    time.sleep(0.02)
    assert gs.pump() == 1
    text = metrics.REGISTRY.expose_text()
    series = metrics.parse_prometheus_text(text)
    assert series['game_queue_wait_ms_count{class="rpc"}'] >= 1
    assert series['game_queue_wait_ms_sum{class="rpc"}'] >= 20.0


def test_compile_seconds_are_counted():
    import jax.numpy as jnp

    from goworld_tpu.utils import compile_cache

    compile_cache.setup()
    h = metrics.histogram("jax_compile_seconds",
                          buckets=compile_cache._COMPILE_BUCKETS_S)
    n0, s0 = h.count, h.sum
    jax.jit(lambda x: (x * 3 + 1).sum())(jnp.ones(17)).block_until_ready()
    assert h.count > n0 and h.sum > s0
    assert "jax_compile_seconds_sum" in metrics.REGISTRY.expose_text()


# =======================================================================
# fault 1 (PERF.md section 7): a DEGRADED tick with a sync record
# =======================================================================
def test_sync_stride_two_decodes_a_tick_with_a_sync_record():
    """The stride branch's shed count was bound to ``dropped``, the
    dict of undecoded interest events that ``_process_outputs`` walks
    further down: the first such tick raised."""
    w = _world()
    arena = w.create_space("Arena")
    got = []
    w.sync_sink = lambda gate, cids, eids, vals: got.append(len(eids))
    a, b = (w.create_entity(
        "Mob", space=arena, pos=(5.0 + i, 0.0, 5.0),
        client=GameClient(1, f"CID{i:013d}", w)) for i in range(2))
    w.tick()
    shed0 = overload.shed_counter(overload.CLASS_SYNC, "stride").value
    w.sync_stride = 2
    for step in range(4):          # both cohorts of the stride get a turn
        w.stage_pos_sync_batch(
            np.array([e.id.encode("ascii") for e in (a, b)], "S16"),
            np.array([(6.0 + step + i, 0.0, 6.0, 0.5)
                      for i in range(2)], np.float32))
        w.tick()                   # raised TypeError before the repair
    assert int(w.last_outputs.sync_n[0]) > 0
    assert sum(got) > 0, "no sync record was delivered at stride 2"
    assert overload.shed_counter(
        overload.CLASS_SYNC, "stride").value > shed0


# =======================================================================
# (5) benchmark/phase_reduce.py on planes built by hand
# =======================================================================
def _phase_reduce():
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    spec = importlib.util.spec_from_file_location(
        "phase_reduce_under_test", os.path.join(BENCH, "phase_reduce.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MS = 1e6        # ns


def _hand_planes(shift_ms=1.5):
    """Three runs of a 60 ms program 100 ms apart (two whole frames);
    the host's clock runs ``shift_ms`` ahead of the device's."""
    def op(name, path, s, d):
        return (name, s * MS, d * MS, {"tf_op": path})

    ops, mods, host = [], [], []
    for r in range(3):
        t = 100.0 * r
        mods.append(("jit_step1(1)", t * MS, 60 * MS, {"run_id": r}))
        ops += [
            op("%copy", "jit(step1)/copy", t, 2),                # unscoped
            # a loop's own event, its children nested inside it
            op("%while.1", "jit(step1)/gw.aoi/while", t + 2, 40),
            op("%fusion.1", "jit(step1)/gw.aoi/while/body/gw.aoi.gather/"
               "dynamic_slice", t + 2, 30),
            op("%fusion.2", "jit(step1)/gw.aoi/while/body/gw.aoi.rank/"
               "top_k", t + 32, 10),
            op("%fusion.3", "jit(step1)/gw.delta/jit(interest_pairs)/x",
               t + 42, 6),
            # one fusion whose time two scopes of the group share
            op("%fusion.4", "jit(step1)/gw.sync/y", t + 46, 8),
            op("%fusion.5", "jit(step1)/gw.attrs/z", t + 54, 6),
        ]
        h = t + shift_ms            # the same instants on the host's clock
        host += [
            ("gw.frame", (h - 3) * MS, 75 * MS, {}),   # ends at h + 72
            ("gw.device_step", (h - 1) * MS, 1.5 * MS, {}),
            ("PJRT_LoadedExecutable_Execute", (h - 0.9) * MS, 1.2 * MS, {}),
            ("DoEnqueueProgram", (h - 0.5) * MS, 0.2 * MS, {"run_id": r}),
            ("gw.fetch_outputs", (h + 0.5) * MS, 59.5 * MS, {}),
            # the device goes idle at h+60 for 40 ms: 5 of them under
            # decode, 5 under fan_out, 2 under the frame alone, 20
            # under the pacing sleep, 5 under no span at all, 2 under
            # the next frame alone and 1 under its device_step
            ("gw.decode_fanout", (h + 60) * MS, 5 * MS, {}),
            ("gw.fan_out", (h + 65) * MS, 5 * MS, {}),
            ("gw.pacing_sleep", (h + 72) * MS, 20 * MS, {}),
        ]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": mods},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [
            {"name": "other/1", "events": [
                ("gw.audit_judge", 10 * MS, 50 * MS, {})]},
            {"name": "main/7", "events": host}]},
    ]


def test_phase_reduce_on_hand_built_planes():
    pr = _phase_reduce()
    res = pr.reduce_phases(_hand_planes())
    ok = pytest.approx
    assert res["frames"] == 2 and res["window_s"] == ok(0.2)
    assert res["busy_ms"] == ok(60.0)
    # nested scopes: the loop's event and its children count once
    assert res["scopes"]["gw.aoi"] == ok(40.0)
    assert res["scopes"]["gw.aoi.gather"] == ok(30.0)
    assert res["scopes"]["gw.aoi.rank"] == ok(10.0)
    assert res["scopes"]["gw.delta"] == ok(6.0)
    assert res["scopes"]["gw.sync"] == ok(8.0)
    # the union of three scopes whose operations overlap: 42..60
    assert res["groups"]["delta_sync"] == ok(18.0)
    assert res["unscoped_ms"] == ok(2.0)
    top = sum(res["scopes"][s] for s in ("gw.aoi", "gw.delta",
                                         "gw.sync", "gw.attrs"))
    assert top == ok(60.0 - 2.0 + 2.0)       # delta and sync overlap by 2
    # the clock shift: the run's enqueue at h - 0.5 against a start at t
    assert res["clock_shift_ms"] == ok(1.5 - 0.5)
    assert res["clock_shift_by"] == "enqueue"
    assert res["host_line"] == "main/7"
    # 40 ms idle a frame, split over the innermost host spans; with
    # the shift 0.5 short of the truth every boundary sits 0.5 late
    idle = res["idle"]
    assert res["idle_ms"] == ok(40.0)
    assert sum(idle.values()) == ok(40.0)
    assert idle["gw.pacing_sleep"] == ok(20.0)
    assert idle["gw.decode_fanout"] == ok(5.0)
    assert idle["gw.fan_out"] == ok(5.0)
    assert idle["gw.fetch_outputs"] == ok(0.5)
    assert idle["gw.frame"] == ok(2.0 + 2.0)
    assert idle[pr.UNLABELLED] == ok(5.0)
    assert idle["gw.device_step"] == ok(1.0 - 0.5)


def test_phase_reduce_without_links_scopes_or_spans():
    pr = _phase_reduce()
    planes = _hand_planes()
    # no run_id anywhere: the nearest launch before the run
    for p in planes:
        for ln in p["lines"]:
            ln["events"] = [(n, s, d, {k: v for k, v in st.items()
                                       if k != "run_id"})
                            for n, s, d, st in ln["events"]]
    res = pr.reduce_phases(planes)
    assert res["clock_shift_by"] == "nearest_launch"
    assert res["clock_shift_ms"] == pytest.approx(0.6)
    # a program without scopes or spans (the parent commit): the
    # readers report nothing rather than 0 or 100%
    planes[1]["lines"] = []
    for ln in planes[0]["lines"]:
        ln["events"] = [(n, s, d, {}) for n, s, d, _st in ln["events"]]
    res = pr.reduce_phases(planes)
    assert res["scopes"] == {} and res["host_line"] is None
    assert res["unscoped_ms"] == pytest.approx(res["busy_ms"])
    pr._CACHE["hand"] = res
    cell = {"cell": {"name": "hand"}}
    assert pr.scope_ms(cell, "gw.aoi") is None
    assert pr.scope_ms(cell, "delta_sync") is None
    assert pr.idle_ms(cell, lambda k: True) is None
    assert pr.reduce_phases([]) is None


def test_scoped_capture_from_the_chip_reads_by_name():
    """``benchmark/tests/make_scoped_trace.py``, recorded once on a TPU
    v5e: 8 matrix products under ``gw.aoi`` (6 of them under
    ``gw.aoi.gather``), 2 under ``gw.sync``; 30 ms of sleep under
    ``gw.decode_fanout``, 50 ms under ``gw.pacing_sleep``."""
    pr = _phase_reduce()
    res = pr.reduce_file(os.path.join(
        BENCH, "tests", "data", "scoped",
        "small_scoped_tpu_v5e.xplane.pb"))
    assert res is not None and res["frames"] == 3
    sc = res["scopes"]
    assert set(sc) == {"gw.aoi", "gw.aoi.gather", "gw.sync"}
    assert sc["gw.sync"] / sc["gw.aoi"] == pytest.approx(0.25, abs=0.04)
    assert sc["gw.aoi.gather"] / sc["gw.aoi"] \
        == pytest.approx(0.75, abs=0.04)
    assert sc["gw.aoi"] + sc["gw.sync"] + res["unscoped_ms"] \
        == pytest.approx(res["busy_ms"], rel=1e-6)
    assert res["idle"]["gw.decode_fanout"] == pytest.approx(30.0, abs=1.5)
    assert res["idle"]["gw.pacing_sleep"] == pytest.approx(50.0, abs=1.5)
    assert res["idle"].get(pr.UNLABELLED, 0.0) < 0.5
    assert 0.5 <= res["clock_shift_ms"] <= 5.0
    assert res["clock_shift_by"] == "enqueue"


def test_nameless_operations_take_their_callers_scope():
    """What the compiler makes itself (the loop a gather is expanded
    into, and that loop's body) has no ``op_name``, and a ``while``
    has no ``tf_op``: the program's HLO proto names them."""
    pr = _phase_reduce()
    hlo_cls = pr.proto_classes()[1]
    hlo = hlo_cls()
    mod = hlo.hlo_module

    def comp(cid, instrs):
        c = mod.computations.add(name=f"c{cid}", id=cid)
        for name, op_name, calls in instrs:
            i = c.instructions.add(name=name)
            i.metadata.op_name = op_name
            i.called_computation_ids.extend(calls)

    gather = "jit(step1)/gw.aoi/jit(f)/while/body/gw.aoi.gather/gather"
    comp(1, [("copy.1", "", []),
             ("while.13", "jit(step1)/gw.aoi/jit(f)/while", [2, 9]),
             ("add.9", "jit(step1)/add", [])])
    comp(2, [("while.12", gather, [3]),
             ("fusion.7", "jit(step1)/gw.aoi/jit(f)/while/body/"
              "gw.aoi.rank/top_k", [4]),
             ("copy.2", "", [])])
    comp(3, [("fusion.6", "", [5]), ("slice.353", "", [])])
    names = pr.hlo_names(hlo.SerializeToString(), hlo_cls)
    assert names["while.12"] == gather
    assert names["fusion.6"] == names["slice.353"] == gather
    assert pr.scopes_of(names["copy.2"]) == ("gw.aoi",)
    assert pr.scopes_of(names["fusion.7"]) == ("gw.aoi", "gw.aoi.rank")
    assert names["copy.1"] == "" and names["add.9"] == "jit(step1)/add"


def test_merged_is_trace_reduces_union():
    import numpy as np

    pr = _phase_reduce()
    from trace_reduce import clip, union

    rng = np.random.default_rng(25)
    s = rng.uniform(0, 100, 400)
    e = s + rng.uniform(0, 2, 400)
    mine = pr.merged(s, e)
    assert mine == union(list(zip(s.tolist(), e.tolist())))
    assert clip(mine, 10.0, 90.0) <= 80.0
    assert pr.merged(s[:0], e[:0]) == []


def test_host_segments_innermost_span_wins():
    pr = _phase_reduce()
    ev = [("gw.frame", 0.0, 100.0, {}), ("gw.a", 10.0, 20.0, {}),
          ("gw.b", 30.0, 10.0, {}), ("other", 0.0, 500.0, {}),
          ("gw.pacing_sleep", 100.0, 50.0, {})]
    assert pr.host_segments(ev) == [
        (0.0, 10.0, "gw.frame"), (10.0, 30.0, "gw.a"),
        (30.0, 40.0, "gw.b"), (40.0, 100.0, "gw.frame"),
        (100.0, 150.0, "gw.pacing_sleep")]
    assert pr.split_gaps([(5.0, 35.0), (140.0, 160.0)],
                         pr.host_segments(ev)) == {
        "gw.frame": 5.0, "gw.a": 20.0, "gw.b": 5.0,
        "gw.pacing_sleep": 10.0, pr.UNLABELLED: 10.0}


def test_readers_read_the_scrapes_and_report_nothing_on_a_bare_program(
        tmp_path, monkeypatch):
    pr = _phase_reduce()
    monkeypatch.setattr(pr, "WORK", str(tmp_path))
    os.makedirs(tmp_path / "c")
    cell = {"cell": {"name": "c"}}

    def edge(frames, **phase_sums):
        game = {"tick_latency_ms_count": frames,
                "tick_latency_ms_sum": 100.0 * frames,
                'game_queue_wait_ms_sum{class="rpc"}': 30.0 * frames,
                'game_queue_wait_ms_count{class="rpc"}': 2.0 * frames,
                'game_queue_wait_ms_sum{class="sync"}': 50.0 * frames,
                'game_queue_wait_ms_count{class="sync"}': 2.0 * frames,
                "jax_compile_seconds_sum": 12.5}
        for k, v in phase_sums.items():
            game[f'tick_phase_ms_sum{{phase="{k}"}}'] = v * frames
        return {"game": game, "gate": {}}

    scrapes = {"open": edge(10, device_step=1.0, fetch_outputs=60.0,
                            unspanned=0.5),
               "close": edge(50, device_step=1.0, fetch_outputs=60.0,
                             unspanned=0.5)}

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            "reader_" + name,
            os.path.join(BENCH, "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        sys.modules["phase_reduce"] = pr     # the one under test
        spec.loader.exec_module(mod)
        return mod.read

    assert reader("fetch_wait_ms")(scrapes, None, cell) \
        == pytest.approx(61.0)
    assert reader("frame_unspanned_ms")(scrapes, None, cell) \
        == pytest.approx(0.5)
    assert reader("queue_wait_ms")(scrapes, None, cell) \
        == pytest.approx(20.0)
    assert reader("setup_compile_s")(scrapes, None, cell) == 12.5
    assert reader("pump_ms")(scrapes, None, cell) is None   # no series
    assert os.path.isfile(tmp_path / "c" / "host_phases.json")
    # the parent commit: none of the series, no capture
    bare = {"open": {"game": {"tick_latency_ms_count": 1.0}, "gate": {}},
            "close": {"game": {"tick_latency_ms_count": 41.0}, "gate": {}}}
    for name in ("queue_wait_ms", "pump_ms", "flush_ms", "fetch_wait_ms",
                 "decode_ms", "fanout_ms", "frame_unspanned_ms",
                 "setup_compile_s", "aoi_ms", "aoi_gather_ms",
                 "delta_sync_ms", "device_unscoped_share",
                 "idle_host_ms", "idle_unlabelled_ms"):
        assert reader(name)(bare, None, {"cell": {"name": "none"}}) \
            is None, name
    sys.modules.pop("phase_reduce", None)
