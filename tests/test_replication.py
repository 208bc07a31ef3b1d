"""Hot-standby replication (ISSUE 18): wire-frame CRC chaining and the
torn-stream classes (truncated / corrupted / reordered / replayed
frames rejected WHOLE, stream self-heals at the next keyframe),
double-apply lattice-plane determinism, the bounded replication
worker's never-block-the-tick contract (slow disk -> loud drops +
keyframe collapse, never a stalled submit), standby apply/mirror
semantics into a live world, the kvreg promotion arbitration
(first-writer-wins + epoch guard — BOTH stale-claim race orders
refused), the byte-replayable decision log, and the ``/standby``
registry payloads."""

import threading
import time

import msgpack
import numpy as np
import pytest

from goworld_tpu import freeze
from goworld_tpu.replication.frames import (
    StreamDecoder,
    StreamEncoder,
    TornStreamError,
)
from goworld_tpu.replication.promote import (
    DecisionLog,
    adjudicate,
    claim_key,
    claim_value,
    parse_claim,
    replay_decisions,
)
from goworld_tpu.replication import standby as standby_mod
from goworld_tpu.replication.standby import StandbyApplier, StandbyTracker
from goworld_tpu.replication.worker import ReplicationWorker
from goworld_tpu.utils import audit, metrics

pytestmark = pytest.mark.replication


@pytest.fixture(autouse=True)
def _fresh_registries():
    metrics.REGISTRY.reset()
    standby_mod.reset()
    yield
    metrics.REGISTRY.reset()
    standby_mod.reset()


# =======================================================================
# a real primary world streaming real chain records
# =======================================================================
def _mk_world(game_id: int):
    from goworld_tpu.core.state import WorldConfig
    from goworld_tpu.entity.entity import Entity
    from goworld_tpu.entity.manager import World
    from goworld_tpu.entity.space import Space
    from goworld_tpu.ops.aoi import GridSpec

    class Mob(Entity):
        ATTRS = {"hp": "allclients hot:0"}

    cfg = WorldConfig(
        capacity=64,
        grid=GridSpec(radius=30.0, extent_x=200.0, extent_z=200.0),
        input_cap=64,
    )
    w = World(cfg, n_spaces=1, game_id=game_id)
    w.register_entity("Mob", Mob)
    w.register_space("Arena", Space)
    return w


@pytest.fixture(scope="module")
def stream(tmp_path_factory):
    """(primary_world, ents, frames) — frames is the encoded wire
    stream: 9 records at keyframe_every=4 (keys at indices 0, 4, 8),
    with deterministic pose churn between captures so the deltas carry
    real sparse rows."""
    from goworld_tpu.entity.entity import GameClient

    d = tmp_path_factory.mktemp("repl_chain")
    w = _mk_world(941)
    w.create_nil_space()
    sp = w.create_space("Arena")
    rng = np.random.default_rng(7)
    ents = []
    for i in range(10):
        x, z = rng.uniform(20.0, 180.0, 2)
        e = sp.create_entity("Mob", pos=(float(x), 0.0, float(z)))
        e.attrs["hp"] = i
        ents.append(e)
    ents[0].set_client(GameClient(1, "repl-c0", w))

    chain = freeze.SnapshotChain(w, str(d), keyframe_every=4)
    enc = StreamEncoder()
    frames = []  # (kind, tick, blob)
    for t in range(9):
        for e in ents:
            if e.destroyed:
                continue
            x, z = rng.uniform(20.0, 180.0, 2)
            w.stage_pose(e, (float(x), 0.0, float(z)),
                         yaw=float(rng.uniform(0, 6.28)))
        w.tick()
        data, tick = freeze.SnapshotChain.complete_capture(
            chain.capture())
        kind, rec = chain.build(data)
        frames.append((kind, tick, enc.encode(tick, kind, rec)))
    assert [k for k, _t, _b in frames].count("key") == 3
    yield w, ents, frames, chain, enc
    audit.unregister("game941")
    if w.audit is not None:
        w.audit.close()


def _tamper(blob: bytes, **patch) -> bytes:
    fr = msgpack.unpackb(blob, raw=False, strict_map_key=False)
    fr.update(patch)
    return msgpack.packb(fr, use_bin_type=True)


# =======================================================================
# stream determinism: double apply -> bit-identical lattice planes
# =======================================================================
def test_double_apply_is_bit_identical(stream):
    _w, _e, frames, _c, _enc = stream
    d1, d2 = StreamDecoder(), StreamDecoder()
    for _kind, _tick, blob in frames:
        k1, t1, _data1, planes1, eids1 = d1.feed(blob)
        k2, t2, _data2, planes2, eids2 = d2.feed(blob)
        assert (k1, t1, eids1) == (k2, t2, eids2)
        assert set(planes1) == {"pos_xz", "pos_y", "yaw", "moving"}
        for nm in planes1:  # the lattice-domain byte surface
            assert planes1[nm] == planes2[nm], nm
    assert d1.applied_seq == d2.applied_seq == len(frames) - 1


def test_delta_resolves_to_keyframe_identical_planes(stream):
    """A delta whose rows all reference the keyframe must reproduce the
    keyframe's planes byte-for-byte for the unchanged entities — the
    lattice-domain bit-exactness guarantee of the disk chain carried
    onto the wire."""
    _w, _e, frames, _c, _enc = stream
    dec = StreamDecoder()
    key_planes = None
    for kind, _t, blob in frames[:2]:
        k, _tick, _data, planes, eids = dec.feed(blob)
        if k == "key":
            key_planes = (planes, eids)
    planes, eids = key_planes
    assert planes["pos_xz"]  # non-empty population


# =======================================================================
# torn streams: rejected whole, named reason, heals at next keyframe
# =======================================================================
def test_truncated_frame_rejected(stream):
    _w, _e, frames, _c, _enc = stream
    dec = StreamDecoder()
    with pytest.raises(TornStreamError) as ei:
        dec.feed(frames[0][2][:-5])
    assert ei.value.reason == "unparseable"
    assert dec.needs_keyframe


def test_body_crc_corruption_rejected(stream):
    _w, _e, frames, _c, _enc = stream
    dec = StreamDecoder()
    fr = msgpack.unpackb(frames[0][2], raw=False, strict_map_key=False)
    body = bytearray(fr["body"])
    body[len(body) // 2] ^= 0x5A
    with pytest.raises(TornStreamError) as ei:
        dec.feed(_tamper(frames[0][2], body=bytes(body)))
    assert ei.value.reason == "body_crc"
    assert dec.needs_keyframe


def test_reordered_delta_rejected_as_seq_gap(stream):
    _w, _e, frames, _c, _enc = stream
    dec = StreamDecoder()
    dec.feed(frames[0][2])
    with pytest.raises(TornStreamError) as ei:
        dec.feed(frames[2][2])  # skipped frames[1]
    assert ei.value.reason == "seq_gap"


def test_chain_break_on_wrong_prev_crc(stream):
    _w, _e, frames, _c, _enc = stream
    dec = StreamDecoder()
    dec.feed(frames[0][2])
    fr = msgpack.unpackb(frames[1][2], raw=False, strict_map_key=False)
    with pytest.raises(TornStreamError) as ei:
        dec.feed(_tamper(frames[1][2],
                         prev_crc=fr["prev_crc"] ^ 1))
    assert ei.value.reason == "chain_break"


def test_replayed_old_keyframe_rejected_stale(stream):
    """A replayed/reordered OLD keyframe must never roll the mirror
    backward behind frames already applied."""
    _w, _e, frames, _c, _enc = stream
    dec = StreamDecoder()
    for _k, _t, blob in frames[:6]:
        dec.feed(blob)
    for old in (frames[0], frames[4]):  # both earlier keys
        with pytest.raises(TornStreamError) as ei:
            dec.feed(old[2])
        assert ei.value.reason == "stale_keyframe"


def test_torn_stream_heals_at_next_keyframe(stream):
    _w, _e, frames, _c, _enc = stream
    dec = StreamDecoder()
    dec.feed(frames[0][2])
    dec.feed(frames[1][2])
    with pytest.raises(TornStreamError):
        dec.feed(frames[2][2][:-9])       # torn mid-stream
    with pytest.raises(TornStreamError) as ei:
        dec.feed(frames[3][2])            # deltas can't re-anchor
    assert ei.value.reason == "awaiting_keyframe"
    kind, _tick, _data, _planes, _eids = dec.feed(frames[4][2])
    assert kind == "key"                  # heals at the next keyframe
    assert not dec.needs_keyframe
    dec.feed(frames[5][2])                # and the chain continues
    assert dec.applied_seq == 5


# =======================================================================
# standby apply: live-world mirror, census equality, quiet destroy
# =======================================================================
def test_applier_mirrors_census_and_destroys(stream):
    w, ents, frames, chain, enc = stream
    sb = _mk_world(942)
    tracker = StandbyTracker(942, 941, tick_hz=60.0)
    ap = StandbyApplier(sb, 941, tracker=tracker)
    for _k, _t, blob in frames:
        out = ap.apply(blob)
        assert out["ok"], out
    def census(world):
        out = {e.id for e in world.entities.values() if not e.destroyed}
        out.discard(world.nil_space.id)
        return out
    assert census(sb) == census(w)
    # attrs + client binding mirrored
    src = ents[0]
    mir = sb.entities[src.id]
    assert mir.attrs.get_int("hp") == src.attrs.get_int("hp")
    assert mir.client is not None
    assert (mir.client.gate_id, mir.client.client_id) == (1, "repl-c0")
    # a standby has no client sink: mirror-side client messages must
    # not pile up in the fallback buffer
    assert sb.client_messages == []

    # primary destroys one entity; the next frame quiet-destroys the
    # mirror copy — and the ledger verdict still balances
    victim = ents[3]
    w.destroy_entity(victim)
    w.tick()
    data, tick = freeze.SnapshotChain.complete_capture(chain.capture())
    kind, rec = chain.build(data)
    out = ap.apply(enc.encode(tick, kind, rec))
    assert out["ok"], out
    assert victim.id not in census(sb)
    assert census(sb) == census(w)
    snap = tracker.snapshot()
    assert snap["frames"] == len(frames) + 1
    assert snap["rejects"] == {}
    if sb.audit is not None:
        sb.audit.drain()
        v = audit.conservation_verdict(
            [sb.audit.snapshot(tick=sb.tick_count)])
        assert v["ok"], v["problems"]
    audit.unregister("game942")


def test_applier_reject_changes_nothing(stream):
    _w, _e, frames, _c, _enc = stream
    sb = _mk_world(943)
    tracker = StandbyTracker(943, 941, tick_hz=60.0)
    ap = StandbyApplier(sb, 941, tracker=tracker)
    out = ap.apply(frames[0][2][:-3])
    assert out == {"ok": False, "reason": "unparseable",
                   "needs_keyframe": True}
    assert len(sb.entities) == 0          # nothing half-applied
    assert tracker.snapshot()["rejects"] == {"unparseable": 1}
    audit.unregister("game943")


# =======================================================================
# the bounded worker: slow disk NEVER blocks the tick thread
# =======================================================================
class StubChain:
    """Chain stand-in: records every build's force_key flag; disk
    writes can be made arbitrarily slow; builds can be made to fail."""

    def __init__(self, write_delay: float = 0.0, fail_builds: int = 0):
        self.write_delay = write_delay
        self.fail_builds = fail_builds
        self.force_flags: list[bool] = []
        self.writes = 0
        self._built = 0
        self._lock = threading.Lock()

    def complete_capture(self, captured):
        return {"n": int(captured)}, int(captured)

    def build(self, data, force_key: bool = False):
        with self._lock:
            if self.fail_builds > 0:
                self.fail_builds -= 1
                raise RuntimeError("deliberate build failure")
            self.force_flags.append(bool(force_key))
            self._built += 1
            kind = "key" if force_key or self._built == 1 else "delta"
        return kind, {"tick": data["n"]}

    def write_record(self, kind, rec):
        if self.write_delay:
            time.sleep(self.write_delay)
        with self._lock:
            self.writes += 1
        return "unused"


def test_slow_disk_never_blocks_submit():
    """The PR-12 tradeoff retired: a wedged/slow disk costs DROPS (loud
    counter + keyframe collapse), never a stalled tick thread."""
    chain = StubChain(write_delay=0.25)
    sent = []
    worker = ReplicationWorker(
        chain, game_id=51, queue_max=2,
        send_fn=lambda blob, kind, tick: sent.append(kind))
    try:
        worst = 0.0
        accepted = 0
        builds_at_first_drop = None
        for i in range(10):
            t0 = time.perf_counter()
            if worker.submit(i, to_disk=True, to_stream=True):
                accepted += 1
            elif builds_at_first_drop is None:
                builds_at_first_drop = len(chain.force_flags)
            worst = max(worst, time.perf_counter() - t0)
        assert worst < 0.05, f"submit blocked {worst * 1e3:.1f} ms"
        assert worker.dropped_total() > 0          # loud, not silent
        assert accepted + worker.dropped_total() == 10
        assert worker.drain(timeout=30.0)
        assert chain.writes == accepted
        assert len(sent) == accepted
        # backlog collapse: a drop arms force_keyframe, so a capture
        # accepted after the drop re-anchors the stream with a full
        # keyframe instead of wedging the consumer on unbounded deltas
        chain.write_delay = 0.0
        assert worker.submit(99)
        assert worker.drain(timeout=10.0)
        assert any(chain.force_flags[builds_at_first_drop:]), \
            (builds_at_first_drop, chain.force_flags)
    finally:
        worker.close()


def test_request_keyframe_forces_next_build():
    chain = StubChain()
    worker = ReplicationWorker(chain, game_id=52, queue_max=4,
                               send_fn=lambda *a: None)
    try:
        worker.submit(1)
        assert worker.drain()
        worker.request_keyframe()           # standby attach / resync
        worker.submit(2)
        assert worker.drain()
        assert chain.force_flags == [False, True]
    finally:
        worker.close()


def test_worker_survives_build_failure():
    chain = StubChain(fail_builds=1)
    worker = ReplicationWorker(chain, game_id=53, queue_max=4,
                               send_fn=lambda *a: None)
    try:
        worker.submit(1)
        worker.submit(2)
        assert worker.drain()
        assert worker.errors == 1
        # the job after a failure is processed AND forced to a keyframe
        assert chain.force_flags == [True]
        assert worker.stats()["frames_sent"] == 1
    finally:
        worker.close()


def test_worker_rejects_zero_queue():
    with pytest.raises(ValueError):
        ReplicationWorker(StubChain(), game_id=54, queue_max=0)


# =======================================================================
# promotion arbitration: both stale-claim race orders refused
# =======================================================================
def _kvreg():
    """The dispatcher's exact first-writer-wins register semantics
    (net/dispatcher.py _h_kvreg) over a local dict."""
    reg: dict = {}

    def register(key, val, force=False):
        if key not in reg or force:
            reg[key] = val
        return reg[key]

    return reg, register


def test_claim_value_roundtrip():
    v = claim_value(4, 3, 77)
    assert parse_claim(v) == {"gid": 4, "epoch": 3, "seq": 77}
    assert parse_claim("garbage") is None
    assert parse_claim("gameX:eY:sZ") is None
    assert claim_key(9) == "promote/game9"


def test_stale_claim_second_is_refused():
    """Race order A: the live standby registers first; a replayed old
    claim (or zombie) lands after. First-writer-wins broadcasts the
    live winner; the zombie adjudicates lost — and the live claim
    adjudicates won against its own broadcast."""
    reg, register = _kvreg()
    key = claim_key(1)
    live = claim_value(2, epoch=3, frame_seq=90)
    stale = claim_value(9, epoch=1, frame_seq=10)
    assert adjudicate(register(key, live), live) == "won"
    assert adjudicate(register(key, stale), stale) == "lost"
    assert reg[key] == live                    # never overwritten


def test_stale_claim_first_is_refused():
    """Race order B: the replay lands FIRST. The live claimant sees a
    winner with a LOWER epoch — stale_winner — which licenses a
    force re-register exactly and only then; the zombie then loses the
    re-adjudication."""
    reg, register = _kvreg()
    key = claim_key(1)
    stale = claim_value(9, epoch=1, frame_seq=10)
    live = claim_value(2, epoch=3, frame_seq=90)
    register(key, stale)                       # zombie lands first
    assert adjudicate(register(key, live), live) == "stale_winner"
    assert adjudicate(register(key, live, force=True), live) == "won"
    assert adjudicate(reg[key], stale) == "lost"


def test_equal_epoch_loser_stands_down():
    """Two live standbys racing the SAME epoch: exactly one wins; the
    other adjudicates lost (never stale_winner — that would force-loop
    both forever)."""
    _reg, register = _kvreg()
    key = claim_key(1)
    a = claim_value(2, epoch=3, frame_seq=90)
    b = claim_value(5, epoch=3, frame_seq=88)
    assert adjudicate(register(key, a), a) == "won"
    assert adjudicate(register(key, b), b) == "lost"


def test_decision_log_replays_byte_for_byte():
    log = DecisionLog()
    log.note("claim", key="promote/game1", value="game2:e1:s9",
             epoch=1, applied_seq=9, applied_tick=40)
    log.note("adjudicate", winner="game2:e1:s9", mine="game2:e1:s9",
             verdict="won")
    log.note("promoted", epoch=1, tick=40, seq=9, entities=12)
    dump = log.dump()
    assert replay_decisions(log.inputs) == dump
    assert dump.endswith(b"\n")
    # field order in a line is canonical (sorted), independent of the
    # kwargs order the caller used
    other = DecisionLog()
    other.note("claim", applied_tick=40, applied_seq=9, epoch=1,
               value="game2:e1:s9", key="promote/game1")
    assert other.lines[0] == log.lines[0]


# =======================================================================
# /standby registry
# =======================================================================
def test_standby_registry_and_promotion_hook():
    clock = [100.0]
    tr = StandbyTracker(6, 5, tick_hz=10.0, lag_budget_ticks=4,
                        clock=lambda: clock[0])
    standby_mod.register("game6", tr)
    tr.note_applied("key", tick=7, seq=0, nbytes=900, apply_ms=1.5)
    clock[0] += 0.2                       # 2 ticks of staleness
    snap = standby_mod.snapshot_all()["game6"]
    assert snap["role"] == "standby"
    assert snap["applied_tick"] == 7
    assert snap["lag_ticks"] == 2.0
    assert snap["pass"] is True
    clock[0] += 1.0                       # blow the budget
    assert standby_mod.snapshot_all()["game6"]["pass"] is False

    calls = []
    tr.on_promote = lambda epoch=None: calls.append(epoch) or \
        {"status": "claiming"}
    out = standby_mod.request_promotion(epoch=9)
    assert out == {"standby": "game6", "status": "claiming"}
    assert calls == [9]
    standby_mod.unregister("game6")
    assert "error" in standby_mod.snapshot_all()   # honest when empty
    assert "error" in standby_mod.request_promotion()
