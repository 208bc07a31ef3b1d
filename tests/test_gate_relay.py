"""The gate's sync relay (``GateService._relay_sync_records``): one
stable sort, one buffer of client records for the whole batch, a slice
and a framed write per client. Every client must receive exactly the
bytes, in the same client order, that the per-client algorithm it
replaced wrote (copied below as ``_old_relay``, the reference), with
the per-client bound, drop, kick and sync-age contracts unchanged."""

import asyncio
import zlib

import numpy as np
import pytest

from goworld_tpu.net import codec, proto
from goworld_tpu.net.gate import ClientProxy, GateService
from goworld_tpu.net.packet import (
    PacketConnection,
    decode_wire,
    new_packet,
    wire_payload,
)
from goworld_tpu.utils import faults, ids, syncage


def _old_relay(gate, cids, eids, vals, age=None) -> None:
    """The relay before the vectorized pass: unique + argsort over the
    16B ids, one ``encode_sync_batch`` and one Packet per client."""
    n = len(cids)
    if n == 0:
        return
    keys = np.ascontiguousarray(cids).view("V16").ravel()
    uniq, inv = np.unique(keys, return_inverse=True)
    order = np.argsort(inv, kind="stable")
    bounds = np.cumsum(np.bincount(inv, minlength=len(uniq)))
    start = 0
    delivered = 0
    for u, stop in zip(uniq, bounds):
        idxs = order[start:start + (stop - start)]
        start = stop
        cp = gate.clients.get(bytes(u).decode("ascii", "replace"))
        if cp is None:
            continue
        out = new_packet(proto.MT_CLIENT_SYNC_POSITION_YAW)
        out.append_bytes(codec.encode_sync_batch(eids[idxs], vals[idxs]))
        if gate._send_to_client(cp, out):
            delivered += len(idxs)
    if age is not None and delivered:
        gate.syncage.observe(age, syncage.now_us(), delivered)


class _Transport:
    def __init__(self):
        self.buffered = 0

    def get_write_buffer_size(self):
        return self.buffered


class _Writer:
    """Appends ``(client id, bytes)`` to a log shared by one gate's
    clients, so the log also records the order clients are served in."""

    def __init__(self, cid, log):
        self.cid, self.log = cid, log
        self.transport = _Transport()

    def write(self, data):
        self.log.append((self.cid, bytes(data)))

    def close(self):
        pass


class _AgeSpy:
    """Stands in for the gate's AgeTracker: one entry per observe."""

    def __init__(self):
        self.weights = []

    def observe(self, stamp, t_deliver_us, n_records):
        self.weights.append(n_records)


def _gate(client_ids, **kw):
    """A gate holding ``client_ids``, each writing into one shared log."""
    compress = kw.pop("compress", False)
    g = GateService(1, "127.0.0.1", 0, [("127.0.0.1", 1)],
                    exit_on_dispatcher_loss=False, **kw)
    g.syncage = _AgeSpy()
    log = []
    for cid in client_ids:
        conn = PacketConnection(None, _Writer(cid, log), compress=compress,
                                compress_codec="zlib", edge="gate->client")
        cp = ClientProxy(conn)
        cp.client_id = cid
        g.clients[cid] = cp
    return g, log


def _batch(rng, client_ids, n):
    cids = np.asarray(client_ids, "S16")[rng.integers(0, len(client_ids),
                                                      n)]
    eids = np.asarray([ids.gen_entity_id() for _ in range(n)], "S16")
    vals = rng.standard_normal((n, 4)).astype(np.float32)
    return cids, eids, vals


def _stamp():
    now = syncage.now_us()
    return syncage.SyncAgeStamp(7, now - 5000, now - 4000, now - 3000,
                                now - 2000, now - 1000)


def _both(client_ids, batches, **kw):
    """Relay ``batches`` through the new algorithm and the reference on
    two gates with the same clients: (logs, age weights) of each."""
    out = []
    for relay in (GateService._relay_sync_records, _old_relay):
        g, log = _gate(client_ids, **kw)
        for cids, eids, vals in batches:
            relay(g, cids, eids, vals, age=_stamp())
        out.append((log, g.syncage.weights))
    return out


@pytest.mark.parametrize("n_clients, seed",
                         [(1, 0), (2, 1), (7, 2), (64, 3), (1024, 4)])
def test_every_client_gets_the_reference_bytes_in_the_reference_order(
        n_clients, seed):
    rng = np.random.default_rng(seed)
    client_ids = [ids.gen_entity_id() for _ in range(n_clients)]
    batches = [_batch(rng, client_ids, int(rng.integers(1, 30 * n_clients)))
               for _ in range(3)]
    (new, w_new), (old, w_old) = _both(client_ids, batches)
    assert new == old
    assert w_new == w_old
    assert sum(w_new) == sum(len(b[0]) for b in batches)


def test_records_for_unknown_clients_are_skipped_as_before():
    rng = np.random.default_rng(5)
    known = [ids.gen_entity_id() for _ in range(5)]
    gone = [ids.gen_entity_id() for _ in range(4)]
    cids, eids, vals = _batch(rng, known + gone, 400)
    (new, w_new), (old, w_old) = _both(known, [(cids, eids, vals)])
    assert new == old and w_new == w_old
    assert {c for c, _ in new} <= set(known)
    on_known = int(np.isin(cids, np.asarray(known, "S16")).sum())
    assert w_new == [on_known]


def test_a_full_client_loses_its_sync_records_without_a_kick():
    async def scenario():
        rng = np.random.default_rng(6)
        client_ids = [ids.gen_entity_id() for _ in range(3)]
        cids, eids, vals = _batch(rng, client_ids, 90)
        g, log = _gate(client_ids, downstream_max_bytes=4096,
                       downstream_kick_secs=60.0)
        full = g.clients[client_ids[1]]
        full.conn.writer.transport.buffered = 4000
        dropped0, kicked0 = g._m_down_dropped.value, g._m_kicked.value
        g._relay_sync_records(cids, eids, vals, age=_stamp())
        assert g._m_down_dropped.value == dropped0 + 1
        assert g._m_kicked.value == kicked0
        assert client_ids[1] in g.clients
        assert full.down_full_since is not None
        assert client_ids[1] in g._down_full
        assert client_ids[1] not in {c for c, _ in log}
        n_full = int((cids == client_ids[1].encode()).sum())
        assert g.syncage.weights == [90 - n_full]
        # the same batch through the reference leaves the same state
        r, rlog = _gate(client_ids, downstream_max_bytes=4096,
                        downstream_kick_secs=60.0)
        r.clients[client_ids[1]].conn.writer.transport.buffered = 4000
        _old_relay(r, cids, eids, vals, age=_stamp())
        assert rlog == log and r.syncage.weights == g.syncage.weights
        # still full past the kick window: kicked, never wedged
        full.down_full_since -= 61.0
        g._relay_sync_records(cids, eids, vals)
        assert g._m_kicked.value == kicked0 + 1
        assert client_ids[1] not in g.clients

    asyncio.run(scenario())


def test_the_age_stamp_is_observed_once_weighted_by_delivered():
    async def scenario():
        rng = np.random.default_rng(8)
        client_ids = [ids.gen_entity_id() for _ in range(4)]
        cids, eids, vals = _batch(rng, client_ids + [ids.gen_entity_id()],
                                  120)
        g, _ = _gate(client_ids, downstream_max_bytes=4096)
        g.clients[client_ids[0]].conn.writer.transport.buffered = 4096
        g._relay_sync_records(cids, eids, vals, age=_stamp())
        taken = np.isin(cids, np.asarray(client_ids[1:], "S16"))
        assert g.syncage.weights == [int(taken.sum())]
        g._relay_sync_records(cids, eids, vals, age=None)
        assert len(g.syncage.weights) == 1

    asyncio.run(scenario())


def test_a_compressing_connection_gets_what_it_got_before():
    rng = np.random.default_rng(9)
    client_ids = [ids.gen_entity_id() for _ in range(6)]
    batches = [_batch(rng, client_ids, 150) for _ in range(3)]
    (new, w_new), (old, w_old) = _both(client_ids, batches, compress=True)
    assert new == old and w_new == w_old
    # and the stream inflates to the plain frames of each client
    (plain, _), _ = _both(client_ids, batches)
    for cid in client_ids:
        d = zlib.decompressobj()
        inflated = b"".join(d.decompress(b[4:]) for c, b in new if c == cid)
        assert inflated == b"".join(b[4:] for c, b in plain if c == cid)


def test_an_armed_fault_plane_takes_the_packet_path():
    faults.install("gate1", "dup:gate->client:1.0", seed=3)
    try:
        rng = np.random.default_rng(10)
        client_ids = [ids.gen_entity_id() for _ in range(5)]
        batches = [_batch(rng, client_ids, 60)]
        (new, _), (old, _) = _both(client_ids, batches)
    finally:
        faults.uninstall()
    assert new == old
    assert len(new) == 2 * len({c for c, _ in new})     # every send twice


def _on_wire(p):
    """A packet as the gate's dispatcher read loop hands it over."""
    return decode_wire(wire_payload(p))


def test_both_sync_legs_relay_the_reference_bytes_and_time_the_batch():
    rng = np.random.default_rng(11)
    client_ids = [ids.gen_entity_id() for _ in range(8)]
    enc = codec.DeltaSyncEncoder(step=0.01)
    batches = [_batch(rng, client_ids, 200)]
    cids, eids, _ = batches[0]
    for tick in (1, 2):      # a keyframe batch, then a batch of deltas
        batches.append(
            (cids, eids, batches[-1][2] + np.float32(0.05 * tick)))
    g, log = _gate(client_ids)
    r, rlog = _gate(client_ids)
    dec = codec.DeltaSyncDecoder()
    n0 = g._m_relay_ms.snapshot()["count"]
    for tick, (c, e, v) in enumerate(batches):
        full = new_packet(proto.MT_SYNC_POSITION_YAW_ON_CLIENTS)
        full.append_u16(1)
        full.append_bytes(codec.encode_client_sync_batch(c, e, v))
        full.age = _stamp()
        g._on_dispatcher_packet_body(0, *_on_wire(full))
        _old_relay(r, c, e, v, age=_stamp())
        delta = new_packet(proto.MT_SYNC_POSITION_YAW_DELTA_ON_CLIENTS)
        delta.append_u16(1)
        delta.append_u16(3)                                # sender game
        payload = enc.encode_batch(c, e, v, tick)
        delta.append_bytes(payload)
        g._on_dispatcher_packet_body(0, *_on_wire(delta))
        _old_relay(r, *dec.decode_batch(payload))
    assert log == rlog
    assert g.syncage.weights == r.syncage.weights == [200] * 3
    assert g._m_relay_ms.snapshot()["count"] == n0 + 6   # once a batch
