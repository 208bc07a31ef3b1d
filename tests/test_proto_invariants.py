"""Message-type space invariants (ISSUE 2 CI satellite).

The dispatcher/gate route packets by RANGE (net/proto.py: 1-999
dispatcher-routed, 1000-1499 gate redirect, 1500-1999 gate service,
2000+ client-direct), and the tracing layer claims bit 15 of the u16
msgtype field for the trace-context trailer (net/packet.py TRACE_FLAG).
A future MT_ constant outside its documented range — or colliding with
the trace bit — would mis-route silently; this guards both."""

from goworld_tpu.net import packet, proto

# the documented routing ranges (inclusive); 2000.. is the client-direct
# space, capped where the trace bit begins
RANGES = (
    (0, 999, "dispatcher-routed"),
    (1000, 1499, "gate redirect"),
    (1500, 1999, "gate service"),
    (2000, packet.MSGTYPE_MASK, "client-direct"),
)


def _mt_constants() -> dict[str, int]:
    return {
        name: val for name, val in vars(proto).items()
        if name.startswith("MT_") and isinstance(val, int)
    }


def test_every_msgtype_lives_in_a_documented_range():
    for name, val in _mt_constants().items():
        assert any(lo <= val <= hi for lo, hi, _ in RANGES), \
            f"{name}={val} is outside every documented routing range"


def test_msgtypes_never_collide_with_trace_flag():
    """Bit 15 is the trace-trailer marker: setting it on any real
    msgtype must be reversible (mask restores the original), which
    requires every constant to keep the bit clear."""
    for name, val in _mt_constants().items():
        assert val & packet.TRACE_FLAG == 0, \
            f"{name}={val} collides with TRACE_FLAG"
        assert (val | packet.TRACE_FLAG) & packet.MSGTYPE_MASK == val


def test_msgtypes_never_collide_with_age_flag():
    """Bit 14 is the sync-age-stamp trailer marker (net/packet.py
    AGE_FLAG, utils/syncage.py): every real msgtype must keep it clear
    so setting and masking the flag is reversible, exactly like the
    trace flag above."""
    for name, val in _mt_constants().items():
        assert val & packet.AGE_FLAG == 0, \
            f"{name}={val} collides with AGE_FLAG"
    # bit 14 sits INSIDE MSGTYPE_MASK: masking a raw wire msgtype with
    # MSGTYPE_MASK strips the trace flag but NOT the age flag, so
    # decode_wire's explicit AGE_FLAG strip is load-bearing — any
    # routing shortcut that only applies MSGTYPE_MASK would misroute
    # stamped packets (this pins the fact the strip code relies on)
    assert packet.AGE_FLAG & packet.MSGTYPE_MASK == packet.AGE_FLAG
    assert packet.TRACE_FLAG & packet.MSGTYPE_MASK == 0


def test_msgtypes_are_unique():
    consts = _mt_constants()
    by_val: dict[int, list[str]] = {}
    for name, val in consts.items():
        by_val.setdefault(val, []).append(name)
    dupes = {v: names for v, names in by_val.items() if len(names) > 1}
    assert not dupes, f"duplicate msgtype values: {dupes}"


def test_range_markers_bracket_their_constants():
    """Constants named into the redirect / gate-service ranges must sit
    strictly between their START/STOP markers."""
    consts = _mt_constants()
    redirect_lo = consts["MT_REDIRECT_TO_GATEPROXY_MSG_TYPE_START"]
    redirect_hi = consts["MT_REDIRECT_TO_GATEPROXY_MSG_TYPE_STOP"]
    service_lo = consts["MT_GATE_SERVICE_MSG_TYPE_START"]
    service_hi = consts["MT_GATE_SERVICE_MSG_TYPE_STOP"]
    assert (redirect_lo, redirect_hi) == (1000, 1499)
    assert (service_lo, service_hi) == (1500, 1999)
    for name, val in consts.items():
        if "START" in name or "STOP" in name:
            continue
        if redirect_lo < val < redirect_hi:
            # gate forwards these verbatim to the owning client — they
            # must carry the [gate_id][client_id] routing prefix, which
            # only redirect-range pack helpers write
            assert name.endswith("_ON_CLIENT") or name in (
                "MT_CLEAR_CLIENT_FILTER_PROP",
            ), f"{name}={val} squats in the redirect range"
        if service_lo < val < service_hi:
            assert name in (
                "MT_SET_CLIENT_FILTER_PROP",
                "MT_CALL_FILTERED_CLIENTS",
                "MT_SYNC_POSITION_YAW_ON_CLIENTS",
                # the delta-compressed sync leg (ISSUE 12): handled by
                # the gate itself like its full-record sibling
                "MT_SYNC_POSITION_YAW_DELTA_ON_CLIENTS",
                "MT_CLIENT_EVENTS_BATCH",
            ), f"{name}={val} squats in the gate-service range"


def test_trace_trailer_roundtrips_on_every_range():
    """A traced packet built at any range decodes to the same msgtype
    and payload with the context recovered."""
    from goworld_tpu.utils import tracing

    for mt in (proto.MT_CALL_ENTITY_METHOD,
               proto.MT_CALL_ENTITY_METHOD_ON_CLIENT,
               proto.MT_CLIENT_EVENTS_BATCH,
               proto.MT_HEARTBEAT):
        p = packet.new_packet(mt)
        p.append_var_str("payload")
        p.trace = tracing.new_trace()
        wire = packet.wire_payload(p)
        mt2, q = packet.decode_wire(wire)
        assert mt2 == mt
        assert q.trace is not None
        assert q.trace.trace_id == p.trace.trace_id
        assert q.read_var_str() == "payload"
        assert q.remaining() == 0
