"""The work one tick cannot avoid, counted from the configuration's
shapes alone — never from which kernel implements it.

Bytes of one tick (``necessary_bytes``):

* every live row's state read once and written once: the planes of
  ``core/state.py`` ``SpaceState`` (pos f32[3], yaw f32, vel f32[3],
  alive, npc_moving, has_client, dirty: 1 byte each, client_gate,
  type_id, gen, attr_dirty, nbr_cnt, nbr_client_cnt: 4 each, hot_attrs
  f32[A], nbr_mean_off f32[3], aoi_radius f32);
* every live row's neighbour list (i32[k]) read once and written once;
* the sync records it emits: one (slot, target, x, y, z, yaw) record
  of 24 bytes per client and mirrored mover — ``clients`` times the
  expected neighbours (NPCs) plus the group's other members.

``least_seconds`` divides by the chip's HBM peak (``peaks.json``); the
bound is bytes: the tick does a few operations per byte, far under the
chip's 240 FLOP per byte ridge. In a world tiled over several chips the
bytes are ONE tile's (its share of the live rows and of the clients)
against ONE chip's peak, as the busy time they are held against is one
chip's (the busiest: benchmark/README.md); ghost rows and what the
exchange moves are the mesh's price, not necessary work. Many spaces on
one chip are ALL that chip's work: every space's live rows and lists,
and the records by the kind of space a client stands in (the mix says
how many clients each kind holds, ``clients_by_kind``; the
configuration what a client of that kind holds in its list,
``world.expected_neighbours_by_kind``).
"""
from __future__ import annotations

import json
import os

from world import tiles as tiles_of

ATTR_WIDTH = 8      # WorldConfig.attr_width default
K = 64              # GridSpec k default (utils/consts.py)
ROW_BYTES = 3 * 4 + 4 + 3 * 4 + 4 * 1 + 6 * 4 + ATTR_WIDTH * 4 \
    + 3 * 4 + 4
SYNC_RECORD_BYTES = 24


def necessary_bytes(live: int, clients: int, group_size: int,
                    expected_neighbours: float, k: int = K,
                    records: float | None = None) -> int:
    """``records``: the sync records a tick emits, where the caller has
    counted them itself (by the kind of space); absent, every client
    mirrors its expected neighbours and its group's other members."""
    state = 2 * ROW_BYTES * live
    lists = 2 * 4 * k * live
    if records is None:
        records = clients * (expected_neighbours + group_size - 1)
    return int(state + lists + SYNC_RECORD_BYTES * records)


def peaks(device_kind: str) -> dict:
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in benchmark/peaks.json")
    return table[device_kind]


def least_seconds(cfg: dict, mix: dict, device_kind: str) -> float:
    """The least time a chip could take for one tick of its tile: the
    tile's necessary bytes at the HBM peak."""
    tiles = tiles_of(cfg)
    by_kind = cfg["world"].get("expected_neighbours_by_kind")
    b = necessary_bytes(int(cfg["world"]["live"]) // tiles,
                        int(mix["clients"]) // tiles,
                        int(mix["group_size"]),
                        float(cfg["world"]["expected_neighbours"]),
                        records=None if not by_kind else sum(
                            float(by_kind[kind]) * int(n) for kind, n
                            in mix["clients_by_kind"].items()))
    return b / float(peaks(device_kind)["hbm_bytes_per_s"])
