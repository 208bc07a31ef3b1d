"""The shape of a cell's world, read from its configuration file: one
space on one chip, one megaspace tiled over the cell's chips, or many
spaces on one chip.

The world's kind is a key of the configuration: ``game.megaspace``
(with ``mesh_devices`` and ``mega_shape``, the program's own ini keys).
Absent or false, the world is one space of ``capacity`` rows; true, it
is ``tx x tz`` tiles of ``capacity`` rows each, ``extent_x`` and
``extent_z`` being the WORLD's, and device ``d`` owns tile ``(ix, iz) =
(d // tz, d % tz)`` (``parallel/megaspace.py``; the benchmark's own
copy of that rule, so that the reference and the check need nothing of
the program). A row of the world has ONE global number, ``tile *
capacity + slot``: the program's own gid.

The third kind is ``game.n_spaces`` > 1 (the program's own ini key, no
megaspace): that many spaces of ``capacity`` rows each on ONE chip,
every one a shard of the one vmapped tick, ``extent_x``/``extent_z``
being each SPACE's. All spaces share their coordinates and nothing
else: a row's number is ``space * capacity + slot`` (the shard is the
space, in the order the spaces are made), and a neighbourhood holds
only rows of the same space. Pure arithmetic: no numpy needed by its
callers in ``run.py``, no jax anywhere.
"""
from __future__ import annotations


def truthy(v) -> bool:
    """An ini value as the program reads it (goworld_tpu/config.py)."""
    return str(v).strip().lower() in ("1", "true", "yes", "on")


def tiles(cfg: dict) -> int:
    """How many tiles (chips) the configuration's world lies on."""
    game = cfg.get("game") or {}
    return int(game["mesh_devices"]) if truthy(game.get("megaspace", "")) \
        else 1


class Shape:
    """Tiles and borders of one configuration's world."""

    def __init__(self, cfg: dict):
        game = cfg["game"]
        self.mega = truthy(game.get("megaspace", ""))
        self.capacity = int(game["capacity"])
        self.extent_x = float(game["extent_x"])
        self.extent_z = float(game["extent_z"])
        self.tx = self.tz = 1
        if self.mega:
            n = int(game["mesh_devices"])
            parts = [int(v) for v in
                     str(game.get("mega_shape") or n).lower().split("x")]
            self.tx, self.tz = parts if len(parts) == 2 else (parts[0], 1)
            if self.tx * self.tz != n:
                raise ValueError(f"mega_shape {game.get('mega_shape')!r} "
                                 f"does not tile {n} devices")
        self.tiles = self.tx * self.tz
        # many spaces on one chip (never with a megaspace, which claims
        # every shard)
        self.spaces = 1 if self.mega else max(int(game.get("n_spaces", 1)), 1)
        self.tile_w = self.extent_x / self.tx
        self.tile_d = self.extent_z / self.tz
        # the inner borders: where a row changes its tile
        self.borders = {
            "x": [self.tile_w * i for i in range(1, self.tx)],
            "z": [self.tile_d * i for i in range(1, self.tz)]}

    def tile_of(self, x, z):
        """Owning tile of world coordinates (scalars or numpy arrays)."""
        ix = _clip(x // self.tile_w, self.tx)
        iz = _clip(z // self.tile_d, self.tz)
        return ix * self.tz + iz

    def space_of(self, row):
        """The space of a global row number (scalars or numpy arrays);
        0 wherever the world is one space or one megaspace."""
        return row // self.capacity if self.spaces > 1 else row * 0

    def border_distance(self, x, z):
        """Chebyshev-wise nearest inner border: the smaller of the
        distances to the nearest border in x and in z (infinite where
        the world has none)."""
        import numpy as np

        d = np.full(np.shape(x), np.inf)
        for b in self.borders["x"]:
            d = np.minimum(d, np.abs(np.asarray(x, np.float64) - b))
        for b in self.borders["z"]:
            d = np.minimum(d, np.abs(np.asarray(z, np.float64) - b))
        return d


def border_untested(over: dict, mega: bool) -> int:
    """How many of the two things judged at the clients met no tile
    border in this run: AOI-edge crossings and final neighbourhoods
    (``bots.py`` ``over_border``). A tiled world's run in which either
    is 0 has not tested what its cell is for; one space has no border
    to meet."""
    return sum(not over.get(k) for k in (
        "crossings_over_border", "finals_over_border")) if mega else 0


def _clip(i, n: int):
    if hasattr(i, "astype"):
        return i.astype("int64").clip(0, n - 1)
    return max(0, min(n - 1, int(i)))
