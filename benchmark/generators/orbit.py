"""Generator kind ``orbit``: groups of clients on a grid, each member
circling its group's anchor.

A mix of this kind is a data file (``benchmark/traffic/<mix>.json``):
``clients``, ``group_size``, the grid's spacing limits, the orbit's
radius and angular step, the send cadence and probability and the RPC
rate. Grid sites lie wider apart than two AOI boxes, so a client holds
its group and the NPCs around it in its interest set and never a group
of another site. ``twin_sites`` sites hold two groups each, ``twin_gap``
apart along x: a little more than the AOI radius, so members on
opposite phases of their orbits cross each other's AOI edge about twice
a lap — enters and leaves between clients inside the window, which the
check holds against the reference — while every client stays in its own
group's AOI (its observer never loses it). Every seed gives the same amount of work: each client sends in
exactly ``send_probability`` of the window's slots (which ones is
drawn), at a drawn phase, and makes one RPC in every 1/rate seconds at
an instant drawn anew each time.

``border_share`` (absent = 0: the plan above, bit for bit) puts that
share of the sites ON an inner border of a tiled world (``borders``,
which the harness reads from the configuration: ``world.py``), spread
over every border line and never on a crossing of two. A group
anchored on a border has its members on either side of it, each
walking over it twice a lap: an entity that changes its tile under its
client, seen by a partner that holds it across the seam.
``twin_border_sites`` of the twin sites lie on a border too, so AOI-edge
crossings between clients happen across a seam. Sites on a border are
``need`` apart along it (the least spacing that keeps two sites out of
each other's AOI); the other sites keep the grid, without the grid
lines closer than that to a border.

The height coordinate carries the send's sequence number (an integer,
exact in f32 up to 2**24), so a receipt names the send it mirrors with
no server field; x, z and yaw follow from the same number, which lets
the check compare every received record with what was sent.
"""
from __future__ import annotations

import math

import numpy as np

SEND, RPC = 0, 1


class Plan:
    def __init__(self, mix: dict, extent: float, aoi_radius: float,
                 clients: int, borders: dict | None = None):
        self.mix = mix
        self.n = clients
        self.g = g = int(mix["group_size"])
        if clients % g:
            raise ValueError(f"{clients} clients do not fill groups of {g}")
        self.groups = clients // g
        self.twins = int(mix.get("twin_sites", 0))
        self.twin_gap = float(mix.get("twin_gap", 0.0))
        if 2 * self.twins > self.groups:
            raise ValueError(f"{self.twins} twin sites need "
                             f"{2 * self.twins} groups")
        side = math.ceil(math.sqrt(self.groups - self.twins))
        spacing = min(float(mix["grid_spacing_max"]),
                      extent / (side + 1))
        need = max(float(mix["grid_spacing_min"]),
                   4.0 * aoi_radius + 4.0 * float(mix["orbit_radius"])
                   + self.twin_gap)
        if spacing < need:
            raise ValueError(
                f"{self.groups} groups need a grid spacing of {need}, "
                f"the extent {extent} gives {spacing:.1f}")
        self.side, self.spacing = side, spacing
        self.r = float(mix["orbit_radius"])
        self.step = float(mix["orbit_step_rad"])
        self.origin = 0.5 * (extent - spacing * (side - 1))
        self.sites = None           # the grid's own arithmetic (anchor)
        self.on_border = np.zeros(self.groups - self.twins, bool)
        share = float(mix.get("border_share", 0.0))
        if share > 0.0:
            self._lay_borders(share, extent, need, borders or {})

    def _lay_borders(self, share: float, extent: float, need: float,
                     borders: dict) -> None:
        """Site positions where ``border_share`` of them lie on an inner
        border: ``self.sites`` f64[sites, 2], ``self.on_border``."""
        n_sites = self.groups - self.twins
        lines = [("x", float(b)) for b in borders.get("x", ())] \
            + [("z", float(b)) for b in borders.get("z", ())]
        if not lines:
            raise ValueError("border_share needs a world with an inner "
                             "border (a megaspace configuration)")
        n_border = int(round(share * n_sites))
        twin_border = min(int(self.mix.get("twin_border_sites", 0)),
                          self.twins, n_border)

        def clear(axis: str, v: float) -> bool:
            """``v`` along a line of ``axis``: inside the world and not
            within ``need`` of a line that crosses it."""
            other = "z" if axis == "x" else "x"
            return need <= v <= extent - need and all(
                abs(v - b) >= need for b in borders.get(other, ()))

        # along every line, outwards from the world's middle, in turn
        on_line: list[tuple[float, float]] = []
        reach = int(extent / need) + 1
        for j in range(reach):
            for sign in ((1,) if j == 0 else (1, -1)):
                v = 0.5 * extent + sign * j * need
                for axis, b in lines:
                    if clear(axis, v):
                        on_line.append((b, v) if axis == "x" else (v, b))
        if len(on_line) < n_border:
            raise ValueError(f"{n_border} border sites do not fit the "
                             f"borders ({len(on_line)} places)")
        # the grid, without the grid lines too close to a border; its
        # side grows until the sites that are left fit
        n_grid = n_sites - n_border
        side = max(math.ceil(math.sqrt(max(n_grid, 1))), 1)
        while True:
            spacing = min(float(self.mix["grid_spacing_max"]),
                          extent / (side + 1))
            if spacing < need:
                raise ValueError(
                    f"{n_grid} sites off the borders need a grid spacing "
                    f"of {need}, the extent {extent} gives {spacing:.1f}")
            origin = 0.5 * (extent - spacing * (side - 1))
            at = [origin + spacing * i for i in range(side)]
            xs = [v for v in at if all(abs(v - b) >= need
                                       for b in borders.get("x", ()))]
            zs = [v for v in at if all(abs(v - b) >= need
                                       for b in borders.get("z", ()))]
            if len(xs) * len(zs) >= n_grid:
                break
            side += 1
        self.side, self.spacing, self.origin = side, spacing, origin
        grid = [(x, z) for z in zs for x in xs][:n_grid]
        # twin sites come first (anchor): some on a border, the rest on
        # the grid; then every other site, the border's before the grid's
        t_grid = self.twins - twin_border
        order = on_line[:twin_border] + grid[:t_grid] \
            + on_line[twin_border:n_border] + grid[t_grid:]
        self.sites = np.array(order, np.float64)
        flag = np.zeros(n_sites, bool)
        flag[:twin_border] = True
        flag[self.twins:self.twins + n_border - twin_border] = True
        self.on_border = flag

    def group_of(self, c: int) -> int:
        return c // self.g

    def members(self, grp: int) -> range:
        return range(grp * self.g, (grp + 1) * self.g)

    def observer(self, c: int) -> int:
        """The client whose mirror times ``c``'s sends: the next member
        of its group (its partner, in a pair)."""
        grp = c // self.g
        return grp * self.g + (c % self.g + 1) % self.g

    def anchor(self, c: int) -> tuple[float, float]:
        grp = c // self.g
        if grp < 2 * self.twins:        # two groups to a site
            site, off = grp // 2, (grp % 2 - 0.5) * self.twin_gap
        else:
            site, off = grp - self.twins, 0.0
        if self.sites is not None:
            return (float(self.sites[site, 0]) + off,
                    float(self.sites[site, 1]))
        return (self.origin + self.spacing * (site % self.side) + off,
                self.origin + self.spacing * (site // self.side))

    def crossers(self) -> list[tuple[int, int]]:
        """Ordered pairs (c, d) of clients of twin groups: the ones
        whose distance can cross the AOI edge while they orbit."""
        out = []
        for site in range(self.twins):
            a, b = self.members(2 * site), self.members(2 * site + 1)
            out += [(c, d) for c in a for d in b]
            out += [(d, c) for c in a for d in b]
        return out

    def positions(self, seqs: int) -> np.ndarray:
        """f32[clients, seqs, 4]: (x, y, z, yaw) of every client's
        ``seq``-th send, as it goes on the wire."""
        c = np.arange(self.n)
        anchors = np.array([self.anchor(i) for i in c])
        ang = self.step * np.arange(seqs)[None, :] \
            + 2.0 * math.pi * (c % self.g)[:, None] / self.g
        out = np.empty((self.n, seqs, 4), np.float32)
        out[:, :, 0] = anchors[:, 0:1] + self.r * np.cos(ang)
        out[:, :, 1] = np.arange(seqs)[None, :]
        out[:, :, 2] = anchors[:, 1:2] + self.r * np.sin(ang)
        out[:, :, 3] = ang % 6.28
        return out


def schedule(mix: dict, clients: int, seed: int, seconds: float,
             stream: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The open-loop schedule of one stretch of ``seconds``: offsets
    from its start (s), client, kind (SEND / RPC), sorted by offset.
    ``stream`` separates the warm-up's draws from the window's."""
    rng = np.random.default_rng([int(seed), int(stream), 0x6f72626974])
    slot = float(mix["send_interval_ms"]) / 1e3
    slots = int(seconds / slot + 1e-9)
    take = int(round(slots * float(mix["send_probability"])))
    rate = float(mix["rpc_per_client_per_s"])
    offs, who, kind = [], [], []
    for c in range(clients):
        phase = rng.uniform(0.0, slot)
        chosen = np.sort(rng.permutation(slots)[:take])
        offs.append(phase + slot * chosen)
        who.append(np.full(take, c))
        kind.append(np.full(take, SEND))
        if rate > 0:
            calls = int(seconds * rate + 1e-9)
            # one call in every 1/rate seconds, at an instant drawn
            # anew each time: a fixed phase would lock a 1 Hz caller to
            # a 1 Hz frame and leave the tail only `clients` samples
            t = (np.arange(calls) + rng.uniform(0.0, 1.0, calls)) / rate
            offs.append(t)
            who.append(np.full(calls, c))
            kind.append(np.full(calls, RPC))
    offs, who, kind = (np.concatenate(a) for a in (offs, who, kind))
    order = np.argsort(offs, kind="stable")
    return offs[order], who[order].astype(np.int64), kind[order]
