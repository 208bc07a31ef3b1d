"""Generator kind ``hop``: pairs of clients in a world of MANY spaces,
some of which change space together now and then, all of which write an
attr now and then.

A mix of this kind is a data file (``benchmark/traffic/<mix>.json``):
``clients`` in pairs (``group_size`` 2: the partner is the observer),
``orbit``'s cadence (``send_interval_ms``, ``send_probability``,
``rpc_per_client_per_s``, ``orbit_radius``, ``orbit_step_rad``), and

* **uneven occupancy**: the first ``crowd_spaces`` spaces hold
  ``crowd_clients`` clients each, their pairs on a square grid of sites
  ``crowd_spacing`` apart in the middle of the space. With the spacing a
  little under the AOI radius a client holds the avatars of the sites
  around it, and members of neighbouring sites on opposite phases of
  their orbits cross each other's AOI edge about twice a lap
  (``crossers``). The other pairs stand two to a space, on opposite ones
  of a space's four sites (the quarter points of the extent, half the
  extent apart: two pairs in one space never see each other), in the
  spaces that follow; every further space holds NPCs only.
* **hops**: every pair outside the crowd changes space once in every
  ``hop_every_s`` seconds, both members at the same instant, each with
  ``("call", "EnterSpace_Client", (space, x, z), {"at": (space, x, z)})``.
  The instant is drawn uniformly inside each period, pair by pair; of a
  stretch's last, cut period as many pairs hop (drawn) as its share of
  a period holds, so every seed gives the same number of hops and any
  one frame holds as many as chance brings. One rule is the client's
  own, and assumed: a client that has asked for a space asks for no
  other in the next ``hop_min_gap_s`` seconds (a player who is loading
  into a space does not ask for the next; an open loop cannot wait for
  the answer, so the plan keeps a gap), across the cut between two
  stretches too: the plan is told the instant of every hop that was
  really sent (``called``) and every stretch's first instant
  (``schedule(..., start)``). The destination is drawn among the
  places (space, site) outside the crowd that no pair holds and none
  has left in the last ``hop_free_s`` seconds, never in the space the
  pair stands in. Nothing here knows how the program stages a change
  of space, what sizes it pads a batch to, or what it does with a
  position sent to an avatar between two spaces.
* **attr writes**: ``attr_per_client_per_s``: one ``("call",
  "SetHp_Client", (value,), {"attrs": {"hp": value}})`` in every 1/rate
  seconds at an instant drawn anew each time, the value drawn too.

The fourth entry of a call says what it does to its client, in words
``bots.py`` knows of any generator (``at``: the avatar stands there
from now on; ``attrs``: what every holder of the avatar must show at
the end), so ``bots.py`` names no method of the fixture.

Every seed gives the same amount of work. The plan knows where its
pairs stand (``called`` tells it of every call that was really sent: a
warm-up stretch is cut short when the window opens), so it schedules
itself: ``Plan.schedule(seed, seconds, stream, start)``.

The height coordinate carries the send's sequence number, as in
``orbit``; x and z are the site's anchor plus the orbit's offset. A
client that has changed space sends from its new site on: ``bots.py``
moves the rows of its table that are still to be sent (by the
difference of the two places it stood at).
"""
from __future__ import annotations

import math

import numpy as np

from generators.orbit import schedule as cadence

SEND, RPC = 0, 1
ENTER, SET_HP = "EnterSpace_Client", "SetHp_Client"


def enter(space: int, x: float, z: float) -> tuple:
    """The fixture's call that puts the caller's avatar into ``space``
    at (x, z), and what it does to its client."""
    place = (int(space), float(x), float(z))
    return ("call", ENTER, place, {"at": place})


class Plan:
    def __init__(self, mix: dict, extent: float, aoi_radius: float,
                 clients: int, spaces: int = 1):
        self.mix = mix
        self.n = clients
        self.g = g = int(mix["group_size"])
        if g != 2 or clients % 2:
            raise ValueError("a hop mix is made of pairs")
        self.groups = clients // g
        self.spaces = int(spaces)
        self.extent = float(extent)
        self.r = float(mix["orbit_radius"])
        self.step = float(mix["orbit_step_rad"])
        self.crowds = int(mix["crowd_spaces"])
        per = int(mix["crowd_clients"]) // g        # pairs a crowd space
        self.crowd_pairs = self.crowds * per
        self.per = per
        self.side = max(math.ceil(math.sqrt(per)), 1)
        self.spacing = float(mix["crowd_spacing"])
        self.origin = 0.5 * (extent - self.spacing * (self.side - 1))
        if self.origin - self.r < 0.0:
            raise ValueError(f"{per} pairs {self.spacing} apart do not "
                             f"fit an extent of {extent}")
        roam = self.groups - self.crowd_pairs       # pairs that hop
        if roam < 0:
            raise ValueError("more clients in the crowd than in the mix")
        q = 0.25 * extent
        if 2.0 * q < 2.0 * (aoi_radius + 2.0 * self.r):
            raise ValueError("two sites of one space would see each other")
        self.corners = [(q, q), (3 * q, q), (q, 3 * q), (3 * q, 3 * q)]
        homes = self.crowds + (roam + 1) // 2       # spaces with clients
        if homes > self.spaces or (roam and self.spaces - self.crowds < 2):
            raise ValueError(f"the mix needs {homes} spaces, the world "
                             f"has {self.spaces}")
        # a place is (space, site); where every roaming pair stands now,
        # and when each place was last left (stretch time, reset by
        # ``schedule``: a stretch starts with every held place held and
        # every other free)
        self.place = {self.crowd_pairs + j:
                      (self.crowds + j // 2, 3 * (j % 2))
                      for j in range(roam)}
        self.hopped: dict[int, float] = {}          # pair -> instant of its
        #                                             last hop (caller's clock)

    # ---- who is who ------------------------------------------------------
    def group_of(self, c: int) -> int:
        return c // self.g

    def members(self, grp: int) -> range:
        return range(grp * self.g, (grp + 1) * self.g)

    def observer(self, c: int) -> int:
        return c ^ 1

    def site_xz(self, space: int, site: int) -> tuple[float, float]:
        if space < self.crowds:
            return (self.origin + self.spacing * (site % self.side),
                    self.origin + self.spacing * (site // self.side))
        return self.corners[site]

    def home(self, grp: int) -> tuple[int, int]:
        """(space, site) a pair logs in at."""
        if grp < self.crowd_pairs:
            return grp // self.per, grp % self.per
        j = grp - self.crowd_pairs
        return self.crowds + j // 2, 3 * (j % 2)

    def login(self, c: int) -> tuple:
        """The call with which client ``c`` enters the world, at its
        site's anchor."""
        space, site = self.home(c // self.g)
        return enter(space, *self.site_xz(space, site))

    def crossers(self) -> list[tuple[int, int]]:
        """Ordered pairs of clients on opposite phases at sites next to
        each other along x in a crowded space: their distance swings
        over the AOI edge (``crowd_spacing`` +- twice the orbit's
        radius) twice a lap."""
        out = []
        for grp in range(self.crowd_pairs):
            site = grp % self.per
            if site % self.side + 1 < self.side and site + 1 < self.per:
                a, b = self.members(grp), self.members(grp + 1)
                for c in a:
                    for d in b:
                        if c % self.g != d % self.g:
                            out += [(c, d), (d, c)]
        return out

    def positions(self, seqs: int) -> np.ndarray:
        """f32[clients, seqs, 4]: (x, y, z, yaw) of every client's
        ``seq``-th send from the site it logs in at."""
        c = np.arange(self.n)
        anchors = np.array([self.login(i)[3]["at"][1:] for i in c])
        ang = self.step * np.arange(seqs)[None, :] \
            + 2.0 * math.pi * (c % self.g)[:, None] / self.g
        out = np.empty((self.n, seqs, 4), np.float32)
        out[:, :, 0] = anchors[:, 0:1] + self.r * np.cos(ang)
        out[:, :, 1] = np.arange(seqs)[None, :]
        out[:, :, 2] = anchors[:, 1:2] + self.r * np.sin(ang)
        out[:, :, 3] = ang % 6.28
        return out

    # ---- what was really sent ---------------------------------------------
    def called(self, c: int, op: tuple, at: float) -> None:
        """Client ``c`` has sent ``op``, due at instant ``at``."""
        does = op[3] if len(op) > 3 else {}
        if "at" in does and c // self.g in self.place:
            space, x, z = does["at"]
            site = min(range(4), key=lambda s: abs(self.corners[s][0] - x)
                       + abs(self.corners[s][1] - z))
            self.place[c // self.g] = (int(space), site)
            self.hopped[c // self.g] = float(at)

    # ---- the schedule ------------------------------------------------------
    def schedule(self, seed: int, seconds: float, stream: int,
                 start: float = 0.0):
        """One stretch of ``seconds`` that begins at instant ``start``
        (the clock ``called`` is told in), open loop: offsets from its
        start (s), client, operation — ``SEND``, ``RPC`` or ``("call",
        method, args, does)`` — sorted by offset. ``stream`` separates
        the warm-up's draws from the window's."""
        mix = self.mix
        offs, who, kind = cadence(mix, self.n, seed, seconds, stream)
        offs, who, kind = list(offs), list(who), list(kind)
        rng = np.random.default_rng([int(seed), int(stream), 0x686f70])
        rate = float(mix.get("attr_per_client_per_s", 0.0))
        if rate > 0:
            writes = int(seconds * rate + 1e-9)
            for c in range(self.n):
                t = (np.arange(writes) + rng.uniform(0.0, 1.0, writes)) / rate
                v = rng.integers(1, 1_000_000, writes)
                offs += t.tolist()
                who += [c] * writes
                kind += [("call", SET_HP, (int(x),), {"attrs": {"hp": int(x)}})
                         for x in v]
        every = float(mix.get("hop_every_s", 0.0))
        pairs = sorted(self.place)
        if every > 0 and pairs:
            gap = float(mix["hop_min_gap_s"])
            free_s = float(mix["hop_free_s"])
            whole = int(seconds // every)           # whole periods, and
            cut = seconds - whole * every           # the cut one's share
            late = set(rng.permutation(len(pairs))[
                :int(len(pairs) * cut / every + 1e-9)].tolist())
            hops = []
            for i, grp in enumerate(pairs):
                last = self.hopped.get(grp, -math.inf) - start
                for k in range(whole + (i in late)):
                    lo = max(every * k, last + gap)
                    hi = min(every * (k + 1), seconds)
                    if lo < hi:
                        last = rng.uniform(lo, hi)
                        hops.append((last, grp))
            hops.sort()
            at = dict(self.place)                   # as the stretch goes
            held = set(at.values())
            left: dict[tuple, float] = {}
            n_places = 4 * (self.spaces - self.crowds)
            for t, grp in hops:
                while True:
                    p = int(rng.integers(n_places))
                    to = (self.crowds + p // 4, p % 4)
                    if to not in held and to[0] != at[grp][0] \
                            and t - left.get(to, -free_s) >= free_s:
                        break
                held.discard(at[grp])
                left[at[grp]] = t
                held.add(to)
                at[grp] = to
                op = enter(to[0], *self.site_xz(*to))
                for c in self.members(grp):
                    offs.append(t)
                    who.append(c)
                    kind.append(op)
        order = np.argsort(np.asarray(offs), kind="stable")
        return (np.asarray(offs)[order], np.asarray(who, np.int64)[order],
                [kind[i] for i in order])
