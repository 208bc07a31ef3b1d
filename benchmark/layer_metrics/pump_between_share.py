"""Game host (net/game.py serve loop): of the packets the serve loop
handled in the window, the share it handled BETWEEN ticks, as they
arrived in the frame's remainder, and not in the frame's own pump ahead
of the tick (``game_pump_packets_total{where}``, window delta of
``between`` over both). A call handled between ticks is answered then;
one the frame's pump takes has waited for the frame. ``None`` where the
game exports no such series (a program from before PR 30)."""
from scrapes import delta


def read(scrapes, trace, cell):
    between = delta(scrapes, "game", "game_pump_packets_total",
                    'where="between"')
    both = delta(scrapes, "game", "game_pump_packets_total")
    if between is None or not both:
        return None
    return 100.0 * between / both
