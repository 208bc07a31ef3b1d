"""Game host (net/game.py serve loop): of the packets the serve loop
handled in the window, the share it handled INSIDE a tick, while the
device computed and the logic thread waited for the tick's outputs on
its queue (``game_pump_packets_total{where}``, window delta of
``device_wait`` over all places). A call handled there is answered
then; before PR 34 it waited for the decode's end. ``None`` where the
game exports no such series (a program from before PR 34)."""
from scrapes import delta


def read(scrapes, trace, cell):
    waiting = delta(scrapes, "game", "game_pump_packets_total",
                    'where="device_wait"')
    everywhere = delta(scrapes, "game", "game_pump_packets_total")
    if waiting is None or not everywhere:
        return None
    return 100.0 * waiting / everywhere
