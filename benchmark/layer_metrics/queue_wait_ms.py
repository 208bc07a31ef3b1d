"""Game host (utils/overload.py ClassQueues, net/game.py pump): how long
a packet stood in the game's class queues between the network thread's
``offer`` and the pump's ``pop`` — ``game_queue_wait_ms``, every class,
window delta, sum over count. At one pump a frame this is most of what
a send waits for."""
from scrapes import mean_ms


def read(scrapes, trace, cell):
    return mean_ms(scrapes, "game", "game_queue_wait_ms")
