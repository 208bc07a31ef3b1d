"""Device tick: the share of the device's busy time that ran under no
``gw.`` scope (programs beside the tick, and what the tick does
outside its named phases), in percent. Nothing where the capture holds
no scope at all: a program without them is not 100% nameless, it is
unread."""
from phase_reduce import phases


def read(scrapes, trace, cell):
    res = phases(cell)
    if not res or not res["scopes"] or not res["busy_ms"]:
        return None
    return 100.0 * res["unscoped_ms"] / res["busy_ms"]
