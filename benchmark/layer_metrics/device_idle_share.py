"""Device: the share of the captured span in which no operation ran on
it (1 - busy / span), in percent: of the busiest device plane where the
cell lies on several chips."""
from scrapes import plane_of


def read(scrapes, trace, cell):
    one = plane_of(trace)
    if not one or not one.get("busy_s") or not one.get("window_s"):
        return None
    return 100.0 * (1.0 - one["busy_s"] / one["window_s"])
