"""Device: the share of the captured span in which no operation ran on
it (1 - busy / span), in percent."""


def read(scrapes, trace, cell):
    if not trace or not trace.get("busy_s") or not trace.get("window_s"):
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
