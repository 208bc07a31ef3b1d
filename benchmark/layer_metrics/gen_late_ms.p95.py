"""Load generator: how late a send or call left against its due time
(95th percentile over the window's operations, benchmark's own clock)."""


def read(scrapes, trace, cell):
    return scrapes["bots"]["gen_late_ms"]["p95"]
