"""Kernels (ops/aoi.py, ops/delta.py, ops/sync.py): the least time the
chip could take for one tick's necessary bytes (work.py, bound: HBM
bytes) over the device's busy time per frame, in percent."""
from scrapes import busy_ms_per_frame
from work import least_seconds


def read(scrapes, trace, cell):
    b = busy_ms_per_frame(trace, cell)
    if b is None:
        return None
    return 100.0 * 1e3 * least_seconds(
        cell["config"], cell["mix"], cell["device_kind"]) / b
