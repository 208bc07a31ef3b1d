"""work.py's bytes against a hand count at one small shape."""
import pytest

import work


def test_row_bytes_by_hand():
    # pos 12 + yaw 4 + vel 12 + four 1-byte flags 4 + six i32/u32 24
    # + hot_attrs 8 x 4 = 32 + nbr_mean_off 12 + aoi_radius 4
    assert work.ROW_BYTES == 12 + 4 + 12 + 4 + 24 + 32 + 12 + 4 == 104


def test_necessary_bytes_small_shape():
    # 10 live rows, k = 4, 2 clients in one pair seeing 3 NPCs each:
    # state 2 x 104 x 10 = 2080; lists 2 x 4 x 4 x 10 = 320;
    # records 24 x 2 x (3 + 1) = 192
    assert work.necessary_bytes(10, 2, 2, 3, k=4) == 2080 + 320 + 192


def test_tile_least_seconds_and_unknown_device():
    cfg = {"world": {"live": 100000, "expected_neighbours": 12}}
    mix = {"clients": 256, "group_size": 2}
    b = work.necessary_bytes(100000, 256, 2, 12)
    assert b == 2 * 104 * 100000 + 2 * 4 * 64 * 100000 + 24 * 256 * 13
    assert work.least_seconds(cfg, mix, "TPU v5 lite") \
        == pytest.approx(b / 819e9)
    with pytest.raises(KeyError):
        work.least_seconds(cfg, mix, "cpu")
