"""The byte counts against a hand count."""

import cost


def test_get_bytes_hand_count():
    # depth 3, eps 4/8: two inner levels and one leaf
    inner = 7 * 8 + 12 + (2 * 4 + 2) * 8 + 4  # first keys, model, window, child
    leaf = 12 + 8 + (2 * 8 + 2) * 8 + 8 + 4  # model, anchor, window, value, count
    assert inner == 152 and leaf == 176
    assert cost.get_bytes(1, 3, 4, 8) == 2 * 152 + 176 + 8 + 8 + 1 == 497
    assert cost.get_bytes(65536, 3, 4, 8) == 65536 * 497

