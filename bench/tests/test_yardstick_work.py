"""Useful coding bytes, counted by hand on a tiny manifest."""

import yardstick_tiny  # noqa: F401  (puts bench/ on the path)

import work  # noqa: E402

MANIFEST = {"leaves": [
    {"groups": [
        # 10 bytes as K=2, P=1: B = 5; encode reads 10, writes 5.
        {"k": 2, "p": 1, "orig_nbytes": 10, "node_ids": [0, 1, 2]},
        # 9 bytes as K=4, P=2: B = 3; encode reads 12, writes 6.
        {"k": 4, "p": 2, "orig_nbytes": 9, "node_ids": [3, 4, 5, 6, 7, 8]},
    ]},
    None,
]}


def test_hand_count():
    assert work.chunk_len(9, 4) == 3
    assert work.save_encode_bytes(MANIFEST) == 15 + 18
    # A leaf saved as no group (None) adds nothing.
    assert [g["k"] for g in work.groups(MANIFEST)] == [2, 4]


def test_padding_lowers_the_share_and_not_the_bytes():
    # The same 9 user bytes padded to a 4096-byte bucket: the kernel
    # moves more bytes and takes longer, the useful count stays put.
    useful = work.encode_bytes(4, 2, 9)
    padded_moved = (4 + 2) * work.chunk_len(4096, 4)
    assert useful == 18 < padded_moved
    peak, kernel_s_unpadded = 819e9, 1e-6
    kernel_s_padded = kernel_s_unpadded * padded_moved / useful
    share = lambda s: 100 * useful / peak / s  # noqa: E731
    assert share(kernel_s_padded) < share(kernel_s_unpadded)
