import zlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.crc32 import RunningCRC, combine_lanes, combine_parts, crc32_combine


@settings(max_examples=60, deadline=None)
@given(a=st.binary(max_size=2000), b=st.binary(max_size=2000))
def test_combine_matches_concatenation(a, b):
    crc_a = zlib.crc32(a) & 0xFFFFFFFF
    crc_b = zlib.crc32(b) & 0xFFFFFFFF
    assert crc32_combine(crc_a, crc_b, len(b)) == (zlib.crc32(a + b) & 0xFFFFFFFF)


@settings(max_examples=20, deadline=None)
@given(parts=st.lists(st.binary(min_size=0, max_size=500), min_size=1, max_size=8))
def test_running_crc_fold(parts):
    acc = RunningCRC()
    for p in parts:
        acc.add(zlib.crc32(p) & 0xFFFFFFFF, len(p))
    assert acc.crc == (zlib.crc32(b"".join(parts)) & 0xFFFFFFFF)
    assert acc.length == sum(len(p) for p in parts)


def test_combine_parts_helper():
    blobs = [b"hello ", b"parallel ", b"world"]
    parts = [(zlib.crc32(b) & 0xFFFFFFFF, len(b)) for b in blobs]
    assert combine_parts(parts) == (zlib.crc32(b"".join(blobs)) & 0xFFFFFFFF)


def test_empty_and_identity():
    assert crc32_combine(0, 0, 0) == 0
    assert crc32_combine(0xDEADBEEF, 0, 0) == 0xDEADBEEF


@pytest.mark.parametrize("lane_len,lanes,used", [(1, 1, 1), (4, 8, 8), (64, 1024, 1000), (4096, 16, 3)])
def test_combine_lanes_matches_zlib(lane_len, lanes, used):
    """The vectorized tree fold equals zlib over the concatenated lanes; a
    row's unused leading lanes hold 0 and fold in as empty prefixes."""
    rng = np.random.default_rng(lane_len * 31 + used)
    blobs = [rng.bytes(lane_len) for _ in range(used)]
    rows = np.zeros((2, lanes), np.uint32)
    rows[0, lanes - used :] = [zlib.crc32(b) for b in blobs]
    rows[1, lanes - 1] = zlib.crc32(blobs[0])
    out = combine_lanes(rows, lane_len)
    assert int(out[0]) == zlib.crc32(b"".join(blobs))
    assert int(out[1]) == zlib.crc32(blobs[0])


def test_combine_lanes_rejects_ragged_lane_count():
    with pytest.raises(ValueError):
        combine_lanes(np.zeros((1, 3), np.uint32), 4)
