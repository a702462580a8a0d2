import struct
import zlib

import numpy as np
import pytest

from repro.core import (
    BitReader,
    DeflateChunkDecoder,
    canonical_stored_offset,
    find_dynamic_skiplut,
    find_dynamic_trial,
    parse_gzip_header,
    scan_dynamic_candidates,
    scan_stored_candidates,
)
from repro.core.block_finder import CombinedBlockFinder, FilterStats
from repro.core.synth import stored_only_compress

from conftest import gzip_bytes, make_base64, make_random, make_text


def _true_blocks(comp):
    br = BitReader(comp)
    parse_gzip_header(br)
    dec = DeflateChunkDecoder(comp)
    res = dec.decode_chunk(br.bit_pos, len(comp) * 8, window=b"")
    return res.blocks


def test_finds_all_true_dynamic_blocks(rng):
    data = make_base64(rng, 500_000)
    comp = gzip_bytes(data, 6)
    blocks = _true_blocks(comp)
    dynamic = [b.bit_offset for b in blocks if b.block_type == 2 and not b.is_final]
    assert len(dynamic) >= 3
    found = set(scan_dynamic_candidates(comp, 0, len(comp) * 8))
    missing = [b for b in dynamic if b not in found]
    assert not missing, f"finder missed true blocks at {missing}"


def test_finds_stored_blocks_canonically(rng):
    data = make_random(rng, 400_000)
    comp = stored_only_compress(data)
    blocks = _true_blocks(comp)
    stored = [
        canonical_stored_offset(b.bit_offset)
        for b in blocks
        if b.block_type == 0 and not b.is_final
    ]
    assert stored
    found = set(scan_stored_candidates(comp, 0, len(comp) * 8))
    missing = [b for b in stored if b not in found]
    assert not missing


def test_combined_finder_orders_candidates(rng):
    data = make_text(rng, 200_000) + make_random(rng, 100_000)
    comp = gzip_bytes(data, 6)
    cands = []
    finder = CombinedBlockFinder(comp, 0, len(comp) * 8)
    for c in finder:
        cands.append(c)
        if len(cands) > 200:
            break
    assert cands == sorted(cands)
    assert len(cands) == len(set(cands))


def _forged_stored_pair(rng) -> tuple:
    """A gzip member whose file name holds a byte with its top 3 bits clear
    and a LEN/NLEN pair: a stored-block candidate before the first dynamic
    block. Returns the member, the forged candidate and the block's start."""
    data = make_base64(rng, 150_000)
    c = zlib.compressobj(6, zlib.DEFLATED, -15)
    raw = c.compress(data) + c.flush()
    name = b"\x01\x34\x12\xcb\xed"  # LEN 0x1234, NLEN its complement
    head = b"\x1f\x8b\x08\x08" + b"\x00" * 4 + b"\x00\xff" + name + b"\x00"
    tail = struct.pack("<II", zlib.crc32(data), len(data))
    return head + raw + tail, 8 * 11 - 3, 8 * len(head)


def _finder_case(name, rng):
    if name == "text_then_random_gzip6":
        return gzip_bytes(make_text(rng, 200_000) + make_random(rng, 100_000), 6), None
    if name == "random_gzip6":
        return gzip_bytes(make_random(rng, 300_000), 6), None
    if name == "stored_only":
        return stored_only_compress(make_random(rng, 300_000)), None
    if name == "base64_gzip1":
        return gzip_bytes(make_base64(rng, 300_000), 1), None
    if name == "forged_stored_pair":
        return _forged_stored_pair(rng)[0], None
    comp = gzip_bytes(make_text(rng, 200_000) + make_random(rng, 100_000), 6)
    bits = len(comp) * 8
    return comp, (bits // 5 + 3, 4 * bits // 5 + 5)  # both ends inside blocks


@pytest.mark.parametrize("case", ["text_then_random_gzip6", "random_gzip6", "stored_only",
                                  "base64_gzip1", "forged_stored_pair", "range_inside_blocks"])
def test_combined_finder_is_the_union_of_both_finders(rng, case):
    """Bounding the dynamic scan by the next stored candidate changes no
    candidate: the merged stream is the sorted union of both finders."""
    comp, span = _finder_case(case, rng)
    start, end = span or (0, len(comp) * 8)
    union = set(scan_dynamic_candidates(comp, start, end)) | set(scan_stored_candidates(comp, start, end))
    finder = CombinedBlockFinder(comp, start, end)
    assert list(finder) == sorted(union)
    assert union and start <= min(union) and max(union) < end
    assert finder.dynamic_bits <= end - start


def test_combined_finder_scans_past_a_false_stored_candidate(rng):
    comp, forged, first_block = _forged_stored_pair(rng)
    finder = CombinedBlockFinder(comp, 0, len(comp) * 8)
    assert next(finder) == forged
    assert finder.dynamic_bits == forged  # the dynamic scan stopped there
    assert next(finder) == first_block
    assert finder.dynamic_bits < len(comp) * 8


def test_combined_finder_scans_a_stored_block_not_the_chunk(rng):
    comp = gzip_bytes(make_random(rng, 300_000), 6)
    start = len(comp) * 4  # half way, inside a stored block
    finder = CombinedBlockFinder(comp, start, len(comp) * 8)
    first = next(finder)
    assert first == next(scan_stored_candidates(comp, start, len(comp) * 8))
    assert finder.dynamic_bits == first - start < (len(comp) * 8 - start) // 8


def test_stored_finder_stops_at_end_bit(rng):
    comp = stored_only_compress(make_random(rng, 300_000))
    every = list(scan_stored_candidates(comp, 0, len(comp) * 8))
    end = (every[1] + every[2]) // 2
    assert list(scan_stored_candidates(comp, 0, end)) == every[:2]
    assert list(scan_stored_candidates(comp, 0, every[1])) == every[:1]
    assert list(scan_stored_candidates(comp, 0, every[1] + 1)) == every[:2]


def test_skiplut_agrees_with_vectorized(rng):
    blob = make_random(rng, 20_000)
    end = len(blob) * 8
    vec = list(scan_dynamic_candidates(blob, 0, end))
    lut = list(find_dynamic_skiplut(blob, 0, end))
    assert vec == lut


def test_trial_agrees_with_vectorized_small(rng):
    blob = make_random(rng, 2_000)
    end = len(blob) * 8
    vec = list(scan_dynamic_candidates(blob, 0, end))
    trial = list(find_dynamic_trial(blob, 0, end))
    assert vec == trial


def test_false_positive_rate_on_random_data(rng):
    """Paper Table 1: ~200 valid headers per 1e12 positions => random data
    yields very few candidates; the cascade must reject almost everything."""
    blob = make_random(rng, 125_000)  # 1e6 bit positions
    stats = FilterStats()
    cands = list(scan_dynamic_candidates(blob, 0, len(blob) * 8, stats=stats))
    assert stats.tested >= 990_000
    # Expected ~2e-10 * 1e6 << 1; allow a little slack for unlucky seeds.
    assert len(cands) <= 2
    # Cascade ordering sanity (paper Table 1 proportions).
    assert stats.invalid_final == pytest.approx(stats.tested * 0.5, rel=0.01)
    assert stats.invalid_type == pytest.approx(stats.tested * 0.375, rel=0.01)
    assert stats.invalid_hlit == pytest.approx(stats.tested * 0.0078, rel=0.15)
    assert stats.invalid_precode_histogram > stats.invalid_precode_data


def test_stored_finder_false_positive_rate(rng):
    """Paper §3.4.1: one false positive every ~514 KiB on random data."""
    blob = make_random(rng, 2 << 20)
    n = len(list(scan_stored_candidates(blob, 0, len(blob) * 8)))
    # 2 MiB / 514 KiB ~ 4; generous bounds:
    assert n <= 25
