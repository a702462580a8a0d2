"""Third-party conformance fixtures (tests/data/) through our codec stack.

The archives are generated by ``tests/data/make_fixtures.py`` directly from
the published BGZF and zstd-seekable specifications, with no code shared
with ``src/repro`` — so these tests check the *parsers* against the specs,
not against our own writers. See tests/data/README.md.
"""

import os

import pytest

from repro.core.codec import detect_codec, have_zstd, parse_zstd_seek_table
from repro.kernels.ref import xxh64
from repro.core.filereader import BytesFileReader
from repro.core.gzip_format import scan_bgzf_members
from repro.core.reader import ParallelGzipReader

DATA_DIR = os.path.join(os.path.dirname(__file__), "data")


def _fixture(name: str) -> str:
    return os.path.join(DATA_DIR, name)


@pytest.fixture(scope="module")
def payload() -> bytes:
    with open(_fixture("conformance_payload.bin"), "rb") as f:
        return f.read()


def test_detect_codec_from_head_bytes():
    with open(_fixture("conformance_bgzip.gz"), "rb") as f:
        assert detect_codec(f.read(4096)).tag == "bgzf"
    with open(_fixture("conformance_seekable.zst"), "rb") as f:
        assert detect_codec(f.read(4096)).tag == "zstd"


def test_bgzf_member_scan_walks_extra_subfields(payload):
    """Member #2 has a vendor FEXTRA subfield before BC — spec-legal."""
    with open(_fixture("conformance_bgzip.gz"), "rb") as f:
        blob = f.read()
    members = scan_bgzf_members(BytesFileReader(blob))
    # 4 payload members (96 KiB / 24 KiB) + the canonical EOF member.
    assert len(members) == 5
    # (offset, size) pairs tile the file exactly — the walk never lost
    # sync across the member with the vendor subfield.
    for (off_a, size_a), (off_b, _) in zip(members, members[1:]):
        assert off_b == off_a + size_a
    last_off, last_size = members[-1]
    assert last_off + last_size == len(blob)
    assert last_size == 28  # canonical EOF member


def test_bgzf_cold_open_zero_speculation(payload):
    """Cold open must build the exact index from metadata: no nominal
    (speculative) tasks, bit-identical bytes."""
    with ParallelGzipReader(
        _fixture("conformance_bgzip.gz"), parallelization=2
    ) as reader:
        assert reader.codec.tag == "bgzf"
        assert reader.index.finalized  # before any read
        data = reader.pread(0, len(payload) + 1)
        fetcher = reader.stats()["fetcher"]
    assert data == payload
    assert fetcher["nominal_tasks"] == 0


def test_zstd_seek_table_parses_without_backend(payload):
    """The checksummed (12-byte-entry) seek table needs no zstd library."""
    with open(_fixture("conformance_seekable.zst"), "rb") as f:
        blob = f.read()
    frames = parse_zstd_seek_table(BytesFileReader(blob))
    assert len(frames) == 3
    assert frames[0][0] == 0
    # offsets chain: each frame starts where the previous ended
    for (off_a, comp_a, _, _), (off_b, _, _, _) in zip(frames, frames[1:]):
        assert off_b == off_a + comp_a
    assert sum(dec for _, _, dec, _ in frames) == len(payload)
    assert all(dec <= 32 << 10 for _, _, dec, _ in frames)
    # Checksum_Flag is set: each entry holds its frame's XXH64 low 32 bits.
    off = 0
    for _, _, dec, checksum in frames:
        assert checksum == xxh64(payload[off : off + dec]) & 0xFFFFFFFF
        off += dec


@pytest.mark.zstd
def test_zstd_seekable_decodes_bit_identically(payload):
    """Raw-block frames are valid zstd: any conformant backend decodes
    them (auto-skipped where no backend is installed)."""
    assert have_zstd()
    with ParallelGzipReader(
        _fixture("conformance_seekable.zst"), parallelization=2
    ) as reader:
        assert reader.codec.tag == "zstd"
        assert reader.pread(0, len(payload) + 1) == payload
