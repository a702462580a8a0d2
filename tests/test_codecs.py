"""Codec interface: detection, the BGZF zero-speculation path, codec-aware
store identity, and the zstd seekable backend.

Detection must be evidence-based (BGZF by its BC FEXTRA subfield, zstd by
frame magic), degrade to deflate on ambiguity (valid gzip can never error
out of auto-detection), and flow consistently into `IndexStore.file_identity`
so twins of the same logical content under different codecs never collide.
Zstd decode tests carry the ``zstd`` marker (auto-skip on a bare container);
everything structural — probing, seek-table parsing, identity — runs
without a zstd library.
"""

import gzip as _gzip
import struct

import pytest

from repro.core import ParallelGzipReader
from repro.core.codec import (
    BgzfCodec,
    DeflateCodec,
    ZstdCodec,
    detect_codec,
    detect_codec_tag,
    have_zstd,
    parse_zstd_seek_table,
    resolve_codec,
)
from repro.core.errors import FormatError
from repro.core.filereader import BytesFileReader
from repro.core.index import GzipIndex
from repro.core.synth import bgzf_compress, gzip_compress
from repro.service.index_store import IndexStore, file_identity

from conftest import make_base64, make_text


# ---------------------------------------------------------------------------
# detection
# ---------------------------------------------------------------------------


def test_detect_plain_gzip_is_deflate(rng):
    comp = gzip_compress(make_text(rng, 10_000), 6)
    assert detect_codec(comp).tag == "deflate"


def test_detect_bgzf_by_bc_subfield(rng):
    comp = bgzf_compress(make_text(rng, 10_000), 6)
    assert detect_codec(comp).tag == "bgzf"


def test_gzip_with_unrelated_fextra_is_not_bgzf(rng):
    """BGZF detection requires the BC subfield, not just FEXTRA presence: a
    gzip member with an unrelated extra field must stay deflate."""
    import zlib

    data = make_text(rng, 20_000)
    xtra = b"XY" + struct.pack("<H", 4) + b"\xde\xad\xbe\xef"
    header = (
        b"\x1f\x8b\x08\x04\x00\x00\x00\x00\x00\xff"
        + struct.pack("<H", len(xtra))
        + xtra
    )
    co = zlib.compressobj(6, zlib.DEFLATED, -15)
    raw = co.compress(data) + co.flush()
    footer = struct.pack("<II", zlib.crc32(data) & 0xFFFFFFFF, len(data) & 0xFFFFFFFF)
    comp = header + raw + footer
    assert _gzip.decompress(comp) == data  # sanity: valid gzip
    assert detect_codec(comp).tag == "deflate"
    with ParallelGzipReader(comp, parallelization=2) as r:
        assert r.codec.tag == "deflate"
        assert r.read() == data


def test_detect_zstd_by_frame_magic():
    assert detect_codec(b"\x28\xb5\x2f\xfd" + b"\x00" * 16).tag == "zstd"
    # skippable-frame-first files (seekable archives may start with one)
    assert detect_codec(struct.pack("<II", 0x184D2A5E, 4) + b"\x00" * 8).tag == "zstd"


def test_detect_garbage_falls_back_to_deflate():
    assert detect_codec(b"").tag == "deflate"
    assert detect_codec(b"\x00\x01\x02not an archive").tag == "deflate"
    assert detect_codec(b"\x1f").tag == "deflate"  # truncated magic


def test_truncated_bgzf_header_degrades_not_errors(rng):
    """A BGZF head truncated mid-header must not raise out of detection —
    the BGZF probe fails closed and deflate (whose probe needs only the
    2-byte magic) takes over."""
    comp = bgzf_compress(make_text(rng, 10_000), 6)
    for cut in (2, 3, 10, 15):
        tag = detect_codec(comp[:cut]).tag
        assert tag == "deflate", cut


def test_bgzf_leading_member_with_gzip_tail_falls_back(rng):
    """First member BGZF, rest plain gzip: the exact-index walk fails midway
    and the reader must fall back to the speculative pass (never error on
    valid gzip), still producing exact bytes — with an unpolluted index."""
    a, b = make_text(rng, 120_000), make_base64(rng, 80_000)
    comp = bgzf_compress(a, 6) + gzip_compress(b, 6)
    truth = a + b
    assert _gzip.decompress(comp) == truth  # sanity: valid multi-member gzip
    with ParallelGzipReader(comp, parallelization=2, chunk_size=48 << 10) as r:
        assert r.read() == truth
        assert r.index.finalized
        assert r.index.decompressed_size == len(truth)


def test_resolve_codec_rejects_unknown_tag():
    with pytest.raises(ValueError):
        resolve_codec("lz77-from-the-future")


# ---------------------------------------------------------------------------
# BGZF: the zero-speculation acceptance (paper §3.4.4)
# ---------------------------------------------------------------------------


def test_bgzf_cold_open_zero_speculation(rng):
    """A cold BGZF open performs ZERO speculative decoding: no nominal
    tasks, no frontier lock acquisitions, index finalized before the first
    read — while serving bit-identical bytes."""
    data = make_text(rng, 600_000)
    comp = bgzf_compress(data, 6)
    with ParallelGzipReader(comp, parallelization=3, chunk_size=64 << 10) as r:
        assert r.codec.tag == "bgzf"
        assert r.index.finalized  # before any read
        assert r.index.codec_tag == "bgzf"
        for off in (0, 123_457, 599_000, 300_000):
            assert r.pread(off, 2000) == data[off : off + 2000]
        assert r.read() == data
        st = r.stats()
        assert st["fetcher"]["nominal_tasks"] == 0
        assert st["fetcher"]["exact_tasks"] == 0
        assert st["fetcher"]["candidates_tried"] == 0
        assert st["frontier"]["lock_acquires"] == 0
        assert st["fetcher"]["zlib_delegations"] > 0


# ---------------------------------------------------------------------------
# codec-aware identity (store + fleet rendezvous keys)
# ---------------------------------------------------------------------------


def test_identity_distinguishes_codec_twins(rng):
    """Twins of the same logical content under different codecs must get
    different store keys — in every source shape (bytes, path, FileReader)."""
    data = make_text(rng, 64 << 10)
    gz = gzip_compress(data, 6)
    bg = bgzf_compress(data, 6)
    assert file_identity(gz) != file_identity(bg)

    # Same bytes, different pinned codec tag: still different keys.
    assert file_identity(gz, codec="deflate") != file_identity(gz, codec="zstd")

    # FileReader branch agrees with the bytes branch about codec mixing.
    assert file_identity(BytesFileReader(gz)) != file_identity(BytesFileReader(bg))


def test_identity_same_key_for_same_blob_any_shape(rng, tmp_path):
    """Auto-probe is deterministic: repeated identity calls agree (this is
    what keeps fleet rendezvous routing consistent across router/server)."""
    data = make_text(rng, 32 << 10)
    bg = bgzf_compress(data, 6)
    assert file_identity(bg) == file_identity(bg)
    assert detect_codec_tag(bg) == "bgzf"
    p = tmp_path / "x.bgzf.gz"
    p.write_bytes(bg)
    assert detect_codec_tag(str(p)) == "bgzf"
    assert file_identity(str(p)) == file_identity(str(p))


def test_store_keys_codec_twins_separately(rng, tmp_path):
    """End-to-end regression: persist a gzip twin's index, then open the
    BGZF twin against the same store — it must MISS (different key), never
    import the deflate index for the BGZF bytes."""
    data = make_text(rng, 200_000)
    gz, bg = gzip_compress(data, 6), bgzf_compress(data, 6)
    store = IndexStore(str(tmp_path / "idx"))

    with ParallelGzipReader(gz, parallelization=2, chunk_size=64 << 10) as r:
        r.read()
        assert store.put(gz, r.index) is not None
    assert store.get(gz) is not None  # warm for the gzip twin
    assert store.get(bg) is None  # cold for the BGZF twin
    assert store.key_for(gz) != store.key_for(bg)


def test_detect_codec_tag_malformed_source_degrades():
    """Identity keys must be computable for malformed sources too (the open
    that follows reports the real error) — probe failures mean deflate."""
    assert detect_codec_tag(b"") == "deflate"
    assert detect_codec_tag("/nonexistent/path/really") == "deflate"


# ---------------------------------------------------------------------------
# zstd: structure without a library, decode with one
# ---------------------------------------------------------------------------


def _fake_seekable(frames, checksums=None):
    """Seekable container with arbitrary (fake) frame bytes — the seek-table
    parser never decodes frames, so it is testable without a zstd library.
    With ``checksums`` the entries are 12 bytes and ``Checksum_Flag`` is set."""
    body = b"".join(f for f, _ in frames)
    if checksums is None:
        entries = b"".join(struct.pack("<II", len(f), d) for f, d in frames)
    else:
        entries = b"".join(struct.pack("<III", len(f), d, c) for (f, d), c in zip(frames, checksums))
    table = entries + struct.pack("<IBI", len(frames), 0 if checksums is None else 0x80, 0x8F92EAB1)
    return body + struct.pack("<II", 0x184D2A5E, len(table)) + table


def test_zstd_seek_table_parses_without_library():
    frames = [(b"\x28\xb5\x2f\xfdAAAA", 100), (b"\x28\xb5\x2f\xfdBBBBBB", 250)]
    blob = _fake_seekable(frames)
    got = parse_zstd_seek_table(BytesFileReader(blob))
    assert got == [(0, 8, 100, None), (8, 10, 250, None)]
    blob = _fake_seekable(frames, checksums=[0xDEADBEEF, 7])
    got = parse_zstd_seek_table(BytesFileReader(blob))
    assert got == [(0, 8, 100, 0xDEADBEEF), (8, 10, 250, 7)]


def test_zstd_seek_table_rejects_inconsistent_footer():
    frames = [(b"\x28\xb5\x2f\xfdAAAA", 100)]
    blob = bytearray(_fake_seekable(frames))
    blob[-1] ^= 0x5A  # corrupt the seekable magic
    with pytest.raises(FormatError):
        parse_zstd_seek_table(BytesFileReader(bytes(blob)))
    with pytest.raises(FormatError):
        parse_zstd_seek_table(BytesFileReader(b"\x28\xb5\x2f\xfd" + b"\x00" * 20))


def test_zstd_open_without_library_fails_loudly():
    """On a bare container a zstd source must produce a clear FormatError at
    open time (mentioning how to get a backend), not a junk decode."""
    if have_zstd():
        pytest.skip("a zstd library is importable; the loud-failure path is moot")
    frames = [(b"\x28\xb5\x2f\xfdAAAA", 100)]
    blob = _fake_seekable(frames)
    with pytest.raises(FormatError, match="zstandard"):
        ParallelGzipReader(blob, parallelization=1)


@pytest.mark.zstd
def test_zstd_roundtrip_real_frames(rng):
    """Real seekable frames (library present): cold open builds the index
    from the seek table — zero speculation, exact bytes, random access."""
    from repro.core.synth import zstd_seekable_compress

    data = make_text(rng, 500_000)
    comp = zstd_seekable_compress(data, 3, frame_size=64 << 10)
    with ParallelGzipReader(comp, parallelization=3) as r:
        assert r.codec.tag == "zstd"
        assert r.index.finalized
        assert r.read() == data
        for off in (0, 123_457, 499_000):
            assert r.pread(off, 1500) == data[off : off + 1500]
        st = r.stats()
        assert st["fetcher"]["nominal_tasks"] == 0
        assert st["frontier"]["lock_acquires"] == 0
        assert st["fetcher"]["zlib_delegations"] > 0  # native-path counter


@pytest.mark.zstd
def test_zstd_index_roundtrip_and_store(rng, tmp_path):
    from repro.core.synth import zstd_seekable_compress

    data = make_base64(rng, 300_000)
    comp = zstd_seekable_compress(data, 3, frame_size=64 << 10)
    store = IndexStore(str(tmp_path / "idx"))
    with ParallelGzipReader(comp, parallelization=2) as r:
        r.read()
        assert store.put(comp, r.index) is not None
    warm = store.get(comp)
    assert warm is not None and warm.codec_tag == "zstd"
    with ParallelGzipReader(comp, parallelization=2, index=warm.to_bytes()) as r2:
        assert r2.codec.tag == "zstd"
        assert r2.pread(150_000, 5000) == data[150_000:155_000]


# ---------------------------------------------------------------------------
# fetcher-level invariance: nothing above the fetcher is codec-aware
# ---------------------------------------------------------------------------


def test_server_surfaces_codec_tag(rng, tmp_path):
    """ArchiveServer auto-detects per handle and reports the resolved tag in
    stat()/metrics() with no per-codec branches of its own."""
    from repro.service.server import ArchiveServer

    data = make_text(rng, 150_000)
    with ArchiveServer(max_workers=2, chunk_size=64 << 10) as srv:
        h_gz = srv.open(gzip_compress(data, 6))
        h_bg = srv.open(bgzf_compress(data, 6))
        assert srv.read_range(h_gz, 1000, 2000) == data[1000:3000]
        assert srv.read_range(h_bg, 1000, 2000) == data[1000:3000]
        assert srv.stat(h_gz).codec == "deflate"
        assert srv.stat(h_bg).codec == "bgzf"
        per_file = srv.metrics()["per_file"]
        assert {v["codec"] for v in per_file.values()} == {"deflate", "bgzf"}


def test_codec_window_size_contract():
    assert DeflateCodec().window_size == 32768
    assert BgzfCodec().window_size == 32768  # members are deflate inside
    assert ZstdCodec().window_size == 0  # frames are independent
    assert not ZstdCodec().supports_speculation
    assert BgzfCodec().supports_speculation  # fallback path needs it
