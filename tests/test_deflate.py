import gzip as _gzip
import zlib

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (
    BitReader,
    DecodeResult,
    DeflateChunkDecoder,
    MARKER_BASE,
    WINDOW_SIZE,
    canonical_stored_offset,
    gzip_decompress_sequential,
    inflate_raw,
    parse_gzip_header,
    replace_markers,
)
from repro.core.errors import DeflateError, GzipFooterError
from repro.core.synth import fixed_only_compress, pigz_like_compress, stored_only_compress

from conftest import gzip_bytes, make_base64, make_random, make_text


@pytest.mark.parametrize("level", [1, 6, 9])
@pytest.mark.parametrize("kind", ["text", "base64", "random"])
def test_sequential_roundtrip(rng, level, kind):
    data = {"text": make_text, "base64": make_base64, "random": make_random}[kind](rng, 200_000)
    assert gzip_decompress_sequential(gzip_bytes(data, level)) == data


def test_multi_member(rng):
    data1, data2 = make_text(rng, 50_000), make_base64(rng, 30_000)
    comp = gzip_bytes(data1) + gzip_bytes(data2) + gzip_bytes(b"")
    assert gzip_decompress_sequential(comp) == data1 + data2


def test_stored_blocks(rng):
    data = make_random(rng, 300_000)  # incompressible -> stored blocks
    assert gzip_decompress_sequential(stored_only_compress(data)) == data


def test_fixed_blocks(rng):
    data = make_text(rng, 100_000)
    assert gzip_decompress_sequential(fixed_only_compress(data)) == data


def test_pigz_like_sync_flush(rng):
    data = make_text(rng, 300_000)
    assert gzip_decompress_sequential(pigz_like_compress(data, block_size=64 << 10)) == data


def test_crc_mismatch_detected(rng):
    comp = bytearray(gzip_bytes(make_text(rng, 10_000)))
    comp[-6] ^= 0xFF  # corrupt stored CRC32
    with pytest.raises(GzipFooterError):
        gzip_decompress_sequential(bytes(comp))


def test_raw_deflate(rng):
    data = make_text(rng, 120_000)
    raw = zlib.compress(data, 6)[2:-4]
    assert inflate_raw(raw) == data


def test_reserved_block_type_rejected():
    # final=1, type=11 (reserved): bits 1,1,1 LSB-first -> byte 0b00000111
    with pytest.raises(DeflateError):
        inflate_raw(b"\x07\x00\x00")


def _block_offsets(comp: bytes):
    br = BitReader(comp)
    parse_gzip_header(br)
    dec = DeflateChunkDecoder(comp)
    res = dec.decode_chunk(br.bit_pos, len(comp) * 8, window=b"")
    return res


@pytest.mark.parametrize("kind", ["text", "base64"])
def test_two_stage_equals_single_stage(rng, kind):
    """Core paper property: marker decode + replacement == known-window decode."""
    data = {"text": make_text, "base64": make_base64}[kind](rng, 400_000)
    comp = gzip_bytes(data, 6)
    full = _block_offsets(comp)
    assert len(full.blocks) >= 2, "need multiple blocks for this test"
    dec = DeflateChunkDecoder(comp)
    for blk in full.blocks[1:3]:
        window = data[max(0, blk.out_offset - WINDOW_SIZE) : blk.out_offset]
        single = dec.decode_chunk(blk.bit_offset, len(comp) * 8, window=window)
        marker = dec.decode_chunk(blk.bit_offset, len(comp) * 8, window=None)
        assert marker.marker_mode and not single.marker_mode
        resolved = replace_markers(marker.data, window)
        np.testing.assert_array_equal(resolved, single.data)
        truth = data[blk.out_offset : blk.out_offset + single.size]
        assert single.data.tobytes() == truth


def test_marker_values_name_window_positions(rng):
    data = make_text(rng, 600_000)
    comp = gzip_bytes(data, 6)
    full = _block_offsets(comp)
    assert len(full.blocks) >= 2, "test data must span multiple deflate blocks"
    blk = full.blocks[1]
    dec = DeflateChunkDecoder(comp)
    res = dec.decode_chunk(blk.bit_offset, len(comp) * 8, window=None)
    syms = res.data
    markers = syms[syms >= MARKER_BASE]
    if markers.size:  # every marker points into the 32 KiB window
        w = markers.astype(np.int64) - MARKER_BASE
        assert w.min() >= 0 and w.max() < WINDOW_SIZE
        # resolve and compare against the original stream
        window = data[max(0, blk.out_offset - WINDOW_SIZE) : blk.out_offset]
        out = replace_markers(syms, window)
        assert out.tobytes() == data[blk.out_offset : blk.out_offset + res.size]
        assert res.first_marker >= 0 and res.last_marker >= res.first_marker


def test_stop_condition_matches_next_chunk(rng):
    """Chunk end offsets must be decodable start offsets for the successor."""
    data = make_base64(rng, 600_000)
    comp = gzip_bytes(data, 6)
    br = BitReader(comp)
    parse_gzip_header(br)
    dec = DeflateChunkDecoder(comp)
    stop = br.bit_pos + 400_000 * 8 // 2
    first = dec.decode_chunk(br.bit_pos, stop, window=b"")
    assert first.end_bit >= stop or first.ended_at_eos
    if not first.ended_at_eos:
        second = dec.decode_chunk(first.end_bit, len(comp) * 8, window=None)
        window = first.data[-WINDOW_SIZE:].tobytes()
        resolved = replace_markers(second.data, window)
        combined = first.data.tobytes() + resolved.tobytes()
        assert combined == data[: len(combined)]


@settings(max_examples=20, deadline=None)
@given(blob=st.binary(min_size=0, max_size=5000), level=st.integers(min_value=0, max_value=9))
def test_property_roundtrip_any_bytes(blob, level):
    assert gzip_decompress_sequential(_gzip.compress(blob, compresslevel=level)) == blob


# -- block bodies handed to zlib once the window is known (paper §3.3) ------

def _ints(rng, n: int) -> bytes:
    """Small-delta little-endian integers: matches stay short-range, so
    markers vanish within a few blocks of a chunk's start."""
    return np.cumsum(rng.integers(0, 16, n // 4)).astype("<u4").tobytes()


def _text16(rng, n: int) -> bytes:
    """Space-separated words from 16: every match may copy a marker, so
    the decoder's conservative marker bound never falls behind."""
    words = [b"the", b"quick", b"brown", b"fox", b"jumps", b"over", b"lazy", b"dog",
             b"rapidgzip", b"parallel", b"deflate", b"window", b"chunk", b"prefetch",
             b"cache", b"marker"]
    idx = rng.integers(0, len(words), n // 4)
    return b" ".join(words[i] for i in idx)[:n]


def _blocks(comp: bytes, framing: str = "gzip"):
    """Every block of ``comp`` (one gzip member, or raw), decoded from its start."""
    start = 0
    if framing == "gzip":
        br = BitReader(comp)
        parse_gzip_header(br)
        start = br.bit_pos
    return DeflateChunkDecoder(comp, framing=framing).decode_chunk(start, None, window=b"").blocks


def _after(blocks, out_offset: int):
    return next(b for b in blocks if b.out_offset >= out_offset)


def _case_ints_early(rng):
    comp = gzip_bytes(_ints(rng, 400_000), 6)
    blocks = _blocks(comp)
    return dict(buf=comp, start=blocks[2].bit_offset, stop=blocks[7].bit_offset + 1), "zlib"


def _case_text_then_ints_late(rng):
    # Block 1 starts about 250 KB into the text; markers last through the
    # rest of it and vanish 32 KiB into the integers.
    data = _text16(rng, 450_000) + _ints(rng, 150_000)
    comp = gzip_bytes(data, 6)
    return dict(buf=comp, start=_blocks(comp)[1].bit_offset, stop=None), "zlib"


def _case_text_never(rng):
    comp = gzip_bytes(_text16(rng, 700_000), 6)
    return dict(buf=comp, start=_blocks(comp)[1].bit_offset, stop=None), "python"


def _case_window_empty(rng):
    data = _text16(rng, 60_000) + _ints(rng, 100_000) + make_random(rng, 40_000)
    comp = gzip_bytes(data, 6)
    br = BitReader(comp)
    parse_gzip_header(br)
    return dict(buf=comp, start=br.bit_pos, stop=None, window=b""), "zlib"


def _case_window_real(rng):
    data = _ints(rng, 300_000)
    comp = gzip_bytes(data, 6)
    blocks = _blocks(comp)
    blk = blocks[3]
    window = data[blk.out_offset - WINDOW_SIZE : blk.out_offset]
    return dict(buf=comp, start=blk.bit_offset, stop=blocks[6].bit_offset - 1, window=window), "zlib"


def _case_window_too_short(rng):
    # A match reaches before the window given: both decoders refuse it.
    data = _ints(rng, 200_000)
    comp = gzip_bytes(data, 6)
    blk = _blocks(comp)[2]
    return dict(buf=comp, start=blk.bit_offset, stop=None, window=b""), "raises"


def _case_stop_on_stored(rng):
    # Stored blocks follow stored blocks byte-aligned, 5 bits before their
    # canonical offset: the chunk ends on the canonical one.
    data = _ints(rng, 100_000) + make_random(rng, 300_000)
    comp = gzip_bytes(data, 6)
    blocks = _blocks(comp)
    stored = [b for b in blocks if b.block_type == 0 and b.bit_offset % 8 == 0]
    assert len(stored) >= 3 and canonical_stored_offset(stored[2].bit_offset) % 8 == 5
    return dict(buf=comp, start=_after(blocks, 60_000).bit_offset,
                stop=stored[2].bit_offset + 1), "zlib"


def _case_fixed_past_stop(rng):
    # Fixed blocks are never stop candidates: the chunk runs on through the
    # final block and the footer.
    comp = fixed_only_compress(_ints(rng, 300_000))
    blocks = _blocks(comp)
    assert all(b.block_type == 1 for b in blocks)
    return dict(buf=comp, start=blocks[1].bit_offset, stop=blocks[2].bit_offset), "zlib"


def _case_multi_member(rng):
    from repro.core.synth import multistream_gzip

    comp = multistream_gzip(_ints(rng, 400_000), 6, stream_size=90_000)
    return dict(buf=comp, start=_blocks(comp[: comp.index(b"\x1f\x8b", 10)])[1].bit_offset,
                stop=None), "zlib"


def _case_raw_stream(rng):
    c = zlib.compressobj(6, zlib.DEFLATED, -15)
    raw = c.compress(_ints(rng, 300_000)) + c.flush()
    blocks = _blocks(raw, "raw")
    return dict(buf=raw, start=blocks[1].bit_offset, stop=None, framing="raw"), "zlib"


def _case_max_out_mid_block(rng):
    comp = gzip_bytes(_ints(rng, 400_000), 6)
    return dict(buf=comp, start=_blocks(comp)[1].bit_offset, stop=None, max_out=150_001), "raises"


def _decode_outcome(buf, start, stop, window=None, max_out=None, framing="gzip"):
    try:
        return DeflateChunkDecoder(buf, framing=framing).decode_chunk(
            start, stop, window=window, max_out=max_out)
    except DeflateError as exc:
        return type(exc)


@pytest.mark.parametrize("case", [
    _case_ints_early, _case_text_then_ints_late, _case_text_never, _case_window_empty,
    _case_window_real, _case_window_too_short, _case_stop_on_stored, _case_fixed_past_stop,
    _case_multi_member, _case_raw_stream, _case_max_out_mid_block,
], ids=lambda f: f.__name__[len("_case_"):])
def test_zlib_block_bodies_equal_the_python_decoder(case, monkeypatch):
    """With zlib taking the block bodies from the first block whose window
    is known, every field a caller reads equals the pure-Python decode."""
    from repro.core import zlib_bridge

    assert zlib_bridge.libz() is not None
    args, expect = case(np.random.default_rng(0x16))
    native = _decode_outcome(**args)
    monkeypatch.setattr(zlib_bridge, "libz", lambda: None)
    python = _decode_outcome(**args)
    if expect == "raises":
        assert native is python is DeflateError
        return
    assert isinstance(native, DecodeResult) and isinstance(python, DecodeResult)
    for name in ("start_bit", "end_bit", "marker_mode", "blocks", "member_ends",
                 "member_starts", "first_marker", "last_marker", "ended_at_eos"):
        assert getattr(native, name) == getattr(python, name), name
    assert native.data.dtype == python.data.dtype
    np.testing.assert_array_equal(native.data, python.data)
    assert python.native_bytes == 0
    assert (native.native_bytes > 0) == (expect == "zlib")
    if expect == "zlib":
        # zlib decoded everything from the first block behind a known
        # (in marker mode: marker-free) 32 KiB on.
        switch = 0 if not native.marker_mode else next(
            b.out_offset for b in native.blocks
            if b.out_offset - 1 - native.last_marker >= WINDOW_SIZE)
        assert native.native_bytes == native.size - switch


def test_corrupt_block_after_the_switch_raises_deflate_error(rng):
    comp = bytearray(gzip_bytes(_ints(rng, 300_000), 6))
    blocks = _blocks(bytes(comp))
    start = blocks[1].bit_offset
    clean = DeflateChunkDecoder(bytes(comp)).decode_chunk(start, blocks[5].bit_offset, window=None)
    assert clean.native_bytes > 0  # zlib had taken over before block 5
    # Block 5's type bits (after its final bit) become the reserved 11.
    for bit in (blocks[5].bit_offset + 1, blocks[5].bit_offset + 2):
        comp[bit // 8] |= 1 << (bit % 8)
    with pytest.raises(DeflateError):
        DeflateChunkDecoder(bytes(comp)).decode_chunk(start, None, window=None)


@pytest.mark.parametrize("n, last_marker, known", [
    (32767, -1, False),  # no marker, but the window still reaches before the chunk
    (32768, -1, True),
    (32769, 0, True),    # the marker just left the 32 KiB before byte n
    (32769, 1, False),
    (40000, 40000 - 32768, False),
])
def test_window_is_known_once_32_kib_hold_no_marker(n, last_marker, known):
    from repro.core.deflate import _DecodeState

    state = _DecodeState(np.zeros(n, np.uint16), True, np.empty(0, np.uint8), 0, None)
    state.n, state.last_marker = n, last_marker
    assert state.window_known() is known
    window_mode = _DecodeState(np.zeros(n, np.uint8), False, np.empty(0, np.uint8), 0, None)
    assert window_mode.window_known()
