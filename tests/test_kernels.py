"""Device kernel validation against pure-jnp oracles, with shape/dtype
sweeps per the repo convention. Pallas kernels run with interpret=True
here; tests/test_tpu_compile.py compiles them for a TPU v5e."""

import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import crc32_parallel, marker_replace, precode_candidates
from repro.kernels.crc32 import (
    BLOCK_WORDS,
    SEG_COLS,
    SEG_ROWS,
    crc32_segments_batched,
)
from repro.kernels.marker_replace import (
    TILE,
    TILE_COLS,
    TILE_ROWS,
    marker_replace_tiles_multi,
)
from repro.kernels.precode_check import BLOCK, HALO, ROWS, precode_check_blocks
from repro.kernels.ref import (
    crc32_segments_batched_ref,
    make_replacement_table,
    marker_replace_multi_ref,
    marker_replace_ref,
    precode_check_ref,
)
from repro.core.block_finder import scan_dynamic_candidates
from repro.core.markers import replace_markers

from conftest import make_random, make_text

pytestmark = pytest.mark.kernels


# ---------------------------------------------------------------------------
# marker_replace
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_tiles", [1, 2, 5])
def test_marker_replace_kernel_vs_ref(rng, n_tiles):
    window = rng.integers(0, 256, 32768, dtype=np.uint8)
    table = jnp.asarray(make_replacement_table(window))
    syms = rng.integers(0, 256 + 32768, (n_tiles, TILE_ROWS, TILE_COLS), dtype=np.int64)
    tiles = jnp.asarray(syms.astype(np.int32))
    # one window: a table stack of one, every tile selecting it
    out_kernel = marker_replace_tiles_multi(
        tiles, table[None], jnp.zeros(n_tiles, jnp.int32)
    )
    out_ref = marker_replace_ref(tiles, table)
    np.testing.assert_array_equal(np.asarray(out_kernel), np.asarray(out_ref))


@pytest.mark.parametrize("n", [0, 1, 1000, TILE, TILE + 17])
def test_marker_replace_op_shapes(rng, n):
    window = rng.integers(0, 256, 32768, dtype=np.uint8).tobytes()
    syms = rng.integers(0, 256 + 32768, n, dtype=np.uint16)
    out = marker_replace(syms, window)
    host = replace_markers(syms, window)
    np.testing.assert_array_equal(out, host)


@settings(max_examples=10, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=3000),
    wlen=st.integers(min_value=0, max_value=32768),
)
def test_marker_replace_property(n, wlen):
    rng = np.random.default_rng(n * 7919 + wlen)
    window = rng.integers(0, 256, wlen, dtype=np.uint8).tobytes()
    # markers may only reference the defined (right-aligned) window region
    lo = 256 + (32768 - wlen)
    lits = rng.integers(0, 256, n, dtype=np.uint16)
    marks = rng.integers(lo, 256 + 32768, n, dtype=np.uint16) if wlen else lits
    pick = rng.integers(0, 2, n, dtype=np.uint16)
    syms = np.where(pick == 1, marks, lits).astype(np.uint16)
    np.testing.assert_array_equal(marker_replace(syms, window), replace_markers(syms, window))


@pytest.mark.parametrize("n_tiles,n_tables", [(1, 1), (4, 2), (6, 4)])
def test_marker_replace_multi_kernel_vs_ref(rng, n_tiles, n_tables):
    """Batched multi-window kernel: per-tile table select matches the oracle
    and the single-table oracle applied table by table."""
    tables_np = np.stack([
        make_replacement_table(rng.integers(0, 256, 32768, dtype=np.uint8))
        for _ in range(n_tables)
    ])
    tables = jnp.asarray(tables_np)
    syms = jnp.asarray(
        rng.integers(0, 256 + 32768, (n_tiles, TILE_ROWS, TILE_COLS), dtype=np.int64)
        .astype(np.int32)
    )
    tids_np = rng.integers(0, n_tables, n_tiles, dtype=np.int64).astype(np.int32)
    tids = jnp.asarray(tids_np)
    out = np.asarray(marker_replace_tiles_multi(syms, tables, tids))
    ref = np.asarray(marker_replace_multi_ref(syms, tables, tids))
    np.testing.assert_array_equal(out, ref)
    for t in range(n_tables):
        sel = tids_np == t
        if not sel.any():
            continue
        single = np.asarray(marker_replace_ref(syms[sel], tables[t]))
        np.testing.assert_array_equal(out[sel], single)


# ---------------------------------------------------------------------------
# precode_check
# ---------------------------------------------------------------------------

def test_precode_kernel_vs_ref(rng):
    """Two grid steps of ROWS rows: halos run on into the next row, across
    the block boundary, and off the end (zeros)."""
    bits = rng.integers(0, 2, (2 * ROWS, BLOCK), dtype=np.int64).astype(np.int32)
    out_kernel = np.asarray(precode_check_blocks(jnp.asarray(bits), interpret=True))
    flat = np.concatenate([bits.reshape(-1), np.zeros(HALO, np.int32)])
    ref = np.asarray(precode_check_ref(jnp.asarray(flat)))
    np.testing.assert_array_equal(out_kernel.reshape(-1), ref)


@pytest.mark.parametrize("nbytes", [1000, 40_000])
def test_precode_candidates_match_host_finder(rng, nbytes):
    blob = make_random(rng, nbytes)
    end = nbytes * 8 - HALO
    kern = set(precode_candidates(blob, 0, end).tolist())
    host = set(
        c for c in scan_dynamic_candidates(blob, 0, nbytes * 8, full_validation=False) if c < end
    )
    assert kern == host


def test_precode_candidates_find_real_blocks(rng):
    import gzip as _gzip

    data = make_text(rng, 300_000)
    comp = _gzip.compress(data, 6)
    from repro.core import BitReader, DeflateChunkDecoder, parse_gzip_header

    br = BitReader(comp)
    parse_gzip_header(br)
    res = DeflateChunkDecoder(comp).decode_chunk(br.bit_pos, len(comp) * 8, window=b"")
    dynamic = [b.bit_offset for b in res.blocks if b.block_type == 2 and not b.is_final]
    cands = set(precode_candidates(comp).tolist())
    assert all(b in cands for b in dynamic)


# ---------------------------------------------------------------------------
# crc32
# ---------------------------------------------------------------------------

def _lane_bytes(words: np.ndarray) -> bytes:
    """The bytes of one lane: its words, little-endian, in order."""
    return words.astype("<u4").tobytes()


def _random_words(rng, shape) -> np.ndarray:
    return rng.integers(0, 1 << 32, shape, dtype=np.uint64).astype(np.uint32).view(np.int32)


@pytest.mark.parametrize("seg_words", [1, 7, 64])
def test_crc32_kernel_vs_ref(rng, seg_words):
    data = _random_words(rng, (1, seg_words, SEG_ROWS, SEG_COLS))
    out_kernel = np.asarray(crc32_segments_batched(jnp.asarray(data), interpret=True))
    out_ref = np.asarray(crc32_segments_batched_ref(jnp.asarray(data)))
    np.testing.assert_array_equal(out_kernel, out_ref)
    # spot-check lane (0,0) against zlib
    seg = _lane_bytes(data[0, :, 0, 0])
    assert (int(out_kernel[0, 0, 0]) & 0xFFFFFFFF) == (zlib.crc32(seg) & 0xFFFFFFFF)


@pytest.mark.parametrize("n", [0, 1, 1023, 4096, 100_001])
def test_crc32_parallel_matches_zlib(rng, n):
    blob = make_random(rng, n)
    assert crc32_parallel(blob) == (zlib.crc32(blob) & 0xFFFFFFFF)


@pytest.mark.parametrize(
    "batch,seg_words", [(1, 1), (2, 7), (4, 16), (2, 2 * BLOCK_WORDS)]
)
def test_crc32_batched_kernel_vs_ref(rng, batch, seg_words):
    """Batch rows are independent; segments longer than one block carry the
    CRC state across the sequential grid axis."""
    data = _random_words(rng, (batch, seg_words, SEG_ROWS, SEG_COLS))
    out = np.asarray(crc32_segments_batched(jnp.asarray(data), interpret=True))
    ref = np.asarray(crc32_segments_batched_ref(jnp.asarray(data)))
    np.testing.assert_array_equal(out, ref)
    # each batch row must equal a batch of one on the same lanes
    for b in range(batch):
        single = np.asarray(
            crc32_segments_batched(jnp.asarray(data[b : b + 1]), interpret=True)
        )
        np.testing.assert_array_equal(out[b], single[0])
    # spot-check one lane against zlib
    seg = _lane_bytes(data[-1, :, 0, 0])
    assert (int(out[-1, 0, 0]) & 0xFFFFFFFF) == (zlib.crc32(seg) & 0xFFFFFFFF)
