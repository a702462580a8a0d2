"""Plain references for every device kernel: pure-jnp oracles (allclose
targets in tests), and XXH64 in Python integers."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

MARKER_BASE = 256
WINDOW_SIZE = 32768
TABLE_SIZE = MARKER_BASE + WINDOW_SIZE  # 33 024


# -- marker replacement -------------------------------------------------------

def marker_replace_ref(syms: jax.Array, table: jax.Array) -> jax.Array:
    """out = table[syms] (identity for literals, window gather for markers)."""
    return jnp.take(table, syms, axis=0)


def marker_replace_multi_ref(
    syms: jax.Array, tables: jax.Array, tile_tables: jax.Array
) -> jax.Array:
    """Oracle for the batched multi-window kernel: per-tile table select.

    syms: (n_tiles, R, C) int32; tables: (n_tables, TABLE_SIZE) int32;
    tile_tables: (n_tiles,) int32.
    """
    per_tile = jnp.take(tables, tile_tables, axis=0)  # (n_tiles, TABLE_SIZE)
    return jnp.take_along_axis(
        per_tile[:, :, None], syms.reshape(syms.shape[0], -1, 1), axis=1
    ).reshape(syms.shape)


def make_replacement_table(window: np.ndarray) -> np.ndarray:
    """int32 replacement table from a (possibly short) window."""
    table = np.empty(TABLE_SIZE, dtype=np.int32)
    table[:MARKER_BASE] = np.arange(MARKER_BASE)
    padded = np.zeros(WINDOW_SIZE, dtype=np.int32)
    w = np.asarray(window, dtype=np.int32)[-WINDOW_SIZE:]
    padded[WINDOW_SIZE - w.shape[0] :] = w
    table[MARKER_BASE:] = padded
    return table


# -- precode / block-finder precheck ------------------------------------------

def precode_check_ref(bits: jax.Array) -> jax.Array:
    """Candidate mask over a flat int32 0/1 bit plane (halo included).

    bits: (n,) with n >= offsets + 74; returns (n - 74,) int32 mask.
    """
    n = bits.shape[0] - 74

    def field(at, width):
        out = jax.lax.dynamic_slice_in_dim(bits, at, n)
        for j in range(1, width):
            out = out | (jax.lax.dynamic_slice_in_dim(bits, at + j, n) << j)
        return out

    b0 = jax.lax.dynamic_slice_in_dim(bits, 0, n)
    b1 = jax.lax.dynamic_slice_in_dim(bits, 1, n)
    b2 = jax.lax.dynamic_slice_in_dim(bits, 2, n)
    ok = (b0 == 0) & (b1 == 0) & (b2 == 1)
    ok &= field(3, 5) < 30
    n_codes = field(13, 4) + 4
    kraft = jnp.zeros((n,), jnp.int32)
    for k in range(19):
        cl = field(17 + 3 * k, 3)
        active = (k < n_codes) & (cl > 0)
        kraft = kraft + jnp.where(active, jax.lax.shift_right_logical(jnp.int32(128), cl), 0)
    ok &= kraft == 128
    return ok.astype(jnp.int32)


# -- crc32 --------------------------------------------------------------------

def make_crc_table() -> np.ndarray:
    """Standard reflected CRC-32 (poly 0xEDB88320) byte table as int32."""
    table = np.empty(256, dtype=np.uint32)
    for i in range(256):
        c = np.uint32(i)
        for _ in range(8):
            c = (c >> np.uint32(1)) ^ (np.uint32(0xEDB88320) * (c & np.uint32(1)))
        table[i] = c
    return table.view(np.int32)


def crc32_segments_batched_ref(data: jax.Array) -> jax.Array:
    """Oracle for the batched CRC kernel, table-driven a byte at a time.

    data: (B, W, R, C) int32, word ``w`` of a lane holding its bytes
    ``[4w, 4w + 4)`` little-endian; returns (B, R, C) lane CRCs.
    """
    table = jnp.asarray(make_crc_table())

    def step(crc, word):
        for k in range(4):
            byte = jax.lax.shift_right_logical(word, 8 * k) & 0xFF
            idx = (crc ^ byte) & 0xFF
            crc = jax.lax.shift_right_logical(crc, 8) ^ jnp.take(table, idx, axis=0)
        return crc, None

    init = jnp.full((data.shape[0],) + data.shape[2:], jnp.int32(-1))
    crc, _ = jax.lax.scan(step, init, jnp.moveaxis(data, 1, 0))
    return ~crc


# -- xxh64 --------------------------------------------------------------------
# XXH64 as its specification writes it (github.com/Cyan4973/xxHash,
# doc/xxhash_spec.md), in Python integers masked to 64 bits.

XXH_P1 = 0x9E3779B185EBCA87
XXH_P2 = 0xC2B2AE3D27D4EB4F
XXH_P3 = 0x165667B19E3779F9
XXH_P4 = 0x85EBCA77C2B2AE63
XXH_P5 = 0x27D4EB2F165667C5
_M64 = (1 << 64) - 1


def _rotl64(x: int, r: int) -> int:
    return ((x << r) | (x >> (64 - r))) & _M64


def xxh64_round(acc: int, lane: int) -> int:
    """One accumulator step over one little-endian 8-byte word."""
    return _rotl64((acc + lane * XXH_P2) & _M64, 31) * XXH_P1 & _M64


def xxh64_init(seed: int = 0) -> list:
    """The four accumulators before the first 32-byte stripe."""
    return [(seed + XXH_P1 + XXH_P2) & _M64, (seed + XXH_P2) & _M64,
            seed & _M64, (seed - XXH_P1) & _M64]


def xxh64_finish(acc: list, data: bytes, seed: int = 0) -> int:
    """XXH64 of ``data`` from its four accumulators after its
    ``len(data) // 32`` whole stripes: the merge, the length, the tail of
    fewer than 32 bytes (8, 4, then 1 at a time) and the avalanche."""
    n = len(data)
    p = n - n % 32
    if n >= 32:
        h = (_rotl64(acc[0], 1) + _rotl64(acc[1], 7) + _rotl64(acc[2], 12)
             + _rotl64(acc[3], 18)) & _M64
        for v in acc:
            h = ((h ^ xxh64_round(0, v)) * XXH_P1 + XXH_P4) & _M64
    else:
        h = (seed + XXH_P5) & _M64
    h = (h + n) & _M64
    while p + 8 <= n:
        h ^= xxh64_round(0, int.from_bytes(data[p : p + 8], "little"))
        h = (_rotl64(h, 27) * XXH_P1 + XXH_P4) & _M64
        p += 8
    if p + 4 <= n:
        h ^= int.from_bytes(data[p : p + 4], "little") * XXH_P1 & _M64
        h = (_rotl64(h, 23) * XXH_P2 + XXH_P3) & _M64
        p += 4
    while p < n:
        h ^= data[p] * XXH_P5 & _M64
        h = _rotl64(h, 11) * XXH_P1 & _M64
        p += 1
    h ^= h >> 33
    h = h * XXH_P2 & _M64
    h ^= h >> 29
    h = h * XXH_P3 & _M64
    return h ^ (h >> 32)


def xxh64(data: bytes, seed: int = 0) -> int:
    """XXH64 of ``data``: every whole 32-byte stripe steps the four
    accumulators, one 8-byte word each, then ``xxh64_finish``."""
    data = bytes(data)
    acc = xxh64_init(seed)
    for p in range(0, len(data) - len(data) % 32, 32):
        for j in range(4):
            acc[j] = xxh64_round(acc[j], int.from_bytes(data[p + 8 * j : p + 8 * j + 8], "little"))
    return xxh64_finish(acc, data, seed)
