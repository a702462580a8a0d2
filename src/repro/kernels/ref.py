"""Pure-jnp oracles for every Pallas kernel (allclose targets in tests)."""

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
