"""Host-side wrappers around the device kernels.

These are the per-call entry points outside the serving engine: they pad
and reshape host data into kernel tiling, dispatch on the default device,
and restore shapes/dtypes. For cross-chunk batching on the serving hot
path, see ``kernels/engine.py``; both use the same kernels.

Whether a Pallas kernel is interpreted is decided per dispatch from the
device it runs on (``interpret_on``): interpreted on the CPU backend, which
is how the tests run, and compiled on a TPU — never interpreted there.
"""

from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from .crc32 import (
    N_SEGMENTS,
    crc32_lanes,
    finish_parts,
    lane_words,
    pack_parts,
)
from .marker_replace import TILE, TILE_COLS, TILE_ROWS, marker_replace_tiles_multi
from .precode_check import BLOCK, HALO, ROWS, precode_check_blocks
from .ref import make_replacement_table


def interpret_on(device: jax.Device) -> bool:
    """Pallas interpret mode: only on the CPU backend, never on a TPU."""
    return device.platform == "cpu"


# -- marker replacement -------------------------------------------------------

def marker_replace(symbols: np.ndarray, window: Optional[bytes]) -> np.ndarray:
    """Resolve a uint16 marker stream to bytes on the device.

    One chunk is a batch of its tiles against a stack of one table.
    """
    device = jax.devices()[0]
    n = symbols.shape[0]
    table = make_replacement_table(np.frombuffer(window or b"", np.uint8))
    n_tiles = max(1, -(-n // TILE))
    padded = np.zeros(n_tiles * TILE, dtype=np.int32)
    padded[:n] = symbols
    out = marker_replace_tiles_multi(
        jax.device_put(padded.reshape(n_tiles, TILE_ROWS, TILE_COLS), device),
        jax.device_put(table[None], device),
        jax.device_put(np.zeros(n_tiles, np.int32), device),
    )
    return np.asarray(out).reshape(-1)[:n].astype(np.uint8)


# -- block-finder precheck ----------------------------------------------------

def precode_candidates(data: bytes, start_bit: int = 0, end_bit: Optional[int] = None) -> np.ndarray:
    """Bit offsets passing finder steps 1-4, computed on-device.

    Returns absolute candidate bit offsets; callers confirm with the strict
    host-side header parse (steps 5-7), exactly like the production finder.
    """
    total_bits = len(data) * 8
    if end_bit is None:
        end_bit = total_bits - HALO
    end_bit = min(end_bit, total_bits - HALO)
    if end_bit <= start_bit:
        return np.empty(0, dtype=np.int64)
    n = end_bit - start_bit

    first_byte = start_bit // 8
    need_bits = (start_bit - first_byte * 8) + n + HALO
    need_bytes = -(-need_bits // 8)
    raw = np.frombuffer(data, np.uint8, count=min(need_bytes, len(data) - first_byte), offset=first_byte)
    bits = np.unpackbits(raw, bitorder="little").astype(np.int32)
    rel = start_bit - first_byte * 8

    # Rows cover every offset plus its halo; bits past the last row read 0.
    n_rows = -(-(n + HALO) // BLOCK)
    n_rows = -(-n_rows // ROWS) * ROWS
    padded = np.zeros(n_rows * BLOCK, dtype=np.int32)
    usable = min(bits.shape[0] - rel, padded.shape[0])
    padded[:usable] = bits[rel : rel + usable]
    device = jax.devices()[0]
    blocks = jax.device_put(padded.reshape(n_rows, BLOCK), device)
    mask = precode_check_blocks(blocks, interpret=interpret_on(device))
    mask = np.asarray(mask).reshape(-1)[:n]
    return np.nonzero(mask)[0].astype(np.int64) + start_bit


# -- crc32 --------------------------------------------------------------------

def crc32_parallel(data: bytes) -> int:
    """CRC32 of ``data`` via 1024 parallel lanes + GF(2) combine."""
    if not data:
        return 0
    device = jax.devices()[0]
    seg_words = lane_words(len(data))
    stage = np.zeros((1, N_SEGMENTS, seg_words), dtype=np.int32)
    pack_parts(stage[0], [data])
    lanes = crc32_lanes(
        jax.device_put(stage, device), interpret=interpret_on(device)
    )
    return finish_parts(np.asarray(lanes)[0], [len(data)], seg_words)[0]
