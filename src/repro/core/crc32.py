"""Parallel CRC32: per-chunk CRCs merged with GF(2) combine.

The paper lists checksum verification as future work (§6); rapidgzip-JAX
implements it. Each chunk's CRC32 is computed independently on the thread
pool (``zlib.crc32`` or the Pallas lane kernel) and the per-chunk
values are merged sequentially with the O(log n) zlib ``crc32_combine``
matrix trick — the merge touches 32-bit state only, so the sequential part
of checksumming is negligible (same Amdahl argument as window propagation).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import numpy as np

_POLY = 0xEDB88320


def _gf2_matrix_times(mat: Sequence[int], vec: int) -> int:
    out = 0
    i = 0
    while vec:
        if vec & 1:
            out ^= mat[i]
        vec >>= 1
        i += 1
    return out


def _gf2_matrix_square(mat: Sequence[int]) -> List[int]:
    return [_gf2_matrix_times(mat, mat[i]) for i in range(32)]


@functools.lru_cache(maxsize=256)
def _zeros_operator(nbytes: int) -> Tuple[int, ...]:
    """GF(2) operator (32 column images) that appends ``nbytes`` zero bytes.

    Built by square-and-multiply from the one-zero-bit operator, as zlib's
    ``crc32_combine`` does, and cached: serving reads combine a handful of
    distinct lengths (member sizes, lane lengths) over and over.
    """
    op: List[int] = [_POLY] + [1 << (i - 1) for i in range(1, 32)]  # one bit
    for _ in range(3):
        op = _gf2_matrix_square(op)  # 2, 4, then 8 bits: one zero byte
    acc: Optional[List[int]] = None
    n = nbytes
    while n:
        if n & 1:
            acc = op if acc is None else [_gf2_matrix_times(op, c) for c in acc]
        n >>= 1
        if n:
            op = _gf2_matrix_square(op)
    return tuple(acc)


def crc32_combine(crc1: int, crc2: int, len2: int) -> int:
    """CRC32 of the concatenation of two blocks (zlib's crc32_combine)."""
    if len2 <= 0:
        return crc1 & 0xFFFFFFFF
    shifted = _gf2_matrix_times(_zeros_operator(len2), crc1 & 0xFFFFFFFF)
    return (shifted ^ crc2) & 0xFFFFFFFF


@functools.lru_cache(maxsize=64)
def _zeros_table(nbytes: int) -> np.ndarray:
    """``_zeros_operator(nbytes)`` as four 256-entry tables, one per byte of
    the CRC: the operator is linear, so its image of a CRC is the XOR of the
    images of the CRC's four bytes."""
    op = np.array(_zeros_operator(nbytes), np.uint32).reshape(4, 8)
    bits = ((np.arange(256)[:, None] >> np.arange(8)) & 1).astype(np.uint32)  # (256, 8)
    table = np.zeros((4, 256), np.uint32)
    for j in range(8):
        table ^= bits[:, j] * op[:, j : j + 1]
    return table


def combine_lanes(crcs: np.ndarray, lane_len: int) -> np.ndarray:
    """Fold each row of equal-length lane CRCs left to right, vectorized.

    ``crcs`` is ``(rows, lanes)`` with ``lanes`` a power of two and every
    lane ``lane_len`` bytes long; returns ``(rows,)`` uint32. A pairwise
    tree: level ``k`` merges neighbours of ``lane_len * 2**k`` bytes with
    one cached operator, applied a byte at a time by table. A lane holding 0
    folds in as the CRC of an empty string, so callers right-align short
    rows.
    """
    arr = np.asarray(crcs, np.uint32)
    if arr.shape[1] & (arr.shape[1] - 1):
        raise ValueError("lane count must be a power of two")
    n = lane_len
    while arr.shape[1] > 1:
        t = _zeros_table(n)
        left, right = arr[:, 0::2], arr[:, 1::2]
        arr = (t[0][left & 0xFF] ^ t[1][(left >> 8) & 0xFF]
               ^ t[2][(left >> 16) & 0xFF] ^ t[3][left >> 24] ^ right)
        n *= 2
    return arr[:, 0]


class RunningCRC:
    """Sequential CRC folding of per-chunk (crc, length) parts."""

    def __init__(self) -> None:
        self.crc = 0
        self.length = 0

    def add(self, crc: int, length: int) -> None:
        self.crc = crc32_combine(self.crc, crc, length)
        self.length += length

    def reset(self) -> None:
        self.crc = 0
        self.length = 0


def combine_parts(parts: Sequence[Tuple[int, int]]) -> int:
    """Fold [(crc, len), ...] left to right."""
    acc = RunningCRC()
    for crc, length in parts:
        acc.add(crc, length)
    return acc.crc
