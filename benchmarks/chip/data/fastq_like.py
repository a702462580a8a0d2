"""FASTQ reads (Illumina-style, 100 bp, as in pugz, arXiv:1905.07224),
exactly ``n`` bytes, vectorised: one fixed-width record per row.

Same record layout as ``DataGen.fastq_like`` in ``benchmarks/common.py``
(``@id`` / sequence / ``+`` / quality), whose per-record Python loop took most
of a 256 MiB set-up; ids here are zero-padded to a fixed width.
"""

from __future__ import annotations

import numpy as np

READ_LEN = 100
ID_DIGITS = 10
PREFIX = b"@SRR0000."


def generate(rng: np.random.Generator, n: int) -> bytes:
    head = len(PREFIX) + ID_DIGITS + 1
    rec = head + READ_LEN + 3 + READ_LEN + 1  # header, seq, "\n+\n", qual, "\n"
    rows = -(-n // rec)
    out = np.empty((rows, rec), np.uint8)
    out[:, : len(PREFIX)] = np.frombuffer(PREFIX, np.uint8)
    ids = np.arange(rows, dtype=np.int64)
    for d in range(ID_DIGITS):  # most significant digit first
        out[:, len(PREFIX) + d] = 48 + (ids // 10 ** (ID_DIGITS - 1 - d)) % 10
    out[:, head - 1] = 10
    seq = slice(head, head + READ_LEN)
    out[:, seq] = np.frombuffer(b"ACGT", np.uint8)[rng.integers(0, 4, (rows, READ_LEN))]
    out[:, head + READ_LEN : head + READ_LEN + 3] = np.frombuffer(b"\n+\n", np.uint8)
    qual = slice(head + READ_LEN + 3, rec - 1)
    out[:, qual] = rng.integers(33, 74, (rows, READ_LEN), dtype=np.uint8)
    out[:, rec - 1] = 10
    return out.tobytes()[:n]
