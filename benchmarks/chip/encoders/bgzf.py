"""BGZF (SAM/BAM specification section 4.1): independent gzip members of at
most 64 KiB, each with a ``BC`` extra field giving its size, ending with the
empty EOF block. Blocks are compressed in a thread pool (zlib releases the
GIL), so 256 MiB takes seconds, not a minute. The reference decoder is the
standard library's: BGZF is valid multi-member gzip."""

from __future__ import annotations

import gzip
import os
import struct
import zlib
from concurrent.futures import ThreadPoolExecutor

#: Uncompressed bytes per block, as htslib's bgzip writes them.
BLOCK = 0xFF00
EOF_BLOCK = bytes.fromhex("1f8b08040000000000ff0600424302001b0003000000000000000000")


def _block(data: bytes, level: int) -> bytes:
    c = zlib.compressobj(level, zlib.DEFLATED, -15)
    body = c.compress(data) + c.flush()
    header = struct.pack("<BBBBIBBHBBHH", 0x1F, 0x8B, 8, 4, 0, 0, 0xFF, 6,
                         ord("B"), ord("C"), 2, len(body) + 25)
    return header + body + struct.pack("<II", zlib.crc32(data), len(data))


def encode(data: bytes, *, level: int) -> bytes:
    view = memoryview(data)
    blocks = [view[i: i + BLOCK] for i in range(0, len(data), BLOCK)]
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        return b"".join(pool.map(lambda b: _block(bytes(b), level), blocks)) + EOF_BLOCK


def decode(archive: bytes) -> bytes:
    return gzip.decompress(archive)
