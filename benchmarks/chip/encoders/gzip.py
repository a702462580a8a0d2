"""Single-member gzip, as ``gzip -<level>`` writes it (RFC 1952), and its
plain reference decoder, the standard library's."""

from __future__ import annotations

import gzip


def encode(data: bytes, *, level: int) -> bytes:
    return gzip.compress(data, compresslevel=level, mtime=0)


def decode(archive: bytes) -> bytes:
    return gzip.decompress(archive)
