"""The zstd seekable format (facebook/zstd, contrib/seekable_format): one
independent zstd frame per ``frame_bytes`` of input, then the seek table, a
skippable frame (magic 0x184D2A5E) of one ``(Compressed_Size,
Decompressed_Size[, Checksum])`` entry per frame and the
``Seek_Table_Footer`` (frame count, ``Seek_Table_Descriptor``,
0x8F92EAB1). With ``checksums`` the descriptor's ``Checksum_Flag`` (bit 7)
is set and each entry holds the low 32 bits of its frame's XXH64; the
frames carry no ``Content_Checksum`` of their own. Frames are compressed by
``zstandard`` (libzstd) in a thread pool. Each checksum comes from libzstd
too: a frame of the same bytes compressed at the fastest level with a
content checksum ends in it.

A deployment of this format guarantees that every frame's XXH64 is checked
against the seek table before any of its bytes is served. ``encode`` first
holds the program to that: it reads a small archive whose seek table has
one damaged checksum through the program's reader, and a program that
serves it cannot run the deployment, so the run stops at set-up with a
non-zero exit and prints no result.

The plain reference decoder (``decode``) imports nothing of the program: it
walks the seek table, decompresses frame by frame with ``zstandard`` and
checks each frame's size and checksum against its entry."""

from __future__ import annotations

import os
import struct
from concurrent.futures import ThreadPoolExecutor

import zstandard

SKIPPABLE_MAGIC = 0x184D2A5E
SEEKABLE_MAGIC = 0x8F92EAB1
CHECKSUM_FLAG = 0x80
#: libzstd's fastest level (ZSTD_minCLevel): only its content checksum is used.
FASTEST_LEVEL = -(1 << 17)


def checksum(data: bytes) -> int:
    """The low 32 bits of XXH64 (seed 0) of ``data``, from libzstd."""
    frame = zstandard.ZstdCompressor(level=FASTEST_LEVEL, write_checksum=True,
                                     write_content_size=False).compress(data)
    return int.from_bytes(frame[-4:], "little")


def _frame(data: bytes, level: int, checksums: bool):
    body = zstandard.ZstdCompressor(level=level, write_checksum=False).compress(data)
    return body, (checksum(data) if checksums else None)


def write(data: bytes, *, level: int, frame_bytes: int, checksums: bool) -> bytes:
    view = memoryview(data)
    pieces = [bytes(view[i: i + frame_bytes]) for i in range(0, len(data), frame_bytes)]
    with ThreadPoolExecutor(min(8, os.cpu_count() or 1)) as pool:
        frames = list(pool.map(lambda p: _frame(p, level, checksums), pieces))
    entry = "<III" if checksums else "<II"
    table = b"".join(struct.pack(entry, len(body), len(p), *([c] if checksums else []))
                     for (body, c), p in zip(frames, pieces))
    table += struct.pack("<IBI", len(frames), CHECKSUM_FLAG if checksums else 0, SEEKABLE_MAGIC)
    return (b"".join(body for body, _ in frames)
            + struct.pack("<II", SKIPPABLE_MAGIC, len(table)) + table)


def damaged_checksum_served() -> bool:
    """Whether the program's reader serves a three-frame archive whose seek
    table holds a wrong checksum for its second frame."""
    from repro.core import FormatError, ParallelGzipReader

    data = bytes(range(256)) * 3 * 64
    archive = bytearray(write(data, level=1, frame_bytes=len(data) // 3, checksums=True))
    second = len(archive) - 9 - 2 * 12  # the second entry; its checksum is 8 bytes on
    archive[second + 8] ^= 0xFF
    try:
        with ParallelGzipReader(bytes(archive), parallelization=1) as r:
            r.read()
    except FormatError:
        return False
    return True


def encode(data: bytes, *, level: int, frame_bytes: int, checksums: bool) -> bytes:
    if damaged_checksum_served():
        raise SystemExit("zstd_seekable: the program served a frame whose XXH64 does not match its "
                         "seek-table checksum; a deployment of this format checks every frame "
                         "before serving it, so this program cannot run it")
    return write(data, level=level, frame_bytes=frame_bytes, checksums=checksums)


def decode(archive: bytes) -> bytes:
    n_frames, descriptor, magic = struct.unpack_from("<IBI", archive, len(archive) - 9)
    if magic != SEEKABLE_MAGIC:
        raise ValueError("no seek table")
    entry = 12 if descriptor & CHECKSUM_FLAG else 8
    table = len(archive) - 9 - n_frames * entry
    out, offset = [], 0
    for i in range(n_frames):
        comp, dec = struct.unpack_from("<II", archive, table + i * entry)
        body = zstandard.ZstdDecompressor().decompressobj().decompress(archive[offset: offset + comp])
        if len(body) != dec:
            raise ValueError("frame %d decompresses to %d bytes, its entry says %d" % (i, len(body), dec))
        if entry == 12 and checksum(body) != struct.unpack_from("<I", archive, table + i * entry + 8)[0]:
            raise ValueError("frame %d does not match its seek-table checksum" % i)
        out.append(body)
        offset += comp
    if offset != table - 8:
        raise ValueError("the frames end at %d, the seek table starts at %d" % (offset, table - 8))
    return b"".join(out)
