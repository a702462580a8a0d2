"""BGZF as htslib's ``bgzip`` writes it (``encoders/bgzf.py``: 0xFF00-byte
blocks, the empty EOF block), with the standard library's streaming gzip
reader as the plain reference decoder. ``gzip.GzipFile`` reads the members
one after another from a buffer, in time linear in the archive;
``gzip.decompress`` copies the rest of the archive once per member, which
at thousands of members takes longer than a run may.

A deployment of this format guarantees that every member's CRC32 and ISIZE
are checked against its own trailer before any of its bytes is served.
``encode`` first holds the program to that: it reads small archives with
one damaged trailer each through the program's reader, and a program that
serves one cannot run the deployment, so the run stops at set-up with a
non-zero exit and prints no result."""

from __future__ import annotations

import gzip
import importlib.util
import io
import os
import struct

_spec = importlib.util.spec_from_file_location(
    "chipbench_encoders_bgzf_writer", os.path.join(os.path.dirname(os.path.abspath(__file__)), "bgzf.py"))
_writer = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_writer)

#: Trailer fields a probe damages: (name, offset from the member's end).
TRAILER_FIELDS = (("CRC32", 8), ("ISIZE", 4))


def damaged_members_served() -> list:
    """The trailer fields whose damage in a member's trailer the program's
    reader does not catch: empty when every damaged member raises."""
    from repro.core import FormatError, ParallelGzipReader

    data = bytes(range(256)) * (3 * _writer.BLOCK // 256)  # three members
    archive = _writer.encode(data, level=1)
    end = struct.unpack_from("<H", archive, 16)[0] + 1  # the first member's BSIZE + 1
    missed = []
    for name, back in TRAILER_FIELDS:
        bad = bytearray(archive)
        bad[end - back] ^= 0xFF
        try:
            with ParallelGzipReader(bytes(bad), parallelization=1) as r:
                r.read()
        except FormatError:
            continue
        missed.append(name)
    return missed


def encode(data: bytes, **options) -> bytes:
    missed = damaged_members_served()
    if missed:
        raise SystemExit("bgzip: the program served a BGZF member whose %s does not match its trailer; "
                         "a deployment of this format checks every member before serving it, so this "
                         "program cannot run it" % " or ".join(missed))
    return _writer.encode(data, **options)


def decode(archive: bytes) -> bytes:
    with gzip.GzipFile(fileobj=io.BytesIO(archive)) as f:
        return f.read()
