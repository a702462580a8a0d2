"""zlib for deflate data whose window is known (paper §1.3, §3.3).

Two entries, both for data whose 32 KiB LZ77 window is known:

* ``BlockInflater`` binds the system ``libz`` through ``ctypes`` and runs a
  raw inflate one deflate block at a time, entered at any bit offset
  (``inflatePrime``) with the window as its dictionary
  (``inflateSetDictionary``). The chunk decoder (``deflate.py``) hands it
  every block body once the 32 KiB before the block hold no marker (from
  the first block when the window is given) — the paper's fallback
  optimisation — and keeps every boundary decision itself. ``libz()`` returns None where the library cannot be loaded; the
  decoder then decodes every block in Python.
* ``zlib_inflate_at`` delegates a whole indexed chunk to the standard
  library's ``zlib`` — "more than twice as fast as the two-stage
  decompression" (paper §1.3). zlib's Python binding starts only at byte
  boundaries, so the compressed stream is re-aligned by a vectorized bit
  shift first; the window is primed via ``zdict`` on a raw-deflate
  decompressobj.
"""

from __future__ import annotations

import ctypes
import ctypes.util
import functools
import zlib
from typing import Optional, Tuple

import numpy as np

from .errors import DeflateError, EndOfStream

Z_OK = 0
Z_BUF_ERROR = -5
Z_BLOCK = 5
#: ``z_stream.data_type`` bit set when inflate stopped at a block's end
_AT_BLOCK_END = 128
#: inflate input is fed at most this many bytes at a time (``uInt``)
_MAX_FEED = 1 << 30


class _ZStream(ctypes.Structure):
    """zlib's ``z_stream`` (zlib.h), for ``ctypes``."""

    _fields_ = [
        ("next_in", ctypes.c_void_p),
        ("avail_in", ctypes.c_uint),
        ("total_in", ctypes.c_ulong),
        ("next_out", ctypes.c_void_p),
        ("avail_out", ctypes.c_uint),
        ("total_out", ctypes.c_ulong),
        ("msg", ctypes.c_char_p),
        ("state", ctypes.c_void_p),
        ("zalloc", ctypes.c_void_p),
        ("zfree", ctypes.c_void_p),
        ("opaque", ctypes.c_void_p),
        ("data_type", ctypes.c_int),
        ("adler", ctypes.c_ulong),
        ("reserved", ctypes.c_ulong),
    ]


def _declare(lib) -> None:
    """Declare the signatures ``BlockInflater`` calls; AttributeError if
    the library lacks one."""
    stream = ctypes.POINTER(_ZStream)
    lib.zlibVersion.argtypes, lib.zlibVersion.restype = [], ctypes.c_char_p
    lib.inflateInit2_.argtypes = [stream, ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
    lib.inflateSetDictionary.argtypes = [stream, ctypes.c_char_p, ctypes.c_uint]
    lib.inflatePrime.argtypes = [stream, ctypes.c_int, ctypes.c_int]
    lib.inflate.argtypes = [stream, ctypes.c_int]
    lib.inflateEnd.argtypes = [stream]
    for fn in (lib.inflateInit2_, lib.inflateSetDictionary, lib.inflatePrime,
               lib.inflate, lib.inflateEnd):
        fn.restype = ctypes.c_int


@functools.lru_cache(maxsize=None)
def libz():
    """The system zlib through ``ctypes``, or None where it cannot be loaded
    or lacks a function ``BlockInflater`` calls."""
    for name in ("libz.so.1", ctypes.util.find_library("z")):
        if not name:
            continue
        try:
            lib = ctypes.CDLL(name)
            _declare(lib)
        except (OSError, AttributeError):
            continue
        return lib
    return None


class BlockInflater:
    """A raw inflate stream over ``data`` (a uint8 array, kept referenced
    while zlib reads it), entered at ``bit_offset`` with ``window`` (at most
    32 KiB) as what precedes it. Each ``inflate_block`` call runs on to the
    end of the current deflate block or until its destination is full.
    ``close`` frees zlib's state; the owner calls it."""

    def __init__(self, lib, data: np.ndarray, bit_offset: int, window: bytes):
        self._lib = lib
        self._data = data
        self._strm = _ZStream()
        self._ref = ctypes.byref(self._strm)
        rc = lib.inflateInit2_(self._ref, -zlib.MAX_WBITS, lib.zlibVersion(),
                               ctypes.sizeof(_ZStream))
        if rc != Z_OK:
            raise DeflateError("zlib inflateInit2_ failed (%d)" % rc)
        self._open = True
        byte, bit = divmod(bit_offset, 8)
        try:
            if window:
                self._check(lib.inflateSetDictionary(self._ref, window, len(window)))
            if bit:
                # The first byte's high bits go in through inflatePrime,
                # which total_in does not count.
                self._check(lib.inflatePrime(self._ref, 8 - bit, int(data[byte]) >> bit))
                byte += 1
        except DeflateError:
            self.close()
            raise
        self._first_bit = 8 * byte
        self._next_byte = byte
        self._feed()

    def _check(self, rc: int) -> None:
        if rc != Z_OK:
            msg = self._strm.msg
            raise DeflateError("zlib: %s" % (msg.decode() if msg else "error %d" % rc))

    def _feed(self) -> None:
        n = min(self._data.shape[0] - self._next_byte, _MAX_FEED)
        self._strm.next_in = self._data.ctypes.data + self._next_byte
        self._strm.avail_in = n
        self._next_byte += n

    @property
    def bit_pos(self) -> int:
        """Absolute bit offset of the next bit zlib has not consumed: the
        bits fed less those still unused in the last byte taken."""
        strm = self._strm
        return self._first_bit + 8 * strm.total_in - (strm.data_type & 63)

    def inflate_block(self, dest: np.ndarray) -> Tuple[int, bool]:
        """Inflate into ``dest`` (a contiguous, writable uint8 array) until
        the current block ends or ``dest`` is full. Returns the bytes
        written and whether the block ended. Raises ``DeflateError`` on bad
        data, ``EndOfStream`` when the data runs out inside the block."""
        strm = self._strm
        strm.next_out = dest.ctypes.data
        strm.avail_out = dest.shape[0]
        while True:
            rc = self._lib.inflate(self._ref, Z_BLOCK)
            if rc != Z_BUF_ERROR:  # no progress is not an error here
                self._check(rc)
            ended = bool(strm.data_type & _AT_BLOCK_END)
            if ended or strm.avail_out == 0:
                return dest.shape[0] - strm.avail_out, ended
            if strm.avail_in:
                raise DeflateError("zlib stopped inside a block with input and room left")
            if self._next_byte >= self._data.shape[0]:
                raise EndOfStream("compressed data ended inside a block")
            self._feed()

    def close(self) -> None:
        if self._open:
            self._open = False
            self._lib.inflateEnd(self._ref)


def shift_bitstream(data, bit_offset: int, max_bytes: Optional[int] = None) -> bytes:
    """Re-pack ``data`` starting at ``bit_offset`` onto a byte boundary.

    Vectorized: each output byte pulls ``8-k`` low bits from one input byte
    and ``k`` bits from the next (deflate is LSB-first, so the shift moves
    toward the LSB).
    """
    byte, bit = divmod(bit_offset, 8)
    if max_bytes is None:
        end = len(data)
    else:
        end = min(len(data), byte + max_bytes + 1)
    at_eof = end >= len(data)
    if bit == 0:
        hi_end = end if max_bytes is None else min(byte + max_bytes, len(data))
        return bytes(data[byte:hi_end])
    arr = np.frombuffer(data, dtype=np.uint8, count=end - byte, offset=byte)
    if arr.shape[0] == 0:
        return b""
    lo = arr >> np.uint8(bit)
    hi = np.empty_like(arr)
    hi[:-1] = arr[1:] << np.uint8(8 - bit)
    hi[-1] = 0
    out = lo | hi
    if not at_eof:
        # The final byte is only partially determined without the next input
        # byte — emit fully-formed bytes only; the caller advances by the
        # returned length and re-reads the boundary byte.
        out = out[:-1]
    return out.tobytes()


def zlib_inflate_at(
    data,
    bit_offset: int,
    window: bytes,
    out_size: int,
    *,
    feed_bytes: int = 1 << 16,
    max_input_bytes: Optional[int] = None,
) -> bytes:
    """Inflate exactly ``out_size`` bytes starting at ``bit_offset``.

    The stream is fed incrementally so only O(out_size / ratio) input is
    bit-shifted, not the whole file tail.

    ``max_input_bytes`` must bound the chunk's compressed span when known:
    zlib eagerly parses the *next* block header even with no output space
    remaining, and a stored-block header does not survive the bit-shift
    realignment — truncating the input at the chunk boundary keeps zlib
    waiting for input instead of erroring on the successor's header.
    """
    if out_size == 0:
        return b""
    d = zlib.decompressobj(wbits=-zlib.MAX_WBITS, zdict=window)
    out = []
    produced = 0
    pos = bit_offset
    total_bits = len(data) * 8
    if max_input_bytes is not None:
        total_bits = min(total_bits, bit_offset + max_input_bytes * 8)
    while produced < out_size:
        if pos >= total_bits:
            raise DeflateError("compressed stream exhausted before chunk end")
        piece = shift_bitstream(data, pos, max_bytes=min(feed_bytes, (total_bits - pos) // 8 + 1))
        if max_input_bytes is not None and pos + len(piece) * 8 > total_bits:
            piece = piece[: max(1, (total_bits - pos) // 8)]
        pos += len(piece) * 8
        try:
            chunk = d.decompress(d.unconsumed_tail + piece, out_size - produced)
        except zlib.error as exc:
            raise DeflateError("zlib delegation failed: %s" % exc) from exc
        out.append(chunk)
        produced += len(chunk)
        if d.eof:
            # End of this deflate stream (gzip member boundary). A chunk can
            # span members; the caller's seek points are built so member
            # boundaries coincide with chunk boundaries or interior block
            # boundaries — restart a fresh raw stream after the footer is
            # not handled here; chunks with interior member ends use the
            # custom decoder instead.
            break
    result = b"".join(out)
    if len(result) < out_size:
        raise DeflateError(
            "zlib delegation produced %d of %d bytes" % (len(result), out_size)
        )
    return result
