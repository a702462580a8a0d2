"""Pallas TPU kernel: parallel CRC32 (paper §6 future work, implemented here).

CRC32 is bit-serial per byte, but splits perfectly: each of 1024 lanes CRCs
its own ``seg_len``-byte segment and the lane CRCs are folded on the host
with the GF(2) combine (``core/crc32.combine_lanes``).

Layout: a request's segments are packed four bytes to a little-endian int32
word, words-major: ``(batch, seg_words, 8, 128)``. One ``fori_loop`` step
reads one whole ``(8, 128)`` vreg — word ``w`` of all 1024 lanes — and
advances every lane's CRC state by 32 bits with the table-free bitwise
update ``crc = (crc >>> 1) ^ (0xEDB88320 & -(crc & 1))``: pure VPU work, no
lookup table and no gather (Mosaic lowers no general gather from VMEM).

The grid is ``(batch, seg_words // block_words)``: the second axis walks a
long segment in VMEM-sized blocks and carries the CRC state in the output
block, so the block size, not the request size, bounds VMEM.

The engine lays every part of a batch into the lanes of one row
(``pack_parts``), each part in whole lanes of its own, so a dispatch has
batch 1 and the compiled shapes are one per ``seg_words``. The host writes
the row lane-major, ``(1024, seg_words)``, the parts' bytes in order with no
transposing copy; ``crc32_lanes`` turns it words-major on the device.
"""

from __future__ import annotations

import functools
import zlib as _zlib
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core.crc32 import combine_lanes, crc32_combine

SEG_ROWS = 8
SEG_COLS = 128
N_SEGMENTS = SEG_ROWS * SEG_COLS
WORD_BYTES = 4
#: Words per lane per grid step: a (256, 8, 128) int32 block is 1 MiB, so the
#: double-buffered input stays far inside v5e's 16 MiB scoped VMEM.
BLOCK_WORDS = 256

_POLY = np.uint32(0xEDB88320).view(np.int32).item()


def _crc32_kernel(data_ref, out_ref):
    """data: (1, block_words, 8, 128) int32 words; out: (1, 8, 128) lane CRCs."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.full(out_ref.shape, -1, jnp.int32)  # 0xFFFFFFFF

    def step(i, crc):
        crc = crc ^ data_ref[0, i]
        for _ in range(32):
            crc = jax.lax.shift_right_logical(crc, 1) ^ (_POLY & -(crc & 1))
        return crc

    crc = jax.lax.fori_loop(0, data_ref.shape[1], step, out_ref[0])
    last = j == pl.num_programs(1) - 1
    out_ref[0] = jnp.where(last, ~crc, crc)  # final XOR with 0xFFFFFFFF


@functools.partial(jax.jit, static_argnames=("interpret",))
def crc32_segments_batched(data: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Per-lane CRC32 for a batch of byte streams in one dispatch.

    data: (batch, seg_words, SEG_ROWS, SEG_COLS) int32 — word ``w`` of lane
          ``(r, c)`` holds bytes ``[4w, 4w + 4)`` of that lane's segment,
          little-endian (``crc32_lanes`` lays ``pack_parts``' rows out this way).
    returns (batch, SEG_ROWS, SEG_COLS) int32 CRCs, one per lane.
    """
    batch, seg_words = data.shape[:2]
    block_words = min(seg_words, BLOCK_WORDS)
    if seg_words % block_words:
        raise ValueError("seg_words must be a multiple of %d" % block_words)
    return pl.pallas_call(
        _crc32_kernel,
        grid=(batch, seg_words // block_words),
        in_specs=[
            pl.BlockSpec(
                (1, block_words, SEG_ROWS, SEG_COLS), lambda b, j: (b, j, 0, 0)
            ),
        ],
        out_specs=pl.BlockSpec((1, SEG_ROWS, SEG_COLS), lambda b, j: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((batch, SEG_ROWS, SEG_COLS), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name="crc32_segments_batched",
    )(data)


@functools.partial(jax.jit, static_argnames=("interpret",))
def crc32_lanes(lanes: jax.Array, *, interpret: bool = False) -> jax.Array:
    """``crc32_segments_batched`` of lane-major rows.

    lanes: (batch, 1024, seg_words) int32 — lane ``s`` of a row holds its
           segment's ``seg_words`` words in order (``pack_parts``).
    returns (batch, SEG_ROWS, SEG_COLS) int32 CRCs, one per lane.
    """
    batch, _, seg_words = lanes.shape
    words = jnp.transpose(lanes, (0, 2, 1)).reshape(batch, seg_words, SEG_ROWS, SEG_COLS)
    return crc32_segments_batched(words, interpret=interpret)


# -- host side: lane packing and the fold back to one CRC per part ------------

def lane_words(nbytes: int) -> int:
    """Words per lane (a power of two) so that 1024 lanes hold ``nbytes``."""
    need = max(1, -(-nbytes // (N_SEGMENTS * WORD_BYTES)))
    return 1 << (need - 1).bit_length()


def _lanes(nbytes: int, seg_len: int) -> int:
    return -(-nbytes // seg_len)


def parts_words(sizes: Sequence[int]) -> int:
    """Words per lane (a power of two) at which the 1024 lanes of one row hold
    every part in whole lanes of its own."""
    if len(sizes) > N_SEGMENTS:
        raise ValueError("%d parts overflow %d lanes" % (len(sizes), N_SEGMENTS))
    words = lane_words(sum(sizes))
    while sum(_lanes(n, words * WORD_BYTES) for n in sizes) > N_SEGMENTS:
        words *= 2
    return words


def pack_parts(row: np.ndarray, datas: Sequence[bytes]) -> None:
    """Lay the parts into one lane-major ``(1024, seg_words)`` row, one after
    another.

    Each part takes whole lanes, right-aligned, with zeros in front of it in
    its first lane: lane ``s`` holds bytes ``[s * seg_len, (s + 1) * seg_len)``
    of the padded parts laid end to end, so packing is one copy per part.
    Every byte of every part is CRC'd on the device; ``finish_parts`` takes
    the leading zeros back out. Lanes past the last part keep whatever
    ``row`` held.
    """
    seg_len = row.shape[1] * WORD_BYTES
    flat = row.reshape(-1).view(np.uint8)
    pos = 0
    for data in datas:
        end = pos + _lanes(len(data), seg_len) * seg_len
        flat[pos : end - len(data)] = 0
        flat[end - len(data) : end] = np.frombuffer(data, np.uint8)
        pos = end


def finish_parts(lane_crcs: np.ndarray, sizes: Sequence[int], seg_words: int) -> List[int]:
    """Fold one row's lane CRCs (``(8, 128)`` kernel output of a row packed by
    ``pack_parts``) into the CRC32 of each part.

    A part's lanes fold to the CRC of its zero-padded form ``Z + M``;
    ``crc(Z + M) = shift(crc(Z), len(M)) ^ crc(M)``, so the CRC of ``M`` is
    that fold with ``crc32_combine(crc(Z), 0, len(M))`` XORed back out.
    """
    if not sizes:
        return []
    flat = np.asarray(lane_crcs).reshape(N_SEGMENTS).view(np.uint32)
    seg_len = seg_words * WORD_BYTES
    lanes = [_lanes(n, seg_len) for n in sizes]
    width = 1 << max(0, max(lanes) - 1).bit_length()
    rows = np.zeros((len(sizes), width), np.uint32)
    pos = 0
    for i, n in enumerate(lanes):
        # Right-aligned: leading 0s fold in as empty prefixes.
        rows[i, width - n :] = flat[pos : pos + n]
        pos += n
    out = []
    fixes = {}  # (pad, n) -> shift(crc(Z), n); a batch repeats a few sizes
    for n, count, crc in zip(sizes, lanes, combine_lanes(rows, seg_len)):
        pad = count * seg_len - n
        fix = fixes.get((pad, n))
        if fix is None:
            fix = fixes[pad, n] = crc32_combine(_zlib.crc32(bytes(pad)), 0, n)
        out.append(int(crc) ^ fix)
    return out
