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
          little-endian (``pack_lanes`` lays a request out this way).
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


# -- host side: lane packing and the fold back to one CRC per request ---------

def lane_words(nbytes: int) -> int:
    """Words per lane (a power of two) so that 1024 lanes hold ``nbytes``."""
    need = max(1, -(-nbytes // (N_SEGMENTS * WORD_BYTES)))
    return 1 << (need - 1).bit_length()


def pack_lanes(row: np.ndarray, data: bytes) -> None:
    """Lay ``data``'s whole segments into one ``(seg_words, 8, 128)`` row.

    Lane ``s`` gets bytes ``[s * seg_len, (s + 1) * seg_len)``. Lanes past
    the last whole segment keep whatever ``row`` held: ``finish_crcs``
    ignores them and CRCs the ragged tail on the host.
    """
    seg_words = row.shape[0]
    seg_len = seg_words * WORD_BYTES
    full = len(data) // seg_len
    if full > N_SEGMENTS:
        raise ValueError("%d bytes overflow %d lanes of %d" % (len(data), N_SEGMENTS, seg_len))
    if full:
        words = np.frombuffer(data, "<u4", count=full * seg_words)
        row.reshape(seg_words, N_SEGMENTS).view(np.uint32)[:, :full] = (
            words.reshape(full, seg_words).T
        )


def finish_crcs(
    lane_crcs: np.ndarray, datas: Sequence[bytes], seg_words: int
) -> List[int]:
    """Fold each request's lane CRCs (plus its ragged tail) into its CRC32.

    lane_crcs: (>= len(datas), SEG_ROWS, SEG_COLS) kernel output.
    """
    seg_len = seg_words * WORD_BYTES
    rows = np.zeros((len(datas), N_SEGMENTS), np.uint32)
    flat = lane_crcs.reshape(lane_crcs.shape[0], N_SEGMENTS).view(np.uint32)
    for i, data in enumerate(datas):
        full = len(data) // seg_len
        if full:
            # Whole lanes right-aligned: a leading 0 folds in as the CRC of
            # an empty prefix, so the tree fold needs no per-request length.
            rows[i, N_SEGMENTS - full :] = flat[i, :full]
    out = []
    for data, crc in zip(datas, combine_lanes(rows, seg_len)):
        tail = data[(len(data) // seg_len) * seg_len :]
        out.append(
            crc32_combine(int(crc), _zlib.crc32(tail) & 0xFFFFFFFF, len(tail))
        )
    return out
