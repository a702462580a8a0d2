"""Pallas TPU kernel: vectorized Dynamic-Block finder precheck (paper §3.4.2).

The paper walks bit offsets sequentially with a skip-LUT; the TPU-native
reformulation evaluates the check cascade for *every bit offset in a tile
simultaneously* on the VPU:

  (1) final-block bit == 0
  (2) block type == 0b01 (stream order 0,1)
  (3) HLIT not in {30, 31}
  (4) precode histogram is a valid, complete Huffman code (Kraft sum == 128)

Step (4) re-expresses the paper's bit-level-parallel packed histogram across
vector lanes: the 19 precode code lengths are gathered with strided bit
reads and the Kraft term ``128 >> cl`` accumulated per offset. Offsets that
survive (≈0.05 % on random data, Table 1) are confirmed on the host with the
full strict header parse (steps 5–7) — the same split as the production
finder in ``core/block_finder.py``.

Input is the LSB-first bit plane as int32 0/1, laid out in rows of ``BLOCK``
bits; a grid step takes ``ROWS`` rows (whole vregs). Offset ``j`` of a row
needs the 74 bits after it, which run on into the next row: the next row of
the same block for rows 0-6, the next block's first row for row 7 (a second
view of the same operand, shifted by one block). Bit ``j + k`` of every
offset is a lane rotation by ``k`` of the row and of its continuation,
merged at the wrap point.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: bits of header probed beyond an offset: 17 header bits + 19*3 precode bits
HALO = 74

BLOCK = 2048  # offsets per row (>= HALO so one continuation row suffices)
ROWS = 8  # rows per grid step: (8, 2048) int32 is 16 whole vregs


def _precode_check_kernel(bits_ref, next_ref, out_ref):
    cur = bits_ref[...]
    last = pl.program_id(0) == pl.num_programs(0) - 1
    nxt = jnp.where(last, 0, next_ref[...])  # past the end reads zeros
    row = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, cur.shape, 1)
    # cont[r] = the row that follows row r in the bit stream.
    cont = jnp.where(
        row == ROWS - 1,
        pltpu.roll(nxt, ROWS - 1, 0),
        pltpu.roll(cur, ROWS - 1, 0),
    )

    def bit(k: int):
        """Bit at offset + k, for every offset of the block."""
        if k == 0:
            return cur
        return jnp.where(
            lane < BLOCK - k,
            pltpu.roll(cur, BLOCK - k, 1),
            pltpu.roll(cont, BLOCK - k, 1),
        )

    def field(at: int, width: int):
        out = bit(at)
        for j in range(1, width):
            out = out | (bit(at + j) << j)
        return out

    ok = (bit(0) == 0) & (bit(1) == 0) & (bit(2) == 1)  # (1) + (2)
    ok &= field(3, 5) < 30  # (3)
    n_codes = field(13, 4) + 4

    # (4) Kraft completeness over the (up to 19) 3-bit precode code lengths.
    kraft = jnp.zeros(cur.shape, jnp.int32)
    for k in range(19):
        cl = field(17 + 3 * k, 3)
        active = (k < n_codes) & (cl > 0)
        term = jax.lax.shift_right_logical(jnp.int32(128), cl)
        kraft = kraft + jnp.where(active, term, 0)
    ok &= kraft == 128

    out_ref[...] = ok.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def precode_check_blocks(bits: jax.Array, *, interpret: bool = False) -> jax.Array:
    """Candidate mask for every bit offset.

    bits: (n_rows, BLOCK) int32 0/1 bit plane, ``n_rows`` a multiple of
          ``ROWS``; bits past the last row read as zero.
    returns (n_rows, BLOCK) int32 mask (1 = candidate for steps 5-7).
    """
    n_rows = bits.shape[0]
    if n_rows % ROWS:
        raise ValueError("row count must be a multiple of %d" % ROWS)
    n_blocks = n_rows // ROWS
    return pl.pallas_call(
        _precode_check_kernel,
        grid=(n_blocks,),
        in_specs=[
            pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
            pl.BlockSpec(
                (ROWS, BLOCK), lambda i: (jnp.minimum(i + 1, n_blocks - 1), 0)
            ),
        ],
        out_specs=pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_rows, BLOCK), jnp.int32),
        interpret=interpret,
        name="precode_check_blocks",
    )(bits, bits)
