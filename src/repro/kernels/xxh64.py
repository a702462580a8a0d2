"""Pallas TPU kernel: XXH64 (seed 0) of many zstd frames in one dispatch.

A seekable-zstd archive's seek table holds the low 32 bits of each frame's
XXH64. XXH64 steps four accumulators, each by one 8-byte word of every
32-byte stripe: ``round(acc, w) = rotl64(acc + w * P2, 31) * P1``. Each
accumulator is a chain of dependent steps (32,768 for a 1 MiB frame) that,
unlike CRC32, cannot be split and folded back together. Parallelism comes
from batching frames alone: each frame takes four lanes, one per
accumulator, and a step advances every lane of every frame by one word.
The kernel is bound by the chain's latency, not by bytes.

64-bit values are int32 ``(lo, hi)`` pairs. The low half of a product is an
int32 multiplication (it wraps); the high half of ``lo * c`` comes from
16-bit partial products, with carries from unsigned compares (xor with
``0x80000000``, then a signed compare) and ``lax.shift_right_logical``.

Layout: the host writes each frame's whole stripes into a row of its own,
in order (``pack_frames``: one copy per frame, no transposing copy), as the
CRC path writes each part into lanes of its own, and the kernel reads the
rows as uploaded. A frame is one sublane; its four accumulators sit in
lanes 0, 2, 4 and 6. One ``fori_loop`` step loads 128 words of every row,
16 stripes, and for each stripe rotates its eight words down
(``pltpu.roll``) so that lane ``2j`` holds word ``j``'s low half (and, one
rotation further, its high half). Frames of different lengths share a
dispatch: a row keeps its value once the step passes its frame's stripe
count. Compiled shapes come from power-of-two buckets of frames and of
stripes.

The merge of the four accumulators, the length, the tail of fewer than 32
bytes and the avalanche run on the host (``ref.xxh64_finish``): they are a
fixed number of 64-bit operations and at most 31 bytes per frame, serial
within the frame, so on the device they would be one more dispatch's worth
of int32-pair code for a few microseconds of host work.
"""

from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .ref import XXH_P1, XXH_P2, xxh64_finish, xxh64_init

STRIPE_BYTES = 32
LANES = 128
#: Stripes in one 128-lane row of words: one fori_loop step runs their rounds.
VREG_STRIPES = LANES * 4 // STRIPE_BYTES
#: Bytes of one grid step's input block (double-buffered in scoped VMEM).
BLOCK_BYTES = 2 << 20

_M32 = 0xFFFFFFFF
_SIGN = -(1 << 31)


def _i32(x: int) -> int:
    """The int32 whose bits are the low 32 bits of ``x``."""
    x &= _M32
    return x - (1 << 32) if x >> 31 else x


def _ltu(a, b):
    """Unsigned ``a < b`` of int32 bit patterns."""
    return (a ^ _SIGN) < (b ^ _SIGN)


def _srl(x, r: int):
    return jax.lax.shift_right_logical(x, r)


def _mulhi(a, c: int):
    """High 32 bits of the unsigned product of ``a`` and the constant ``c``."""
    c0, c1 = c & 0xFFFF, c >> 16
    a0, a1 = a & 0xFFFF, _srl(a, 16)
    p00, p01, p10, p11 = a0 * c0, a0 * c1, a1 * c0, a1 * c1
    mid = p01 + p10
    low = p00 + (mid << 16)
    return (p11 + _srl(mid, 16) + jnp.where(_ltu(mid, p01), 1 << 16, 0)
            + jnp.where(_ltu(low, p00), 1, 0))


def _mul64(lo, hi, c: int):
    """``(lo, hi) * c`` modulo 2**64, ``c`` a 64-bit constant."""
    c_lo, c_hi = c & _M32, c >> 32
    return lo * _i32(c_lo), _mulhi(lo, c_lo) + lo * _i32(c_hi) + hi * _i32(c_lo)


def _round(lo, hi, w_lo, w_hi):
    """``rotl64(acc + w * P2, 31) * P1`` on int32 pairs."""
    p_lo, p_hi = _mul64(w_lo, w_hi, XXH_P2)
    s_lo = lo + p_lo
    s_hi = hi + p_hi + jnp.where(_ltu(s_lo, lo), 1, 0)
    r_lo = (s_lo << 31) | _srl(s_hi, 1)
    r_hi = (s_hi << 31) | _srl(s_lo, 1)
    return _mul64(r_lo, r_hi, XXH_P1)


def _xxh64_kernel(n_ref, rows_ref, out_ref):
    """n: (frames, 128) stripes of each row's frame; rows: (frames, block)
    int32 words; out: (2, frames, 128) accumulators (lo, hi), lane ``2j``
    of row ``f`` holding frame ``f``'s accumulator ``j``, carried across
    the grid."""
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _init():
        acc = jax.lax.broadcasted_iota(jnp.int32, n_ref.shape, 1) >> 1
        for h in (0, 1):
            v = [_i32(a >> (32 * h)) for a in xxh64_init(0)]
            out_ref[h] = jnp.where(acc == 0, v[0], jnp.where(
                acc == 1, v[1], jnp.where(acc == 2, v[2], v[3])))

    n = n_ref[...]
    vregs = rows_ref.shape[1] // LANES

    def step(q, acc):
        lo, hi = acc
        x = rows_ref[:, pl.ds(pl.multiple_of(q * LANES, LANES), LANES)]
        first = (j * vregs + q) * VREG_STRIPES
        for k in range(VREG_STRIPES):
            # Stripe k's words sit in lanes 8k..8k+7 (lo, hi per word):
            # rotate them down so that lane 2j holds word j's lo (hi).
            w_lo = pltpu.roll(x, (LANES - 8 * k) % LANES, 1) if k else x
            w_hi = pltpu.roll(x, LANES - 8 * k - 1, 1)
            n_lo, n_hi = _round(lo, hi, w_lo, w_hi)
            live = first + k < n
            lo = jnp.where(live, n_lo, lo)
            hi = jnp.where(live, n_hi, hi)
        return lo, hi

    lo, hi = jax.lax.fori_loop(0, vregs, step, (out_ref[0], out_ref[1]))
    out_ref[0] = lo
    out_ref[1] = hi


@functools.partial(jax.jit, static_argnames=("interpret",))
def xxh64_rows(rows: jax.Array, counts: jax.Array, *, interpret: bool = False) -> jax.Array:
    """XXH64 accumulators of frames laid out by ``pack_frames``.

    rows: (frames, stripes * 8) int32, frame ``f``'s whole stripes in row
    ``f``, each 8-byte word as its little-endian (lo, hi) halves;
    counts: (frames,) int32, each frame's whole stripes.
    returns (2, frames, 4) int32: the low and high halves of each frame's
    four accumulators.
    """
    frames, words = rows.shape
    block = min(words, max(LANES, BLOCK_BYTES // (4 * frames)))
    if words % block:
        raise ValueError("stripes * 8 must be a multiple of %d" % block)
    n = jnp.broadcast_to(counts[:, None], (frames, LANES))
    out = pl.pallas_call(
        _xxh64_kernel,
        grid=(words // block,),
        in_specs=[
            pl.BlockSpec((frames, LANES), lambda j: (0, 0)),
            pl.BlockSpec((frames, block), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((2, frames, LANES), lambda j: (0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((2, frames, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="xxh64_stripes",
    )(n, rows)
    return out[:, :, 0:8:2]


# -- host side: frame packing and the finish ---------------------------------

def stripes_bucket(sizes: Sequence[int]) -> int:
    """Stripes per row (a power of two, at least one tile) that hold the
    whole stripes of every frame of ``sizes`` bytes."""
    need = max([VREG_STRIPES] + [n // STRIPE_BYTES for n in sizes])
    return 1 << (need - 1).bit_length()


def pack_frames(rows: np.ndarray, datas: Sequence[bytes]) -> np.ndarray:
    """Write each frame's whole stripes into its own row of ``rows``
    ((frames, stripes * 8) int32), one copy per frame; returns the stripe
    counts. Bytes past a frame's stripes keep whatever ``rows`` held: the
    kernel never steps a lane past its count."""
    flat = rows.view(np.uint8)
    counts = np.zeros(rows.shape[0], np.int32)
    for f, data in enumerate(datas):
        n = len(data) - len(data) % STRIPE_BYTES
        flat[f, :n] = np.frombuffer(data, np.uint8, n)
        counts[f] = n // STRIPE_BYTES
    return counts


def finish_frames(acc: np.ndarray, datas: Sequence[bytes]) -> List[int]:
    """XXH64 of each frame from ``xxh64_rows``' accumulators."""
    acc = np.asarray(acc).view(np.uint32).astype(np.uint64)
    acc = (acc[1] << np.uint64(32)) | acc[0]
    return [xxh64_finish([int(a) for a in acc[f]], data) for f, data in enumerate(datas)]
