"""Stage-2 marker replacement on the device (paper §2.2 step 3, Table 2).

Marker replacement is the data-parallel half of two-stage decompression:

    out[i] = sym[i]                      if sym[i] < 256   (resolved literal)
    out[i] = window[sym[i] - 256]        otherwise         (marker)

which collapses into a single gather through a 33 024-entry replacement
table (``[0..255] ++ window``).

The gather is one jitted XLA gather, not a Pallas kernel: Mosaic lowers no
general gather from a VMEM table (only gathers within one vreg), and the
table is 33 024 entries. XLA's TPU gather reads the table stack from HBM.

Tiling: symbols arrive in (8, 1024) int32 tiles (whole (8, 128) vregs); a
dispatch carries tiles from many chunks plus a per-tile table selector.
"""

from __future__ import annotations

import jax

from .ref import TABLE_SIZE

# One tile = SUBLANES x LANES*8 elements; int32 VREGs are (8, 128).
TILE_ROWS = 8
TILE_COLS = 1024
TILE = TILE_ROWS * TILE_COLS


@jax.jit
def marker_replace_tiles_multi(
    syms: jax.Array, tables: jax.Array, tile_tables: jax.Array
) -> jax.Array:
    """Gather-replace over tiles drawn from many chunks/windows in one call.

    syms:        (n_tiles, TILE_ROWS, TILE_COLS) int32 (padded, < TABLE_SIZE)
    tables:      (n_tables, TABLE_SIZE) int32 — one replacement table per
                 distinct window in the batch
    tile_tables: (n_tiles,) int32 — table index for each tile
    returns syms-shaped int32 with markers resolved.
    """
    with jax.named_scope("marker_replace_tiles_multi"):
        flat = tile_tables[:, None, None] * TABLE_SIZE + syms
        return tables.reshape(-1).at[flat].get(mode="clip")
