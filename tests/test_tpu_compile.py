"""Compile-only checks: the device kernels at the engine's real bucket shapes,
compiled for a described TPU v5e chip (no chip attached, nothing runs).

Interpret mode, which the other kernel tests use, accepts kernels that the
chip's compiler refuses (unaligned blocks, gathers Mosaic cannot lower,
more VMEM than a kernel may use). These compiles catch that here.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import inspect

import pytest

import jax
import jax.numpy as jnp

from repro.kernels.crc32 import BLOCK_WORDS, SEG_COLS, SEG_ROWS, crc32_lanes
from repro.kernels.engine import DeviceDecodeEngine
from repro.kernels.marker_replace import TILE_COLS, TILE_ROWS, marker_replace_tiles_multi
from repro.kernels.precode_check import BLOCK, ROWS, precode_check_blocks
from repro.kernels.ref import TABLE_SIZE

pytestmark = pytest.mark.kernels

#: TPU v5e: 16 GiB of HBM per chip; 16 MiB is the default scoped-VMEM limit
#: a Pallas kernel may use.
V5E_HBM_BYTES = 16 << 30
V5E_SCOPED_VMEM_BYTES = 16 << 20

#: The engine's largest dispatch shapes come from its defaults.
_ENGINE = {
    name: p.default
    for name, p in inspect.signature(DeviceDecodeEngine).parameters.items()
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # A compile for a described chip is written to the persistent cache
        # but cannot be read back without the chip: keep the cache out.
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", enabled)
            compilation_cache.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip) for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    hbm = mem.argument_size_in_bytes + mem.output_size_in_bytes + mem.temp_size_in_bytes
    assert 0 < hbm <= V5E_HBM_BYTES
    return compiled


def _is_pallas(compiled) -> bool:
    return 'custom_call_target="tpu_custom_call"' in compiled.as_text()


def test_marker_replace_compiles_for_v5e(one_chip):
    tiles, tables = _ENGINE["max_batch_tiles"], _ENGINE["max_tables"]
    compiled = _compile(
        marker_replace_tiles_multi,
        one_chip,
        (tiles, TILE_ROWS, TILE_COLS),
        (tables, TABLE_SIZE),
        (tiles,),
    )
    # An XLA gather, not a Mosaic kernel: nothing of it is staged in VMEM.
    assert not _is_pallas(compiled)
    assert "gather" in compiled.as_text()


def test_crc32_compiles_for_v5e(one_chip):
    # The engine lays a whole batch into the lanes of one lane-major row
    # (batch 1), which the program turns words-major on the device.
    seg_words = _ENGINE["max_batch_crc_bytes"] // (SEG_ROWS * SEG_COLS * 4)
    compiled = _compile(
        lambda d: crc32_lanes(d, interpret=False),
        one_chip,
        (1, SEG_ROWS * SEG_COLS, seg_words),
    )
    assert _is_pallas(compiled)
    # Double-buffered input block plus the resident output block.
    block = min(seg_words, BLOCK_WORDS) * SEG_ROWS * SEG_COLS * 4
    assert 2 * block + 2 * SEG_ROWS * SEG_COLS * 4 <= V5E_SCOPED_VMEM_BYTES


def test_precode_check_compiles_for_v5e(one_chip):
    n_rows = (1 << 20) * 8 // BLOCK  # one 1 MiB chunk of bit offsets
    assert n_rows % ROWS == 0
    compiled = _compile(
        lambda b: precode_check_blocks(b, interpret=False), one_chip, (n_rows, BLOCK)
    )
    assert _is_pallas(compiled)
    # Two double-buffered input views plus the output block.
    block = ROWS * BLOCK * 4
    assert 3 * 2 * block <= V5E_SCOPED_VMEM_BYTES
    assert compiled.memory_analysis().output_size_in_bytes == n_rows * BLOCK * 4
