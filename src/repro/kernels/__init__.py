"""Device kernels for the paper's compute hot spots.

Each kernel ships three layers (repo convention):
  * ``<name>.py`` — the device computation: ``pl.pallas_call`` with
    explicit BlockSpec VMEM tiling (CRC32, block-finder precheck), or a
    jitted XLA gather where Mosaic has no lowering (marker replacement).
  * ``ops.py``    — per-call host wrappers (padding/reshape/dtype glue).
  * ``ref.py``    — pure-jnp oracle for bit-exact validation.

Pallas kernels are interpreted on the CPU backend (the tests) and compiled
on a TPU (``ops.interpret_on``); TPU v5e is the target.
"""

from .engine import DeviceDecodeEngine, EngineClosedError
from .ops import crc32_parallel, marker_replace, precode_candidates

__all__ = [
    "DeviceDecodeEngine",
    "EngineClosedError",
    "crc32_parallel",
    "marker_replace",
    "precode_candidates",
]
