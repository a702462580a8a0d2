"""Work each device kernel must do, counted from the requests it served.

A kernel's roofline share is the least time the chip could take for the
valid work of the window (bytes over the HBM peak of ``peaks.json``) over
the device time the trace gives the kernel. The bytes are the least any
implementation of the request contract has to move, counted per valid,
unpadded element from the ``engine.batch_wait`` spans that carry each
request's size; padding, staging and table uploads are waste, not work, so
a share can never pass 100% whatever later implements the kernel.

- ``marker_replace_tiles_multi``: each symbol is read once at 16 bits (its
  values run to 33 023) and each resolved byte written once: 3 bytes per
  symbol. The replacement tables are not counted.
- ``crc32_segments_batched``: every byte is read once. The table-free CRC is
  VPU work for which no peak is published, so its share is bytes-bound only.
"""

from __future__ import annotations

import json
import os
from typing import Dict, Iterable, Optional

HERE = os.path.dirname(os.path.abspath(__file__))

#: kernel -> (engine request kind, span attribute with its size, bytes per unit)
KERNELS = {
    "marker_replace_tiles_multi": ("replace", "symbols", 3),
    "crc32_segments_batched": ("crc", "nbytes", 1),
}


def peaks(device_kind: str) -> Dict[str, float]:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError("no peaks for device kind %r in peaks.json" % device_kind)
    return table[device_kind]


def kernel_bytes(kernel: str, spans: Iterable[dict]) -> int:
    kind, attr, per_unit = KERNELS[kernel]
    return sum(
        per_unit * int(s["attrs"].get(attr, 0))
        for s in spans
        if s["name"] == "engine.batch_wait" and s["attrs"].get("kind") == kind
    )


def roofline_percent(kernel: str, spans: Iterable[dict], kernel_s: float,
                     device_kind: str) -> Optional[float]:
    """Share of the HBM roofline, in %; None where the kernel did not run."""
    if not kernel_s:
        return None
    nbytes = kernel_bytes(kernel, spans)
    if not nbytes:
        return None
    return 100.0 * nbytes / peaks(device_kind)["hbm_bytes_per_s"] / kernel_s
