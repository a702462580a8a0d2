"""Kernels: the CRC32 kernel's share of the HBM roofline, bytes-bound only
(``kernel_work.py``: no peak is published for its VPU work)."""

from kernel_work import roofline_percent

KERNEL = "crc32_segments_batched"


def read(run):
    if run.trace is None:
        return None
    return roofline_percent(KERNEL, run.spans, run.trace["kernel_s"].get(KERNEL, 0.0),
                            run.device_kind)
