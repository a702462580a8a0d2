"""Kernels: the marker replacement's share of the HBM roofline
(``kernel_work.py`` counts its bytes, the trace gives its time)."""

from kernel_work import roofline_percent

KERNEL = "marker_replace_tiles_multi"


def read(run):
    if run.trace is None:
        return None
    return roofline_percent(KERNEL, run.spans, run.trace["kernel_s"].get(KERNEL, 0.0),
                            run.device_kind)
