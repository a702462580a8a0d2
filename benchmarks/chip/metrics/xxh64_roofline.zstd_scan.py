"""Kernels: the XXH64 kernel's share of the HBM roofline, bytes-bound only.

The least work of a request is to read each frame byte once: the bytes are
summed from the ``engine.batch_wait`` spans of kind ``xxh64``
(``frame_bytes``). The kernel's device time is summed from the trace's
costliest device operations whose operation is ``xxh64_stripes``. The
kernel is bound by its serial chains' latency, not by bytes, so the share
is small by nature. Nothing on a program without the kernel."""

import re

from kernel_work import peaks

KERNEL = "xxh64_stripes"


def frame_bytes(spans) -> int:
    return sum(int(s["attrs"].get("nbytes", 0)) for s in spans
               if s["name"] == "engine.batch_wait" and s["attrs"].get("kind") == "xxh64")


def kernel_seconds(trace) -> float:
    return sum(t for name, t in trace["device_ops"]
               if re.sub(r"\.\d+$", "", name.rsplit("/", 1)[-1]) == KERNEL)


def read(run):
    if run.trace is None:
        return None
    seconds, nbytes = kernel_seconds(run.trace), frame_bytes(run.spans)
    if not seconds or not nbytes:
        return None
    return 100.0 * nbytes / peaks(run.device_kind)["hbm_bytes_per_s"] / seconds
