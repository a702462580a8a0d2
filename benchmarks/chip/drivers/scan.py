"""Closed-loop cold scans: one client reads the archive front to back in
fixed-size ``read_range`` calls, reopening it cold at EOF, until the
deadline. The rate is taken over every byte and all the time of the window,
restarts included."""

from __future__ import annotations

import time


def drive(cell, traffic, deadline):
    size = int(traffic["read_bytes"])
    reads, failed, scans = [], 0, 0
    t0 = t_end = time.perf_counter()
    while time.perf_counter() < deadline:
        handle = cell.open()
        offset = 0
        try:
            while time.perf_counter() < deadline:
                try:
                    with cell.annotate("bench.read_range"):
                        data = cell.server.read_range(handle, offset, size)
                except Exception as exc:  # noqa: BLE001 - a failed read is counted, not fatal
                    failed += 1
                    reads.append((offset, size, None))
                    cell.log("read_range(%d, %d) failed: %r" % (offset, size, exc))
                    break
                reads.append((offset, size, data))
                offset += len(data)
                if len(data) < size:
                    scans += 1
                    break
            t_end = time.perf_counter()
        finally:
            cell.close(handle)
    elapsed = t_end - t0
    delivered = sum(len(d) for _, _, d in reads if d is not None)
    cell.log("scan: %d reads, %d whole scans, %d bytes in %r s" % (len(reads), scans, delivered, elapsed))
    return {
        "reads": reads,
        "attempted": len(reads),
        "failed": failed,
        "elapsed_s": elapsed,
        "metrics": {"scan_MBps": delivered / elapsed / 1e6},
    }
