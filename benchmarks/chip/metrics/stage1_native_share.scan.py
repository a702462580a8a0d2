"""Fetcher and stage 1 (``core/deflate.py``, ``core/zlib_bridge.py``): share
of the bytes the speculative (``nom``) and exact (``fp``) chunk decodes
produced that zlib decoded, once the window before a block was known
(summed ``native_bytes`` over summed ``bytes`` of those ``fetcher.task``
spans), %. A program whose spans carry no ``native_bytes`` reads nothing."""

STAGE1 = ("nom", "fp")


def read(run):
    tasks = [s["attrs"] for s in run.spans
             if s["name"] == "fetcher.task" and s["attrs"].get("kind") in STAGE1]
    if not any("native_bytes" in a for a in tasks):
        return None
    decoded = sum(a.get("bytes", 0) for a in tasks)
    native = sum(a.get("native_bytes", 0) for a in tasks)
    return 100.0 * native / decoded if decoded else None
