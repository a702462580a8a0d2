"""Fetcher and stage 1 (``core/chunk_fetcher.py``, ``core/deflate.py``): bytes
the first pass finalized, per second that workers spent in speculative
(``nom``) and exact (``fp``) chunk decodes."""

STAGE1 = ("nom", "fp")


def read(run):
    busy = sum(s["dur_s"] for s in run.spans
               if s["name"] == "fetcher.task" and s["attrs"].get("kind") in STAGE1)
    done = run.fetcher.get("bytes_decompressed", 0)
    return done / busy / 1e6 if busy and done else None
