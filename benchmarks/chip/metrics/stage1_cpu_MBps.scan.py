"""Fetcher and stage 1 (``core/chunk_fetcher.py``, ``core/deflate.py``): bytes
the speculative (``nom``) and exact (``fp``) chunk decodes produced, per
second of CPU their worker threads spent on them (the ``bytes`` and ``cpu_s``
of the ``fetcher.task`` spans): the decoder's speed with the waits left out."""

STAGE1 = ("nom", "fp")


def read(run):
    tasks = [s["attrs"] for s in run.spans
             if s["name"] == "fetcher.task" and s["attrs"].get("kind") in STAGE1
             and "cpu_s" in s["attrs"]]
    cpu = sum(a["cpu_s"] for a in tasks)
    done = sum(a.get("bytes", 0) for a in tasks)
    return done / cpu / 1e6 if cpu and done else None
