"""Fetcher (``core/chunk_fetcher.py``): bytes the indexed tasks (``ix`` runs
and ``ixm`` members alone) produced per second of their own time, inflate
and trailer checks included, in MB/s."""


def read(run):
    tasks = [s for s in run.spans
             if s["name"] == "fetcher.task" and s["attrs"].get("kind") in ("ix", "ixm")]
    busy = sum(s["dur_s"] for s in tasks)
    done = sum(int(s["attrs"].get("bytes", 0)) for s in tasks)
    return done / busy / 1e6 if busy and done else None
