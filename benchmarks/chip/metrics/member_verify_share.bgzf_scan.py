"""Fetcher (``core/chunk_fetcher.py``): share of indexed task time (``ix``
runs and ``ixm`` members alone) spent waiting for the verdict on the task's
member CRCs (``fetcher.member_verify``), %. Nothing on a program without the
span."""


def read(run):
    busy = sum(s["dur_s"] for s in run.spans
               if s["name"] == "fetcher.task" and s["attrs"].get("kind") in ("ix", "ixm"))
    wait = sum(s["dur_s"] for s in run.spans if s["name"] == "fetcher.member_verify")
    return 100.0 * wait / busy if busy and wait else None
