"""Fetcher (``core/chunk_fetcher.py``): share of indexed task time (``ix``
runs and ``ixm`` frames alone) spent waiting for the verdict on the task's
frame checksums (``fetcher.member_verify`` spans whose ``checksum`` is
``xxh64``), %. Nothing on a program whose spans lack the attribute."""


def read(run):
    busy = sum(s["dur_s"] for s in run.spans
               if s["name"] == "fetcher.task" and s["attrs"].get("kind") in ("ix", "ixm"))
    wait = sum(s["dur_s"] for s in run.spans
               if s["name"] == "fetcher.member_verify" and s["attrs"].get("checksum") == "xxh64")
    return 100.0 * wait / busy if busy and wait else None
