"""Fetcher and stage 1: share of the bytes the speculative (``nom``) and exact
(``fp``) chunk decodes produced that the first pass finalized
(``FetcherStats.bytes_decompressed`` over the summed ``bytes`` of those
``fetcher.task`` spans); the rest was decoded and thrown away."""

STAGE1 = ("nom", "fp")


def read(run):
    decoded = sum(s["attrs"].get("bytes", 0) for s in run.spans
                  if s["name"] == "fetcher.task" and s["attrs"].get("kind") in STAGE1)
    return 100.0 * run.fetcher.get("bytes_decompressed", 0) / decoded if decoded else None
