"""Fetcher and stage 1: share of the speculative (nominal) and exact chunk
decodes that ran in the stage-1 worker pool (``FetcherStats``:
``stage1_offloaded`` over ``nominal_tasks + exact_tasks``), %. A program
without the pool keeps no such counter, and the metric reads nothing."""


def read(run):
    offloaded = run.fetcher.get("stage1_offloaded")
    tasks = run.fetcher.get("nominal_tasks", 0) + run.fetcher.get("exact_tasks", 0)
    return 100.0 * offloaded / tasks if offloaded is not None and tasks else None
