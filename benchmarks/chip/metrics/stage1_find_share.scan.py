"""Fetcher and stage 1 (``core/block_finder.py``, reached from
``core/stage1_worker.py``): share of the speculative (``nom``) chunk tasks'
time spent inside the block finder (summed ``find_s`` over summed duration
of those ``fetcher.task`` spans), %. Spans without ``find_s`` read nothing."""


def read(run):
    tasks = [s for s in run.spans
             if s["name"] == "fetcher.task" and s["attrs"].get("kind") == "nom"
             and "find_s" in s["attrs"]]
    dur = sum(s["dur_s"] for s in tasks)
    return 100.0 * sum(s["attrs"]["find_s"] for s in tasks) / dur if dur else None
