"""Scheduler (``service/scheduler.py``): median wait of a task between its
submission and a worker taking it, over every task the window ran, from the
``queue_wait_s`` of the ``executor.run`` spans."""

import statistics


def read(run):
    waits = [s["attrs"]["queue_wait_s"] for s in run.spans
             if s["name"] == "executor.run" and "queue_wait_s" in s["attrs"]]
    return 1e3 * statistics.median(waits) if waits else None
