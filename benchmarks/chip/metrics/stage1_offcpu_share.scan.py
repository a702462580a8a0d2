"""Fetcher and stage 1: share of the wall time of the speculative (``nom``)
and exact (``fp``) chunk decodes in which their threads were off the CPU,
1 - (summed ``cpu_s``) / (summed duration) over those ``fetcher.task`` spans.
Worker threads that queue on the interpreter lock read high."""

STAGE1 = ("nom", "fp")


def read(run):
    tasks = [s for s in run.spans
             if s["name"] == "fetcher.task" and s["attrs"].get("kind") in STAGE1
             and "cpu_s" in s["attrs"]]
    wall = sum(s["dur_s"] for s in tasks)
    return 100.0 * (1.0 - sum(s["attrs"]["cpu_s"] for s in tasks) / wall) if wall else None
