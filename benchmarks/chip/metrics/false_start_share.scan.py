"""Fetcher and stage 1: share of the speculative trial decodes that started
at a false block boundary, wasted work (``FetcherStats``)."""


def read(run):
    tried = run.fetcher.get("candidates_tried", 0)
    return 100.0 * run.fetcher.get("false_positive_starts", 0) / tried if tried else None
