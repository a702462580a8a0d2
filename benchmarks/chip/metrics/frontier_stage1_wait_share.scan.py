"""Reader (``core/reader.py``): share of the window the client's frontier spent
waiting for first-pass chunks (``fetcher.chunk_wait``, inside
``reader.frontier_wait``)."""


def read(run):
    waits = [s["dur_s"] for s in run.spans if s["name"] == "fetcher.chunk_wait"]
    return 100.0 * sum(waits) / run.window_s if waits else None
