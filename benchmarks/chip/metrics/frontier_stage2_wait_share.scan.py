"""Reader (``core/reader.py``): share of the window the client's frontier spent
blocked on stage 2, marker replacement and CRC32 (``reader.stage2_wait``,
inside ``reader.frontier_wait``)."""


def read(run):
    waits = [s["dur_s"] for s in run.spans if s["name"] == "reader.stage2_wait"]
    return 100.0 * sum(waits) / run.window_s if waits else None
