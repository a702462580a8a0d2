"""Reader (``core/reader.py``): share of the window the client spent in
``reader.frontier_wait``, the first pass advancing (or waiting its turn)
past the position asked for."""


def read(run):
    waited = sum(s["dur_s"] for s in run.spans if s["name"] == "reader.frontier_wait")
    return 100.0 * waited / run.window_s if run.spans else None
