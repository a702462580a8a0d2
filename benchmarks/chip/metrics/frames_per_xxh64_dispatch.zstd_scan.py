"""Engine (``kernels/engine.py``): zstd frames whose XXH64 was checked
(``FetcherStats.frames_verified``) per device batch over the window; what
one engine round trip verifies. Nothing on a program without the counter."""


def read(run):
    frames = run.fetcher.get("frames_verified", 0)
    batches = run.engine.get("batches", 0)
    return frames / batches if frames and batches else None
