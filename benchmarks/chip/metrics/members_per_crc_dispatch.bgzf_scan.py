"""Engine (``kernels/engine.py``): members whose trailers were verified
(``FetcherStats.members_verified``) per device batch over the window; what
one engine round trip verifies. Nothing on a program without the counter."""


def read(run):
    members = run.fetcher.get("members_verified", 0)
    batches = run.engine.get("batches", 0)
    return members / batches if members and batches else None
