"""Engine (``kernels/engine.py``): stage-2 requests per device batch over the
window, from ``DeviceDecodeEngine.stats()``; 1 means nothing coalesced."""


def read(run):
    batches = run.engine.get("batches", 0)
    return run.engine["batched_requests"] / batches if batches else None
