"""Shared benchmark utilities: timing, CSV emission, data generation."""

from __future__ import annotations

import gzip as _gzip
import os
import time
from typing import Callable, Dict, List, Tuple

import numpy as np

#: Smoke mode (``python -m benchmarks.run --smoke``): every section shrinks
#: its data through :func:`scale` so the whole harness finishes in <60 s —
#: a CI-grade "do all benchmarks still execute" check, not a measurement.
SMOKE = False
SMOKE_DIVISOR = 32


#: The persistent compile cache's place when ``JAX_COMPILATION_CACHE_DIR``
#: is not set: fixed, so that one checkout's runs find each other's entries.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def use_compile_cache() -> str:
    """Turn on JAX's persistent compile cache for an entry point; returns
    its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, is read by JAX itself and this
    sets no other directory; otherwise the cache is ``COMPILE_CACHE_DIR``.
    Every compile is cached, however short: the kernels compile in about a
    second each, under JAX's default threshold.
    """
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


def set_smoke(on: bool = True) -> None:
    global SMOKE
    SMOKE = on


def scale(n: int, floor: int = 1 << 12) -> int:
    """Benchmark size ``n``, shrunk in smoke mode (never below ``floor``)."""
    return max(floor, n // SMOKE_DIVISOR) if SMOKE else n


def timeit(fn: Callable, *, repeats: int = 5, warmup: int = 1) -> Tuple[float, float]:
    """Returns (best_seconds, mean_seconds). Smoke mode: 1 repeat, no warmup."""
    if SMOKE:
        repeats, warmup = 1, 0
    for _ in range(warmup):
        fn()
    times = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return min(times), float(np.mean(times))


#: Results accumulated by :func:`emit` since the last :func:`drain_results`
#: call — the run.py harness drains this after each section to persist the
#: section's rows as ``BENCH_<section>.json`` alongside the CSV stdout.
RESULTS: List[Dict[str, object]] = []


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.3f},{derived}")
    RESULTS.append({"name": name, "value_us": round(us_per_call, 3), "derived": derived})


def drain_results() -> List[Dict[str, object]]:
    """Return and clear the rows emitted since the previous drain."""
    out = list(RESULTS)
    RESULTS.clear()
    return out


class DataGen:
    def __init__(self, seed: int = 0xBEEF):
        self.rng = np.random.default_rng(seed)

    def text(self, n: int) -> bytes:
        words = [b"the", b"quick", b"brown", b"fox", b"jumps", b"over", b"lazy",
                 b"dog", b"rapidgzip", b"parallel", b"deflate", b"window",
                 b"chunk", b"prefetch", b"cache", b"marker"]
        idx = self.rng.integers(0, len(words), size=max(8, n // 5))
        return b" ".join(words[i] for i in idx)[:n]

    def base64(self, n: int) -> bytes:
        import base64

        raw = self.rng.integers(0, 256, (n * 3) // 4 + 3, dtype=np.uint8).tobytes()
        return base64.b64encode(raw)[:n]

    def random(self, n: int) -> bytes:
        return self.rng.integers(0, 256, n, dtype=np.uint8).tobytes()

    def silesia_like(self, n: int) -> bytes:
        """Mixed corpus stand-in: text + structured binary + low-entropy runs."""
        parts = []
        per = max(1, n // 4)
        parts.append(self.text(per))
        # structured little-endian ints with small deltas (db-like)
        base = np.cumsum(self.rng.integers(0, 16, per // 4, dtype=np.int64)).astype("<u4")
        parts.append(base.tobytes())
        parts.append(self.random(per // 2))  # incompressible section
        parts.append((b"ABCD" * (per // 4 + 1))[:per])  # runs
        out = b"".join(parts)
        return out[:n]

    def fastq_like(self, n: int) -> bytes:
        """FASTQ records: @id / sequence / + / quality."""
        out = []
        size = 0
        i = 0
        bases = np.frombuffer(b"ACGT", np.uint8)
        quals = np.arange(33, 74, dtype=np.uint8)
        while size < n:
            seq = bases[self.rng.integers(0, 4, 100)].tobytes()
            qual = quals[self.rng.integers(0, len(quals), 100)].tobytes()
            rec = b"@SRR0000." + str(i).encode() + b"\n" + seq + b"\n+\n" + qual + b"\n"
            out.append(rec)
            size += len(rec)
            i += 1
        return b"".join(out)[:n]


def gzip_bytes(data: bytes, level: int = 6) -> bytes:
    return _gzip.compress(data, compresslevel=level)
