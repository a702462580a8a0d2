"""Mixed corpus standing in for the Silesia corpus: text, small-delta
little-endian integers, an incompressible section and low-entropy runs, in
the proportions 2 : 2 : 1 : 2, exactly ``n`` bytes, vectorised.

Same sections as ``DataGen.silesia_like`` in ``benchmarks/common.py``, which
returns 7/8 of the bytes asked for and joins its text word by word.
"""

from __future__ import annotations

import numpy as np

WORDS = (b"the", b"quick", b"brown", b"fox", b"jumps", b"over", b"lazy", b"dog",
         b"rapidgzip", b"parallel", b"deflate", b"window", b"chunk", b"prefetch",
         b"cache", b"marker")


def text(rng: np.random.Generator, n: int) -> bytes:
    """At least ``n`` bytes of space-separated words, cut to ``n``."""
    width = max(len(w) for w in WORDS) + 1
    table = np.zeros((len(WORDS), width), np.uint8)
    lens = np.array([len(w) + 1 for w in WORDS])
    for i, w in enumerate(WORDS):
        table[i, : len(w) + 1] = np.frombuffer(w + b" ", np.uint8)
    idx = rng.integers(0, len(WORDS), n // 4 + 8)  # >= 4 bytes per word
    keep = np.arange(width) < lens[idx][:, None]
    return table[idx][keep][:n].tobytes()


def generate(rng: np.random.Generator, n: int) -> bytes:
    per = 2 * n // 7
    ints = np.cumsum(rng.integers(0, 16, per // 4, dtype=np.int64)).astype("<u4")
    head = b"".join([text(rng, per), ints.tobytes(), rng.bytes(per // 2)])
    runs = n - len(head)  # the last section takes the rounding
    return head + (b"ABCD" * (runs // 4 + 1))[:runs]
