"""Production mesh construction.

Single pod: (data=16, model=16) — 256 TPU v5e chips.
Multi-pod:  (pod=2, data=16, model=16) — 512 chips; "pod" is a pure-DP axis
by default (gradient all-reduce crosses pods once per step; EP all-to-all and
TP collectives stay intra-pod), or a 2-stage pipeline axis with
``--pipeline pod``.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh with Auto axis types (smoke tests, examples)."""
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes)
    )


def make_host_mesh():
    """Whatever devices exist on this host as a (data, model) mesh."""
    n = len(jax.devices())
    return make_mesh((n, 1), ("data", "model"))
