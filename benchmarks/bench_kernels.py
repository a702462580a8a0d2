"""Device kernel benchmarks: kernel throughput (Pallas kernels interpreted on
the CPU backend, compiled on a TPU) beside the jnp-oracle throughput.

The ``kernel_engine_*`` rows sweep batched vs per-chunk dispatch through the
DeviceDecodeEngine (batch 1/4/16/64) — the numbers ``engine.derive_crossover``
reads back out of the committed ``BENCH_kernels.json`` to place the
CPU/device routing threshold."""

from __future__ import annotations

import zlib as _zlib

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.marker_replace import TILE_COLS, TILE_ROWS, marker_replace_tiles_multi
from repro.kernels.ops import interpret_on
from repro.kernels.ref import make_replacement_table, marker_replace_ref, precode_check_ref
from repro.kernels.precode_check import BLOCK, HALO, ROWS, precode_check_blocks

from . import common
from .common import DataGen, emit, timeit


def bench_marker_replace(gen: DataGen) -> None:
    window = gen.rng.integers(0, 256, 32768, dtype=np.uint8)
    table = jnp.asarray(make_replacement_table(window))
    n_tiles = 4 if common.SMOKE else 64
    syms = jnp.asarray(
        gen.rng.integers(0, 33024, (n_tiles, TILE_ROWS, TILE_COLS), dtype=np.int64).astype(np.int32)
    )
    nbytes = n_tiles * TILE_ROWS * TILE_COLS

    ref = jax.jit(marker_replace_ref)
    ref(syms, table).block_until_ready()
    best, _ = timeit(lambda: ref(syms, table).block_until_ready(), repeats=5)
    emit("kernel_marker_replace_jnp", best * 1e6, f"{nbytes/best/1e6:.0f}MB/s")

    tables, tids = table[None], jnp.zeros(n_tiles, jnp.int32)
    marker_replace_tiles_multi(syms, tables, tids).block_until_ready()
    best, _ = timeit(
        lambda: marker_replace_tiles_multi(syms, tables, tids).block_until_ready(),
        repeats=3,
    )
    emit("kernel_marker_replace_multi", best * 1e6, f"{nbytes/best/1e6:.0f}MB/s")


def bench_precode(gen: DataGen) -> None:
    n_rows = ROWS if common.SMOKE else 8 * ROWS
    bits = jnp.asarray(gen.rng.integers(0, 2, (n_rows, BLOCK), dtype=np.int64).astype(np.int32))
    n_offsets = n_rows * BLOCK

    interpret = interpret_on(jax.devices()[0])
    fn = jax.jit(lambda b: precode_check_blocks(b, interpret=interpret))
    fn(bits).block_until_ready()
    best, _ = timeit(lambda: fn(bits).block_until_ready(), repeats=3)
    emit("kernel_precode_pallas_%s" % ("interpret" if interpret else "compiled"),
         best * 1e6, f"{n_offsets/8/best/1e6:.2f}MB/s(bit-offsets/8)")

    flat = jnp.concatenate([bits.reshape(-1), jnp.zeros(HALO, jnp.int32)])
    ref = jax.jit(precode_check_ref)
    ref(flat).block_until_ready()
    best, _ = timeit(lambda: ref(flat).block_until_ready(), repeats=3)
    emit("kernel_precode_jnp", best * 1e6, f"{(flat.shape[0]-74)/8/best/1e6:.2f}MB/s")


def bench_engine(gen: DataGen) -> None:
    """Batched vs per-chunk dispatch through the DeviceDecodeEngine.

    The per-chunk baseline is the pre-engine hot path: one
    ``ops.marker_replace`` per chunk (per-call table build + upload + device
    round trip). The batched path submits the same chunks to one engine and
    waits for the coalesced dispatch. One tile per chunk models the
    seeking-heavy serving shape — many small concurrent reads — where
    per-dispatch overhead dominates and batching pays most.

    Dispatches are slabbed at 16 tiles: interpret mode unrolls the grid at
    trace time, so larger single dispatches go super-linear in this
    container (a tracing artifact, not a device property).
    """
    from repro.core.markers import replace_markers as cpu_replace
    from repro.kernels import ops as kops
    from repro.kernels.engine import DeviceDecodeEngine

    import time as _time

    def best_of(fn, repeats: int = 3) -> float:
        """Best-of-N seconds, independent of smoke mode: these rows feed the
        crossover derivation and the batched/per-chunk ratio, and a single
        cold sample is dominated by thread-handoff jitter, not dispatch
        cost. N stays small enough that smoke mode is still quick."""
        fn()  # warmup (compile + caches)
        best = float("inf")
        for _ in range(repeats):
            t0 = _time.perf_counter()
            fn()
            best = min(best, _time.perf_counter() - t0)
        return best

    chunk_syms = TILE_ROWS * TILE_COLS  # one tile per chunk
    windows = [
        gen.rng.integers(0, 256, 32768, dtype=np.uint8).tobytes() for _ in range(4)
    ]

    def make_chunk() -> np.ndarray:
        return gen.rng.integers(0, 33024, chunk_syms, dtype=np.int64).astype(np.uint16)

    # CPU gather reference — the "cpu" input of the crossover derivation.
    syms0 = make_chunk()
    t_cpu = best_of(lambda: cpu_replace(syms0, windows[0]), repeats=5)
    emit("kernel_engine_cpu_replace", t_cpu * 1e6, f"{chunk_syms/t_cpu/1e6:.0f}MB/s")

    t_single = None  # batch-1 engine time: the single-chunk-dispatch baseline
    for B in (1, 4, 16, 64):
        chunks = [make_chunk() for _ in range(B)]
        wins = [windows[i % len(windows)] for i in range(B)]

        def per_chunk():
            for c, w in zip(chunks, wins):
                kops.marker_replace(c, w)

        t_pc = best_of(per_chunk)
        emit(f"kernel_engine_per_chunk_b{B}", t_pc * 1e6,
             f"{B*chunk_syms/t_pc/1e6:.1f}MB/s")

        eng = DeviceDecodeEngine(
            force_device=True, crossover=None,
            max_batch_tiles=min(B, 16), max_delay_s=0.05,
        )

        def batched():
            futs = [eng.submit_replace(c, w) for c, w in zip(chunks, wins)]
            for f in futs:
                f.result()

        t_b = best_of(batched)
        if t_single is None:
            t_single = t_b  # B == 1
        # x_vs_single: batched throughput over dispatching the same chunks
        # one at a time through the engine (B * t_single); x_vs_per_chunk:
        # over the pre-engine ops.marker_replace loop.
        emit(f"kernel_engine_batched_b{B}", t_b * 1e6,
             f"{B*chunk_syms/t_b/1e6:.1f}MB/s;{t_pc/t_b:.2f}x_vs_per_chunk"
             f";{B*t_single/t_b:.2f}x_vs_single")
        eng.shutdown()

    # CRC: zlib reference vs batched device dispatch (crossover inputs).
    # Fixed-tiny payloads: interpret mode executes the kernel's per-byte
    # fori_loop step by step (~ms each), so cost scales with seg_len and
    # anything larger stalls the section. The derivation only needs the
    # *sign* of the cpu-vs-device comparison, which tiny data settles.
    crc_nbytes = 8 << 10
    datas = [gen.random(crc_nbytes) for _ in range(8)]
    t_zc = best_of(lambda: _zlib.crc32(datas[0]), repeats=5)
    emit("kernel_engine_cpu_crc", t_zc * 1e6, f"{crc_nbytes/t_zc/1e6:.0f}MB/s")
    for B in (1, 8):
        eng = DeviceDecodeEngine(
            force_device=True, crossover=None,
            max_crc_requests=B, max_delay_s=0.05,
        )

        def crc_batched():
            futs = [eng.submit_crc(d) for d in datas[:B]]
            for f in futs:
                f.result()

        t_c = best_of(crc_batched, repeats=1)
        emit(f"kernel_engine_crc_batched_b{B}", t_c * 1e6,
             f"{B*crc_nbytes/t_c/1e6:.1f}MB/s")
        eng.shutdown()

    # Interactive scenario: default routing policy on THIS host (crossover
    # derived from the committed artifact). Singleton requests must take the
    # CPU path — the row's derived field records the engine's own fallback
    # count as proof.
    eng = DeviceDecodeEngine()
    t_i = best_of(lambda: eng.replace_markers(syms0, windows[0]), repeats=5)
    stats = eng.stats()
    emit("kernel_engine_interactive_singleton", t_i * 1e6,
         f"fallbacks={stats['fallbacks']['replace']};batches={stats['batches']}")
    eng.shutdown()


def main() -> None:
    gen = DataGen()
    bench_marker_replace(gen)
    bench_precode(gen)
    bench_engine(gen)
