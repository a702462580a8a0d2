"""Chip smoke run: the archive service's served path on one TPU chip.

    python chip_smoke.py          # from the repository root, on a TPU host

One process owns the chip and starts no other process. Phases, in order:

1. device check — exits non-zero, running nothing else, unless JAX's first
   device is a TPU;
2. kernel parity — the batched marker replacement, the batched CRC32 and
   the block-finder precheck at the engine's real bucket shapes, checked
   bit for bit against ``kernels/ref.py``, NumPy and ``zlib.crc32``;
3. served reads at deployment size through ``ArchiveServer`` with the
   device engine forced on — a gzip -6 archive of mixed text (speculative
   two-stage decode, so marker resolution and CRC32 on the device) and a
   BGZF FASTQ-like archive larger than the cache pool (zlib inflate, every
   member's CRC32 on the device); a cold full read, after which the engine
   must have CRC'd every byte it served, seeded random preads, a warm reopen
   through the same ``IndexStore``, and preads through a loopback gateway,
   every byte compared with the generated source;
4. engine check — the engine ran on the chip, uninterpreted, with no CPU
   fallback and no error.

Earlier lines report phase wall times and compile time (a smoke run, not a
benchmark). The last line is ``{"ok": true, "device": {...}}``; any failure
raises before it is printed.
"""

from __future__ import annotations

import gzip
import importlib.util
import json
import os
import sys
import tempfile
import time
import zlib

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

#: Deployment sizes. The gzip archive is cut from the paper's multi-GB
#: Silesia-scale inputs to 32 MiB: its stage 1 is host-side pure Python.
GZIP_BYTES = 32 << 20
BGZF_BYTES = 256 << 20
N_PREADS = 32
N_GATEWAY_PREADS = 4
SEED = 0x5EED


def log(msg: str) -> None:
    print(msg, flush=True)


def check(ok: bool, what: str) -> None:
    if not ok:
        raise AssertionError(what)


def use_compile_cache() -> str:
    """JAX's persistent compile cache with every compile kept: where
    ``JAX_COMPILATION_CACHE_DIR`` names it, else ``<repo>/.jax_cache``."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return jax.config.jax_compilation_cache_dir


class CompileClock:
    """Sums JAX's backend-compile time (persistent-cache reads included)."""

    def __init__(self) -> None:
        from jax import monitoring

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _on_event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def report(self) -> str:
        return "compile: %r s over %d programs, %d persistent-cache hits" % (
            self.seconds, self.compiles, self.cache_hits,
        )


# -- phase 1 -------------------------------------------------------------------

def device_check():
    import jax

    devices = jax.devices()
    dev = devices[0]
    log("jax %s; device_kind=%s; platform=%s; count=%d"
        % (jax.__version__, dev.device_kind, dev.platform, len(devices)))
    if dev.platform != "tpu":
        raise SystemExit(
            "chip_smoke: no TPU found (JAX's first device is %r); nothing ran"
            % dev.platform
        )
    return devices


# -- phase 2 -------------------------------------------------------------------

def kernel_parity(rng, *, tiles=32, tables=8, crc_batch=16, crc_words=1024,
                  precode_bytes=1 << 20) -> None:
    """Each kernel at the engine's largest bucket, against plain references."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.crc32 import (
        N_SEGMENTS, SEG_COLS, SEG_ROWS, crc32_lanes, crc32_segments_batched, finish_parts,
        pack_parts, parts_words,
    )
    from repro.kernels.marker_replace import TILE_COLS, TILE_ROWS, marker_replace_tiles_multi
    from repro.kernels.ops import interpret_on
    from repro.kernels.precode_check import BLOCK, HALO, precode_check_blocks

    device = jax.devices()[0]
    interpret = interpret_on(device)

    # Marker replacement: (tiles, 8, 1024) symbols over a stack of tables.
    stack = np.stack([
        ref.make_replacement_table(rng.integers(0, 256, 32768, dtype=np.uint8))
        for _ in range(tables)
    ])
    syms = rng.integers(0, ref.TABLE_SIZE, (tiles, TILE_ROWS, TILE_COLS)).astype(np.int32)
    tids = rng.integers(0, tables, tiles).astype(np.int32)
    out = np.asarray(marker_replace_tiles_multi(
        jax.device_put(syms, device), jax.device_put(stack, device),
        jax.device_put(tids, device),
    ))
    check(np.array_equal(out, stack[tids[:, None, None], syms]),
          "marker replacement != NumPy gather")
    oracle = ref.marker_replace_multi_ref(jnp.asarray(syms), jnp.asarray(stack), jnp.asarray(tids))
    check(np.array_equal(out, np.asarray(oracle)), "marker replacement != kernels/ref.py")
    log("  marker_replace_tiles_multi %s x %d tables: bit-identical"
        % ((tiles, TILE_ROWS, TILE_COLS), tables))

    # CRC32: whole lanes against the oracle and zlib, then ragged parts laid
    # into one row as the engine lays a batch, folded back to one CRC each.
    words = rng.integers(0, 1 << 32, (crc_batch, crc_words, SEG_ROWS, SEG_COLS),
                         dtype=np.uint64).astype(np.uint32).view(np.int32)
    lanes = np.asarray(crc32_segments_batched(
        jax.device_put(words, device), interpret=interpret))
    oracle = np.asarray(ref.crc32_segments_batched_ref(jnp.asarray(words)))
    check(np.array_equal(lanes, oracle), "crc32 lanes != kernels/ref.py")
    per_lane = words.astype("<u4").transpose(0, 2, 3, 1).reshape(crc_batch * N_SEGMENTS, -1)
    want = np.array([zlib.crc32(row.tobytes()) for row in per_lane], np.uint32)
    check(np.array_equal(lanes.reshape(-1).view(np.uint32), want), "crc32 lanes != zlib")
    sizes = [0, 1, 3, 4096, 65280] + [
        int(n) for n in rng.integers(1, 200_000, crc_batch - 5)
    ]
    datas = [rng.bytes(n) for n in sizes]
    row_words = parts_words(sizes)
    stage = np.zeros((1, N_SEGMENTS, row_words), np.int32)
    pack_parts(stage[0], datas)
    lanes = np.asarray(crc32_lanes(jax.device_put(stage, device), interpret=interpret))
    got = finish_parts(lanes[0], sizes, row_words)
    check(got == [zlib.crc32(d) for d in datas], "folded crc32 != zlib.crc32")
    log("  crc32_segments_batched %s: lanes bit-identical; %d ragged parts in one %s"
        " lane-major row too"
        % (words.shape, len(datas), stage.shape))

    # Block-finder precheck over one chunk of bit offsets.
    bits = np.unpackbits(np.frombuffer(rng.bytes(precode_bytes), np.uint8),
                         bitorder="little").astype(np.int32)
    mask = np.asarray(precode_check_blocks(
        jax.device_put(bits.reshape(-1, BLOCK), device), interpret=interpret))
    oracle = ref.precode_check_ref(jnp.asarray(np.concatenate([bits, np.zeros(HALO, np.int32)])))
    check(np.array_equal(mask.reshape(-1), np.asarray(oracle)), "precode mask != kernels/ref.py")
    log("  precode_check_blocks over %d bytes: bit-identical, %d candidates"
        % (precode_bytes, int(mask.sum())))


# -- phase 3 -------------------------------------------------------------------

def _generator(kind: str):
    """``generate(rng, n)`` of the chip benchmark's ``data/<kind>.py``."""
    path = os.path.join(ROOT, "benchmarks", "chip", "data", kind + ".py")
    spec = importlib.util.spec_from_file_location("chip_smoke_" + kind, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.generate


def make_archives(workdir: str, rng, *, gzip_bytes: int, bgzf_bytes: int):
    """Seeded sources and their archives on disk: [(label, path, source)]."""
    from repro.core.synth import bgzf_compress

    text = _generator("silesia_like")(rng, gzip_bytes)
    fastq = _generator("fastq_like")(rng, bgzf_bytes)
    out = []
    for label, source, archive in (
        ("gzip-6 silesia-like", text, gzip.compress(text, compresslevel=6)),
        ("bgzf fastq-like", fastq, bgzf_compress(fastq, 6)),
    ):
        path = os.path.join(workdir, label.split()[0] + ".gz")
        with open(path, "wb") as f:
            f.write(archive)
        log("  %s: %d bytes -> %d compressed" % (label, len(source), len(archive)))
        out.append((label, path, source))
    return out


def _preads(read, source: bytes, rng, n: int, what: str) -> int:
    """``n`` seeded random reads of 64 KiB - 4 MiB, each checked."""
    total = 0
    for _ in range(n):
        size = int(rng.integers(64 << 10, (4 << 20) + 1))
        off = int(rng.integers(0, max(1, len(source) - size)))
        check(read(off, size) == source[off : off + size],
              "%s pread(%d, %d) differs from the source" % (what, off, size))
        total += size
    return total


def served_reads(srv, archives, rng, *, n_preads: int, n_gateway_preads: int) -> None:
    from repro.service.gateway import GatewayClient, GatewayServer

    for label, path, source in archives:
        before = srv.device_engine.stats()
        t = time.perf_counter()
        h = srv.open(path)
        check(srv.read_range(h, 0, len(source)) == source, "%s cold read differs" % label)
        cold = time.perf_counter() - t
        crc_bytes = srv.device_engine.stats()["crc_bytes"] - before["crc_bytes"]
        check(crc_bytes >= len(source),
              "%s cold read: the engine CRC'd %d of %d bytes" % (label, crc_bytes, len(source)))
        t = time.perf_counter()
        nbytes = _preads(lambda o, n: srv.read_range(h, o, n), source, rng, n_preads, label)
        hot = time.perf_counter() - t
        srv.close(h)  # persists the finalized index into the store

        t = time.perf_counter()
        h = srv.open(path)
        _preads(lambda o, n: srv.read_range(h, o, n), source, rng, n_preads, label)
        check(srv.stat(h).index_was_warm, "%s reopen missed the IndexStore" % label)
        warm = time.perf_counter() - t
        srv.close(h)
        after = srv.device_engine.stats()["requests"]
        log("  %s: cold full read %r s, %d bytes CRC'd on the engine; %d preads (%d bytes) %r s;"
            " warm reopen + %d preads %r s; engine requests replace=%d crc=%d"
            % (label, cold, crc_bytes, n_preads, nbytes, hot, n_preads, warm,
               after["replace"] - before["requests"]["replace"],
               after["crc"] - before["requests"]["crc"]))

    with GatewayServer(srv) as gw:
        for label, path, source in archives:
            t = time.perf_counter()
            with GatewayClient(gw.url, source=path) as client:
                check(client.size() == len(source), "%s gateway size differs" % label)
                _preads(client.pread, source, rng, n_gateway_preads, label + " gateway")
            log("  %s: %d gateway preads %r s"
                % (label, n_gateway_preads, time.perf_counter() - t))


# -- phase 4 -------------------------------------------------------------------

def engine_check(stats) -> None:
    log("  engine: " + json.dumps(stats, sort_keys=True))
    check(stats["interpret"] is False, "engine interpreted its kernels")
    check(stats["batches"] > 0 and stats["dispatches"] > 0, "engine dispatched nothing")
    check(stats["requests"]["replace"] > 0, "no marker resolution reached the engine")
    check(stats["requests"]["crc"] > 0, "no CRC32 reached the engine")
    check(stats["fallbacks"] == {"replace": 0, "crc": 0}, "engine fell back to the CPU")
    check(stats["errors"] == 0, "engine dispatches failed")


def run_served(rng, *, gzip_bytes: int, bgzf_bytes: int, n_preads: int,
               n_gateway_preads: int) -> dict:
    """Phases 3 and 4; returns the engine's stats."""
    from repro.service import ArchiveServer, IndexStore

    with tempfile.TemporaryDirectory(prefix=".chip_smoke-", dir=ROOT) as workdir:
        t = time.perf_counter()
        archives = make_archives(workdir, rng, gzip_bytes=gzip_bytes, bgzf_bytes=bgzf_bytes)
        log("phase 3 set-up (data + archives): %r s" % (time.perf_counter() - t))
        t = time.perf_counter()
        with ArchiveServer(
            index_store=IndexStore(), engine_options={"force_device": True}
        ) as srv:
            served_reads(srv, archives, rng, n_preads=n_preads,
                         n_gateway_preads=n_gateway_preads)
            stats = srv.metrics()["engine"]
        log("phase 3 served reads: %r s" % (time.perf_counter() - t))
    engine_check(stats)
    return stats


def main() -> None:
    t_start = time.perf_counter()
    devices = device_check()

    import numpy as np

    log("compile cache: %s" % use_compile_cache())
    clock = CompileClock()
    rng = np.random.default_rng(SEED)

    t = time.perf_counter()
    kernel_parity(rng)
    log("phase 2 kernel parity: %r s; %s" % (time.perf_counter() - t, clock.report()))

    log("size cut: gzip archive %d MiB of mixed text (paper: multi-GB Silesia-"
        "scale inputs; stage 1 is host-side pure Python), BGZF archive %d MiB"
        % (GZIP_BYTES >> 20, BGZF_BYTES >> 20))
    run_served(rng, gzip_bytes=GZIP_BYTES, bgzf_bytes=BGZF_BYTES,
               n_preads=N_PREADS, n_gateway_preads=N_GATEWAY_PREADS)
    log("phase 4 engine check: passed")
    log("total %r s; %s" % (time.perf_counter() - t_start, clock.report()))
    dev = devices[0]
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }), flush=True)


if __name__ == "__main__":
    main()
