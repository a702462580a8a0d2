"""BGZF member verification on the indexed read path: every member's ISIZE and
CRC32 are checked against its own trailer before any of its bytes is cached
or served, the CRCs on the device engine (interpret mode here).

The plain reference: the stdlib's gzip reader over the archive for the
bytes (``encoders/bgzip.py`` of the chip benchmark), and ``zlib.crc32`` and
``len`` of each member's inflated body against its trailer for the verdict,
with the members found from the framing alone (SAM/BAM specification §4.1).
"""

from __future__ import annotations

import functools
import os
import struct
import sys
import time
import zlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

import repro.core

from conftest import gzip_bytes
from repro.core.errors import GzipFooterError
from repro.core.index import GzipIndex
from repro.core.reader import ParallelGzipReader
from repro.kernels.engine import DeviceDecodeEngine
from repro.obs import trace as obs_trace
from repro.service.index_store import IndexStore, file_identity
from repro.service.server import ArchiveServer

CHIP_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "benchmarks", "chip")
if CHIP_BENCH not in sys.path:
    sys.path.insert(0, CHIP_BENCH)

import harness  # noqa: E402
from registry import Registry  # noqa: E402

BLOCK = 0xFF00
#: 21 members: two indexed tasks of 16 and 5, the last member short.
N = 20 * BLOCK + 1234
BAD = 17  # a member of the second task


@pytest.fixture(scope="module")
def corpus():
    reg = Registry()
    data = reg.generator("fastq_like")(np.random.default_rng(0xB6F), N)
    codec = reg.encoder("bgzip")
    archive = codec.encode(data, level=6)
    assert codec.decode(archive) == data
    return data, archive


@pytest.fixture
def engine():
    eng = DeviceDecodeEngine(force_device=True, max_delay_s=0.002)
    yield eng
    eng.shutdown()


def members(archive: bytes):
    """``(start, body_start, end, crc32, isize)`` of each member, from the BC
    subfield's BSIZE alone."""
    out, pos = [], 0
    while pos < len(archive):
        xlen = struct.unpack_from("<H", archive, pos + 10)[0]
        j, bsize = pos + 12, None
        while j < pos + 12 + xlen:
            si1, si2, slen = archive[j], archive[j + 1], struct.unpack_from("<H", archive, j + 2)[0]
            if (si1, si2) == (66, 67):
                bsize = struct.unpack_from("<H", archive, j + 4)[0]
            j += 4 + slen
        end = pos + bsize + 1
        crc, isize = struct.unpack_from("<II", archive, end - 8)
        out.append((pos, pos + 12 + xlen, end, crc, isize))
        pos = end
    return out


def reference_verdicts(archive: bytes):
    """Per member: does the trailer match the inflated body?"""
    verdicts = []
    for _, body_at, end, crc, isize in members(archive):
        body = zlib.decompress(archive[body_at: end - 8], -15)
        verdicts.append(zlib.crc32(body) == crc and len(body) == isize)
    return verdicts


def test_served_bytes_equal_the_reference(corpus, engine):
    data, archive = corpus
    assert all(reference_verdicts(archive))
    with ParallelGzipReader(archive, resolver=engine) as r:
        assert r.codec.tag == "bgzf"
        # Inside one member, across members and across the two tasks.
        for off, n in ((0, 100), (BLOCK - 10, 30), (15 * BLOCK + 7, 2 * BLOCK), (N - 50, 500)):
            assert r.pread(off, n) == data[off: off + n]
        assert r.read() == data


def test_every_member_is_verified_on_the_engine(corpus, engine):
    data, archive = corpus
    n_members = sum(1 for m in members(archive) if m[4])
    pool = ThreadPoolExecutor(max_workers=4)
    obs_trace.enable_tracing()
    obs_trace.reset_tracing()
    try:
        with ParallelGzipReader(archive, resolver=engine, executor=pool) as r:
            assert r.read() == data
            f = r.stats()["fetcher"]
        pool.shutdown(wait=True)
        spans = obs_trace.drain_spans()
    finally:
        obs_trace.disable_tracing()
        obs_trace.reset_tracing()
    # The first read is cold: member 0 alone answers it, while its run of
    # 16 (the engine's 4 MiB CRC batch over the reader's 4 tasks in flight,
    # at most 64 KiB a member) and the next run follow.
    decoded = len(data) + BLOCK
    assert n_members == 21 and f["members_verified"] == n_members + 1
    assert f["member_crc_mismatches"] == 0
    assert f["member_crc_device_bytes"] == f["bytes_decompressed"] == decoded
    st = engine.stats()
    assert st["crc_bytes"] == decoded and st["fallbacks"]["crc"] == 0
    # One engine request per task.
    assert st["requests"]["crc"] == f["indexed_tasks"] == 3

    verify = [s for s in spans if s["name"] == "fetcher.member_verify"]
    assert sorted(s["attrs"]["members"] for s in verify) == [1, 5, 16]
    assert sum(s["attrs"]["bytes"] for s in verify) == decoded
    tasks = [s for s in spans if s["name"] == "fetcher.task" and s["attrs"]["kind"] in ("ix", "ixm")]
    assert sorted(s["attrs"]["kind"] for s in tasks) == ["ix", "ix", "ixm"]
    assert sum(s["attrs"]["bytes"] for s in tasks) == decoded
    assert sum(s["attrs"]["members"] for s in tasks) == n_members + 1
    waits = [s for s in spans if s["name"] == "engine.batch_wait" and s["attrs"]["kind"] == "crc"]
    assert sum(s["attrs"]["nbytes"] for s in waits) == decoded
    # The dispatcher's own work, batch by batch.
    dispatched = [s for s in spans if s["name"] == "engine.dispatch"]
    resolved = [s for s in spans if s["name"] == "engine.resolve"]
    assert sum(s["attrs"]["bytes"] for s in dispatched) == decoded
    assert sum(s["attrs"]["requests"] for s in dispatched) == sum(s["attrs"]["requests"] for s in resolved) == 3
    for s in dispatched:
        assert s["attrs"]["pack_s"] + s["attrs"]["launch_s"] <= s["dur_s"] + 1e-6
    for s in resolved:
        assert s["attrs"]["readback_s"] + s["attrs"]["fold_s"] <= s["dur_s"] + 1e-6


@pytest.mark.parametrize("batch_bytes, parallelization, per_task", [
    (4 << 20, 4, 16), (2 << 20, 4, 8), (4 << 20, 2, 32), (1 << 16, 4, 1), (None, 4, 1),
])
def test_members_per_task_follow_the_resolver_batch(corpus, batch_bytes, parallelization, per_task):
    """The tasks a sequential reader keeps in flight fill one CRC batch
    together; without a resolver a task is one member, the unit of random
    access."""
    data, archive = corpus
    eng = (DeviceDecodeEngine(force_device=True, max_batch_crc_bytes=batch_bytes)
           if batch_bytes else None)
    try:
        with ParallelGzipReader(archive, resolver=eng, parallelization=parallelization) as r:
            assert r._fetcher.task_points == per_task
            assert r.pread(BLOCK * 5 - 7, 3 * BLOCK) == data[BLOCK * 5 - 7: BLOCK * 8 - 7]
            assert r.read() == data
            f = r.stats()["fetcher"]
        assert f["indexed_tasks"] >= -(-21 // per_task)
        assert f["members_verified"] >= 21
    finally:
        if eng is not None:
            eng.shutdown()


def test_a_cold_read_inflates_its_member_alone(corpus, engine):
    """Random access keeps a member's granularity: a cold read waits for its
    own member only; the member's run follows as a prefetch, and no run
    beyond it, as a new stream prefetches two members ahead."""
    data, archive = corpus
    obs_trace.enable_tracing()
    obs_trace.reset_tracing()
    try:
        with ParallelGzipReader(archive, resolver=engine) as r:
            off = 5 * BLOCK + 5
            assert r.pread(off, 10) == data[off: off + 10]
            r._fetcher.get_indexed(0)  # joins or finds the run
            tasks = [s for s in obs_trace.drain_spans() if s["name"] == "fetcher.task"]
            assert r.read() == data
    finally:
        obs_trace.disable_tracing()
        obs_trace.reset_tracing()
    assert sorted((s["attrs"]["kind"], s["attrs"]["key"]) for s in tasks) == [("ix", "0"), ("ixm", "5")]
    alone = next(s for s in tasks if s["attrs"]["kind"] == "ixm")
    assert alone["attrs"]["members"] == 1 and alone["attrs"]["bytes"] == BLOCK


def test_every_inflate_is_counted_and_crcd(corpus):
    """A task evicted and read again is inflated and CRC'd again, and counted
    again, so ``bytes_decompressed`` equals the engine's ``crc_bytes``."""
    data, archive = corpus
    eng = DeviceDecodeEngine(force_device=True, max_batch_crc_bytes=1 << 18)
    try:
        with ParallelGzipReader(archive, resolver=eng, parallelization=1) as r:
            assert r._fetcher.task_points == 4  # 6 tasks, caches of 1 and 2
            for _ in range(2):
                for off in (20 * BLOCK, 0, 19 * BLOCK, BLOCK, 10 * BLOCK):
                    assert r.pread(off, 100) == data[off: off + 100]
            f = r.stats()["fetcher"]
        crc_bytes = eng.stats()["crc_bytes"]
    finally:
        eng.shutdown()
    assert f["indexed_tasks"] > 6 and f["bytes_decompressed"] > len(data)
    assert f["bytes_decompressed"] == f["member_crc_device_bytes"] == crc_bytes


def corrupt(archive: bytes, member: int, field: str, byte: int) -> bytes:
    end = members(archive)[member][2]
    at = end - 8 + (0 if field == "crc" else 4) + byte
    out = bytearray(archive)
    out[at] ^= 0xFF
    return bytes(out)


@pytest.mark.parametrize("field, byte", [("crc", 0), ("crc", 3), ("isize", 0), ("isize", 2)])
def test_a_damaged_trailer_is_never_served(corpus, engine, field, byte):
    data, archive = corpus
    bad = corrupt(archive, BAD, field, byte)
    assert [i for i, ok in enumerate(reference_verdicts(bad)) if not ok] == [BAD]
    with ParallelGzipReader(bad, resolver=engine) as r:
        with pytest.raises(GzipFooterError):
            r.pread(BAD * BLOCK + 100, 10)
        # The first task's members are sound and still served; so is a
        # sound member of the damaged member's own task, alone.
        assert r.pread(0, 16 * BLOCK) == data[: 16 * BLOCK]
        for _ in range(2):  # the run failed, or is failing
            assert r.pread(16 * BLOCK, 10) == data[16 * BLOCK: 16 * BLOCK + 10]
            with pytest.raises(GzipFooterError):
                r.pread(16 * BLOCK, 2 * BLOCK)  # runs into the damaged member
        assert r.stats()["fetcher"]["member_crc_mismatches"] >= 1
    with ParallelGzipReader(bad, resolver=engine) as r:
        with pytest.raises(GzipFooterError):
            r.read()


def test_an_isize_damaged_to_zero_does_not_drop_the_member():
    """An ISIZE of 0 marks the EOF block, which no read inflates: a member
    whose ISIZE reads 0 must not fall out of the stream unchecked. Its
    archive is not BGZF by its index, so the first pass reads it as gzip and
    checks the trailer there."""
    data = Registry().generator("fastq_like")(np.random.default_rng(5), 3 * BLOCK)
    archive = Registry().encoder("bgzf").encode(data, level=6)
    bad = corrupt(archive, 1, "isize", 1)  # 0x0000FF00 -> 0
    assert members(bad)[1][4] == 0 and reference_verdicts(bad) == [True, False, True, True]
    with ParallelGzipReader(bad, chunk_size=64 << 10) as r:
        with pytest.raises(GzipFooterError):
            r.pread(BLOCK + 10, 10)


def test_a_damaged_twin_is_never_served(corpus):
    """A BGZF twin is read by the same path: its member trailers are
    checked as the origin's would be."""
    data, archive = corpus
    origin = gzip_bytes(data, 1)  # stands in for the seek-hostile original
    twin = corrupt(archive, BAD, "crc", 1)
    with ParallelGzipReader(twin, codec="bgzf") as r:
        index = GzipIndex.from_bytes(r.index.to_bytes())
    store = IndexStore()
    store.register_twin(file_identity(origin), codec_tag="bgzf", data=twin, index=index)
    server = ArchiveServer(index_store=store, transcode="off",
                           engine_options={"force_device": True})
    try:
        h = server.open(origin)
        assert server.read_range(h, 0, 1000) == data[:1000]
        assert server.stat(h).twin == "bgzf"
        with pytest.raises(GzipFooterError):
            server.read_range(h, BAD * BLOCK, 1000)
        f = server.metrics()["per_reader"][h]["fetcher"]
        assert f["member_crc_mismatches"] >= 1 and f["member_crc_device_bytes"] > 0
    finally:
        server.shutdown()


@pytest.mark.parametrize("sizes", [
    [0], [1], [BLOCK], [0, 1, BLOCK, 3, 4097, 99_999, BLOCK - 1],
    [int(n) for n in np.random.default_rng(7).integers(0, 70_000, 40)],
])
def test_engine_crc_of_members_equals_zlib(engine, sizes):
    rng = np.random.default_rng(len(sizes))
    datas = [rng.bytes(n) for n in sizes]
    crcs, on_device = engine.crc32_many(datas)
    assert crcs == [zlib.crc32(d) for d in datas]
    assert on_device


def test_a_full_batch_goes_without_waiting():
    """Requests share a dispatch up to ``max_crc_requests``; a batch with no
    room for another request like its largest goes at once."""
    rng = np.random.default_rng(12)
    tasks = [[rng.bytes(BLOCK) for _ in range(16)] for _ in range(3)]
    slow = DeviceDecodeEngine(force_device=True, max_delay_s=0.5,
                              max_crc_requests=2)
    try:
        futs = [slow.submit_crcs(t) for t in tasks]
        for fut, t in zip(futs, tasks):
            assert fut.result(timeout=60) == [zlib.crc32(d) for d in t]
        assert slow.stats()["dispatches"] == 2  # two requests, then one
    finally:
        slow.shutdown()
    small = DeviceDecodeEngine(force_device=True, max_delay_s=30.0,
                               max_batch_crc_bytes=len(tasks[0]) * BLOCK * 3 // 2)
    try:
        small.crc32_many(tasks[0])  # compiles outside the timing below
        t0 = time.perf_counter()
        assert small.crc32_many(tasks[1])[0] == [zlib.crc32(d) for d in tasks[1]]
        assert time.perf_counter() - t0 < 15.0
    finally:
        small.shutdown()


def test_requests_share_one_dispatch(engine):
    """Parts of several requests are laid into one row: one dispatch, each
    request answered with its own CRCs."""
    rng = np.random.default_rng(11)
    many = [rng.bytes(n) for n in (BLOCK, 17, 5000)]
    one = rng.bytes(12_345)
    slow = DeviceDecodeEngine(force_device=True, max_delay_s=0.2)
    try:
        fut_many, fut_one = slow.submit_crcs(many), slow.submit_crc(one)
        assert fut_many.result(timeout=60) == [zlib.crc32(d) for d in many]
        assert fut_one.result(timeout=60) == zlib.crc32(one)
        assert slow.stats()["dispatches"] == 1
    finally:
        slow.shutdown()


def test_a_program_that_serves_a_damaged_member_cannot_run_the_deployment(monkeypatch):
    """``encoders/bgzip.py`` stops a run at set-up when the program under
    test serves a member whose trailer does not match."""
    codec = Registry().encoder("bgzip")
    assert codec.damaged_members_served() == []
    monkeypatch.setattr(repro.core, "ParallelGzipReader", functools.partial(ParallelGzipReader, verify=False))
    assert codec.damaged_members_served() == ["CRC32"]  # the ISIZE check stays
    with pytest.raises(SystemExit, match="CRC32"):
        codec.encode(bytes(100), level=6)


@pytest.fixture
def jax_cache_restored(tmp_path_factory, monkeypatch):
    """The harness turns JAX's persistent cache on for the process: keep it
    out of the checkout, and give the worker back the state it had."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(harness, "COMPILE_CACHE_DIR", str(tmp_path_factory.mktemp("jax_cache")))
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", before[1])
    compilation_cache.reset_cache()


def unverified(server) -> None:
    server.verify = False


@pytest.mark.parametrize("hook", [None, unverified])
def test_harness_rehearsal_holds_bgzf_to_device_crc(jax_cache_restored, capsys, hook):
    r = harness.main(["--workload", "fastq_bgzf.scan", "--seed", str(2**32 + 15),
                      "--seconds", "1.5", "--trace", "0", "--rehearsal"], server_hook=hook)
    capsys.readouterr()
    without = r["checks"]["bytes_without_device_crc"]["value"]
    if hook is None:
        assert r["correct"] is True and r["attempted"] > 0, r["checks"]
        assert without == 0
    else:
        assert r["correct"] is False and without > 0
