"""Seekable-zstd frame verification: every frame's size and the low 32 bits
of its XXH64 are checked against its seek-table entry before any of its
bytes is cached or served, the XXH64 on the device engine (interpret mode
here).

The plain references: ``kernels/ref.py``'s ``xxh64``, written as the xxHash
specification writes it, and libzstd's own frame checksum (a frame of the
same bytes compressed with a content checksum ends in XXH64's low 32 bits).
"""

from __future__ import annotations

import os
import struct
import sys

import numpy as np
import pytest

from repro.core.chunk_fetcher import ChunkFetcher
from repro.core.codec import frame_checksum, parse_zstd_seek_table
from repro.core.errors import ZstdFrameError
from repro.core.filereader import BytesFileReader
from repro.core.reader import ParallelGzipReader
from repro.kernels.engine import DeviceDecodeEngine
from repro.kernels.ref import xxh64
from repro.kernels.xxh64 import finish_frames, pack_frames, stripes_bucket, xxh64_rows
from repro.obs import trace as obs_trace

CHIP_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "benchmarks", "chip")
if CHIP_BENCH not in sys.path:
    sys.path.insert(0, CHIP_BENCH)

import harness  # noqa: E402
from registry import Registry  # noqa: E402
from test_bgzf_verify import jax_cache_restored  # noqa: E402,F401 - fixture

pytestmark = pytest.mark.kernels

FRAME = 64 << 10
#: Eight frames, the last short; the engine's 4 MiB batch over a reader's
#: four tasks in flight puts 16 frames in a task, so all eight in one.
N = 7 * FRAME + 4321
BAD = 5


def seekable(data: bytes, frame: int = FRAME, checksums: bool = True) -> bytes:
    """The benchmark's seekable-zstd writer (``encoders/zstd_seekable.py``),
    without its set-up probe."""
    return Registry().encoder("zstd_seekable").write(
        data, level=3, frame_bytes=frame, checksums=checksums)


def entry_offset(archive: bytes, frame: int) -> int:
    n = struct.unpack_from("<I", archive, len(archive) - 9)[0]
    return len(archive) - 9 - 12 * (n - frame)


@pytest.fixture(scope="module")
def corpus():
    data = Registry().generator("silesia_like")(np.random.default_rng(0x2D), N)
    return data, seekable(data)


@pytest.fixture
def engine():
    eng = DeviceDecodeEngine(force_device=True, max_delay_s=0.002)
    yield eng
    eng.shutdown()


# ---------------------------------------------------------------------------
# the reference and the kernel
# ---------------------------------------------------------------------------

def test_reference_of_the_empty_input():
    assert xxh64(b"") == 0xEF46DB3751D8E999


@pytest.mark.zstd
@pytest.mark.parametrize("n", [0, 1, 3, 4, 7, 8, 31, 32, 33, 63, 64, 1000, 65_541])
def test_reference_equals_libzstd_frame_checksum(n):
    data = np.random.default_rng(n).bytes(n)
    assert xxh64(data) & 0xFFFFFFFF == frame_checksum(data)


def test_kernel_equals_reference_on_a_mixed_batch():
    """One dispatch of frames from 0 to 70,000 bytes, most ending inside a
    stripe, each 64-bit digest against the reference's."""
    rng = np.random.default_rng(20)
    sizes = [0, 1, 31, 32, 33, 64, 127, 512, 513, 4_099, 70_000, 65_541]
    sizes += [int(n) for n in rng.integers(0, 70_000, 4)]
    datas = [rng.bytes(n) for n in sizes]
    rows = np.full((16, 8 * stripes_bucket(sizes)), -1, np.int32)  # stale bytes past each frame
    counts = pack_frames(rows, datas)
    acc = np.asarray(xxh64_rows(rows, counts, interpret=True))
    assert finish_frames(acc, datas) == [xxh64(d) for d in datas]


def test_engine_digests_on_the_device_count_as_checksummed_bytes(engine):
    rng = np.random.default_rng(21)
    datas = [rng.bytes(n) for n in (0, 100, 40_000, 70_001)]
    before = engine.stats()
    digests, on_device = engine.xxh64_many(datas)
    after = engine.stats()
    assert on_device and digests == [xxh64(d) & 0xFFFFFFFF for d in datas]
    assert after["crc_bytes"] - before["crc_bytes"] == sum(map(len, datas))
    assert after["fallbacks"]["xxh64"] == 0 and after["requests"]["xxh64"] == 1


@pytest.mark.zstd
def test_default_routing_computes_digests_with_libzstd():
    """Without ``force_device`` the request is libzstd's, counted as a
    fallback, and nothing reaches the device."""
    rng = np.random.default_rng(22)
    datas = [rng.bytes(n) for n in (5, 33, 9_000)]
    with DeviceDecodeEngine() as eng:
        digests, on_device = eng.xxh64_many(datas)
        st = eng.stats()
    assert not on_device and digests == [xxh64(d) & 0xFFFFFFFF for d in datas]
    assert st["fallbacks"]["xxh64"] == 1 and st["crc_bytes"] == 0 and st["dispatches"] == 0


# ---------------------------------------------------------------------------
# the read path
# ---------------------------------------------------------------------------

@pytest.mark.zstd
def test_every_frame_is_verified_on_the_engine(corpus, engine):
    data, archive = corpus
    obs_trace.enable_tracing()
    obs_trace.reset_tracing()
    try:
        with ParallelGzipReader(archive, resolver=engine) as r:
            assert r.codec.tag == "zstd" and r._fetcher.task_points == 16
            assert r.read() == data
            f = r.stats()["fetcher"]
        spans = obs_trace.drain_spans()
    finally:
        obs_trace.disable_tracing()
        obs_trace.reset_tracing()
    # The cold first read: frame 0 alone, then the run of all eight.
    assert f["frames_verified"] == 9 and f["members_verified"] == 0
    assert f["frames_unchecked"] == 0 and f["member_crc_mismatches"] == 0
    decoded = len(data) + FRAME
    assert f["bytes_decompressed"] == f["member_crc_device_bytes"] == decoded
    st = engine.stats()
    assert st["crc_bytes"] == decoded and st["fallbacks"]["xxh64"] == 0
    verify = [s for s in spans if s["name"] == "fetcher.member_verify"]
    assert sorted(s["attrs"]["members"] for s in verify) == [1, 8]
    assert {s["attrs"]["checksum"] for s in verify} == {"xxh64"}
    waits = [s for s in spans if s["name"] == "engine.batch_wait"]
    assert {s["attrs"]["kind"] for s in waits} == {"xxh64"}
    assert sum(s["attrs"]["nbytes"] for s in waits) == decoded


@pytest.mark.zstd
@pytest.mark.parametrize("on_device", [False, True])
def test_a_damaged_table_checksum_is_never_served(corpus, on_device):
    data, archive = corpus
    bad = bytearray(archive)
    bad[entry_offset(archive, BAD) + 8] ^= 0x40
    eng = DeviceDecodeEngine(force_device=True) if on_device else None
    try:
        with ParallelGzipReader(bytes(bad), resolver=eng, parallelization=1) as r:
            with pytest.raises(ZstdFrameError, match="XXH64"):
                r.pread(BAD * FRAME + 10, 10)
            # Neither the damaged frame alone nor any run holding it is cached.
            fetcher = r._fetcher
            keys = (("ixm", BAD), ("ix", BAD // fetcher.task_points))
            assert not any(k in c for k in keys for c in (fetcher.access_cache, fetcher.prefetch_cache))
            assert r.pread(0, FRAME) == data[:FRAME]  # a sound frame is served
            assert r.stats()["fetcher"]["member_crc_mismatches"] >= 1
        with ParallelGzipReader(bytes(bad), resolver=eng) as r:
            with pytest.raises(ZstdFrameError):
                r.read()
    finally:
        if eng is not None:
            eng.shutdown()


@pytest.mark.zstd
def test_a_wrong_decompressed_size_is_never_served(corpus):
    data, archive = corpus
    bad = bytearray(archive)
    at = entry_offset(archive, 2) + 4
    struct.pack_into("<I", bad, at, FRAME + 1)
    with ParallelGzipReader(bytes(bad), parallelization=1) as r:
        assert r.pread(0, 100) == data[:100]
        with pytest.raises(ZstdFrameError, match="size"):
            r.pread(2 * FRAME + 5, 10)


@pytest.mark.zstd
def test_an_archive_without_table_checksums_is_served_unchecked(corpus):
    data, _ = corpus
    plain = seekable(data, checksums=False)
    assert all(c is None for *_, c in parse_zstd_seek_table(BytesFileReader(plain)))
    with ParallelGzipReader(plain, parallelization=2) as r:
        assert r.read() == data
        f = r.stats()["fetcher"]
    assert f["frames_unchecked"] == 8 and f["frames_verified"] == 0


@pytest.mark.zstd
def test_an_imported_index_still_checks_the_table(corpus, engine):
    """A warm open skips the seek-table walk that builds the index: the
    table's checksums are read all the same."""
    data, archive = corpus
    with ParallelGzipReader(archive) as r:
        blob = r.index.to_bytes()
    bad = bytearray(archive)
    bad[entry_offset(archive, 0) + 8] ^= 1
    with ParallelGzipReader(bytes(bad), index=blob, resolver=engine) as r:
        with pytest.raises(ZstdFrameError):
            r.pread(0, 10)
    with ParallelGzipReader(archive, index=blob, resolver=engine) as r:
        assert r.pread(3 * FRAME, 2 * FRAME) == data[3 * FRAME: 5 * FRAME]
        assert r.stats()["fetcher"]["frames_verified"] >= 1


@pytest.mark.zstd
def test_twins_are_written_with_table_checksums():
    """The service's own zstd twins set ``Checksum_Flag``: 12-byte entries,
    each the low 32 bits of its frame's XXH64, checked as they are served."""
    from repro.core.synth import zstd_seekable_compress

    data = np.random.default_rng(23).integers(0, 8, 300_000, np.uint8).tobytes()
    twin = zstd_seekable_compress(data, 3, frame_size=FRAME)
    n, descriptor = struct.unpack_from("<IB", twin, len(twin) - 9)
    assert descriptor == 0x80 and n == 5
    frames = parse_zstd_seek_table(BytesFileReader(twin))
    for (_, _, dec, checksum), off in zip(frames, range(0, len(data), FRAME)):
        assert checksum == xxh64(data[off: off + dec]) & 0xFFFFFFFF
    with ParallelGzipReader(twin) as r:
        assert r.read() == data
        assert r.stats()["fetcher"]["frames_verified"] == 5


# ---------------------------------------------------------------------------
# the benchmark's deployment
# ---------------------------------------------------------------------------

@pytest.mark.zstd
def test_a_program_that_skips_the_xxh64_check_cannot_run_the_deployment(monkeypatch):
    """``encoders/zstd_seekable.py`` stops a run at set-up when the program
    serves a frame whose XXH64 does not match its seek-table checksum."""
    codec = Registry().encoder("zstd_seekable")
    assert codec.damaged_checksum_served() is False
    monkeypatch.setattr(ChunkFetcher, "_checksum_mismatches", lambda self, bodies, wants: [])
    assert codec.damaged_checksum_served() is True
    with pytest.raises(SystemExit, match="XXH64"):
        codec.encode(bytes(100), level=3, frame_bytes=64, checksums=True)


@pytest.mark.zstd
def test_the_reference_decoder_rejects_a_damaged_archive(corpus):
    data, archive = corpus
    codec = Registry().encoder("zstd_seekable")
    assert codec.decode(archive) == data
    bad = bytearray(archive)
    bad[entry_offset(archive, BAD) + 8] ^= 1
    with pytest.raises(ValueError, match="checksum"):
        codec.decode(bytes(bad))


@pytest.mark.zstd
def test_harness_rehearsal_holds_zstd_to_device_xxh64(jax_cache_restored, capsys):  # noqa: F811
    r = harness.main(["--workload", "silesia_zstd_seekable.scan", "--seed", str(2**32 + 20),
                      "--seconds", "1.5", "--trace", "0", "--rehearsal"])
    capsys.readouterr()
    assert r["correct"] is True and r["attempted"] > 0, r["checks"]
    assert r["checks"]["bytes_without_device_crc"]["value"] <= 0
    assert r["checks"]["stage2_cpu_fallbacks"]["value"] == 0
