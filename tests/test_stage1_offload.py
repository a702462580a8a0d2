"""Stage-1 decodes in worker processes: the pool's results equal the
in-thread ones field for field, false starts are counted the same, the
decoder's exceptions keep their types across the process boundary, and an
`ArchiveServer` owns its pool from start to shutdown."""

from __future__ import annotations

import dataclasses
import multiprocessing
import os
import sys
import zlib
from concurrent.futures import ProcessPoolExecutor

import numpy as np
import pytest

from conftest import gzip_bytes, make_base64
from repro.core import stage1_worker
from repro.core.chunk_fetcher import ChunkFetcher
from repro.core.codec import DeflateCodec
from repro.core.deflate import DecodeResult
from repro.core.errors import DeflateError, EndOfStream
from repro.core.filereader import open_file_reader
from repro.core.synth import bgzf_compress, multistream_gzip
from repro.obs import trace as obs_trace

CHIP_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "benchmarks", "chip")
if CHIP_BENCH not in sys.path:
    sys.path.insert(0, CHIP_BENCH)

from registry import Registry  # noqa: E402


@pytest.fixture(scope="module")
def pool():
    p = ProcessPoolExecutor(2, mp_context=multiprocessing.get_context("spawn"))
    yield p
    p.shutdown(wait=True, cancel_futures=True)


@pytest.fixture(scope="module")
def silesia_gz():
    data = Registry().generator("silesia_like")(np.random.default_rng(0x14), 160_000)
    return data, gzip_bytes(data, 6)


@pytest.fixture(scope="module")
def multi_member_gz():
    data = make_base64(np.random.default_rng(0x15), 150_000)
    return data, multistream_gzip(data, 6, stream_size=50_000)


@pytest.fixture(scope="module")
def damaged_raw():
    """Raw deflate with a run of garbage in its second chunk and its tail
    cut off: trials into either are false starts, one by `DeflateError`,
    one by `EndOfStream` at the end of the file."""
    data = make_base64(np.random.default_rng(7), 400_000)
    c = zlib.compressobj(6, zlib.DEFLATED, -15)
    raw = bytearray(c.compress(data) + c.flush())
    raw[100_000:100_400] = np.random.default_rng(1).bytes(400)
    return bytes(raw[: int(len(raw) * 0.8)])


def fetcher(archive: bytes, stage1_pool=None, framing="gzip", chunk_size=32 << 10):
    return ChunkFetcher(open_file_reader(archive), chunk_size=chunk_size, parallelization=1,
                        codec=DeflateCodec(framing), stage1_pool=stage1_pool)


def assert_same_result(a: DecodeResult, b: DecodeResult) -> None:
    for f in dataclasses.fields(DecodeResult):
        x, y = getattr(a, f.name), getattr(b, f.name)
        if f.name == "data":
            assert x.dtype == y.dtype and np.array_equal(x, y)
        else:
            assert x == y, f.name


def nominal_pass(f: ChunkFetcher):
    try:
        return [f._task_nominal(k) for k in range(f.n_nominal)]
    finally:
        f.shutdown()


@pytest.mark.parametrize("archive", ["silesia_gz", "multi_member_gz"])
def test_pool_results_equal_in_thread_results(archive, pool, request):
    data, comp = request.getfixturevalue(archive)
    here, there = fetcher(comp, chunk_size=16 << 10), fetcher(comp, pool, chunk_size=16 << 10)
    assert here.stage1_pool is None and there.stage1_pool is pool
    mine, theirs = nominal_pass(here), nominal_pass(there)
    assert any(r is not None and r.contains_markers() for r in mine)
    if archive == "multi_member_gz":
        assert any(r is not None and r.contains_markers() and r.member_starts and r.member_ends
                   for r in mine)
    for a, b in zip(mine, theirs):
        assert (a is None) == (b is None)
        if a is not None:
            assert_same_result(a, b)
    stats, offloaded = here.stats.as_dict(), there.stats.as_dict()
    assert offloaded.pop("stage1_offloaded") == there.n_nominal
    assert stats.pop("stage1_offloaded") == 0
    assert stats == offloaded


@pytest.fixture(scope="module")
def ints_gz():
    """Small-delta integers: markers vanish about 32 KiB into a chunk, so
    zlib decodes the rest of each 48 KiB (compressed) chunk."""
    rng = np.random.default_rng(0x1A)
    data = np.cumsum(rng.integers(0, 16, 100_000)).astype("<u4").tobytes()
    return data, gzip_bytes(data, 6)


def test_native_bytes_reach_the_fetcher_from_a_worker(ints_gz, pool):
    data, comp = ints_gz
    here = fetcher(comp, chunk_size=48 << 10)
    there = fetcher(comp, pool, chunk_size=48 << 10)
    mine, theirs = nominal_pass(here), nominal_pass(there)
    found = [r for r in theirs if r is not None]
    assert len(found) >= 2 and all(r.native_bytes > 0 for r in found[1:])
    for a, b in zip(mine, theirs):
        assert (a is None) == (b is None)
        if a is not None:
            assert_same_result(a, b)
    assert there.stats.stage1_native_bytes == sum(r.native_bytes for r in found)
    assert here.stats.stage1_native_bytes == there.stats.stage1_native_bytes
    # An exact task given the window hands every block to zlib.
    first = found[0]
    assert first.start_bit == 80 and found[1].start_bit == first.end_bit
    exact = fetcher(comp, pool, chunk_size=48 << 10)
    try:
        res = exact._task_exact(first.end_bit, data[first.size - (32 << 10) : first.size])
    finally:
        exact.shutdown()
    assert res.native_bytes == res.size == found[1].size
    assert exact.stats.stage1_native_bytes == res.native_bytes


def test_false_starts_in_a_worker_count_as_in_thread(damaged_raw, pool):
    here = fetcher(damaged_raw, framing="raw")
    there = fetcher(damaged_raw, pool, framing="raw")
    mine, theirs = nominal_pass(here), nominal_pass(there)
    assert [r is None for r in mine] == [r is None for r in theirs]
    assert mine[2] is None and mine[-1] is None  # the garbage, the cut tail
    assert here.stats.false_positive_starts >= 2
    for k in ("candidates_tried", "false_positive_starts", "nominal_tasks"):
        assert getattr(here.stats, k) == getattr(there.stats, k), k


@pytest.mark.parametrize("chunk, error", [(1, DeflateError), (6, EndOfStream)])
def test_decoder_errors_keep_their_type_across_processes(damaged_raw, pool, chunk, error):
    # The exact task that starts where chunk `chunk` ends runs into the
    # garbage (DeflateError) or the cut tail (EndOfStream).
    start = nominal_pass(fetcher(damaged_raw, framing="raw"))[chunk].end_bit
    for f in (fetcher(damaged_raw, framing="raw"), fetcher(damaged_raw, pool, framing="raw")):
        try:
            with pytest.raises(error):
                f._task_exact(start, None)
        finally:
            f.shutdown()


def test_only_codecs_a_worker_can_rebuild_are_offloaded(pool):
    class Custom(DeflateCodec):
        pass

    comp = gzip_bytes(b"x" * 4096)
    assert stage1_worker.offloadable(DeflateCodec("raw"))
    assert not stage1_worker.offloadable(Custom())
    f = ChunkFetcher(open_file_reader(comp), chunk_size=1 << 10, codec=Custom(), stage1_pool=pool)
    try:
        assert f.stage1_pool is None
    finally:
        f.shutdown()


# -- the server's pool --------------------------------------------------------

def served_scan(path: str, data: bytes, chunk_size: int = 16 << 10, **kwargs):
    """A cold scan of ``path`` through a fresh `ArchiveServer`; returns the
    bytes, the fleet's fetcher counters and the server, shut down."""
    from repro.service import ArchiveServer

    server = ArchiveServer(max_workers=2, chunk_size=chunk_size, device_engine="off",
                           transcode="off", **kwargs)
    try:
        handle = server.open(path)
        got = b"".join(server.read_range(handle, off, 32 << 10)
                       for off in range(0, len(data), 32 << 10))
        fetched = server.metrics()["fleet"]["fetcher"]
        server.close(handle, persist_index=False)
    finally:
        server.shutdown()
    return got, fetched, server


@pytest.fixture
def two_cpus(monkeypatch):
    monkeypatch.setattr(stage1_worker, "usable_cpus", lambda: 2)


def test_served_gzip_scan_decodes_stage1_in_the_pool(tmp_path, two_cpus):
    data = make_base64(np.random.default_rng(0x16), 150_000)
    path = tmp_path / "a.gz"
    path.write_bytes(gzip_bytes(data, 6))
    got, fetched, server = served_scan(str(path), data)
    assert got == data
    assert fetched["stage1_offloaded"] > 0
    assert fetched["stage1_offloaded"] == fetched["nominal_tasks"] + fetched["exact_tasks"]


def test_served_bgzf_scan_offloads_nothing(tmp_path, two_cpus):
    data = make_base64(np.random.default_rng(0x17), 150_000)
    path = tmp_path / "a.bgzf.gz"
    path.write_bytes(bgzf_compress(data, 6, block_size=16 << 10))
    got, fetched, server = served_scan(str(path), data)
    assert got == data
    assert fetched["indexed_tasks"] > 0 and fetched["stage1_offloaded"] == 0


def test_one_cpu_serves_without_a_pool(tmp_path, monkeypatch):
    monkeypatch.setattr(stage1_worker, "usable_cpus", lambda: 1)
    data = make_base64(np.random.default_rng(0x18), 60_000)
    path = tmp_path / "a.gz"
    path.write_bytes(gzip_bytes(data, 6))
    got, fetched, server = served_scan(str(path), data)
    assert server.stage1_pool is None
    assert got == data and fetched["nominal_tasks"] > 0 and fetched["stage1_offloaded"] == 0


def test_traced_offloaded_task_counts_the_workers_cpu(tmp_path, two_cpus):
    data = make_base64(np.random.default_rng(0x19), 150_000)
    path = tmp_path / "a.gz"
    path.write_bytes(gzip_bytes(data, 6))
    obs_trace.enable_tracing(1 << 16)
    obs_trace.reset_tracing()
    try:
        got, _, _ = served_scan(str(path), data)
        spans = obs_trace.recorded_spans()
    finally:
        obs_trace.disable_tracing()
        obs_trace.reset_tracing()
    assert got == data
    tasks = [s["attrs"] for s in spans if s["name"] == "fetcher.task"]
    stage1 = [a for a in tasks if a["kind"] in ("nom", "fp")]
    assert stage1 and all(a["offloaded"] is True for a in stage1)
    assert all(a["offloaded"] is False for a in tasks if a["kind"] == "ix")
    decoded = [s for s in spans if s["name"] == "fetcher.task" and s["attrs"]["bytes"]]
    assert decoded and all(0 < s["attrs"]["cpu_s"] <= s["dur_s"] + 1e-3 for s in decoded)


def test_traced_offloaded_task_carries_native_bytes(tmp_path, two_cpus, ints_gz):
    data, comp = ints_gz
    path = tmp_path / "ints.gz"
    path.write_bytes(comp)
    obs_trace.enable_tracing(1 << 16)
    obs_trace.reset_tracing()
    try:
        got, fetched, _ = served_scan(str(path), data, chunk_size=48 << 10)
        spans = obs_trace.recorded_spans()
    finally:
        obs_trace.disable_tracing()
        obs_trace.reset_tracing()
    assert got == data
    stage1 = [s["attrs"] for s in spans
              if s["name"] == "fetcher.task" and s["attrs"]["kind"] in ("nom", "fp")]
    assert stage1 and all(a["offloaded"] is True for a in stage1)
    found = [a for a in stage1 if a["bytes"]]
    assert all(0 <= a["native_bytes"] <= a["bytes"] for a in found)
    assert sum(a["native_bytes"] for a in found) == fetched["stage1_native_bytes"] > 0


def test_shutdown_leaves_no_worker_alive(two_cpus):
    before = {p.pid for p in multiprocessing.active_children()}
    from repro.service import ArchiveServer

    server = ArchiveServer(max_workers=2, device_engine="off", transcode="off")
    try:
        workers = [p for p in multiprocessing.active_children() if p.pid not in before]
        assert len(workers) == 2
        assert all(type(p).__name__ == "SpawnProcess" for p in workers)
    finally:
        server.shutdown()
    for p in workers:
        p.join(timeout=10)
        assert not p.is_alive()
