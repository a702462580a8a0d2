"""Tracing of the first pass from inside: stage-1 CPU time and bytes per
chunk task, what the frontier waits on, the metrics that read them, and the
clock those spans share with the device trace."""

from __future__ import annotations

import os
import sys
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from conftest import gzip_bytes, make_base64, make_random
from repro.core.chunk_fetcher import ChunkFetcher
from repro.core.codec import DeflateCodec
from repro.core.errors import GzipHeaderError
from repro.core.filereader import open_file_reader
from repro.core.reader import ParallelGzipReader
from repro.obs import trace as obs_trace

CHIP_BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "benchmarks", "chip")
if CHIP_BENCH not in sys.path:
    sys.path.insert(0, CHIP_BENCH)

import xtrace  # noqa: E402
from registry import Registry  # noqa: E402

@pytest.fixture(autouse=True)
def _clean_tracing():
    obs_trace.disable_tracing()
    obs_trace.reset_tracing()
    yield
    obs_trace.disable_tracing()
    obs_trace.reset_tracing()


@pytest.fixture(scope="module")
def corpus():
    # Base64 compresses to about 3/4: 16 KiB chunks give the first pass
    # several speculative chunks, which start with markers to replace.
    data = make_base64(np.random.default_rng(0x13), 150_000)
    return data, gzip_bytes(data, 6)


def cold_scan(comp: bytes) -> bytes:
    """A cold read of the whole stream whose speculative tasks have all
    ended on return: none may run on into the next test."""
    pool = ThreadPoolExecutor(max_workers=2)
    try:
        with ParallelGzipReader(comp, parallelization=2, chunk_size=16 << 10,
                                executor=pool) as r:
            return r.pread(0, 1 << 30)
    finally:
        pool.shutdown(wait=True)


def by_name(spans, name):
    return [s for s in spans if s["name"] == name]


# -- spans of a traced cold scan ---------------------------------------------

def test_traced_cold_scan_records_the_first_pass_from_inside(corpus):
    data, comp = corpus
    obs_trace.enable_tracing(1 << 16)
    assert cold_scan(comp) == data
    spans = obs_trace.recorded_spans()
    assert obs_trace.tracing_stats()["dropped"] == 0

    stage1 = [s for s in by_name(spans, "fetcher.task") if s["attrs"]["kind"] in ("nom", "fp")]
    assert stage1
    for s in stage1:
        assert 0 <= s["attrs"]["cpu_s"] <= s["dur_s"] + 1e-3
        assert s["attrs"]["bytes"] >= 0
    nominal = [s for s in stage1 if s["attrs"]["kind"] == "nom"]
    assert any(s["attrs"]["bytes"] > 0 for s in nominal)
    for s in nominal:
        assert s["attrs"]["trials"] >= (1 if s["attrs"]["bytes"] else 0)
        assert 0 <= s["attrs"]["find_s"] <= s["dur_s"]
        assert 0 <= s["attrs"]["find_bits"]

    waits = by_name(spans, "fetcher.chunk_wait")
    assert waits
    assert {s["attrs"]["source"] for s in waits} <= {"cache", "nominal", "exact"}
    # Every chunk the frontier took, with its size, sums to the stream.
    assert sum(s["attrs"]["bytes"] for s in waits) == len(data)

    frontier = {s["span_id"]: s for s in by_name(spans, "reader.frontier_wait")}
    stage2 = by_name(spans, "reader.stage2_wait")
    assert {s["attrs"]["part"] for s in stage2} == {"crc", "replace"}
    for s in waits + stage2:
        assert s["parent_id"] in frontier
    # The children lie inside their parents.
    inside = sum(s["dur_s"] for s in waits + stage2)
    assert inside <= sum(s["dur_s"] for s in frontier.values())


def test_nominal_tasks_over_stored_blocks_scan_few_bits():
    """In a run of stored blocks the dynamic-header scan stops at the first
    stored block of each chunk, a small part of the chunk's bits."""
    data = make_random(np.random.default_rng(0x18), 1 << 20)
    comp = gzip_bytes(data, 6)
    chunk = 256 << 10
    obs_trace.enable_tracing(1 << 16)
    pool = ThreadPoolExecutor(max_workers=2)
    try:
        with ParallelGzipReader(comp, parallelization=2, chunk_size=chunk, executor=pool) as r:
            assert r.pread(0, 1 << 30) == data
    finally:
        pool.shutdown(wait=True)
    nominal = [s["attrs"] for s in by_name(obs_trace.recorded_spans(), "fetcher.task")
               if s["attrs"]["kind"] == "nom"]
    assert sum(a["bytes"] > 0 for a in nominal) >= 3
    chunk_bits = chunk * 8
    for a in nominal:
        assert 0 <= a["find_bits"] < chunk_bits // 8


def test_served_cold_scan_traces_tasks_under_the_executor(corpus, tmp_path):
    from repro.service import ArchiveServer

    data, comp = corpus
    path = tmp_path / "corpus.gz"
    path.write_bytes(comp)
    server = ArchiveServer(max_workers=2, chunk_size=16 << 10, device_engine="off",
                           transcode="off")
    try:
        obs_trace.enable_tracing(1 << 16)
        handle = server.open(str(path))
        got = b"".join(server.read_range(handle, off, 32 << 10)
                       for off in range(0, len(data), 32 << 10))
        server.close(handle, persist_index=False)
    finally:
        server.shutdown()
        server.executor.shutdown(wait=True)
    assert got == data
    spans = obs_trace.recorded_spans()
    runs = {s["span_id"] for s in by_name(spans, "executor.run")}
    stage1 = [s for s in by_name(spans, "fetcher.task") if s["attrs"]["kind"] in ("nom", "fp")]
    assert stage1 and all(s["parent_id"] in runs and "cpu_s" in s["attrs"] for s in stage1)
    assert sum(s["attrs"]["bytes"] for s in by_name(spans, "fetcher.chunk_wait")) == len(data)


def test_disabled_tracing_records_nothing_and_reads_no_cpu_clock(corpus, monkeypatch):
    data, comp = corpus

    def no_clock():
        raise AssertionError("thread_time read while tracing is off")

    monkeypatch.setattr(time, "thread_time", no_clock)
    assert cold_scan(comp) == data
    assert obs_trace.recorded_spans() == []
    assert obs_trace.tracing_stats()["recorded_total"] == 0


# -- the false-start fault -----------------------------------------------------

class HeaderTrapCodec(DeflateCodec):
    """Offers a bogus candidate first, whose trial runs into bytes that are
    no gzip header, then the true chunk start."""

    BOGUS = 8 * 1000

    def __init__(self, true_start: int):
        super().__init__()
        self.true_start = true_start

    def find_chunk_starts(self, buf, start_bit, stop_bit):
        return iter([self.BOGUS, self.true_start])

    def decode_chunk(self, buf, start_bit, stop_bit=None, *, window=None, max_out=None):
        if start_bit == self.BOGUS:
            raise GzipHeaderError("bad gzip magic 4d7f")
        return super().decode_chunk(buf, start_bit, stop_bit, window=window, max_out=max_out)


def test_nominal_trial_into_a_bad_header_is_a_false_start(corpus):
    data, comp = corpus
    reader = open_file_reader(comp)
    codec = HeaderTrapCodec(true_start=80)  # right after the 10-byte header
    fetcher = ChunkFetcher(reader, chunk_size=1 << 20, parallelization=1, codec=codec)
    try:
        res = fetcher._task_nominal(0)
        assert res is not None and res.start_bit == 80
        assert fetcher.stats.false_positive_starts == 1
        assert fetcher.stats.candidates_tried == 2
        # The exact path still raises: a chunk asked for by its exact start
        # that is no chunk start is an error.
        with pytest.raises(GzipHeaderError):
            fetcher._task_exact(HeaderTrapCodec.BOGUS, None)
    finally:
        fetcher.shutdown()
        reader.close()


# -- metric readers ------------------------------------------------------------

def task(kind, dur, cpu, nbytes, **attrs):
    return {"name": "fetcher.task", "dur_s": dur,
            "attrs": {"kind": kind, "key": "0", "cpu_s": cpu, "bytes": nbytes, **attrs}}


SYNTHETIC = [
    task("nom", 2.0, 0.5, 3_000_000, native_bytes=2_500_000, find_s=0.5),
    task("fp", 1.0, 0.5, 1_000_000, native_bytes=500_000),
    task("ix", 5.0, 5.0, 9_000_000),  # indexed reads are not stage 1
    {"name": "reader.frontier_wait", "dur_s": 4.0, "attrs": {}},
    {"name": "fetcher.chunk_wait", "dur_s": 3.0, "attrs": {"source": "nominal"}},
    {"name": "reader.stage2_wait", "dur_s": 0.5, "attrs": {"part": "replace"}},
    {"name": "reader.stage2_wait", "dur_s": 0.25, "attrs": {"part": "crc"}},
]


@pytest.mark.parametrize("metric, expected", [
    ("stage1_cpu_MBps.scan", 4.0),                  # 4 MB over 1 CPU-second
    ("stage1_offcpu_share.scan", 100 * (1 - 1 / 3)),  # 1 CPU-s of 3 wall-s
    ("stage1_useful_share.scan", 75.0),             # 3 of 4 MB finalized
    ("frontier_stage1_wait_share.scan", 30.0),      # 3 s of a 10 s window
    ("frontier_stage2_wait_share.scan", 7.5),       # 0.75 s of 10 s
    ("stage1_native_share.scan", 75.0),             # zlib decoded 3 of 4 MB
    ("stage1_find_share.scan", 25.0),               # 0.5 s of a 2 s nominal task
])
def test_first_pass_metric_readers(metric, expected):
    from types import SimpleNamespace

    run = SimpleNamespace(window_s=10.0, spans=SYNTHETIC, fetcher={"bytes_decompressed": 3_000_000},
                          engine={}, trace=None, device_kind="TPU v5 lite")
    assert Registry().metric(metric)(run) == pytest.approx(expected)


def test_native_share_reads_a_recorded_scan(monkeypatch):
    """Over a traced cold scan the share reads the spans' ``native_bytes``
    (zlib takes over 32 KiB into each chunk of small-delta integers); over
    the same scan without zlib it reads 0."""
    from types import SimpleNamespace

    from repro.core import zlib_bridge

    rng = np.random.default_rng(0x1B)
    data = np.cumsum(rng.integers(0, 16, 80_000)).astype("<u4").tobytes()
    comp = gzip_bytes(data, 6)
    read = Registry().metric("stage1_native_share.scan")
    shares = []
    for libz in (zlib_bridge.libz, lambda: None):
        monkeypatch.setattr(zlib_bridge, "libz", libz)
        obs_trace.reset_tracing()
        obs_trace.enable_tracing(1 << 16)
        pool = ThreadPoolExecutor(max_workers=2)
        try:
            with ParallelGzipReader(comp, parallelization=2, chunk_size=48 << 10,
                                    executor=pool) as r:
                assert r.pread(0, 1 << 30) == data
        finally:
            pool.shutdown(wait=True)
            obs_trace.disable_tracing()
        spans = obs_trace.recorded_spans()
        tasks = [s["attrs"] for s in by_name(spans, "fetcher.task")
                 if s["attrs"]["kind"] in ("nom", "fp")]
        native = sum(a.get("native_bytes", 0) for a in tasks)
        decoded = sum(a["bytes"] for a in tasks)
        run = SimpleNamespace(window_s=1.0, spans=spans, fetcher={}, engine={}, trace=None,
                              device_kind="TPU v5 lite")
        assert read(run) == pytest.approx(100.0 * native / decoded)
        shares.append(read(run))
    assert shares[0] > 50.0 and shares[1] == 0.0


# -- one clock with the device trace ------------------------------------------

def test_span_timestamps_share_the_profiler_clock(tmp_path):
    import jax

    obs_trace.enable_tracing()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.clock_probe"):
            with obs_trace.span("probe"):
                time.sleep(0.01)
    finally:
        jax.profiler.stop_trace()
    raw = xtrace.load(str(tmp_path))
    starts = [s for plane, lines in raw["planes"].items() for _, events in lines
              for name, s, _ in events if name == "bench.clock_probe"]
    assert len(starts) == 1
    (probe,) = by_name(obs_trace.recorded_spans(), "probe")
    assert abs(probe["ts"] * 1e9 - raw["start_wall_ns"] - starts[0]) < 1e6
