"""DeviceDecodeEngine: batched stage-2 dispatch on the serving hot path.

Covers the engine's whole contract surface:
  * bit-identical parity vs the host reference under interpret=True,
    including ragged last tiles, empty chunks, and >1-slab requests;
  * coalescing of interleaved multi-tenant submissions into shared batches;
  * CRC parity (device lanes + GF(2) combine, zero-padded first lanes) vs zlib;
  * routing (without ``force_device`` every request takes the CPU path and
    is counted as a fallback);
  * shutdown-while-queued and readback failures — futures error, never hang;
  * the threading through codec -> fetcher -> reader -> server, with
    engine stats exported from ``ArchiveServer.metrics()``.
"""

import gzip
import io
import threading
import zlib

import numpy as np
import pytest

from repro.core.markers import replace_markers as cpu_replace
from repro.kernels.engine import DeviceDecodeEngine, EngineClosedError

from conftest import make_random, make_text

pytestmark = pytest.mark.kernels

TABLE_SIZE = 256 + 32768


def make_engine(**kw):
    kw.setdefault("force_device", True)
    kw.setdefault("max_delay_s", 0.005)
    return DeviceDecodeEngine(**kw)


def make_syms(rng, n):
    return rng.integers(0, TABLE_SIZE, n, dtype=np.int64).astype(np.uint16)


def make_window(rng, n=32768):
    return rng.integers(0, 256, n, dtype=np.uint8).tobytes()


# ---------------------------------------------------------------------------
# replace parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "n", [0, 1, 100, 8191, 8192, 8193, 3 * 8192 + 17]
)
def test_replace_parity_ragged_sizes(rng, n):
    """Empty, sub-tile, exact-tile, and ragged multi-tile requests all come
    back bit-identical to the host gather."""
    with make_engine() as eng:
        syms = make_syms(rng, n)
        window = make_window(rng)
        out = eng.submit_replace(syms, window).result(timeout=60)
        assert out.dtype == np.uint8
        np.testing.assert_array_equal(out, cpu_replace(syms, window))


@pytest.mark.parametrize("wlen", [0, 1, 300, 32768, 40000])
def test_replace_parity_window_lengths(rng, wlen):
    window = make_window(rng, wlen)
    if wlen == 0:
        syms = rng.integers(0, 256, 500, dtype=np.int64).astype(np.uint16)
    else:
        # markers must reference the defined (right-aligned) window region
        lo = 256 + (32768 - min(wlen, 32768))
        syms = rng.integers(lo, TABLE_SIZE, 500, dtype=np.int64).astype(np.uint16)
    with make_engine() as eng:
        out = eng.submit_replace(syms, window).result(timeout=60)
        np.testing.assert_array_equal(out, cpu_replace(syms, window))


def test_replace_oversized_request_spans_slabs(rng):
    """A single request larger than max_batch_tiles tiles is slabbed across
    several kernel launches and reassembled in order."""
    with make_engine(max_batch_tiles=2) as eng:
        syms = make_syms(rng, 5 * 8192 + 123)  # 6 tiles > 2-tile slabs
        window = make_window(rng)
        out = eng.submit_replace(syms, window).result(timeout=60)
        np.testing.assert_array_equal(out, cpu_replace(syms, window))
        assert eng.stats()["dispatches"] >= 3


def test_replace_uint8_passthrough(rng):
    with make_engine() as eng:
        data = np.frombuffer(make_random(rng, 100), np.uint8)
        out = eng.submit_replace(data, b"").result(timeout=60)
        np.testing.assert_array_equal(out, data)
        # resolved inline: no device work for already-resolved chunks
        assert eng.stats()["batches"] == 0


def test_interleaved_multi_tenant_batches(rng):
    """Concurrent submitters with distinct windows coalesce into shared
    dispatches (batched_requests > batches) and every result stays
    bit-identical to its own window's host gather."""
    with make_engine(max_delay_s=0.02, max_batch_tiles=32) as eng:
        windows = [make_window(rng) for _ in range(3)]
        cases = []
        for i in range(24):
            cases.append((make_syms(rng, 2000 + 37 * i), windows[i % 3]))

        results = [None] * len(cases)
        errors = []

        def submit(lo, hi):
            try:
                futs = [
                    (j, eng.submit_replace(cases[j][0], cases[j][1]))
                    for j in range(lo, hi)
                ]
                for j, f in futs:
                    results[j] = f.result(timeout=60)
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [
            threading.Thread(target=submit, args=(t * 8, (t + 1) * 8))
            for t in range(3)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not errors
        for (syms, window), out in zip(cases, results):
            np.testing.assert_array_equal(out, cpu_replace(syms, window))
        stats = eng.stats()
        assert stats["batched_requests"] == len(cases)
        # coalescing happened: strictly fewer dispatch groups than requests
        assert stats["batches"] < len(cases)
        assert stats["occupancy"] > 0.0


# ---------------------------------------------------------------------------
# crc parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [0, 1, 1023, 1024, 4096, 50_000])
def test_crc_parity_sizes(rng, n):
    blob = make_random(rng, n)
    with make_engine() as eng:
        assert eng.submit_crc(blob).result(timeout=60) == (
            zlib.crc32(blob) & 0xFFFFFFFF
        )


def test_crc_accepts_ndarray(rng):
    arr = np.frombuffer(make_random(rng, 5000), np.uint8)
    with make_engine() as eng:
        assert eng.crc32(arr) == (zlib.crc32(arr.tobytes()) & 0xFFFFFFFF)


def test_crc_batch_of_mixed_sizes(rng):
    blobs = [make_random(rng, n) for n in (10, 1024, 3333, 20_000)]
    with make_engine(max_delay_s=0.02) as eng:
        futs = [eng.submit_crc(b) for b in blobs]
        for blob, fut in zip(blobs, futs):
            assert fut.result(timeout=60) == (zlib.crc32(blob) & 0xFFFFFFFF)


# ---------------------------------------------------------------------------
# routing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [100, 8192])
@pytest.mark.parametrize("kind", ["replace", "crc"])
def test_singleton_requests_take_cpu_path(rng, kind, n):
    """A default engine (no ``force_device``) resolves every request on the
    CPU inline, whatever its size, and records it as a fallback (never
    queued, never batched)."""
    with DeviceDecodeEngine() as eng:
        if kind == "replace":
            syms = make_syms(rng, n)
            window = make_window(rng)
            np.testing.assert_array_equal(
                eng.replace_markers(syms, window), cpu_replace(syms, window)
            )
        else:
            blob = make_random(rng, n)
            assert eng.crc32(blob) == (zlib.crc32(blob) & 0xFFFFFFFF)
        stats = eng.stats()
        assert stats["requests"][kind] == 1
        assert stats["fallbacks"][kind] == 1
        assert stats["batches"] == 0


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

def test_shutdown_errors_queued_futures(rng):
    """Requests still queued at shutdown get EngineClosedError — a future
    the worker will never serve must fail loudly, not hang."""
    eng = make_engine(max_delay_s=0.5)  # long coalescing window: stay queued
    futs = [
        eng.submit_replace(make_syms(rng, 1000), make_window(rng))
        for _ in range(8)
    ]
    eng.shutdown()
    errored = completed = 0
    for f in futs:
        try:
            out = f.result(timeout=10)
        except EngineClosedError:
            errored += 1
        else:
            # an in-flight batch at shutdown is allowed to complete
            assert out.dtype == np.uint8
            completed += 1
    assert errored + completed == len(futs)
    assert errored > 0  # with a 500ms window, most never dispatched


def test_submit_after_shutdown_raises(rng):
    eng = make_engine()
    eng.shutdown()
    with pytest.raises(EngineClosedError):
        eng.submit_replace(make_syms(rng, 1000), b"")
    with pytest.raises(EngineClosedError):
        eng.submit_crc(b"data")
    # the blocking surface degrades to CPU instead of raising
    syms = make_syms(rng, 1000)
    np.testing.assert_array_equal(
        eng.replace_markers(syms, b""), cpu_replace(syms, b"")
    )
    assert eng.crc32(b"data") == (zlib.crc32(b"data") & 0xFFFFFFFF)


def test_import_touches_no_device():
    """Importing the kernel, engine and service modules initializes no JAX
    backend: on a TPU host an import-time probe would take the chip."""
    import os
    import subprocess
    import sys

    code = (
        "import repro.kernels, repro.kernels.engine, repro.service\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized()\n"
    )
    src = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    env = dict(os.environ, PYTHONPATH=src, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code], env=env, capture_output=True, text=True, timeout=120
    )
    assert proc.returncode == 0, proc.stderr


def test_readback_failure_fails_the_batch(rng, monkeypatch):
    """A device error surfaces at readback (np.asarray of the output). Every
    request of that batch must get the error, not wait forever, and the
    engine must keep serving the next batch."""
    import repro.kernels.engine as engine_mod

    class Unreadable:
        def __array__(self, *args, **kwargs):
            raise RuntimeError("device readback failed")

    real = engine_mod.marker_replace_tiles_multi
    monkeypatch.setattr(
        engine_mod, "marker_replace_tiles_multi", lambda *a: Unreadable()
    )
    with make_engine(max_delay_s=0.02) as eng:
        futs = [
            eng.submit_replace(make_syms(rng, 1000), make_window(rng))
            for _ in range(3)
        ]
        for f in futs:
            with pytest.raises(RuntimeError, match="readback"):
                f.result(timeout=10)
        assert eng.stats()["errors"] >= 1
        monkeypatch.setattr(engine_mod, "marker_replace_tiles_multi", real)
        syms, window = make_syms(rng, 1000), make_window(rng)
        np.testing.assert_array_equal(
            eng.submit_replace(syms, window).result(timeout=60),
            cpu_replace(syms, window),
        )


def test_shutdown_idempotent():
    eng = make_engine()
    eng.shutdown()
    eng.shutdown()
    assert eng.stats()["closed"]


# ---------------------------------------------------------------------------
# threading: codec -> fetcher -> reader -> server
# ---------------------------------------------------------------------------

def test_reader_roundtrip_bit_identical_with_engine(rng):
    """Full ParallelGzipReader round-trip through the engine (forced device)
    is bit-identical to the input, with CRC verification on."""
    data = make_text(rng, 300_000)
    gz = gzip.compress(data, 6)
    with make_engine(max_delay_s=0.002) as eng:
        from repro.core.reader import ParallelGzipReader

        with ParallelGzipReader(
            io.BytesIO(gz), chunk_size=32 << 10, parallelization=4,
            resolver=eng, verify=True,
        ) as r:
            assert r.read() == data
        stats = eng.stats()
        assert stats["batches"] > 0  # stage 2 actually ran on the engine
        assert stats["crc_bytes"] > 0  # CRC verification routed through too


def test_reader_pread_with_engine(rng):
    data = make_text(rng, 200_000)
    gz = gzip.compress(data, 6)
    with make_engine() as eng:
        from repro.core.reader import ParallelGzipReader

        with ParallelGzipReader(
            io.BytesIO(gz), chunk_size=32 << 10, resolver=eng
        ) as r:
            for start, ln in ((0, 100), (50_000, 9999), (199_000, 5000)):
                assert r.pread(start, ln) == data[start : start + ln]


def test_codec_resolver_hook(rng):
    """DeflateCodec.replace_markers routes through the resolver when set and
    falls back to the host path when cleared."""
    from repro.core.codec import DeflateCodec

    class CountingResolver:
        def __init__(self):
            self.calls = 0

        def replace_markers(self, symbols, window):
            self.calls += 1
            return cpu_replace(symbols, window)

        def crc32(self, data):
            if isinstance(data, np.ndarray):
                data = data.tobytes()
            return zlib.crc32(data) & 0xFFFFFFFF

    codec = DeflateCodec()
    res = CountingResolver()
    codec.set_stage2_resolver(res)
    syms = make_syms(rng, 1000)
    window = make_window(rng)
    np.testing.assert_array_equal(
        codec.replace_markers(syms, window), cpu_replace(syms, window)
    )
    assert res.calls == 1
    # uint8 input short-circuits before the resolver
    plain = np.frombuffer(make_random(rng, 64), np.uint8)
    np.testing.assert_array_equal(codec.replace_markers(plain, None), plain)
    assert res.calls == 1
    codec.set_stage2_resolver(None)
    codec.replace_markers(syms, window)
    assert res.calls == 1


def test_server_owns_engine_and_exports_stats(rng, tmp_path):
    """ArchiveServer("auto") owns a shared engine, serves bit-identical
    reads, exports engine stats in metrics(), and records CPU fallbacks for
    interactive traffic on an interpret host."""
    from repro.service.server import ArchiveServer

    # big enough that the *compressed* stream spans several chunks, so
    # stage 2 actually produces marker chunks to route
    data = make_text(rng, 600_000)
    path = tmp_path / "x.gz"
    path.write_bytes(gzip.compress(data, 6))
    with ArchiveServer(chunk_size=16 << 10) as srv:
        assert srv.device_engine is not None
        h = srv.open(str(path), tenant="t1")
        got = srv.read_range(h, 0, len(data))
        assert bytes(got) == data
        m = srv.metrics()
        assert m["engine"]["platform"] == "cpu"
        assert m["engine"]["interpret"]  # Pallas interprets on CPU only
        # interactive scenario on an interpret host: every stage-2 request
        # fell back to the CPU and the stats prove it
        assert m["engine"]["fallbacks"]["replace"] > 0
        assert m["engine"]["requests"]["replace"] >= m["engine"]["fallbacks"]["replace"]
        from repro.service.metrics import format_summary

        assert any(
            line.startswith("engine[") for line in format_summary(m).splitlines()
        )
    assert srv.device_engine.stats()["closed"]


def test_server_forced_device_engine_batches(rng, tmp_path):
    """An externally owned force_device engine threads through the server and
    actually batches; the server must NOT shut it down."""
    from repro.service.server import ArchiveServer

    data = make_text(rng, 600_000)
    path = tmp_path / "x.gz"
    path.write_bytes(gzip.compress(data, 6))
    with make_engine() as eng:
        with ArchiveServer(chunk_size=16 << 10, device_engine=eng) as srv:
            h = srv.open(str(path))
            assert bytes(srv.read_range(h, 0, len(data))) == data
            assert srv.metrics()["engine"]["batches"] > 0
        assert not eng.stats()["closed"]  # external engine survives server


def test_server_engine_off():
    from repro.service.server import ArchiveServer

    with ArchiveServer(device_engine="off") as srv:
        assert srv.device_engine is None
        assert "engine" not in srv.metrics()
