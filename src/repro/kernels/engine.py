"""DeviceDecodeEngine — batched stage-2 dispatch on the serving hot path.

The paper's two-stage scheme (§2.2) leaves stage 2 — marker resolution and
CRC32 — embarrassingly data-parallel, which is exactly what an accelerator
rewards *if* it is fed full batches. The per-chunk wrappers in ``ops.py``
pay one host↔device round trip, one table upload, and one dispatch per
chunk; CODAG and Sitaridi et al. (PAPERS.md) both show that decompression
on wide-SIMD hardware lives or dies on amortizing exactly those costs.

This engine is the process-wide aggregation point: every reader/tenant
submits marker-resolution, CRC32 and XXH64 requests here; a single
dispatcher thread packs them into fixed-size batches, dispatches the batched
kernels (``marker_replace_tiles_multi`` / ``crc32_segments_batched`` /
``xxh64_stripes``) once per batch, and scatters results back to per-request
futures. An XXH64 request holds the frames of a seekable-zstd task, whose
digests the fetcher checks against the seek table; it batches like a CRC
request, under the same byte cap.

Layout and policy:

  * **Tile packing** — symbol streams are padded into (8, 1024) int32 tiles
    (``marker_replace.TILE``); a batch is a stack of tiles from many chunks
    plus a per-tile ``int32`` table selector. Distinct windows dedupe into a
    small VMEM-resident stack of replacement tables (132 KiB each, capped at
    ``max_tables`` per dispatch).
  * **Shape bucketing** — tile counts and table counts round up to powers of
    two (capped at ``max_batch_tiles``), so the jitted dispatches compile a
    bounded set of shapes once and are reused forever (cached compiled
    kernels). A CRC batch lays all its parts into the lanes of one row
    (``crc32.pack_parts``), so its shape is ``seg_len`` alone, a power of
    two: whatever the number of requests, one shape per size class. An
    XXH64 batch gives each frame a row (``xxh64.pack_frames``): its shape
    is the frames and the stripes, each a power of two.
  * **Double-buffered staging** — two host staging buffers per bucket shape
    alternate between dispatches, and result readback of batch N overlaps
    the launch of batch N+1 (one dispatch in flight), so host packing and
    device compute pipeline instead of serializing.
  * **Routing** — without ``force_device`` every request is resolved on
    the CPU inline (``core.markers`` / ``zlib.crc32`` / libzstd's frame
    checksum, ``core.codec.frame_checksum``) and counted in ``fallbacks``;
    with it, every request goes to the device. A size
    threshold measured on the device belongs in ``_route_device``, the one
    place the rule lives.
  * **One device** — every array the engine dispatches is placed on
    ``self.device`` (the first device JAX reports), and the kernels are
    interpreted only when that device is the CPU.

Bit-identity: the device path computes the same gather/CRC as the host path
(int32 tables hold byte values; CRCs are exact), so results are
bit-identical regardless of routing — verified by the parity suite in
``tests/test_device_engine.py`` and the reader round-trip tests.
"""

from __future__ import annotations

import threading
import time
import zlib as _zlib
from collections import OrderedDict, deque
from concurrent.futures import Future
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from ..core.codec import frame_checksum as _cpu_frame_checksum
from ..core.markers import replace_markers as _cpu_replace_markers
from ..obs import trace as _obs_trace
from .crc32 import (
    N_SEGMENTS,
    crc32_lanes,
    finish_parts,
    pack_parts,
    parts_words,
)
from .marker_replace import (
    TABLE_SIZE,
    TILE,
    TILE_COLS,
    TILE_ROWS,
    marker_replace_tiles_multi,
)
from .ops import interpret_on
from .ref import make_replacement_table
from .xxh64 import finish_frames, pack_frames, stripes_bucket, xxh64_rows


class EngineClosedError(RuntimeError):
    """Raised on futures queued (or submits attempted) after shutdown."""


def _pow2_at_least(n: int, cap: Optional[int] = None) -> int:
    p = 1
    while p < n:
        p *= 2
    return min(p, cap) if cap is not None else p


class _Request:
    """One queued request: a marker stream, or the byte strings (``parts``)
    whose CRC32s or XXH64s one future resolves to (a list, or an int for
    ``crc32``)."""

    __slots__ = ("kind", "symbols", "window", "parts", "many", "tiles", "nbytes", "future")

    def __init__(self, kind: str, *, symbols=None, window=None, parts=(), many=False):
        self.kind = kind
        self.symbols = symbols
        self.window = window
        self.parts = parts
        self.many = many
        if kind == "replace":
            self.nbytes = int(symbols.shape[0])
            self.tiles = max(1, -(-self.nbytes // TILE))
        else:
            self.nbytes = sum(len(p) for p in parts)
            self.tiles = 0
        self.future: Future = Future()


class DeviceDecodeEngine:
    """Process-wide batched dispatcher for stage-2 device work.

    One engine per process (the service layer owns it like ``CachePool`` /
    ``FairExecutor``); every entry point is thread-safe. The duck-typed
    resolver surface consumed by ``core.codec`` / ``core.chunk_fetcher``:

      * ``replace_markers(symbols, window) -> np.uint8 ndarray`` (blocking)
      * ``crc32(data) -> int`` and ``crc32_many(datas) -> (crcs, on_device)``
        (blocking)
      * ``xxh64_many(datas) -> (digests, on_device)`` (blocking): the low 32
        bits of each one's XXH64, as zstd frames and seek tables hold them
      * ``submit_replace`` / ``submit_crc`` / ``submit_crcs`` /
        ``submit_xxh64s`` -> Future (async variants)
      * ``stats() -> dict`` / ``shutdown()``
    """

    def __init__(
        self,
        *,
        max_batch_tiles: int = 32,
        max_tables: int = 8,
        max_batch_crc_bytes: int = 4 << 20,
        max_crc_requests: int = 16,
        max_delay_s: float = 0.002,
        force_device: bool = False,
    ):
        self.max_batch_tiles = max(1, max_batch_tiles)
        self.max_tables = _pow2_at_least(max(1, max_tables))
        self.max_batch_crc_bytes = max(1 << 10, max_batch_crc_bytes)
        # Requests per CRC batch; their parts together fit the row's 1024
        # lanes and their bytes ``max_batch_crc_bytes``.
        self.max_crc_requests = max(1, max_crc_requests)
        self.max_delay_s = max(0.0, max_delay_s)
        self.force_device = force_device
        self.device = jax.devices()[0]
        self.interpret = interpret_on(self.device)

        self._cond = threading.Condition()
        self._rq: Deque[_Request] = deque()
        self._cq: Deque[_Request] = deque()
        self._xq: Deque[_Request] = deque()
        self._closed = False
        # Replacement tables are pure functions of the window; serving reads
        # hit the same windows repeatedly (re-reads, overlapping ranges), so
        # an LRU of built tables (132 KiB each) turns the per-dispatch table
        # cost into a cache probe. Worker-thread only — no lock needed.
        self._table_cache: "OrderedDict[bytes, np.ndarray]" = OrderedDict()
        self._table_cache_cap = 32
        # Device-side cache of padded, uploaded table *stacks* keyed by the
        # dispatch's window set — a repeat batch skips assembly + transfer.
        self._stack_cache: "OrderedDict[Tuple, Any]" = OrderedDict()
        self._stack_cache_cap = 8
        # Double-buffered host staging: two numpy buffers per bucket shape,
        # alternating between consecutive dispatches of that shape so packing
        # batch N+1 never scribbles over memory the in-flight transfer of
        # batch N may still be reading (at most one batch is in flight).
        self._staging: Dict[Tuple, List[np.ndarray]] = {}

        # Counters (mutated under self._cond).
        self._requests = {"replace": 0, "crc": 0, "xxh64": 0}
        self._fallbacks = {"replace": 0, "crc": 0, "xxh64": 0}
        self._batches = 0
        self._dispatches = 0
        self._batched_requests = 0
        self._tiles_dispatched = 0
        self._tiles_padded = 0
        # Bytes checksummed on the device, any checksum (CRC32, XXH64).
        self._crc_bytes = 0
        self._max_queue_depth = 0
        self._errors = 0

        self._worker = threading.Thread(
            target=self._worker_loop, name="device-decode-engine", daemon=True
        )
        self._worker.start()

    # ------------------------------------------------------------------
    # routing policy
    # ------------------------------------------------------------------

    def _route_device(self) -> bool:
        return not self._closed and self.force_device

    def _count(self, counter: Dict[str, int], kind: str) -> None:
        with self._cond:
            counter[kind] += 1

    # ------------------------------------------------------------------
    # submission API
    # ------------------------------------------------------------------

    def submit_replace(self, symbols: np.ndarray, window: Optional[bytes]) -> Future:
        """Queue a marker-resolution request; resolves to a uint8 array.

        Already-resolved (uint8) and empty requests resolve immediately
        without touching the queue.
        """
        self._count(self._requests, "replace")
        fut: Future = Future()
        if symbols.dtype == np.uint8 or symbols.shape[0] == 0:
            fut.set_result(np.asarray(symbols, dtype=np.uint8))
            return fut
        req = _Request("replace", symbols=symbols, window=window)
        self._enqueue(self._rq, req)
        return req.future

    def submit_crc(self, data) -> Future:
        """Queue a CRC32 request; resolves to the int checksum."""
        return self._submit_crc([_as_bytes(data)], many=False)

    def submit_crcs(self, datas: Sequence) -> Future:
        """Queue one request for the CRC32 of each of ``datas`` (at most 1024
        parts); resolves to the list of checksums. The parts dispatch
        together, with those of other requests queued beside them."""
        return self._submit_crc([_as_bytes(d) for d in datas], many=True)

    def _submit_crc(self, parts: List[bytes], *, many: bool) -> Future:
        if len(parts) > N_SEGMENTS:
            raise ValueError("%d parts in one CRC request; at most %d" % (len(parts), N_SEGMENTS))
        self._count(self._requests, "crc")
        req = _Request("crc", parts=parts, many=many)
        if req.nbytes == 0:
            req.future.set_result([0] * len(parts) if many else 0)
            return req.future
        self._enqueue(self._cq, req)
        return req.future

    def submit_xxh64s(self, datas: Sequence) -> Future:
        """Queue one request for the low 32 bits of the XXH64 (seed 0) of
        each of ``datas``; resolves to the list of digests. The frames
        dispatch together, with those of other requests queued beside them."""
        self._count(self._requests, "xxh64")
        req = _Request("xxh64", parts=[_as_bytes(d) for d in datas])
        if not req.parts:
            req.future.set_result([])
            return req.future
        self._enqueue(self._xq, req)
        return req.future

    def _enqueue(self, queue: Deque[_Request], req: _Request) -> None:
        with self._cond:
            if self._closed:
                raise EngineClosedError("DeviceDecodeEngine is shut down")
            queue.append(req)
            depth = len(self._rq) + len(self._cq) + len(self._xq)
            if depth > self._max_queue_depth:
                self._max_queue_depth = depth
            self._cond.notify_all()

    # ------------------------------------------------------------------
    # blocking resolver surface (what codec/fetcher call)
    # ------------------------------------------------------------------

    def replace_markers(self, symbols: np.ndarray, window: Optional[bytes]) -> np.ndarray:
        """Resolve a marker stream — batched on the device where
        ``_route_device`` says so, inline on the CPU otherwise."""
        if symbols.dtype == np.uint8:
            return symbols
        if self._route_device():
            try:
                fut = self.submit_replace(symbols, window)
                with _obs_trace.timed(
                    "engine.batch_wait", {"kind": "replace", "symbols": int(symbols.shape[0])}
                ):
                    return fut.result()
            except EngineClosedError:
                pass  # raced shutdown: serve on the CPU like any fallback
        else:
            self._count(self._requests, "replace")
        self._count(self._fallbacks, "replace")
        return _cpu_replace_markers(symbols, window)

    def crc32(self, data) -> int:
        """CRC32 — batched on the device where ``_route_device`` says so,
        zlib otherwise."""
        return self.crc32_many([data])[0][0]

    def crc32_many(self, datas: Sequence) -> Tuple[List[int], bool]:
        """CRC32 of each of ``datas`` as one request, routed like ``crc32``;
        returns the checksums and whether the device computed them."""
        datas = [_as_bytes(d) for d in datas]
        nbytes = sum(len(d) for d in datas)
        if self._route_device():
            try:
                fut = self.submit_crcs(datas)
                with _obs_trace.timed("engine.batch_wait", {"kind": "crc", "nbytes": nbytes}):
                    return fut.result(), True
            except EngineClosedError:
                pass
        else:
            self._count(self._requests, "crc")
        self._count(self._fallbacks, "crc")
        return [_zlib.crc32(d) & 0xFFFFFFFF for d in datas], False

    def xxh64_many(self, datas: Sequence) -> Tuple[List[int], bool]:
        """The low 32 bits of the XXH64 of each of ``datas`` as one request,
        routed like ``crc32``: batched on the device, or libzstd's frame
        checksum on the CPU; returns the digests and whether the device
        computed them."""
        datas = [_as_bytes(d) for d in datas]
        if self._route_device():
            try:
                fut = self.submit_xxh64s(datas)
                with _obs_trace.timed("engine.batch_wait",
                                      {"kind": "xxh64", "nbytes": sum(len(d) for d in datas)}):
                    return fut.result(), True
            except EngineClosedError:
                pass
        else:
            self._count(self._requests, "xxh64")
        self._count(self._fallbacks, "xxh64")
        return [_cpu_frame_checksum(d) for d in datas], False

    # ------------------------------------------------------------------
    # dispatcher thread
    # ------------------------------------------------------------------

    def _full(self, queue: Deque[_Request], max_parts: Optional[int]) -> bool:
        """The queued checksum requests fill a batch: as many requests as one
        may hold, ``max_parts`` parts (every CRC lane) taken, or no room for
        one more like the largest."""
        if not queue:
            return False
        return (
            len(queue) >= self.max_crc_requests
            or (max_parts is not None and sum(len(r.parts) for r in queue) >= max_parts)
            or sum(r.nbytes for r in queue) + max(r.nbytes for r in queue)
            > self.max_batch_crc_bytes
        )

    def _take(self, queue: Deque[_Request], max_parts: Optional[int]) -> List[_Request]:
        """Pop the checksum requests of one batch off ``queue``: at least
        one, then while they fit the request, part and byte caps."""
        out: List[_Request] = []
        parts = nbytes = 0
        while queue:
            req = queue[0]
            if out and (
                len(out) >= self.max_crc_requests
                or (max_parts is not None and parts + len(req.parts) > max_parts)
                or nbytes + req.nbytes > self.max_batch_crc_bytes
            ):
                break
            queue.popleft()
            out.append(req)
            parts += len(req.parts)
            nbytes += req.nbytes
        return out

    def _collect_batch(self) -> Optional[Tuple[List[_Request], List[_Request], List[_Request], float]]:
        """Block until work (or shutdown); return one coalesced batch and the
        seconds spent waiting for it to fill.

        After the first request arrives, waits up to ``max_delay_s`` for the
        batch to fill — the window in which concurrent readers' stage-2 work
        coalesces into one dispatch. Returns None at shutdown.
        """
        with self._cond:
            while not self._closed and not self._rq and not self._cq and not self._xq:
                self._cond.wait()
            if self._closed:
                return None
            t_fill = time.monotonic()
            if self.max_delay_s > 0.0:
                deadline = t_fill + self.max_delay_s
                while not self._closed:
                    tiles = sum(r.tiles for r in self._rq)
                    if (tiles >= self.max_batch_tiles or self._full(self._cq, N_SEGMENTS)
                            or self._full(self._xq, None)):
                        break
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        break
                    self._cond.wait(remaining)
                if self._closed:
                    return None
            fill_s = time.monotonic() - t_fill

            rep: List[_Request] = []
            tiles = 0
            tables: set = set()
            while self._rq:
                req = self._rq[0]
                key = bytes(req.window or b"")
                new_table = key not in tables
                if rep and (
                    tiles + req.tiles > self.max_batch_tiles
                    or (new_table and len(tables) >= self.max_tables)
                ):
                    break
                self._rq.popleft()
                rep.append(req)
                tiles += req.tiles
                tables.add(key)
            return rep, self._take(self._cq, N_SEGMENTS), self._take(self._xq, None), fill_s

    def _worker_loop(self) -> None:
        pending = None  # resolve-callback of the previous (in-flight) batch
        while True:
            batch = self._collect_batch()
            if batch is None:
                break
            rep, crc, xxh, fill_s = batch
            launched = []
            try:
                if rep:
                    launched.append(self._dispatch_replace(rep))
                if crc:
                    launched.append(self._dispatch_crc(crc, fill_s))
                if xxh:
                    launched.append(self._dispatch_xxh64(xxh, fill_s))
            except BaseException as exc:  # noqa: BLE001 - fail the batch, keep serving
                with self._cond:
                    self._errors += 1
                _fail(rep + crc + xxh, exc)
                continue
            # Pipeline: resolve the *previous* dispatch only after launching
            # this one — readback of batch N overlaps device work of N+1.
            if pending is not None:
                self._resolve_safely(pending)
            if launched:
                with self._cond:
                    self._batches += 1
                    self._batched_requests += len(rep) + len(crc) + len(xxh)
            pending = launched or None
            with self._cond:
                idle = not self._rq and not self._cq and not self._xq
            if idle and pending is not None:
                self._resolve_safely(pending)
                pending = None
        if pending is not None:
            self._resolve_safely(pending)

    def _resolve_safely(self, launched) -> None:
        """Read back launched dispatches; a failure fails its requests.

        Device errors surface here, at readback, not at launch. Every
        future of a failed dispatch gets the exception: a reader blocked on
        one must never wait for a result that will not come.
        """
        for resolve, reqs in launched:
            try:
                resolve()
            except BaseException as exc:  # noqa: BLE001 - fail the batch, keep serving
                with self._cond:
                    self._errors += 1
                _fail(reqs, exc)

    # -- marker replacement dispatch ------------------------------------

    def _replacement_table(self, window: bytes) -> np.ndarray:
        table = self._table_cache.get(window)
        if table is not None:
            self._table_cache.move_to_end(window)
            return table
        table = make_replacement_table(np.frombuffer(window, np.uint8))
        self._table_cache[window] = table
        if len(self._table_cache) > self._table_cache_cap:
            self._table_cache.popitem(last=False)
        return table

    def _staging_buffer(self, key: Tuple, shape: Tuple[int, ...]) -> np.ndarray:
        bufs = self._staging.get(key)
        if bufs is None:
            bufs = [np.zeros(shape, np.int32), np.zeros(shape, np.int32)]
            self._staging[key] = bufs
        bufs.reverse()  # alternate: the other one may still be in flight
        return bufs[0]

    def _table_stack(self, keys: Tuple[bytes, ...]) -> Any:
        """Device-resident (n_tables, TABLE_SIZE) stack for a window set.

        Window sets recur across dispatches (the same few chunks' windows
        serve a burst of reads), so the padded, uploaded stack is cached
        whole — a hit skips both the host assembly and the transfer.
        """
        n_tables = _pow2_at_least(len(keys), self.max_tables)
        cache_key = (n_tables,) + keys
        stack = self._stack_cache.get(cache_key)
        if stack is not None:
            self._stack_cache.move_to_end(cache_key)
            return stack
        tab_stack = np.zeros((n_tables, TABLE_SIZE), np.int32)
        for i in range(n_tables):
            tab_stack[i] = self._replacement_table(keys[min(i, len(keys) - 1)])
        stack = jax.device_put(tab_stack, self.device)
        self._stack_cache[cache_key] = stack
        if len(self._stack_cache) > self._stack_cache_cap:
            self._stack_cache.popitem(last=False)
        return stack

    def _dispatch_replace(self, reqs: List[_Request]):
        """Pack, upload, and launch one marker batch.

        Returns ``(resolve, reqs)``: ``resolve()`` reads the result back and
        completes the requests' futures.
        """
        # Dedupe windows into a table stack; selector per tile.
        table_ids: Dict[bytes, int] = {}
        total_tiles = sum(r.tiles for r in reqs)
        tid_flat = np.zeros(total_tiles, np.int32)
        spans: List[Tuple[_Request, int, int]] = []
        single = total_tiles <= self.max_batch_tiles
        if single:
            # Common case: the whole batch is one slab — pack symbols
            # straight into the staging buffer, no intermediate copy. Pad
            # gaps keep whatever the buffer last held: stale values were
            # themselves valid symbols (< TABLE_SIZE), so the gather stays
            # in range and the padded outputs are simply never read.
            bucket = _pow2_at_least(total_tiles, self.max_batch_tiles)
            stage = self._staging_buffer(
                ("rep", bucket), (bucket, TILE_ROWS, TILE_COLS)
            )
            sym_flat = stage.reshape(-1)
        else:
            # Oversized: every slab is a view of one fresh buffer, so no
            # slab's upload can be overwritten by the next slab's packing.
            n_slabs = -(-total_tiles // self.max_batch_tiles)
            sym_flat = np.zeros(n_slabs * self.max_batch_tiles * TILE, np.int32)
        pos = 0
        for req in reqs:
            key = bytes(req.window or b"")
            tid = table_ids.get(key)
            if tid is None:
                tid = len(table_ids)
                table_ids[key] = tid
            n = req.nbytes
            sym_flat[pos * TILE : pos * TILE + n] = req.symbols
            tid_flat[pos : pos + req.tiles] = tid
            spans.append((req, pos * TILE, n))
            pos += req.tiles

        tab_dev = self._table_stack(tuple(table_ids))

        # Slab the packed tiles: oversized single requests span multiple
        # dispatches, everything else fits one. Bucketed shapes keep the set
        # of compiled programs small and cached.
        outs: List[Tuple[Any, int]] = []
        slabs = 0
        for s0 in range(0, total_tiles, self.max_batch_tiles):
            n = min(self.max_batch_tiles, total_tiles - s0)
            bucket = _pow2_at_least(n, self.max_batch_tiles)
            slab = sym_flat[s0 * TILE : (s0 + bucket) * TILE]
            tids = np.zeros(bucket, np.int32)
            tids[:n] = tid_flat[s0 : s0 + n]
            out = marker_replace_tiles_multi(
                jax.device_put(
                    slab.reshape(bucket, TILE_ROWS, TILE_COLS), self.device
                ),
                tab_dev,
                jax.device_put(tids, self.device),
            )
            outs.append((out, n))
            slabs += 1
            with self._cond:
                self._tiles_dispatched += n
                self._tiles_padded += bucket - n
        with self._cond:
            self._dispatches += slabs

        def resolve() -> None:
            flat_out = np.concatenate(
                [np.asarray(out).reshape(-1)[: n * TILE] for out, n in outs]
            )
            for req, off, n in spans:
                if not req.future.done():
                    req.future.set_result(
                        flat_out[off : off + n].astype(np.uint8)
                    )

        return resolve, reqs

    # -- CRC dispatch ----------------------------------------------------

    def _dispatch_crc(self, reqs: List[_Request], fill_s: float = 0.0):
        """Pack every part of every request into one lane-major
        (1, 1024, seg_words) dispatch, each part in whole lanes of its own.

        Returns ``(resolve, reqs)`` like ``_dispatch_replace``. Under tracing
        the dispatcher's host work shows as an ``engine.dispatch`` span
        (packing, upload and launch; the batch's fill wait before it as an
        attribute) and an ``engine.resolve`` span (readback, fold and
        answering the futures).
        """
        tracing = _obs_trace.tracing_enabled()
        t0 = time.perf_counter()
        c0 = time.thread_time() if tracing else 0.0
        parts = [p for r in reqs for p in r.parts]
        sizes = [len(p) for p in parts]
        seg_words = parts_words(sizes)
        stage = self._staging_buffer(("crc", seg_words), (1, N_SEGMENTS, seg_words))
        pack_parts(stage[0], parts)
        t1 = time.perf_counter()
        out = crc32_lanes(jax.device_put(stage, self.device), interpret=self.interpret)
        with self._cond:
            self._dispatches += 1
            self._crc_bytes += sum(sizes)
        if tracing:
            t2 = time.perf_counter()
            _obs_trace.record_span("engine.dispatch", t0, t2 - t0, {
                "kind": "crc", "requests": len(reqs), "parts": len(parts),
                "bytes": sum(sizes), "seg_words": seg_words, "fill_s": fill_s,
                "pack_s": t1 - t0, "launch_s": t2 - t1, "cpu_s": time.thread_time() - c0})

        def resolve() -> None:
            r0 = time.perf_counter()
            rc0 = time.thread_time() if tracing else 0.0
            lane_crcs = np.asarray(out)[0]
            r1 = time.perf_counter()
            crcs = iter(finish_parts(lane_crcs, sizes, seg_words))
            for req in reqs:
                got = [next(crcs) for _ in req.parts]
                if not req.future.done():
                    req.future.set_result(got if req.many else got[0])
            if tracing:
                r2 = time.perf_counter()
                _obs_trace.record_span("engine.resolve", r0, r2 - r0, {
                    "kind": "crc", "requests": len(reqs), "readback_s": r1 - r0,
                    "fold_s": r2 - r1, "cpu_s": time.thread_time() - rc0})

        return resolve, reqs

    # -- XXH64 dispatch --------------------------------------------------

    def _dispatch_xxh64(self, reqs: List[_Request], fill_s: float = 0.0):
        """Pack every frame of every request into a row of its own of one
        (frames, stripes * 8) dispatch, both powers of two.

        Returns ``(resolve, reqs)`` like ``_dispatch_crc``, with the same
        ``engine.dispatch`` / ``engine.resolve`` spans under tracing;
        ``resolve`` finishes each frame's digest on the host
        (``xxh64.finish_frames``).
        """
        tracing = _obs_trace.tracing_enabled()
        t0 = time.perf_counter()
        c0 = time.thread_time() if tracing else 0.0
        parts = [p for r in reqs for p in r.parts]
        nbytes = sum(len(p) for p in parts)
        shape = (_pow2_at_least(len(parts)), 8 * stripes_bucket([len(p) for p in parts]))
        stage = self._staging_buffer(("xxh64",) + shape, shape)
        counts = pack_frames(stage, parts)
        t1 = time.perf_counter()
        out = xxh64_rows(jax.device_put(stage, self.device), jax.device_put(counts, self.device),
                         interpret=self.interpret)
        with self._cond:
            self._dispatches += 1
            self._crc_bytes += nbytes
        if tracing:
            t2 = time.perf_counter()
            _obs_trace.record_span("engine.dispatch", t0, t2 - t0, {
                "kind": "xxh64", "requests": len(reqs), "parts": len(parts),
                "bytes": nbytes, "rows": shape[0], "fill_s": fill_s,
                "pack_s": t1 - t0, "launch_s": t2 - t1, "cpu_s": time.thread_time() - c0})

        def resolve() -> None:
            r0 = time.perf_counter()
            rc0 = time.thread_time() if tracing else 0.0
            acc = np.asarray(out)
            r1 = time.perf_counter()
            digests = iter(finish_frames(acc, parts))
            for req in reqs:
                got = [next(digests) & 0xFFFFFFFF for _ in req.parts]
                if not req.future.done():
                    req.future.set_result(got)
            if tracing:
                r2 = time.perf_counter()
                _obs_trace.record_span("engine.resolve", r0, r2 - r0, {
                    "kind": "xxh64", "requests": len(reqs), "readback_s": r1 - r0,
                    "fold_s": r2 - r1, "cpu_s": time.thread_time() - rc0})

        return resolve, reqs

    # ------------------------------------------------------------------
    # lifecycle & telemetry
    # ------------------------------------------------------------------

    def shutdown(self) -> None:
        """Stop the dispatcher and fail queued requests loudly.

        Requests already collected into an in-flight batch complete; anything
        still queued gets ``EngineClosedError`` — callers must never hang on
        a future the worker will no longer serve.
        """
        with self._cond:
            if self._closed:
                return
            self._closed = True
            self._cond.notify_all()
        if self._worker is not None:
            self._worker.join(timeout=30)
        with self._cond:
            leftovers = list(self._rq) + list(self._cq) + list(self._xq)
            self._rq.clear()
            self._cq.clear()
            self._xq.clear()
        for req in leftovers:
            if not req.future.done():
                req.future.set_exception(
                    EngineClosedError("DeviceDecodeEngine shut down with requests queued")
                )

    def __enter__(self) -> "DeviceDecodeEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.shutdown()

    def stats(self) -> Dict[str, Any]:
        """Snapshot for ``/v1/metrics`` (server threads it through)."""
        with self._cond:
            tiles_total = self._tiles_dispatched + self._tiles_padded
            return {
                "platform": self.device.platform,
                "interpret": self.interpret,
                "force_device": self.force_device,
                "requests": dict(self._requests),
                "fallbacks": dict(self._fallbacks),
                "batches": self._batches,
                "dispatches": self._dispatches,
                "batched_requests": self._batched_requests,
                "tiles_dispatched": self._tiles_dispatched,
                "tiles_padded": self._tiles_padded,
                "occupancy": (
                    self._tiles_dispatched / tiles_total if tiles_total else 0.0
                ),
                "crc_bytes": self._crc_bytes,
                "queue_depth": len(self._rq) + len(self._cq) + len(self._xq),
                "max_queue_depth": self._max_queue_depth,
                "errors": self._errors,
                "closed": self._closed,
            }


def _fail(reqs: List[_Request], exc: BaseException) -> None:
    for req in reqs:
        if not req.future.done():
            req.future.set_exception(exc)


def _as_bytes(data) -> bytes:
    """Normalize ndarray/memoryview/bytes input to bytes for zlib/packing."""
    if isinstance(data, bytes):
        return data
    if isinstance(data, np.ndarray):
        return np.ascontiguousarray(data).tobytes()
    return bytes(data)
