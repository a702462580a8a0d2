"""One run of one cell: set up, warm up, measure, check, print one line.

    python benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

In order: load the cell's configuration and traffic mix by name; generate
the data and its archive from ``--seed``; start the ``ArchiveServer``; warm
every engine bucket the window can use (from the persistent compile cache
after the first run); drive the traffic for ``--seconds``; free the server;
compare every byte the window delivered with the stdlib's decompression of
the archive; print the result as the last line of standard output.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
same window under the JAX profiler and the program's span recorder and
reports its per-layer metrics, the device's busy time and a breakdown.

A run that finds no TPU, or fewer chips than the cell asks for, exits
non-zero and prints no result. ``--rehearsal`` lifts that for a CPU run of
the same code at the small sizes each configuration and mix names under
``rehearsal``; its numbers are never device numbers.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shutil
import sys
import tempfile
import time
from types import SimpleNamespace
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
#: JAX's persistent compile cache: fixed and inside the checkout, so that
#: only a checkout's first run of a cell compiles.
COMPILE_CACHE_DIR = os.path.join(ROOT, ".jax_cache")
SEED_MASK = (1 << 64) - 1
SPAN_CAPACITY = 1 << 18

from registry import Registry  # noqa: E402


def log(msg: str) -> None:
    print(msg, flush=True)


def merged(base: Dict[str, Any], over: Dict[str, Any]) -> Dict[str, Any]:
    out = dict(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) and isinstance(out.get(k), dict) else v
    return out


def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearsal", action="store_true",
                   help="CPU rehearsal at the sizes under 'rehearsal'; never a measurement")
    return p.parse_args(argv)


class CompileClock:
    """Counts and sums JAX's backend compiles (persistent-cache loads too)."""

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


def use_compile_cache() -> str:
    import jax

    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)  # no eviction
    return COMPILE_CACHE_DIR


#: Engine counters whose change over the window the run reports.
ENGINE_COUNTERS = ("requests", "fallbacks", "batches", "batched_requests", "dispatches",
                   "tiles_dispatched", "tiles_padded", "crc_bytes", "errors")


def device_check(chips: int, rehearsal: bool):
    import jax

    devices = jax.devices()
    dev = devices[0]
    log("device: platform=%s kind=%s count=%d" % (dev.platform, dev.device_kind, len(devices)))
    if not rehearsal and (dev.platform != "tpu" or len(devices) < chips):
        sys.stderr.write("no TPU with %d chip(s) found (JAX reports %d %s device(s)); nothing ran\n"
                         % (chips, len(devices), dev.platform))
        raise SystemExit(3)
    return devices


def warm_engine(engine, max_request_bytes: int) -> int:
    """Dispatch one request per bucket the window can form: marker batches
    of 1..max_batch_tiles tiles against one table, CRC batches of one
    request up to the archive's size. One client's first pass has one
    stage-2 request in flight at a time, so batches hold one request."""
    import numpy as np
    from repro.kernels.crc32 import N_SEGMENTS, WORD_BYTES, lane_words
    from repro.kernels.marker_replace import TILE

    n = 0
    window = bytes(32 << 10)
    tiles = 1
    while tiles <= engine.max_batch_tiles:
        engine.replace_markers(np.zeros(tiles * TILE, np.uint16), window)
        tiles, n = tiles * 2, n + 1
    words = 1
    while words <= lane_words(max_request_bytes):
        data = bytes(words * WORD_BYTES * N_SEGMENTS)
        engine.crc32(data)
        words, n = words * 2, n + 1
    return n


def stat_delta(after: Any, before: Any) -> Any:
    if isinstance(after, dict):
        return {k: stat_delta(v, before.get(k, 0)) for k, v in after.items()}
    return after - before


class Cell:
    """What a traffic driver sees of a run: the server, the archive, and
    hooks that keep the window cold and its counters whole."""

    def __init__(self, server, path: str, tracing: bool):
        self.server = server
        self.path = path
        self.tracing = tracing
        self.fetcher: Dict[str, int] = {}
        self.log = log

    def annotate(self, name: str):
        if not self.tracing:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(name)

    def open(self) -> str:
        with self.annotate("bench.open"):
            return self.server.open(self.path)

    def close(self, handle: str) -> None:
        """Keep the reader's counters, then close without storing its index,
        so that the next open is cold."""
        with self.annotate("bench.close"):
            report = self.server.metrics()["per_reader"].get(handle)
            if report is not None:
                for k, v in report["fetcher"].items():
                    self.fetcher[k] = self.fetcher.get(k, 0) + v
            st = self.server.stat(handle)
            if st.index_was_warm or st.twin is not None:
                raise AssertionError("scan of %s was not cold: %r" % (self.path, st))
            self.server.close(handle, persist_index=False)


def compare(reads, reference: bytes) -> Dict[str, int]:
    """Every read of the window against the reference decompression."""
    mismatched = 0
    for offset, size, data in reads:
        if data is not None and data != reference[offset: offset + size]:
            mismatched += 1
    return {"mismatched_reads": mismatched}


def main(argv: Optional[List[str]] = None, t0: Optional[float] = None,
         server_hook=None, registry: Optional[Registry] = None) -> Dict[str, Any]:
    """One run; returns the result it printed. ``server_hook(server)`` lets a
    control or a fault test put a broken part in the program's place."""
    t0 = time.perf_counter() if t0 is None else t0
    args = parse(argv)
    reg = registry or Registry()
    cell_spec = reg.workload(args.workload)
    config = reg.config(cell_spec["config"])
    traffic = reg.traffic(cell_spec["traffic"])
    if args.rehearsal:
        config = merged(config, config.get("rehearsal", {}))
        traffic = merged(traffic, traffic.get("rehearsal", {}))

    devices = device_check(int(cell_spec["chips"]), args.rehearsal)
    dev = devices[0]
    cache_dir = use_compile_cache()
    clock = CompileClock()
    import jax
    import numpy as np

    from repro import obs
    from repro.service import ArchiveServer

    t_imports = time.perf_counter()
    log("set-up: imports and chip %r s; compile cache %s" % (t_imports - t0, cache_dir))

    rng = np.random.default_rng(args.seed & SEED_MASK)
    n = int(config["decompressed_bytes"])
    source = reg.generator(config["data"]["kind"])(rng, n)
    t_data = time.perf_counter()
    codec = reg.encoder(config["archive"]["format"])
    archive = codec.encode(source, **config["archive"]["options"])
    workdir = tempfile.mkdtemp(prefix="chipbench-")
    path = os.path.join(workdir, "%s.%s" % (config["name"], config["archive"]["format"]))
    with open(path, "wb") as f:
        f.write(archive)
    t_archive = time.perf_counter()
    log("set-up: data %d bytes %r s; archive %d bytes %r s"
        % (n, t_data - t_imports, len(archive), t_archive - t_data))

    server = ArchiveServer(**config.get("server", {}))
    try:
        if server_hook is not None:
            server_hook(server)
        t_server = time.perf_counter()
        programs = warm_engine(server.device_engine, n) if server.device_engine else 0
        t_warm = time.perf_counter()
        log("set-up: server %r s; engine warm-up %r s over %d buckets; %d compiles %r s, %d cache hits"
            % (t_server - t_archive, t_warm - t_server, programs, clock.compiles,
               clock.seconds, clock.cache_hits))

        cell = Cell(server, path, tracing=bool(args.trace))
        engine_before = server.device_engine.stats() if server.device_engine else {}
        compiles_before = clock.compiles
        trace_dir = None
        if args.trace:
            trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            obs.enable_tracing(SPAN_CAPACITY)
            obs.reset_tracing()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            options.host_tracer_level = 1
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        t_window = time.perf_counter()
        setup_s = t_window - t0
        window_wall = [time.time_ns()]
        window = reg.driver(traffic["driver"])(cell, traffic, t_window + args.seconds)
        window_wall.append(time.time_ns())
        spans: List[dict] = []
        if args.trace:
            jax.profiler.stop_trace()
            spans = obs.drain_spans()
            stats = obs.tracing_stats()
            obs.disable_tracing()
            log("spans: %d recorded in the window, %d dropped" % (len(spans), stats["dropped"]))
        window_compiles = clock.compiles - compiles_before
        log("window: %r s; %d compiles inside it" % (window["elapsed_s"], window_compiles))
        engine = {}
        if server.device_engine:
            after = server.device_engine.stats()
            engine = {k: stat_delta(after[k], engine_before[k]) for k in ENGINE_COUNTERS}
        log("engine in the window: " + json.dumps(engine, sort_keys=True))
        mem = dev.memory_stats() or {}
        memory_peak = int(mem.get("peak_bytes_in_use", 0))
    finally:
        server.shutdown()
        shutil.rmtree(workdir, ignore_errors=True)

    trace = None
    if trace_dir is not None:
        import xtrace

        from kernel_work import KERNELS

        raw = xtrace.load(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        lo, hi = (t - raw["start_wall_ns"] for t in window_wall)
        trace = xtrace.reduce(raw, (lo, hi), tuple(KERNELS), spans)

    # The reference: a plain decoder of the same archive bytes, run once the
    # program's state is freed.
    reference = codec.decode(archive)
    if reference != source:
        raise AssertionError("the archive does not decompress to its seeded source")
    checks = compare(window["reads"], reference)
    checks["failed_reads"] = window["failed"]
    checks["bytes_without_device_crc"] = int(cell.fetcher.get("bytes_decompressed", 0)) - int(engine.get("crc_bytes", 0))
    checks["stage2_cpu_fallbacks"] = sum(engine.get("fallbacks", {}).values())
    checks = {k: checks[k] for k in config["checks"]}
    limits = {k: 0 for k in checks}

    metrics: Dict[str, Dict[str, Any]] = {}
    if not args.trace:
        for m in reg.metrics_for(args.workload, "end_to_end"):
            value = setup_s if m["name"] == "setup_s" else window["metrics"][m["name"]]
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        run = SimpleNamespace(window_s=window["elapsed_s"], spans=spans, fetcher=cell.fetcher,
                              engine=engine, trace=trace, device_kind=dev.device_kind)
        for m in reg.metrics_for(args.workload, "per_layer"):
            value = reg.metric(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": memory_peak}
    result: Dict[str, Any] = {
        "correct": all(checks[k] <= limits[k] for k in checks),
        "attempted": window["attempted"],
        "failed": window["failed"],
        "metrics": metrics,
        "device": device,
    }
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"], "idle_gaps": trace["idle_gaps"]}
    result["checks"] = {k: {"value": checks[k], "limit": limits[k]} for k in checks}
    for k in checks:
        sys.stderr.write("check %s: %d (limit %d)\n" % (k, checks[k], limits[k]))
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return result
