"""Tests of the chip benchmark's harness, on the CPU at rehearsal sizes.

They check discovery by name (a cell built only from added files runs with
no edit to a file that is there), the seeded generators, the trace reduction
and kernel byte counts on a hand-written trace, the result line's schema,
that a run without a TPU fails, and that broken runs come out not correct.
"""

from __future__ import annotations

import gzip
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import control  # noqa: E402
import harness  # noqa: E402
import kernel_work  # noqa: E402
import xtrace  # noqa: E402
from registry import Registry  # noqa: E402

SPEC = Registry().spec
silesia_like = Registry()._module("data", "silesia_like")
CELLS = [w["name"] for w in SPEC["workloads"]]
PER_LAYER = [m["name"] for m in SPEC["per_layer"]]


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


def rehearse(capsys, workload, *, trace=0, seconds=1.0, **kwargs):
    argv = ["--workload", workload, "--seed", str(2**31 + 11), "--seconds", str(seconds),
            "--trace", str(trace), "--rehearsal"]
    result = harness.main(argv, **kwargs)
    out = capsys.readouterr().out.strip().splitlines()
    assert json.loads(out[-1]) == json.loads(json.dumps(result))
    return result


# -- discovery ----------------------------------------------------------------

@pytest.mark.parametrize("cell", CELLS)
def test_cell_parts_are_found_by_name(cell):
    reg = Registry()
    w = reg.workload(cell)
    config, traffic = reg.config(w["config"]), reg.traffic(w["traffic"])
    assert callable(reg.driver(traffic["driver"]))
    assert callable(reg.generator(config["data"]["kind"]))
    codec = reg.encoder(config["archive"]["format"])
    assert codec.decode(codec.encode(b"abc" * 100, **config["archive"]["options"])) == b"abc" * 100
    for c in SPEC["configs"]:
        if c["name"] == w["config"]:
            assert os.path.samefile(os.path.join(ROOT, c["file"]),
                                    os.path.join(HERE, "configs", w["config"] + ".json"))
    assert set(config["checks"]) <= {"mismatched_reads", "failed_reads", "bytes_without_device_crc",
                                     "stage2_cpu_fallbacks"}
    assert reg.metrics_for(cell, "per_layer")


@pytest.mark.parametrize("metric", PER_LAYER)
def test_metric_readers_stay_silent_without_data(metric):
    from types import SimpleNamespace

    run = SimpleNamespace(window_s=1.0, spans=[], fetcher={}, engine={}, trace=None,
                          device_kind="TPU v5 lite")
    assert Registry().metric(metric)(run) is None


@pytest.fixture
def added_cell(tmp_path):
    """A copy of the benchmark with one cell made only of added files: a
    FASTQ configuration (whose small chunks carry markers, unlike the
    silesia stand-in's at rehearsal size), a mix and a metric."""
    bench = tmp_path / "chip"
    shutil.copytree(HERE, bench, ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "configs" / "tiny.json").write_text(json.dumps({
        "name": "tiny", "data": {"kind": "fastq_like"},
        "archive": {"format": "gzip", "options": {"level": 6}},
        "decompressed_bytes": 196608,
        "server": {"chunk_size": 32768, "engine_options": {"force_device": True}},
        "checks": ["mismatched_reads", "failed_reads", "bytes_without_device_crc"],
    }))
    (bench / "traffic" / "tiny_scan.json").write_text(json.dumps({
        "driver": "scan", "read_bytes": 32768}))
    (bench / "metrics" / "window_s.tiny.py").write_text(
        "def read(run):\n    return run.window_s\n")
    spec = json.loads(json.dumps(SPEC))
    spec["configs"].append({"name": "tiny", "source": "test", "file": "chip/configs/tiny.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "tiny.scan", "config": "tiny", "traffic": "tiny_scan",
                              "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "window_s.tiny", "unit": "s", "better": "lower",
                              "source": "host_clock", "layer": "test", "moves": "scan_MBps",
                              "workloads": ["tiny.scan"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return Registry(str(bench), str(tmp_path / "BENCHMARK.json"))


def test_a_cell_made_of_added_files_runs_without_edits(added_cell, capsys, jax_cache_restored):
    assert [m["name"] for m in added_cell.metrics_for("tiny.scan", "per_layer")] == ["window_s.tiny"]
    result = rehearse(capsys, "tiny.scan", trace=1, registry=added_cell)
    assert result["correct"] is True, result["checks"]
    assert result["metrics"]["window_s.tiny"]["value"] > 0


# -- data ---------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["silesia_like", "fastq_like"])
@pytest.mark.parametrize("n", [1, 4097, 1 << 17])
def test_generators_are_seeded_and_exact(kind, n):
    gen = Registry().generator(kind)
    a = gen(np.random.default_rng(2**33 + 5), n)
    assert len(a) == n
    assert a == gen(np.random.default_rng(2**33 + 5), n)
    if n > 4096:
        assert a != gen(np.random.default_rng(6), n)


def test_silesia_like_keeps_its_sections():
    data = Registry().generator("silesia_like")(np.random.default_rng(1), 7 << 10)
    words = data[: 2 * len(data) // 7].split(b" ")[:-1]  # the last word may be cut
    assert set(words) <= set(silesia_like.WORDS)
    assert data.endswith(b"ABCD" * 8)


def test_fastq_like_records():
    data = Registry().generator("fastq_like")(np.random.default_rng(3), 10 * 236)
    lines = data.split(b"\n")
    assert lines[0].startswith(b"@SRR0000.") and lines[2] == b"+"
    assert len(lines[1]) == len(lines[3]) == 100 and set(lines[1]) <= set(b"ACGT")


def test_gzip_archive_reads_back():
    codec = Registry().encoder("gzip")
    data = Registry().generator("silesia_like")(np.random.default_rng(9), 50000)
    assert gzip.decompress(codec.encode(data, level=6)) == data


# -- trace reduction and kernel work -----------------------------------------

def synthetic_trace():
    ms = 1_000_000
    ops = [("%fusion = s32[8] fusion(...)", 10 * ms, 2 * ms),
           ("%copy.1 = s32[8] copy(...)", 11 * ms, 2 * ms),          # overlaps the fusion
           ("%crc32_segments_batched.1 = custom-call(...)", 50 * ms, 4 * ms),
           ("%fusion = s32[8] fusion(...)", 95 * ms, 10 * ms)]       # runs past the window
    modules = [("jit_marker_replace_tiles_multi(123)", 10 * ms, 3 * ms),
               ("jit_crc32_segments_batched(456)", 50 * ms, 4 * ms),
               ("jit_marker_replace_tiles_multi(123)", 95 * ms, 10 * ms)]
    return {"start_wall_ns": 10**18,
            "planes": {"/device:TPU:0": [("XLA Ops", ops), ("XLA Modules", modules)],
                       "/host:CPU": [("python", [("bench.read_range", 0, 100 * ms)])]}}


def test_trace_reduction_busy_idle_and_kernels():
    ms = 1e6
    spans = [{"name": "fetcher.task", "attrs": {"kind": "nom"}, "ts": 1e9 + 0.014, "dur_s": 0.030},
             {"name": "reader.frontier_wait", "attrs": {}, "ts": 1e9 + 0.014, "dur_s": 0.080},
             {"name": "server.read_range", "attrs": {}, "ts": 1e9, "dur_s": 0.1}]
    r = xtrace.reduce(synthetic_trace(), (0, 100 * ms), tuple(kernel_work.KERNELS), spans)
    assert r["devices"] == 1 and r["window_s"] == pytest.approx(0.1)
    assert r["busy_s"] == pytest.approx((3 + 4 + 5) / 1e3)
    assert r["kernel_s"]["marker_replace_tiles_multi"] == pytest.approx((3 + 5) / 1e3)
    assert r["kernel_s"]["crc32_segments_batched"] == pytest.approx(4 / 1e3)
    assert r["device_ops"][0] == ["jit_marker_replace_tiles_multi/fusion", pytest.approx(7 / 1e3)]
    gaps = {round(t * 1e3): name for name, t in r["idle_gaps"]}
    assert set(gaps) == {10, 37, 41}
    assert gaps[37] == "reader.frontier_wait+fetcher.task:nom"
    assert gaps[10] == "server.read_range"


def test_kernel_time_found_inside_a_larger_program():
    ms = 1_000_000
    ops = [("%crc32_segments_batched.3 = custom-call(...)", 5 * ms, 2 * ms)]
    trace = {"start_wall_ns": 0, "planes": {"/device:TPU:0": [
        ("XLA Ops", ops), ("XLA Modules", [("jit_stage2(9)", 4 * ms, 4 * ms)])]}}
    r = xtrace.reduce(trace, (0, 10 * ms), tuple(kernel_work.KERNELS))
    assert r["kernel_s"] == {"marker_replace_tiles_multi": 0.0, "crc32_segments_batched": 0.002}


def test_trace_reduction_without_a_device():
    trace = {"start_wall_ns": 0, "planes": {"/host:CPU": [("python", [("bench.x", 0, 5)])]}}
    r = xtrace.reduce(trace, (0, 10), tuple(kernel_work.KERNELS))
    assert r["devices"] == 0 and r["busy_s"] == 0 and r["idle_gaps"] == []


def test_kernel_bytes_count_valid_work_only():
    spans = [{"name": "engine.batch_wait", "attrs": {"kind": "replace", "symbols": 1000}},
             {"name": "engine.batch_wait", "attrs": {"kind": "crc", "nbytes": 4096}},
             {"name": "server.read_range", "attrs": {"size": 10**9}}]
    assert kernel_work.kernel_bytes("marker_replace_tiles_multi", spans) == 3000
    assert kernel_work.kernel_bytes("crc32_segments_batched", spans) == 4096
    share = kernel_work.roofline_percent("crc32_segments_batched", spans, 1e-6, "TPU v5 lite")
    assert share == pytest.approx(100 * 4096 / 819e9 / 1e-6)
    assert kernel_work.roofline_percent("crc32_segments_batched", spans, 0.0, "TPU v5 lite") is None
    with pytest.raises(KeyError):
        kernel_work.roofline_percent("crc32_segments_batched", spans, 1e-6, "no such chip")


# -- whole runs ----------------------------------------------------------------

@pytest.mark.parametrize("trace", [0, 1])
def test_result_line_schema(capsys, jax_cache_restored, trace):
    cell = CELLS[0]
    r = rehearse(capsys, cell, trace=trace)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True and r["attempted"] > 0 and r["failed"] == 0
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(r["device"])
    section = "per_layer" if trace else "end_to_end"
    names = {m["name"] for m in Registry().metrics_for(cell, section)}
    assert set(r["metrics"]) <= names
    if trace:
        assert {"busy_s", "window_s"} <= set(r["device"]) and "breakdown" in r
    else:
        assert set(r["metrics"]) == names and r["metrics"]["setup_s"]["value"] > 0
    for v in r["checks"].values():
        assert v == {"value": 0, "limit": 0}


def test_no_tpu_no_result(capsys, jax_cache_restored):
    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert e.value.code not in (0, None)
    assert "{" not in capsys.readouterr().out


def test_benchmark_files_alone_do_not_run(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for path in SPEC["paths"]:
        shutil.copytree(os.path.join(ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, PYTHONPATH="", JAX_PLATFORMS="cpu")
    p = subprocess.run(SPEC["command"] + ["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                                          "--trace", "0"],
                       cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and "{" not in p.stdout


def alter_answers(server):
    read = server.read_range

    def read_range(handle, offset, size):
        data = read(handle, offset, size)
        return data[:-1] + bytes([data[-1] ^ 1]) if data else data

    server.read_range = read_range


def stale_answers(server):
    read, last = server.read_range, []

    def read_range(handle, offset, size):
        data = read(handle, offset, size)
        if last and len(last[0]) == len(data) and offset:
            return last[0]
        last[:] = [data]
        return data

    server.read_range = read_range


@pytest.mark.parametrize("fault, checks", [
    (alter_answers, ["mismatched_reads"]),
    (stale_answers, ["mismatched_reads"]),
    (control.unverified, ["bytes_without_device_crc"]),
    (control.cpu_stage2, ["stage2_cpu_fallbacks"]),
])
def test_broken_runs_are_not_correct(capsys, jax_cache_restored, fault, checks):
    r = rehearse(capsys, CELLS[0], server_hook=fault, seconds=2.0)
    assert r["correct"] is False and sum(r["checks"][c]["value"] for c in checks) > 0


def test_wrong_stage2_bytes_are_not_correct(added_cell, capsys, jax_cache_restored):
    # Wrong bytes from stage 2 fail the member's CRC32 where they do not
    # reach the client first.
    r = rehearse(capsys, "tiny.scan", server_hook=control.stale_window, seconds=2.0,
                 registry=added_cell)
    assert r["correct"] is False
    assert r["checks"]["mismatched_reads"]["value"] + r["checks"]["failed_reads"]["value"] > 0


def test_bgzf_archive_is_valid_bgzf():
    from repro.core.reader import ParallelGzipReader

    codec = Registry().encoder("bgzf")
    data = Registry().generator("fastq_like")(np.random.default_rng(4), 200_000)
    archive = codec.encode(data, level=6)
    assert gzip.decompress(archive) == data == codec.decode(archive)
    with ParallelGzipReader(archive) as r:
        assert r.codec.tag == "bgzf" and r.pread(70_000, 100_000) == data[70_000:170_000]
