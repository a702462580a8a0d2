"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (see DESIGN.md §8 for the
table/figure mapping). ``python -m benchmarks.run [--only sections] [--smoke]
[--check]``. ``--check`` diffs each section's fresh rows against the
committed ``BENCH_<section>.json`` before overwriting it and flags >25%
per-row regressions (benchmarks/trajectory.py) — the cross-PR trajectory
gate.

``--smoke`` shrinks every section to tiny sizes (common.scale) so the whole
harness completes in a couple of minutes — a CI check that each benchmark
still runs, not a measurement. The service section includes the concurrent-reader
scaling scenario (locked cursor vs lock-free pread vs async front-end), so
every smoke run records that trajectory; the matching tier-2 correctness
suite is ``pytest -m stress`` (threaded/async consistency with timeouts).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback

from . import common, trajectory

#: BENCH_<section>.json lands next to the repo's other BENCH_* artifacts.
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _persist_section(name: str, rows, elapsed_s: float, smoke: bool) -> None:
    """One JSON artifact per section: the same rows as the CSV stdout, plus
    enough context (smoke flag, wall time, timestamp) to compare runs."""
    payload = {
        "section": name,
        "smoke": smoke,
        "elapsed_s": round(elapsed_s, 3),
        "generated_at": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "results": rows,
    }
    out = os.path.join(_REPO_ROOT, "BENCH_%s.json" % name)
    with open(out, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--only", default="",
        help="comma list: components,decomp,kernels,roofline,codecs,service,"
             "remote,gateway,fleet,transcode,obs",
    )
    ap.add_argument(
        "--smoke", action="store_true",
        help="tiny sizes, 1 repeat: verify every section runs in <60 s total",
    )
    ap.add_argument(
        "--check", action="store_true",
        help="diff fresh rows against the committed BENCH_<section>.json"
             " before overwriting it; flag >25%% per-row regressions",
    )
    args = ap.parse_args()
    common.use_compile_cache()
    only = set(args.only.split(",")) if args.only else None
    if args.smoke:
        common.set_smoke(True)

    sections = []
    if only is None or "components" in only:
        from . import bench_components

        sections.append(("components", lambda: bench_components.main(tempfile.mkdtemp())))
    if only is None or "decomp" in only:
        from . import bench_decompression

        sections.append(("decompression", bench_decompression.main))
    if only is None or "kernels" in only:
        from . import bench_kernels

        sections.append(("kernels", bench_kernels.main))
    if only is None or "roofline" in only:
        from . import roofline_report

        sections.append(("roofline", roofline_report.main))
    if only is None or "codecs" in only:
        from . import bench_codecs

        # Same logical corpus under deflate/BGZF/zstd: cold vs warm
        # random-access p50, with the cold row recording nominal_tasks
        # (BGZF must show 0 — exact index from framing metadata alone).
        sections.append(("codecs", bench_codecs.main))
    if only is None or "service" in only:
        from . import bench_service

        sections.append(("service", bench_service.main))
    if only is None or "remote" in only:
        from . import bench_service as _bench_remote_mod

        # Hermetic: latency-injected loopback HTTP server, no external
        # network — safe under --smoke in CI.
        sections.append(("remote", _bench_remote_mod.bench_remote))
    if only is None or "gateway" in only:
        from . import bench_service as _bench_gateway_mod

        # Hermetic: in-process loopback GatewayServer — wire overhead vs
        # in-process, chunked streaming, and the flood-isolation acceptance.
        sections.append(("gateway", _bench_gateway_mod.bench_gateway))
    if only is None or "fleet" in only:
        from . import bench_service as _bench_fleet_mod

        # Hermetic: 3 loopback gateways behind a FleetRouter — routed vs
        # direct read latency, failover recovery, index-exchange warm open.
        sections.append(("fleet", _bench_fleet_mod.bench_fleet))
    if only is None or "obs" in only:
        from . import bench_obs

        # Tracing overhead: warm pread p50/p99 traced vs untraced (the ≤5%
        # acceptance bar) and the disabled-path noop span cost.
        sections.append(("obs", bench_obs.main))
    if only is None or "transcode" in only:
        from . import bench_transcode

        # Seek-hostile archive cold random access before vs after the
        # background twin install — the acceptance bar is a >=5x p99 win.
        sections.append(("transcode", lambda: bench_transcode.main(tempfile.mkdtemp())))

    failures = 0
    regressed_sections = 0
    t_start = time.perf_counter()
    for name, fn in sections:
        print(f"# === {name} ===")
        common.drain_results()  # a failed prior section must not leak rows
        t_section = time.perf_counter()
        try:
            fn()
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"# section {name} FAILED", file=sys.stderr)
            traceback.print_exc()
        else:
            rows = common.drain_results()
            if args.check:
                # Diff against the committed artifact *before* _persist_section
                # overwrites it — this is the cross-PR trajectory gate.
                report = trajectory.check_section(
                    _REPO_ROOT, name, rows, smoke=args.smoke
                )
                for line in trajectory.format_report(report):
                    print(line)
                if report.get("status") == "regressed":
                    regressed_sections += 1
            _persist_section(
                name, rows, time.perf_counter() - t_section, args.smoke,
            )
    if args.smoke:
        print(f"# smoke total: {time.perf_counter() - t_start:.1f}s")
    if args.check:
        print(f"# trajectory: {regressed_sections} section(s) with regressions")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
