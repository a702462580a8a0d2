"""The controls of ``correct``: runs of a cell with a guarantee broken, which
the comparison has to call not correct. The benchmark's own runs never run
them.

    python benchmarks/chip/control.py --workload <name> --seeds 11,12,13 --seconds 30

Each seed runs once per control, all in one process:

- ``unverified``: the program's own ``verify=False`` path, the step a
  faster build would be tempted by: no CRC32 is computed or checked;
- ``stale_window``: stage 2 resolves markers against a zeroed window, as a
  build that skipped window propagation would; the bytes come out wrong;
- ``cpu_stage2``: the engine's own default routing, whose crossover (read
  from a CPU artifact) sends every stage-2 request to the CPU.
"""

from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402


def unverified(server) -> None:
    server.verify = False


def stale_window(server) -> None:
    engine = server.device_engine
    resolve = engine.replace_markers

    def replace_markers(symbols, window):
        return resolve(symbols, bytes(len(window or b"")))

    engine.replace_markers = replace_markers


def cpu_stage2(server) -> None:
    server.device_engine.force_device = False


CONTROLS = {"unverified": unverified, "stale_window": stale_window, "cpu_stage2": cpu_stage2}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", required=True)
    p.add_argument("--controls", default=",".join(CONTROLS))
    p.add_argument("--rehearsal", action="store_true")
    args = p.parse_args(argv)
    caught = True
    for name in args.controls.split(","):
        for seed in args.seeds.split(","):
            harness.log("=== control %s, seed %s" % (name, seed))
            run = ["--workload", args.workload, "--seed", seed, "--seconds", args.seconds,
                   "--trace", "0"] + (["--rehearsal"] if args.rehearsal else [])
            result = harness.main(run, server_hook=CONTROLS[name])
            caught &= not result["correct"]
    harness.log("every control came out not correct: %s" % caught)
    return 0 if caught else 1


if __name__ == "__main__":
    raise SystemExit(main())
