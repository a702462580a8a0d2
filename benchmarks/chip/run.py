"""Chip benchmark of the archive service; see ``harness.py`` and PERF.md.

    python benchmarks/chip/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>
"""

import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [HERE, os.path.join(os.path.dirname(os.path.dirname(HERE)), "src")]
# libtpu would otherwise log to a fixed directory under /tmp.
os.environ.setdefault("TPU_LOG_DIR", "disabled")

if __name__ == "__main__":
    import harness

    harness.main(sys.argv[1:], t0=T0)
