"""Run one cell of the on-chip benchmark once.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration and its traffic
are found by name from ``BENCHMARK.json``; see ``chipbench/harness.py``.
"""
import os
import sys
import time

T_START = time.perf_counter()  # set-up is counted from here

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# the TPU runtime would otherwise keep its logs under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

if __name__ == "__main__":
    from chipbench import harness

    sys.exit(harness.main(sys.argv[1:], T_START))
