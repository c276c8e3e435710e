"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, traffic, driver and metric readers are found by
name from ``BENCHMARK.json`` (see ``bench/harness.py``). Off a TPU, or with
fewer chips than the cell asks for, it exits 2 before any work.
"""
import os
import sys
import time

T_START = time.perf_counter()  # set-up is timed from here

from pathlib import Path  # noqa: E402

# libtpu would otherwise log under a fixed path in /tmp, outside the checkout
os.environ.setdefault("TPU_LOG_DIR", "disabled")

_ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(_ROOT), str(_ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.run_cell(harness.parse_args(), T_START))
