"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run_cell.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

from the root of a checkout. The cell, its configuration, traffic mix and
metrics are looked up by name from ``BENCHMARK.json`` (see
``psbench/registry.py``). Without a TPU it exits 2 and prints no result.
"""
import time

T0 = time.perf_counter()

import sys  # noqa: E402

from psbench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T0))
