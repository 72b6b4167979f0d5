"""One run of one benchmark cell on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Run from the root of a checkout; ``BENCHMARK.json`` names the cells.
See ``bench/harness/main.py`` for what a run does and prints.
"""

import time

T_START = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

from harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main.main(t_start=T_START))
