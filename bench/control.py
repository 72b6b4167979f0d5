"""Readings of the control (``harness/control.py``) at a cell's own size.

    python3 bench/control.py --workload <cell> --seeds 1 2 3

Prints one JSON line per seed with the numbers a run compares, as the
control gives them; each must exceed its limit (0) for the comparison
to be worth anything.  Needs no chip: the control stands in for the
program.
"""

import argparse
import json
import os
import sys
import tempfile

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

from harness import catalog, control  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cat = catalog.Catalog()
    workload = cat.workload(args.workload)
    config = cat.config(workload["config"])
    mix = cat.mix(workload["traffic"])
    with tempfile.TemporaryDirectory(prefix="bench_control_") as tmp:
        for seed in args.seeds:
            got = control.readings(config, mix, seed,
                                   cat.spec["run_seconds"], tmp)
            print(json.dumps({"workload": args.workload, "seed": seed,
                              **got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
