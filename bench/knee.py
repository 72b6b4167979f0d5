"""Sweep of offered rates for an open-loop cell, in one process on the chip.

    python3 bench/knee.py --workload indy_uniform_1g.ycsb_c_zipf \
        --seeds 5 6 --seconds 30 --rates 10 12 14 16 [--write]

For each seed, set-up (corpus, sort, warm-up) runs once; then each rate
gets a fresh
server, a fresh client and a schedule of its own, exactly as a run's
window.  One JSON line per rate gives what was offered and completed,
p50 and p99 from the due time, shed and unanswered requests, how late
the client ran, and whether the backlog grew: the median latency of the
last quarter of the requests against that of the first quarter.

The knee is the highest rate, below the first one that fails on any
seed, at which nothing was shed, left unanswered or answered wrong, and
the backlog did not grow, on every seed.  The last line gives it with
the rate at four fifths of it; ``--write`` puts both into the mix's
file.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(1, os.path.join(os.path.dirname(BENCH), "src"))

import numpy as np  # noqa: E402

from harness import catalog, loadgen, open_loop, runtime  # noqa: E402

GROWTH = 2.0  # last-quarter median over first-quarter median
GROWTH_MIN_S = 0.010  # ... and at least this much higher
KNEE_SHARE = 0.8


def reading(run, target, rate, seconds, tag) -> dict:
    due, keys = open_loop.schedule(run, target, rate=rate, seconds=seconds)
    t0, stats, answers = open_loop.serve_window(run, target, due, keys,
                                                seconds, tag)
    wrong, missing = open_loop.wrong_answers(target, keys, answers,
                                             run.ordered)
    lat = open_loop.latencies(t0, due, answers)
    q = max(1, due.shape[0] // 4)
    first, last = np.median(lat[:q]), np.median(lat[-q:])
    status = answers["status"]
    done = int(((status == loadgen.OK)
                & (answers["t_done"] <= t0 + seconds)).sum())
    cap = seconds + open_loop.ANSWER_GRACE_S
    return {
        "rate_per_s": rate,
        "requests": int(due.shape[0]),
        "completed_in_window": done,
        "p50_ms": open_loop.percentile_ms(lat, 50, cap),
        "p99_ms": open_loop.percentile_ms(lat, 99, cap),
        "shed": int((status == loadgen.OVERLOADED).sum()),
        "missing": missing,
        "wrong": wrong,
        "client_late_p99_ms": float(np.nanpercentile(
            answers["t_sent"] - (t0 + due), 99)) * 1e3,
        "first_quarter_p50_ms": float(first) * 1e3,
        "last_quarter_p50_ms": float(last) * 1e3,
        "batch_occupancy": stats.batch_occupancy,
        "cache_hit_rate": stats.cache_hit_rate,
        "window_compiles": run.window_compiles,
    }


def sustained(r: dict) -> bool:
    grew = (r["last_quarter_p50_ms"] > GROWTH * r["first_quarter_p50_ms"]
            and r["last_quarter_p50_ms"] - r["first_quarter_p50_ms"]
            > GROWTH_MIN_S * 1e3)
    return (r["shed"] == 0 and r["missing"] == 0 and r["wrong"] == 0
            and not grew)


def sweep(cat, workload, mix, seed, seconds, rates):
    """One reading per rate, as each is made, on the corpus of ``seed``."""
    import jax

    from harness import reference

    tmp = tempfile.mkdtemp(prefix="bench_knee_")
    run = runtime.Run(workload=workload, config=cat.config(workload["config"]),
                      mix=mix, seed=seed, seconds=seconds, trace=False,
                      tmp=tmp, t_start=time.perf_counter())
    jax.monitoring.register_event_duration_secs_listener(run.compiles)
    try:
        target = open_loop.Target(run)
        try:
            run.ordered = reference.stable_order(target.records)
            for i, rate in enumerate(rates):
                yield reading(run, target, rate, seconds, f"_{i}")
        finally:
            target.close()
    finally:
        jax.monitoring.unregister_event_duration_listener(run.compiles)
        shutil.rmtree(tmp, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--write", action="store_true",
                    help="put the knee and its four fifths into the mix")
    args = ap.parse_args(argv)
    cat = catalog.Catalog()
    workload = cat.workload(args.workload)
    mix = cat.mix(workload["traffic"])
    runtime.check_devices(workload["chips"])
    runtime.enable_compile_cache()
    rates = sorted(args.rates)
    held = {rate: True for rate in rates}
    for seed in args.seeds:
        for r in sweep(cat, workload, mix, seed, args.seconds, rates):
            r["seed"] = seed
            r["sustained"] = sustained(r)
            held[r["rate_per_s"]] &= r["sustained"]
            print(json.dumps(r), flush=True)
    knee = None  # the last rate held on every seed below the first that is not
    for rate in rates:
        if not held[rate]:
            break
        knee = rate
    chosen = round(KNEE_SHARE * knee) if knee else None
    print(json.dumps({"knee_per_s": knee, "rate_per_s": chosen}), flush=True)
    if args.write and knee:
        path = os.path.join(BENCH, "mixes", f"{workload['traffic']}.json")
        mix.update(knee_per_s=knee, rate_per_s=chosen)
        with open(path, "w") as f:
            json.dump(mix, f, indent=2)
            f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
