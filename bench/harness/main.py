"""``python bench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell.

1. Find the cell, its configuration and its traffic mix by name
   (:mod:`harness.catalog`).
2. Check the devices: no TPU, or fewer chips than the cell asks for,
   exits 3 before anything is made, and prints no result.
3. Point JAX's persistent compile cache at ``$JAX_COMPILATION_CACHE_DIR``
   when set, else at ``<checkout>/.jax_cache``.
4. Hand the run to the mix's driver (:func:`driver_for`): it makes the
   inputs from the seed, warms up every shape (set-up), measures for
   ``--seconds``, then compares what the window produced with the plain
   reference.
5. Print the set-up split and the compile counts as ``key=value`` lines,
   each compared number beside its limit as the last lines of standard
   error, and as the last line of standard output one JSON object:
   ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
   end-to-end metrics, or with ``--trace 1`` its per-layer metrics),
   ``device``, with ``--trace 1`` a ``breakdown``, and last ``checks``.

A driver is a module of this package, ``bench/harness/<driver>.py``,
named by the mix's ``driver`` key.  It has ``drive(run, devices)``, and
``control_readings(config, mix, seed, seconds, records)``, the numbers
its run compares as the control gives them (:mod:`harness.control`).  A
later kind of traffic brings its driver as a new file there.

Scratch files (corpus, sorted outputs, spills, the trace) live in a new
temporary directory under ``$TMPDIR``, removed at exit.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import tempfile
import time

from harness import catalog, runtime, shapes

HARNESS_DIR = os.path.dirname(os.path.abspath(__file__))


def parse_args(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def driver_for(mix: dict):
    """The module ``harness.<driver>`` that the mix names, found as the
    file ``bench/harness/<driver>.py`` with a ``drive`` function."""
    name = mix["driver"]
    path = os.path.join(HARNESS_DIR, f"{name}.py")
    if not os.path.isfile(path):
        raise KeyError(f"mix names driver {name!r}: no file {path}")
    driver = importlib.import_module(f"harness.{name}")
    if not callable(getattr(driver, "drive", None)):
        raise KeyError(f"driver {path} has no drive(run, devices)")
    return driver


def main(argv=None, *, t_start: "float | None" = None,
         check_devices=runtime.check_devices,
         cat: "catalog.Catalog | None" = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    cat = cat or catalog.Catalog()
    workload = cat.workload(args.workload)
    config = cat.config(workload["config"])
    mix = cat.mix(workload["traffic"])
    driver = driver_for(mix)

    t0 = time.perf_counter()
    import jax

    try:
        devices = check_devices(workload["chips"])
    except runtime.NoChip as e:
        runtime.stderr(f"bench: {e}")
        return 3
    devices = devices[: workload["chips"]]
    peaks = shapes.peaks(devices[0].device_kind)
    cache = runtime.enable_compile_cache()
    jax_init_s = time.perf_counter() - t0

    tmp = tempfile.mkdtemp(prefix="bench_")
    run = runtime.Run(
        workload=workload, config=config, mix=mix, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), tmp=tmp,
        t_start=t_start,
    )
    run.peaks = peaks
    run.setup_phases["jax_init"] = jax_init_s
    run.log("platform", devices[0].platform)
    run.log("device_kind", devices[0].device_kind)
    run.log("device_count", len(devices))
    run.log("compile_cache_dir", cache)
    jax.monitoring.register_event_duration_secs_listener(run.compiles)
    try:
        driver.drive(run, devices)
        run.reduce_trace()
    finally:
        jax.monitoring.unregister_event_duration_listener(run.compiles)
        shutil.rmtree(tmp, ignore_errors=True)

    for name, sec in run.setup_phases.items():
        run.log(f"setup.{name}_s", sec)
    run.log("setup_s", run.setup_s)
    run.log("compiles.setup", run.compiles.count(t1=run.window[0]))
    run.log("compiles.setup_s", run.compiles.seconds(t1=run.window[0]))
    run.log("compiles.window", run.window_compiles)
    run.e2e["setup_s"] = run.setup_s

    metrics = {}
    if args.trace:
        for m in cat.per_layer(args.workload):
            value = cat.reader(m["name"])(run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cat.end_to_end(args.workload):
            metrics[m["name"]] = {
                "value": float(run.e2e[m["name"]]), "unit": m["unit"],
            }
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": run.memory_peak_bytes,
    }
    result = {
        "correct": all(v <= lim for v, lim in run.checks.values())
        and bool(run.checks),
        "attempted": run.attempted,
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
    }
    if run.reduced is not None:
        device["busy_s"] = run.reduced.busy_s
        device["window_s"] = run.reduced.window_s
        result["breakdown"] = run.reduced.breakdown()
    result["checks"] = {
        name: {"value": v, "limit": lim}
        for name, (v, lim) in run.checks.items()
    }
    for name, (v, lim) in run.checks.items():
        runtime.stderr(f"check {name}={v} limit={lim}")
    print(json.dumps(result), flush=True)
    return 0
