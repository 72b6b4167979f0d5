"""Driver of the ``sort_jobs`` mixes: whole file sorts, back to back.

Set-up writes the corpus of the configuration from the seed, then runs
the mix's warm-up jobs, which compile (or read from the cache) every
dispatch shape the window's jobs use: each job sorts the same corpus,
so the planner and the executor form the same batches every time.

The window runs ``external.sort_file`` jobs with the mix's ``SortConfig``
fields, each ending with an ``fsync`` of its output, and closes at the
end of the job that crosses ``--seconds``; no partial job counts.
``sort_mb_s`` is the input MB of the window's jobs over the time from
the window's start to the end of its last job.

Once the window has closed, every output byte of every job is compared
with the reference's stable memcmp sort of the corpus; under the control
that comparison reads ``wrong_records`` (:func:`control_readings`).
"""

from __future__ import annotations

import os
import time

from harness import gensort, reference


def _fsync(path: str) -> None:
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def make_corpus(run) -> str:
    cfg = run.config
    path = os.path.join(run.tmp, "input.bin")
    with run.setup_phase("corpus"):
        gensort.write_corpus(
            path, cfg["records"], seed=run.seed,
            skewed=cfg["key_distribution"] == "skewed",
            fixed_key_bytes=run.mix.get("fixed_key_bytes", 0),
        )
    return path


def sort_config(run):
    from repro.core.config import SortConfig

    return SortConfig(
        memory_budget_bytes=run.config["memory_budget_bytes"],
        workdir=run.tmp,
        **run.mix["sort_config"],
    )


def sort_job(run, inp: str, out: str, cfg):
    """One job: the program's file sort, then an fsync of its output."""
    from repro.core import external

    with run.span("bench.job"):
        stats = external.sort_file(inp, out, cfg)
        _fsync(out)
    return stats


def drive(run, devices) -> None:
    inp = make_corpus(run)
    cfg = sort_config(run)
    with run.setup_phase("warmup"):
        for i in range(run.mix["warmup_jobs"]):
            out = os.path.join(run.tmp, f"warmup{i}.bin")
            sort_job(run, inp, out, cfg)
            os.unlink(out)

    outputs, jobs = [], []
    t0 = run.window_begin()
    while True:
        out = os.path.join(run.tmp, f"job{len(jobs)}.bin")
        jobs.append(sort_job(run, inp, out, cfg))
        outputs.append(out)
        t1 = time.perf_counter()
        if t1 - t0 >= run.seconds:
            break
    run.window_end(t1)
    run.read_memory(devices)

    records = gensort.read_corpus(inp)
    t = time.perf_counter()
    with run.span("bench.reference"):
        order, _ = reference.stable_order(records)
        wrong = [
            reference.mismatched_records(gensort.read_corpus(p), records,
                                         order)
            for p in outputs
        ]
    run.log("reference_s", time.perf_counter() - t)

    input_bytes = sum(s.input_bytes for s in jobs)
    run.attempted = len(jobs)
    run.failed = sum(1 for w in wrong if w)
    run.checks["wrong_records"] = (sum(wrong), 0)
    run.e2e["sort_mb_s"] = input_bytes / 1e6 / (t1 - t0)
    run.log("window_jobs", len(jobs))
    run.log("window_s", t1 - t0)
    run.log("job_wall_s", [round(s.wall_seconds, 4) for s in jobs])
    run.log("executor", sorted({s.executor for s in jobs}))
    run.log("device_dispatches", [s.device_dispatches for s in jobs])
    run.log("partitions", [len(s.partition_counts) for s in jobs])
    run.log("spill_disk_bytes", [s.spill_disk_bytes for s in jobs])
    run.layer.update(
        jobs=jobs,
        input_gb=input_bytes / 1e9,
        records=int(sum(s.n_records for s in jobs)),
    )
    # the cell exists to drive the device path: a host sort is no run
    run.checks["jobs_off_device"] = (
        sum(1 for s in jobs
            if s.executor != "batched" or s.device_dispatches == 0),
        0,
    )
    run.log("wrong_records_per_job", wrong)


def control_readings(config, mix, seed, seconds, records) -> dict:
    """``wrong_records`` of the control's sort of the corpus ``records``."""
    from harness import control

    return {"wrong_records": control.sort_reading(records)}
