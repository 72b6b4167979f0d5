"""Driver of the ``terasort_jobs`` mixes: whole distributed sorts over
the cell's chips, back to back.

Set-up writes the corpus of the configuration from the seed
(:func:`sort_jobs.make_corpus`), builds the program's 1-D data mesh over
the cell's chips, and runs the mix's warm-up jobs, which compile (or
read from the cache) the route program and every final-pass shape the
window's jobs use: each job sorts the same corpus, so the route's chunks
and the ranges are the same every time.

The window runs ``terasort.sort_file_distributed`` jobs with the mix's
``sort_config`` fields, each ending with an ``fsync`` of its output, and
closes at the end of the job that crosses ``--seconds``, as
:mod:`sort_jobs` does; no partial job counts.  ``sort_mb_s`` is the
input MB of the window's jobs over the time from the window's start to
the end of its last job.

Once the window has closed, every output byte of every job is compared
with the reference's stable memcmp sort of the corpus
(``wrong_records``).  Two more checks hold the run to the path the cell
exists for: ``jobs_off_mesh``, a job whose final pass was not the mesh
executor or dispatched nothing to the chips, and ``jobs_unrouted``, a
job that routed no chunk, where the program counts its routed chunks
(counter ``route.chunks``).
"""

from __future__ import annotations

import os
import time

from harness import gensort, reference, sort_jobs

ROUTE_CHUNKS = "route.chunks"


def make_mesh(devices):
    """The program's data mesh over the cell's chips."""
    from repro.launch.mesh import make_data_mesh

    return make_data_mesh(len(devices))


def sort_job(run, inp: str, out: str, mesh):
    """One job: the program's distributed sort, then an fsync of its
    output."""
    from repro.core import terasort

    with run.span("bench.job"):
        stats = terasort.sort_file_distributed(
            inp, out, mesh, workdir=run.tmp, **run.mix["sort_config"]
        )
        sort_jobs._fsync(out)
    return stats


def counts_routes() -> bool:
    """Whether the program counts its routed chunks (a program that
    predates the route counters does not)."""
    from repro.core import terasort

    return hasattr(terasort, "route_exchange_bytes")


def drive(run, devices) -> None:
    inp = sort_jobs.make_corpus(run)
    with run.setup_phase("mesh"):
        mesh = make_mesh(devices)
    with run.setup_phase("warmup"):
        for i in range(run.mix["warmup_jobs"]):
            out = os.path.join(run.tmp, f"warmup{i}.bin")
            sort_job(run, inp, out, mesh)
            os.unlink(out)

    outputs, jobs = [], []
    t0 = run.window_begin()
    while True:
        out = os.path.join(run.tmp, f"job{len(jobs)}.bin")
        jobs.append(sort_job(run, inp, out, mesh))
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
    run.log("range_records", jobs[0].partition_counts)
    run.log("route_chunks", [s.counters.get(ROUTE_CHUNKS) for s in jobs])
    run.log("fallbacks", [s.fallbacks for s in jobs])
    run.layer.update(
        jobs=jobs,
        input_gb=input_bytes / 1e9,
        records=int(sum(s.n_records for s in jobs)),
        n_dev=len(devices),
    )
    # the cell exists to drive the route and the mesh executor
    run.checks["jobs_off_mesh"] = (
        sum(1 for s in jobs
            if s.executor != "mesh" or s.device_dispatches == 0),
        0,
    )
    if counts_routes():
        run.checks["jobs_unrouted"] = (
            sum(1 for s in jobs if not s.counters.get(ROUTE_CHUNKS)), 0
        )
    run.log("wrong_records_per_job", wrong)


def control_readings(config, mix, seed, seconds, records) -> dict:
    """``wrong_records`` of the control's sort of the corpus ``records``."""
    from harness import control

    return {"wrong_records": control.sort_reading(records)}
