"""Driver of the ``open_loop`` mixes: point reads sent over TCP loopback,
on a schedule, to the program's ``QueryServer``.

Set-up writes the corpus of the configuration from the seed, sorts it
with the program (the mix's ``sort_config`` fields of ``SortConfig``,
which ask for a manifest), opens the sorted file as a ``SortedFileIndex`` and
sends every batch size the scheduler can form (1 to ``max_batch``)
through ``index.lookup``, so that the window compiles nothing.

The window: a ``QueryServer`` with the mix's ``serve_config`` fields
listens on a loopback port, and a client process of its own
(``loadgen.py``, which never imports JAX) sends the schedule of
:mod:`harness.traffic` on ``connections`` connections.  Each request is
timed from when it was due; one that failed, was shed or never came
counts as slower than any limit.  The client waits for answers up to
:data:`ANSWER_GRACE_S` past the window's close.

Once the window has closed, every answer is compared with the plain
reference's answer for its key; under the control that comparison reads
``wrong_answers`` (:func:`control_readings`).
"""

from __future__ import annotations

import asyncio
import os
import sys
import time
import types

import numpy as np

from harness import gensort, loadgen, reference, sort_jobs, traffic

ANSWER_GRACE_S = 60.0
STOP_TIMEOUT_S = 30.0
CLIENT_LEAD_S = 2.0  # the client's start-up, before the window opens
LOADGEN = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "loadgen.py")


def _spanned(run, call, name, sizes=None):
    """``call`` inside the host span ``name``; notes when each call began
    and its batch size."""

    def wrapped(keys, *a, **kw):
        if sizes is not None:
            sizes.append((time.perf_counter(), int(keys.shape[0])))
        with run.span(name):
            return call(keys, *a, **kw)

    return wrapped


class Target:
    """The sorted, indexed corpus the server answers from."""

    def __init__(self, run):
        from repro.core import external
        from repro.serve.index import SortedFileIndex

        self.corpus = sort_jobs.make_corpus(run)
        self.sorted = os.path.join(run.tmp, "sorted.bin")
        with run.setup_phase("target_sort"):
            external.sort_file(self.corpus, self.sorted,
                               sort_jobs.sort_config(run))
        self.index = SortedFileIndex.open(self.sorted)
        self.records = gensort.read_corpus(self.corpus)
        self.serve_config = self._serve_config(run)
        with run.setup_phase("warmup"):
            self._warm_up(run)
        self.lookup_sizes: list[tuple[float, int]] = []

    @staticmethod
    def _serve_config(run):
        from repro.core.config import ServeConfig

        return ServeConfig(host="127.0.0.1", port=0, **run.mix["serve_config"])

    def _warm_up(self, run) -> None:
        rows = np.random.default_rng(
            [run.seed & (2**63 - 1), 7]
        ).integers(0, self.records.shape[0], self.serve_config.max_batch)
        keys = np.ascontiguousarray(self.records[rows, : self.index.key_width])
        for b in range(1, self.serve_config.max_batch + 1):
            self.index.lookup(keys[:b],
                              use_kernels=self.serve_config.use_kernels)

    def trace_lookups(self, run) -> None:
        """Wrap ``index.lookup`` in a host span per batch, and note each
        batch's start and size (traced runs only)."""
        self.index.lookup = _spanned(run, self.index.lookup, "bench.lookup",
                                     self.lookup_sizes)

    def close(self) -> None:
        self.index.close()


def schedule(run, target, rate: "float | None" = None, seconds=None):
    mix = dict(run.mix)
    if rate is not None:
        mix["rate_per_s"] = rate
    seconds = run.seconds if seconds is None else seconds
    traffic.check_operations(mix)
    due = traffic.arrival_offsets(mix, seconds, run.seed)
    rows = traffic.key_rows(mix, due.shape[0], target.records.shape[0],
                            run.seed)
    keys = np.ascontiguousarray(
        target.records[rows, : target.index.key_width]
    )
    return due, keys


async def _window(run, target, due, keys, seconds, tag):
    from repro.serve.server import QueryServer

    sched = os.path.join(run.tmp, f"schedule{tag}.npz")
    out = os.path.join(run.tmp, f"answers{tag}.npz")
    np.savez(sched, due=due, keys=keys)
    server = QueryServer(target.index, target.serve_config,
                         own_indexes=False)
    if run.trace and server.cache is not None:
        # the record fetch after each predict: rows in, records out
        server.cache.fetch_rows = _spanned(
            run, server.cache.fetch_rows, "bench.fetch")
    await server.start()
    host, port = server.address
    t0 = time.monotonic() + CLIENT_LEAD_S
    deadline = t0 + seconds + ANSWER_GRACE_S
    proc = await asyncio.create_subprocess_exec(
        sys.executable, LOADGEN, "--schedule", sched, "--host", host,
        "--port", str(port), "--t0", repr(t0), "--deadline", repr(deadline),
        "--connections", str(run.mix["connections"]), "--out", out,
    )
    try:
        run.start_trace()
        await asyncio.sleep(max(0.0, t0 - time.monotonic()))
        run.window_begin()
        loop = asyncio.get_running_loop()
        loop.call_later(max(0.0, t0 + seconds - time.monotonic()),
                        run.close_window_span)
        rc = await proc.wait()
    finally:
        if proc.returncode is None:
            proc.kill()
            await proc.wait()
    # the window's end on the harness's clock
    run.window_end(time.perf_counter() - (time.monotonic() - (t0 + seconds)))
    try:
        await asyncio.wait_for(server.stop(), STOP_TIMEOUT_S)
    except asyncio.TimeoutError:
        # requests the server never answered hold it open; asyncio.run
        # cancels what is left
        run.log("server_stop", "timed out")
    if rc != 0:
        raise RuntimeError(f"load generator exited {rc}")
    with np.load(out) as z:
        answers = {k: z[k] for k in z.files}
    return t0, server.stats, answers


def serve_window(run, target, due, keys, seconds, tag=""):
    """Serve one schedule; returns ``(t0, ServeStats, answers)``."""
    return asyncio.run(_window(run, target, due, keys, seconds, tag))


def latencies(t0: float, due: np.ndarray, answers: dict) -> np.ndarray:
    """Seconds from due to answered; +inf for a request that failed,
    was shed or got no answer."""
    lat = answers["t_done"] - (t0 + due)
    return np.where(answers["status"] == loadgen.OK, lat, np.inf)


def percentile_ms(lat: np.ndarray, pct: float, cap_s: float) -> float:
    """The ``pct`` percentile in ms; a failure in that place reads as
    ``cap_s`` (the longest the client waits), which no limit admits."""
    v = float(np.percentile(np.minimum(lat, cap_s), pct))
    return v * 1e3


def wrong_answers(target, keys, answers, ordered=None):
    """Answers that say something else than the reference for their
    key; a failed answer other than a shed one counts as wrong.  Returns
    ``(wrong, missing)``."""
    order, sorted_keys = ordered or reference.stable_order(target.records)
    found, rows = reference.point_answers(target.records, order,
                                          sorted_keys, keys)
    status = answers["status"]
    ok = status == loadgen.OK
    want = np.where(found[:, None], target.records[rows], 0)
    got = answers["records"]
    bad = ok & ((answers["found"] != found)
                | (got != want).any(axis=1)
                | (answers["record_len"] != np.where(found, want.shape[1], 0)))
    wrong = int(bad.sum()) + int((status == loadgen.ERROR).sum())
    missing = int((status == loadgen.NO_ANSWER).sum())
    return wrong, missing


def drive(run, devices) -> None:
    target = Target(run)
    try:
        due, keys = schedule(run, target)
        if run.trace:
            target.trace_lookups(run)
        t0, stats, answers = serve_window(run, target, due, keys, run.seconds)
        run.read_memory(devices)
        t = time.perf_counter()
        with run.span("bench.reference"):
            wrong, missing = wrong_answers(target, keys, answers)
        run.log("reference_s", time.perf_counter() - t)
    finally:
        target.close()

    lat = latencies(t0, due, answers)
    cap = run.seconds + ANSWER_GRACE_S
    status = answers["status"]
    in_window = (status == loadgen.OK) & (
        answers["t_done"] <= t0 + run.seconds
    )
    late = answers["t_sent"] - (t0 + due)
    run.e2e["serve_qps"] = float(in_window.sum()) / run.seconds
    run.attempted = int(due.shape[0])
    run.failed = int((status != loadgen.OK).sum())
    run.checks["wrong_answers"] = (wrong, 0)
    run.checks["missing_answers"] = (missing, 0)
    run.log("latency_ms", {p: percentile_ms(lat, p, cap)
                           for p in (50, 90, 95, 99, 100)})
    run.log("requests", due.shape[0])
    run.log("offered_per_s", due.shape[0] / run.seconds)
    run.log("shed", int((status == loadgen.OVERLOADED).sum()))
    run.log("client_late_p99_ms", float(np.nanpercentile(late, 99)) * 1e3)
    run.log("client_imported_jax", bool(answers["jax_imported"]))
    run.log("serve_stats", stats.summary())
    run.layer.update(
        serve_stats=stats,
        client_late_s=late,
        latency_s=np.minimum(lat, cap),
        lookup_sizes=list(target.lookup_sizes),
        n_leaf=int(np.asarray(
            target.index.manifest.model.leaf_slope).shape[0]),
    )


def control_readings(config, mix, seed, seconds, records) -> dict:
    """``wrong_answers`` of the control's answers to the keys a run of
    ``seconds`` sends over the corpus ``records``."""
    from harness import control

    run = types.SimpleNamespace(mix=mix, seconds=seconds, seed=seed)
    target = types.SimpleNamespace(
        records=records, index=types.SimpleNamespace(key_width=10)
    )
    _, keys = schedule(run, target)
    return {"wrong_answers": control.serve_reading(records, keys)}
