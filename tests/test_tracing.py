"""Spans and counters inside the sort pipeline and the query server.

``PhaseClock.span`` splits the partition phase and the batching
executors' ``sort`` phase into their parts; ``ServeStats.span`` times
the server's layers per batch.  Every phase and span is also a
``jax.profiler.TraceAnnotation`` named ``elsar.<name>``, so a profiler
session records it beside the device ops, on one clock.  These tests
hold the accounting to its invariants (parts within their phase, phase
totals untouched), read the annotations back from a CPU trace, and
check the server's queue-wait and cache-load counters.
"""

import asyncio
import binascii
import glob
import json
import os
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import external
from repro.core.config import ServeConfig, SortConfig
from repro.core.stages import stats as stats_lib
from repro.core.stages.stats import PhaseClock, ServeStats, SortStats
from repro.data import gensort
from repro.serve.cache import PartitionBlockCache
from repro.serve.index import SortedFileIndex
from repro.serve.scheduler import FifoBatchScheduler
from repro.serve.server import QueryServer

N = 20_000  # 2 MB under a 1 MiB budget: several partitions, spills
BUDGET = 1 << 20
EPS = 1e-3  # seconds: the spans' own bookkeeping around a phase

PARTITION_SPANS = (
    "partition.read", "partition.bucket", "partition.group",
    "partition.spill",
)
SORT_SPANS = (
    "sort.pack", "sort.wait", "sort.touchup", "sort.gather", "sort.handoff",
)
SERVE_SPANS = ("serve.predict", "serve.search", "serve.block_load")
# the phases a device sort without a manifest has always reported
PHASES = {
    "setup", "train", "plan", "partition", "sort_read", "sort",
    "write_prep", "write",
}


@pytest.fixture(scope="module")
def corpus(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("tracing")
    inp = str(tmp / "in.bin")
    gensort.write_file(inp, N, seed=11)
    return tmp, inp


def _sort(corpus, name, **cfg):
    tmp, inp = corpus
    out = str(tmp / f"{name}.bin")
    config = SortConfig(memory_budget_bytes=BUDGET, workdir=str(tmp), **cfg)
    stats = external.sort_file(inp, out, config)
    os.unlink(out)
    return stats


def _xplane(trace_dir) -> ProfileData:
    paths = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    assert paths, f"no trace under {trace_dir}"
    return ProfileData.from_file(sorted(paths)[-1])


def _elsar_events(profile: ProfileData):
    """``(line index, name, start, end, stats)`` of every host event named
    ``elsar.*``; events of one thread share a line index."""
    out, k = [], 0
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(stats_lib.SPAN_PREFIX):
                    out.append((k, ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
            k += 1
    return out


def _inside(child, parents) -> bool:
    line, _, a, b, _ = child
    return any(p[0] == line and p[2] <= a and b <= p[3] for p in parents)


# ---------------------------------------------------------------------------
# the sort: spans split their phase and stay out of its totals
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "executor,n_readers", [("batched", 1), ("batched", 3), ("mesh", 1)]
)
def test_sort_spans_sum_within_their_phase(corpus, executor, n_readers):
    stats = _sort(corpus, f"{executor}{n_readers}", device_sort=True,
                  executor=executor, n_readers=n_readers)
    assert len(stats.partition_counts) > 1 and stats.device_dispatches
    assert set(stats.span_seconds) == set(PARTITION_SPANS + SORT_SPANS)
    assert set(stats.span_counts) == set(stats.span_seconds)
    assert all(stats.span_counts[s] >= 1 for s in stats.span_counts)
    part = sum(stats.span_seconds[s] for s in PARTITION_SPANS)
    sort = sum(stats.span_seconds[s] for s in SORT_SPANS)
    assert 0 < part <= stats.phase_seconds["partition"] + EPS
    assert 0 < sort <= stats.phase_seconds["sort"] + EPS
    # one touch-up, gather and handoff per dispatched partition
    n_sorted = sum(1 for c in stats.partition_counts if c > 1)
    for s in ("sort.touchup", "sort.gather", "sort.handoff"):
        assert stats.span_counts[s] == n_sorted, s
    assert stats.span_counts["sort.pack"] == stats.device_dispatches
    assert stats.span_counts["sort.wait"] == stats.device_dispatches


def test_spans_stay_out_of_phase_accounting(corpus):
    stats = _sort(corpus, "phases", device_sort=True)
    assert set(stats.phase_seconds) == PHASES
    assert set(stats.phase_wall_seconds) == PHASES
    assert stats.total_seconds == sum(stats.phase_seconds.values())
    assert not set(stats.span_seconds) & set(stats.phase_seconds)
    assert not hasattr(stats, "phase_cpu_seconds")


def test_host_executor_splits_only_the_partition_phase(corpus):
    stats = _sort(corpus, "host", device_sort=False)
    assert stats.executor == "host"
    assert set(stats.span_seconds) == set(PARTITION_SPANS)


def test_phase_clock_span_and_discard():
    clock = PhaseClock()
    with clock.timer("partition"):
        with clock.span("partition.read"):
            time.sleep(0.002)
    with clock.timer("sort_read") as t:
        t.discard()
    stats = SortStats()
    clock.finish(stats)
    assert set(stats.phase_seconds) == {"partition"}
    assert stats.span_counts == {"partition.read": 1}
    assert 0.002 <= stats.span_seconds["partition.read"] \
        <= stats.phase_seconds["partition"]


def test_annotation_cost_without_profiler():
    """Spans run on the hot path: with no profiler session an interval
    (annotation plus two clock reads) costs a few microseconds."""
    clock = PhaseClock()
    n = 2000
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(n):
            with clock.span("partition.read"):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 10e-6, best


def test_sort_spans_in_profiler_trace(corpus, tmp_path):
    _sort(corpus, "warm", device_sort=True)  # compile outside the trace
    with jax.profiler.trace(str(tmp_path / "trace")):
        _sort(corpus, "traced", device_sort=True)
    events = _elsar_events(_xplane(tmp_path / "trace"))
    names = {e[1] for e in events}
    for phase in PHASES:
        assert f"elsar.{phase}" in names, phase
    for span in PARTITION_SPANS + SORT_SPANS:
        assert f"elsar.{span}" in names, span
        phase = span.split(".")[0]
        parents = [e for e in events if e[1] == f"elsar.{phase}"]
        for child in (e for e in events if e[1] == f"elsar.{span}"):
            assert _inside(child, parents), (span, child)
    assert not any(e[1].startswith("elsar.bench") for e in events)


# ---------------------------------------------------------------------------
# the server: queue wait, spans per batch, cache loads, the stats op
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def index(corpus):
    tmp, inp = corpus
    out = str(tmp / "served.bin")
    external.sort_file(inp, out, SortConfig(manifest=True, n_partitions=8))
    idx = SortedFileIndex.open(out)
    yield idx
    idx.close()


def _keys(index, n, seed=0):
    rows = np.random.default_rng(seed).choice(index.n, size=n)
    return index.keys_at(rows)


def test_queue_wait_is_pop_minus_submit():
    now = [10.0]
    stats = ServeStats()

    async def go():
        sched = FifoBatchScheduler(max_batch=2, max_wait_s=60.0,
                                   stats=stats, clock=lambda: now[0])
        sched.submit("point", b"a")
        now[0] = 10.5
        sched.submit("point", b"b")
        now[0] = 13.0
        return await sched.next_batch()

    batch = asyncio.run(go())
    assert [r.payload for r in batch] == [b"a", b"b"]
    assert len(stats.queue_wait_s) == 2
    assert stats.queue_wait_s.min_s == pytest.approx(2.5)
    assert stats.queue_wait_s.max_s == pytest.approx(3.0)


@pytest.mark.parametrize("capacity_blocks", [0.5, 3])
def test_cache_load_bytes_counts_blocks_loaded(index, capacity_blocks):
    """Every miss copies its whole partition block, bypassing blocks (a
    cache smaller than one block) included; a hit copies nothing."""
    starts = index.manifest.part_starts()
    block_bytes = np.diff(starts) * index.fmt.record_bytes
    stats = ServeStats()
    cache = PartitionBlockCache(int(capacity_blocks * block_bytes.max()),
                                stats=stats)
    rows = np.asarray([starts[0], starts[0] + 1, starts[2], starts[0]])
    cache.fetch_rows(index, rows, np.ones(rows.size, dtype=bool))
    loaded = [0, 0, 2, 0] if capacity_blocks < 1 else [0, 2]
    assert stats.cache_misses == len(loaded)
    assert stats.cache_load_bytes == int(block_bytes[loaded].sum())
    assert stats.span_counts["serve.block_load"] == len(loaded)


def test_lookup_without_stats_records_nothing(index, monkeypatch):
    opened = []
    real = stats_lib._annotation
    monkeypatch.setattr(stats_lib, "_annotation",
                        lambda name, meta: opened.append(name) or
                        real(name, meta))
    keys = _keys(index, 16)
    rows, found = index.lookup(keys)
    assert found.all() and opened == []
    stats = ServeStats()
    rows2, found2 = index.lookup(keys, stats=stats)
    assert (rows2 == rows).all() and (found2 == found).all()
    assert opened == ["serve.predict", "serve.search"]
    assert stats.span_counts == {"serve.predict": 1, "serve.search": 1}


async def _serve(index, keys, cfg):
    server = await QueryServer(index, cfg, own_indexes=False).start()
    answers = await asyncio.gather(*[server.point(k.tobytes())
                                     for k in keys])
    await server.stop()
    return server, answers


def test_serve_spans_in_profiler_trace(index, tmp_path):
    keys = _keys(index, 12, seed=1)
    cfg = ServeConfig(max_batch=4, max_wait_ms=1.0, cache_bytes=64 << 20)
    asyncio.run(_serve(index, keys[:4], cfg))  # warm the predict shapes
    with jax.profiler.trace(str(tmp_path / "trace")):
        server, answers = asyncio.run(_serve(index, keys, cfg))
    assert all(a["ok"] and a["found"] for a in answers)
    events = _elsar_events(_xplane(tmp_path / "trace"))
    execute = [e for e in events if e[1] == "elsar.serve.execute"]
    assert len(execute) == server.stats.n_batches
    assert sorted(e[4]["batch"] for e in execute) == \
        list(range(1, server.stats.n_batches + 1))
    for span in SERVE_SPANS:
        children = [e for e in events if e[1] == f"elsar.{span}"]
        assert children, span
        for child in children:
            # inside the execute span of its own batch, on its thread
            same = [e for e in execute if e[4]["batch"] == child[4]["batch"]]
            assert _inside(child, same), (span, child)
    s = server.stats
    assert s.span_counts["serve.execute"] == s.n_batches
    assert s.span_counts["serve.predict"] == s.n_batches
    assert s.span_seconds["serve.execute"] <= s.wall_seconds
    assert s.span_seconds["serve.block_load"] \
        <= s.span_seconds["serve.execute"]
    assert len(s.queue_wait_s) == len(keys)


def test_stats_op_returns_tracing_fields(index, tmp_path):
    sock = str(tmp_path / "elsar.sock")
    keys = _keys(index, 6, seed=2)

    async def go():
        cfg = ServeConfig(max_batch=8, max_wait_ms=1.0, socket_path=sock)
        server = await QueryServer(index, cfg, own_indexes=False).start()
        reader, writer = await asyncio.open_unix_connection(sock)
        for i, k in enumerate(keys):
            writer.write((json.dumps(
                {"id": i, "op": "point",
                 "key": binascii.hexlify(k.tobytes()).decode()}
            ) + "\n").encode())
        await writer.drain()
        for _ in keys:
            await reader.readline()
        writer.write(b'{"id": "s", "op": "stats"}\n')
        await writer.drain()
        snap = json.loads(await reader.readline())
        writer.close()
        await writer.wait_closed()
        await server.stop()
        return snap

    snap = asyncio.run(go())
    assert snap["ok"] and snap["id"] == "s"
    st = snap["stats"]
    assert st["n_point"] == len(keys)
    assert 0 < st["queue_wait_p50_ms"] <= st["queue_wait_p99_ms"]
    assert {"serve.execute", "serve.predict", "serve.search",
            "serve.block_load"} <= set(st["span_seconds"])
    assert st["cache_load_bytes"] > 0
