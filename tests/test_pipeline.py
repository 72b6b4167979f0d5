"""Pipelined runtime (core/pipeline.py): reader-count invariance, stripe
serving, and the phase-overlap instrumentation."""

import hashlib

import numpy as np
import pytest

from repro.core import external, validate
from repro.data import gensort
from repro.data.pipeline import record_stripes, stripe_batches

N = 60_000  # 6 MB; skewed -> duplicate full keys, exercising tie stability


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    d = tmp_path_factory.mktemp("pipedata")
    path = str(d / "in.bin")
    gensort.write_file(path, N, skewed=True, seed=7)
    return path, validate.checksum(gensort.read_records(path, mmap=False))


@pytest.fixture(scope="module")
def runs(dataset, tmp_path_factory):
    """One sort per reader count, shared by the assertions below."""
    inp, refsum = dataset
    d = tmp_path_factory.mktemp("pipeout")
    out = {}
    for r in (1, 2, 4):
        path = str(d / f"out{r}.bin")
        stats = external.sort_file(
            inp,
            path,
            memory_budget_bytes=4 << 20,
            batch_records=20_000,
            n_readers=r,
        )
        res = validate.validate_file(path, refsum, N)
        assert res["ok"], (r, res)
        out[r] = (path, stats)
    return out


def _sha256(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


def test_reader_counts_byte_identical(runs):
    """n_readers ∈ {1, 2, 4} must produce byte-identical sorted output:
    fragments are reordered to input order, so ties between duplicate keys
    never depend on reader scheduling."""
    hashes = {r: _sha256(path) for r, (path, _) in runs.items()}
    assert len(set(hashes.values())) == 1, hashes


def test_reader_counts_consistent_stats(runs):
    """Byte counters and partition histograms match the sequential path
    (n_readers=1 keeps the historical accounting) for every reader count."""
    base = runs[1][1]
    # every record: read in partition, spilled, re-read, written = 2x each way
    assert base.bytes_written == 2 * N * gensort.RECORD_BYTES
    assert base.bytes_read >= 2 * N * gensort.RECORD_BYTES  # + sample keys
    assert sum(base.partition_counts) == N
    for r, (_, stats) in runs.items():
        assert stats.n_records == N
        assert stats.n_readers == r
        assert stats.bytes_read == base.bytes_read, r
        assert stats.bytes_written == base.bytes_written, r
        assert stats.partition_counts == base.partition_counts, r


def test_phase_accounting_shape(runs):
    """Busy and wall-span accounting cover the same phases; the
    end-to-end wall clock is positive and overlap is never negative."""
    for r, (_, stats) in runs.items():
        for phase in ("train", "partition", "sort_read", "sort", "write"):
            assert phase in stats.phase_seconds, (r, phase)
            assert phase in stats.phase_wall_seconds, (r, phase)
        assert stats.wall_seconds > 0
        assert stats.overlap_seconds >= 0
        # a phase's merged wall span never exceeds the whole run
        for phase, span in stats.phase_wall_seconds.items():
            assert span <= stats.wall_seconds + 1e-6, (r, phase)


def test_reader_buffer_cap_many_partitions(dataset, tmp_path):
    """With many partitions no single buffer reaches flush_bytes; the
    per-reader total cap must bound memory by flushing the largest buffer,
    without changing the output bytes."""
    from repro.core.pipeline import SortPipelineConfig, run_pipeline

    inp, refsum = dataset
    outs = []
    for r in (1, 2):
        out = str(tmp_path / f"cap{r}.bin")
        run_pipeline(inp, out, SortPipelineConfig(
            n_readers=r,
            n_partitions=64,
            batch_records=20_000,
            memory_budget_bytes=256 << 10,
            flush_bytes=32 << 10,
        ))
        assert validate.validate_file(out, refsum, N)["ok"], r
        outs.append(_sha256(out))
    assert outs[0] == outs[1]


def test_spill_ram_disk_mix_matches_disk_only(tmp_path):
    """RAM-first spills (SpillBudget) must reproduce the all-disk blob
    exactly: placement changes where fragments wait, never their order."""
    from repro.core.stages import PartitionSpill, SpillBudget

    frags = [  # (stripe, seq, blob) appended out of stripe order
        (2, 0, b"E" * 300),
        (0, 0, b"A" * 200),
        (1, 1, b"D" * 100),
        (0, 1, b"B" * 500),
        (1, 0, b"C" * 50),
    ]
    ram = SpillBudget(550)  # fits ~2 fragments; the rest overflow to disk
    mixed = PartitionSpill(str(tmp_path / "mix.spill"), ram=ram)
    disk = PartitionSpill(str(tmp_path / "disk.spill"))
    for i, (stripe, seq, blob) in enumerate(frags):
        mixed.append(stripe, seq, blob, n_records=1)
        disk.append(stripe, seq, blob, n_records=1)
        if i == 2:  # interleave a mid-write prefetch like the loader does
            assert mixed.prefetch() == 600
    total = sum(len(b) for _, _, b in frags)
    assert mixed.n_bytes == disk.n_bytes == total
    assert 0 < ram.disk_bytes < total  # genuinely mixed placement
    for sp in (mixed, disk):
        sp.close_writer()
    blob_mixed, fresh_mixed = mixed.take()
    blob_disk, fresh_disk = disk.take()
    assert blob_mixed == blob_disk  # (stripe, seq) order, not arrival
    assert blob_mixed.startswith(b"A" * 200 + b"B" * 500 + b"C" * 50)
    # prefetch bytes + take bytes account every byte exactly once
    assert 600 + fresh_mixed == fresh_disk == total
    assert ram._used == 0  # budget returned after the drain
    assert not (tmp_path / "mix.spill").exists()


def test_record_stripes_partition_input():
    """Stripes tile [0, n) contiguously in index order, any stripe count."""
    for n, s in [(10, 1), (10, 3), (10, 10), (10, 64), (1_000_003, 16)]:
        stripes = record_stripes(n, s)
        assert stripes[0].start == 0 and stripes[-1].stop == n
        for a, b in zip(stripes, stripes[1:]):
            assert a.stop == b.start and a.index + 1 == b.index
        assert all(st.n_records >= 1 for st in stripes)
    assert record_stripes(0, 4) == []


def test_stripe_batches_cover_in_order(tmp_path):
    path = str(tmp_path / "r.bin")
    gensort.write_file(path, 1_000, seed=3)
    ref = gensort.read_records(path, mmap=False)
    for n_stripes, batch in [(1, 128), (4, 100), (7, 1_000)]:
        got = []
        for stripe in record_stripes(1_000, n_stripes):
            for off, b in stripe_batches(path, stripe, batch):
                assert off == (got[-1][0] + len(got[-1][1]) if got else 0)
                got.append((off, b))
        cat = np.concatenate([b for _, b in got])
        np.testing.assert_array_equal(cat, ref)
