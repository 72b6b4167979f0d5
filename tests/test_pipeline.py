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
    blob_mixed, blob_disk = blob_mixed.tobytes(), blob_disk.tobytes()
    assert blob_mixed == blob_disk  # (stripe, seq) order, not arrival
    assert blob_mixed.startswith(b"A" * 200 + b"B" * 500 + b"C" * 50)
    # prefetch bytes + take bytes account every byte exactly once
    assert 600 + fresh_mixed == fresh_disk == total
    assert ram._used == 0  # budget returned after the drain
    assert not (tmp_path / "mix.spill").exists()


def test_spill_ram_disk_mix_array_pieces(tmp_path):
    """The partition grouping hands fragments over as owned ``uint8``
    arrays: RAM-resident pieces, ``writev`` overflow and the ``take()``
    join give the all-disk blob, and the budget drains to 0."""
    from repro.core.stages import PartitionSpill, SpillBudget

    rng = np.random.default_rng(5)
    frags = [  # (stripe, seq, pieces) appended out of stripe order
        (2, 0, [rng.integers(0, 256, 300, dtype=np.uint8)]),
        (0, 0, [rng.integers(0, 256, 200, dtype=np.uint8), b"a" * 37]),
        (1, 1, [rng.integers(0, 256, 100, dtype=np.uint8)]),
        (0, 1, [rng.integers(0, 256, 500, dtype=np.uint8),
                rng.integers(0, 256, 74, dtype=np.uint8)]),
        (1, 0, [rng.integers(0, 256, 50, dtype=np.uint8)]),
    ]
    ram = SpillBudget(700)  # RAM holds a few; the rest overflow to disk
    mixed = PartitionSpill(str(tmp_path / "mix.spill"), ram=ram)
    disk = PartitionSpill(str(tmp_path / "disk.spill"))
    for stripe, seq, pieces in frags:
        mixed.append(stripe, seq, list(pieces), n_records=len(pieces))
        disk.append(stripe, seq, list(pieces), n_records=len(pieces))
    total = sum(len(p) for _, _, ps in frags for p in ps)
    assert mixed.n_bytes == disk.n_bytes == total
    assert 0 < ram.disk_bytes < total  # genuinely mixed placement
    for sp in (mixed, disk):
        sp.close_writer()
    blob_mixed, fresh_mixed = mixed.take()
    blob_disk, fresh_disk = disk.take()
    in_order = sorted(frags, key=lambda f: f[:2])
    expect = b"".join(bytes(p) for _, _, ps in in_order for p in ps)
    assert blob_mixed.tobytes() == blob_disk.tobytes() == expect
    assert fresh_mixed == fresh_disk == total
    assert ram._used == 0  # budget returned after the drain


def _grouping_before(block, bucket, n_partitions):
    """The grouping the partition phase used to run: a stable argsort of
    the int32 ids, a whole-batch ``take``, one ``tobytes`` per fragment."""
    order = np.argsort(bucket, kind="stable")
    grouped = block.take(order)
    counts = np.bincount(bucket, minlength=n_partitions)
    ends = np.cumsum(counts)
    off = grouped.offsets
    return counts, [
        (j, grouped.data[off[ends[j] - counts[j]] : off[ends[j]]].tobytes())
        for j in np.nonzero(counts)[0]
    ]


@pytest.mark.parametrize("stride", [100, 37])
@pytest.mark.parametrize(
    "n_partitions,ids",
    [
        (1, "all"),
        (15, "all"),
        (300, "all"),  # over 255: ids need more than 8 bits
        (15, "gaps"),  # empty partitions between occupied ones
        (300, "one"),  # every record in one partition
    ],
)
def test_group_fragments_match_argsort_take(n_partitions, ids, stride):
    from repro.core.format import FixedFormat
    from repro.core.stages.reader import group_fragments

    n = 5_000
    rng = np.random.default_rng(n_partitions * stride)
    mat = rng.integers(0, 256, (n, stride), dtype=np.uint8)
    block = FixedFormat(stride, min(stride, 10))._block_from_matrix(mat)
    bucket = {
        "all": lambda: rng.integers(0, n_partitions, n),
        "gaps": lambda: rng.choice([0, 3, 4, 14], n),
        "one": lambda: np.full(n, n_partitions - 2),
    }[ids]().astype(np.int32)

    counts, frags, copied = group_fragments(block, bucket, n_partitions)
    ref_counts, ref_frags = _grouping_before(block, bucket, n_partitions)
    np.testing.assert_array_equal(counts, ref_counts)
    assert [j for j, _ in frags] == [j for j, _ in ref_frags]
    for (j, frag), (_, ref) in zip(frags, ref_frags):
        assert frag.dtype == np.uint8 and len(frag) == counts[j] * stride
        assert frag.tobytes() == ref
        # each fragment its own allocation: never a view of the batch
        assert not np.shares_memory(frag, block.data)
    assert copied == n * stride  # one copy of every record


def test_group_copies_each_record_once(runs):
    """Fixed-stride sorts take the one-copy grouping: the counter reads
    the input bytes exactly."""
    for r, (_, stats) in runs.items():
        assert stats.counters["partition.group_bytes"] == stats.input_bytes, r


def test_line_sort_keeps_two_copy_grouping(tmp_path):
    """Variable-length blocks keep the gather-then-slice grouping (two
    copies of every record), and the output is the stable host sort."""
    from repro.core.format import LineFormat
    from repro.data import lines

    inp, out = str(tmp_path / "in.txt"), str(tmp_path / "out.txt")
    lines.write_lines(inp, 60_000, kind="uniform", seed=4, max_len=12)
    raw = open(inp, "rb").read()
    stats = external.sort_file(
        inp, out, memory_budget_bytes=256 << 10, batch_records=20_000,
        n_partitions=8, fmt=LineFormat(max_key_bytes=16),
    )
    assert len(stats.partition_counts) > 1
    assert stats.counters["partition.group_bytes"] == 2 * len(raw)
    recs = [ln + b"\n" for ln in raw[:-1].split(b"\n")]
    assert open(out, "rb").read() == b"".join(sorted(recs))


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
