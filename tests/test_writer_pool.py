"""Zero-copy parallel writer pool (core/stages/writer.py, DESIGN.md §15):
byte-identity across pool widths, out-of-order arrival, zero-copy
enqueue, the disjoint-range tripwire, fault-injection cleanup, and the
fresh-path creation bugfix."""

import hashlib
import os
import queue
import threading

import numpy as np
import pytest

from repro.core import external, validate
from repro.core.format import GENSORT, LineFormat
from repro.core.stages.stats import PhaseClock, SortStats
from repro.core.stages.writer import WriterPool, writer_worker
from repro.data import gensort, lines

N = 20_000  # 2 MB fixed corpus; the 512 KB budget forces disk spill


def _sha256(path):
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


@pytest.fixture(scope="module")
def fixed_corpus(tmp_path_factory):
    d = tmp_path_factory.mktemp("wpool_fixed")
    path = str(d / "in.bin")
    gensort.write_file(path, N, skewed=True, seed=11)
    return path, validate.checksum(gensort.read_records(path, mmap=False))


@pytest.fixture(scope="module")
def line_corpus(tmp_path_factory):
    d = tmp_path_factory.mktemp("wpool_line")
    path = str(d / "in.txt")
    lines.write_lines(path, 8_000, kind="skewed", seed=11)
    fmt = LineFormat(max_key_bytes=16)
    return path, validate.checksum_block(fmt.read_block(path)), fmt


def test_byte_identity_grid_fixed(fixed_corpus, tmp_path):
    """formats x readers {1,3} x writers {1,4} under forced disk spill:
    every cell byte-identical, every cell validated sorted."""
    inp, refsum = fixed_corpus
    digests = set()
    for readers in (1, 3):
        for writers in (1, 4):
            out = str(tmp_path / f"f_r{readers}_w{writers}.bin")
            stats = external.sort_file(
                inp, out,
                config=external.SortConfig(
                    memory_budget_bytes=512 << 10, batch_records=5_000,
                    n_readers=readers, n_writers=writers,
                ),
            )
            assert validate.validate_file(out, refsum, N)["ok"]
            assert stats.spill_disk_bytes > 0  # spill genuinely forced
            assert stats.n_writers == writers
            assert sum(stats.writer_bytes) == os.path.getsize(out)
            assert len(stats.writer_stall_seconds) == writers
            digests.add(_sha256(out))
    assert len(digests) == 1


def test_byte_identity_grid_line(line_corpus, tmp_path):
    inp, refsum, fmt = line_corpus
    digests = set()
    for readers in (1, 3):
        for writers in (1, 4):
            out = str(tmp_path / f"l_r{readers}_w{writers}.txt")
            stats = external.sort_file(
                inp, out,
                config=external.SortConfig(
                    memory_budget_bytes=256 << 10, batch_records=2_000,
                    n_readers=readers, n_writers=writers, fmt=fmt,
                ),
            )
            res = validate.validate_file(out, refsum, stats.n_records,
                                         fmt=fmt)
            assert res["ok"], (readers, writers, res)
            digests.add(_sha256(out))
    assert len(digests) == 1


def _block(payload: bytes):
    """A RecordBlock over arbitrary fixed-stride payload bytes."""
    assert len(payload) % GENSORT.record_bytes == 0
    return GENSORT.parse_blob(payload)


def _run_pool(out_path, items, n_writers, out_bytes, clock=None):
    """Drive a WriterPool directly: enqueue ``(offset, block)`` items in
    the given order, then the sorter sentinel."""
    clock = clock or PhaseClock()
    write_q = queue.Queue()
    abort = threading.Event()
    errors = []
    pool = WriterPool(
        clock, out_path, write_q, 1, abort, errors,
        n_writers=n_writers, out_bytes=out_bytes,
    )
    pool.start()
    for item in items:
        write_q.put(item)
    write_q.put(None)
    pool.join()
    return pool, errors


def test_out_of_order_arrival(tmp_path):
    """Blocks arriving in any order land at their precomputed offsets —
    positioned writes have no ordering constraint (§3.5)."""
    rec = GENSORT.record_bytes
    parts = [bytes([65 + i]) * (rec * (i + 1)) for i in range(6)]
    offsets = np.concatenate(
        [[0], np.cumsum([len(p) for p in parts])]
    ).astype(int)
    items = [(int(offsets[i]), _block(parts[i])) for i in range(6)]
    rng = np.random.default_rng(3)
    rng.shuffle(items)
    out = str(tmp_path / "ooo.bin")
    pool, errors = _run_pool(out, items, 3, int(offsets[-1]))
    assert not errors
    with open(out, "rb") as f:
        assert f.read() == b"".join(parts)
    assert sum(pool.writer_bytes) == int(offsets[-1])


def test_writer_enqueues_views_not_copies(tmp_path, monkeypatch):
    """The pool writes memoryviews sharing the block's buffer, never
    tobytes() copies: RecordBlock.memview is zero-copy and every buffer
    handed to pwrite is a view over the enqueued block's data."""
    blk = _block(b"Z" * (GENSORT.record_bytes * 4))
    mv = blk.memview()
    assert isinstance(mv, memoryview)
    assert np.shares_memory(np.frombuffer(mv, dtype=np.uint8), blk.data)

    import repro.core.stages.writer as writer_mod

    seen = []
    real_pwrite = os.pwrite

    def spy(fd, buf, offset):
        seen.append(buf)
        return real_pwrite(fd, buf, offset)

    monkeypatch.setattr(writer_mod.os, "pwrite", spy)
    out = str(tmp_path / "views.bin")
    _, errors = _run_pool(out, [(0, blk)], 1, blk.n_bytes)
    assert not errors
    assert seen, "pwrite never called"
    for buf in seen:
        assert isinstance(buf, memoryview)
        assert np.shares_memory(
            np.frombuffer(buf, dtype=np.uint8), blk.data
        )


def test_overlap_tripwire(tmp_path):
    """Two blocks claiming overlapping output ranges is a partitioning
    bug — the pool must fail loudly, not silently interleave bytes."""
    rec = GENSORT.record_bytes
    a = _block(b"A" * (rec * 2))
    b = _block(b"B" * (rec * 2))
    out = str(tmp_path / "overlap.bin")
    _, errors = _run_pool(out, [(0, a), (rec, b)], 2, rec * 3)
    assert errors and isinstance(errors[0], RuntimeError)
    assert "overlap" in str(errors[0])


def test_fault_injection_cleanup(fixed_corpus, tmp_path, monkeypatch):
    """A writer failing mid-sort aborts the whole pipeline: the error
    propagates to the caller, and neither a partial output file nor
    spill fragments are left behind."""
    import repro.core.stages.writer as writer_mod

    inp, _ = fixed_corpus

    def boom(fd, buf, offset):
        raise OSError(28, "No space left on device (injected)")

    monkeypatch.setattr(writer_mod.os, "pwrite", boom)
    workdir = str(tmp_path / "spills")
    os.makedirs(workdir)
    out = str(tmp_path / "failed.bin")
    with pytest.raises(OSError, match="injected"):
        external.sort_file(
            inp, out,
            config=external.SortConfig(
                memory_budget_bytes=512 << 10, batch_records=5_000,
                n_readers=2, n_writers=4, workdir=workdir,
            ),
        )
    assert not os.path.exists(out)  # partial output removed
    assert os.listdir(workdir) == []  # spill dir cleaned up


def test_pool_creates_fresh_path(tmp_path):
    """The pool owns creation + preallocation: a fresh path (no
    pre-created file) must work — the historical writer opened "r+b"
    and crashed with FileNotFoundError here."""
    blk = _block(b"Q" * (GENSORT.record_bytes * 3))
    out = str(tmp_path / "sub" / "fresh.bin")
    os.makedirs(os.path.dirname(out))
    assert not os.path.exists(out)
    _, errors = _run_pool(out, [(0, blk)], 2, blk.n_bytes)
    assert not errors
    assert os.path.getsize(out) == blk.n_bytes


def test_legacy_writer_worker_fresh_path(tmp_path):
    """The single-writer compatibility entry point also creates missing
    output files (the ISSUE-10 bugfix for embedders that skip the
    pipeline's preallocation)."""
    blk = _block(b"R" * (GENSORT.record_bytes * 2))
    out = str(tmp_path / "legacy.bin")
    write_q = queue.Queue()
    write_q.put((0, blk))
    write_q.put(None)
    errors = []
    writer_worker(
        PhaseClock(), out, write_q, 1, threading.Event(), errors
    )
    assert not errors
    with open(out, "rb") as f:
        assert f.read() == blk.tobytes()


def test_write_phase_split(fixed_corpus, tmp_path):
    """Serialization (buffer prep) accounts under write_prep, syscall
    time under write — the I/O phase no longer absorbs GIL-held copy
    work."""
    inp, refsum = fixed_corpus
    out = str(tmp_path / "phases.bin")
    stats = external.sort_file(
        inp, out,
        config=external.SortConfig(
            memory_budget_bytes=512 << 10, n_writers=2,
        ),
    )
    assert validate.validate_file(out, refsum, N)["ok"]
    assert "write" in stats.phase_seconds
    assert "write_prep" in stats.phase_seconds
    assert stats.phase_seconds["write"] > 0


def test_spill_pieces_append_matches_bytes(tmp_path):
    """PartitionSpill.append accepts the reader's unjoined piece lists
    (written zero-copy via writev) and single bytes blobs
    interchangeably — same segments, same drained blob."""
    from repro.core.stages import PartitionSpill

    joined = PartitionSpill(str(tmp_path / "j.spill"))
    pieces = PartitionSpill(str(tmp_path / "p.spill"))
    frags = [
        (0, 0, [b"aa" * 40, b"bb" * 30, b"c" * 7]),
        (1, 0, [b"dd" * 25]),
        (0, 1, [b"e" * 3, b"f" * 9]),
    ]
    for stripe, seq, ps in frags:
        joined.append(stripe, seq, b"".join(ps), n_records=len(ps))
        pieces.append(stripe, seq, ps, n_records=len(ps))
    assert joined.n_bytes == pieces.n_bytes
    assert joined.segments == pieces.segments
    for sp in (joined, pieces):
        sp.close_writer()
    blob_j, _ = joined.take()
    blob_p, _ = pieces.take()
    assert blob_j.tobytes() == blob_p.tobytes()


def test_spill_root_resolution(tmp_path, monkeypatch):
    """spill_root: explicit workdir wins, REPRO_SPILL_DIR is the
    fallback, per_host appends the process-index subdir (NVMe-aware
    placement at pod scale)."""
    from repro.core.stages import spill_root

    monkeypatch.delenv("REPRO_SPILL_DIR", raising=False)
    assert spill_root(None) is None
    env_dir = str(tmp_path / "envspill")
    monkeypatch.setenv("REPRO_SPILL_DIR", env_dir)
    assert spill_root(None) == env_dir
    assert os.path.isdir(env_dir)
    explicit = str(tmp_path / "explicit")
    assert spill_root(explicit) == explicit  # workdir beats the env
    per_host = spill_root(None, per_host=True)
    assert per_host.startswith(env_dir + os.sep + "host")
    assert os.path.isdir(per_host)


def test_terasort_uses_spill_env(tmp_path, monkeypatch):
    """sort_file_distributed places range spills under REPRO_SPILL_DIR
    (per-host subdir) and drains the final pass through the writer
    pool, byte-identical to the single-device sorter."""
    jax = pytest.importorskip("jax")
    from repro.core import terasort
    from repro.launch.mesh import make_data_mesh

    inp = str(tmp_path / "in.bin")
    gensort.write_file(inp, 5_000, skewed=True, seed=5)
    refsum = validate.checksum(gensort.read_records(inp, mmap=False))
    spill_env = str(tmp_path / "nvme")
    monkeypatch.setenv("REPRO_SPILL_DIR", spill_env)
    out = str(tmp_path / "dist.bin")
    stats = terasort.sort_file_distributed(
        inp, out, make_data_mesh(1), n_writers=2,
    )
    assert validate.validate_file(out, refsum, 5_000)["ok"]
    assert stats.n_writers == 2
    assert sum(stats.writer_bytes) == os.path.getsize(out)
    # the per-host spill tree was created under the env root, and the
    # whole host<k> subtree was cleaned up after the run
    assert os.path.isdir(spill_env)
    assert os.listdir(spill_env) == []
