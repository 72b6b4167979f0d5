"""Sort-executor seam (core/executor.py) + fused segmented sort
(kernels/fused.py): oracle parity at padding boundaries, byte-identity
against the host LearnedSort path across formats and reader counts,
dispatch batching, O(log) jit-compile growth, and the empty/tiny
partition short-circuit (DESIGN.md §10)."""

import hashlib

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import encoding, external, rmi, validate
from repro.core.executor import (
    BatchedDeviceExecutor,
    HostSortExecutor,
    MeshBatchedExecutor,
    make_executor,
    sort_partition,
)
from repro.core.format import GENSORT, LineFormat
from repro.core.stages.stats import PhaseClock
from repro.data import gensort, lines
from repro.kernels import fused, ref


def _model(n=4096, seed=0):
    return rmi.fit(gensort.uniform_keys(n, seed=seed), n_leaf=256)


def _blocks(sizes, seed=0, dup=False):
    """One RecordBlock per size, with globally range-partitioned keys so
    consecutive blocks mimic the pipeline's equi-depth partitions."""
    rng = np.random.default_rng(seed)
    total = sum(sizes)
    recs = gensort.make_records(total, seed=seed)
    if dup:  # duplicate-saturate: one key everywhere
        recs[:, : gensort.KEY_BYTES] = recs[0, : gensort.KEY_BYTES]
    else:
        kv = recs[:, : gensort.KEY_BYTES].copy().view("S10").reshape(-1)
        recs = recs[np.argsort(kv, kind="stable")]
    out, off = [], 0
    for m in sizes:
        part = recs[off : off + m]
        off += m
        part = part[rng.permutation(m)]  # input order within the partition
        out.append(GENSORT.parse_blob(part.tobytes()))
    return out


def _host_sorted(model, block):
    return HostSortExecutor(model).sort_iter([(0, block)]).__next__()[1]


# ---------------------------------------------------------------------------
# Fused kernel parity vs the stable oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n", [1, 2, 7, 255, 256, 257, 1023, 1024, 1025]
)
@pytest.mark.parametrize("n_segs", [1, 3])
def test_fused_parity_padding_boundaries(n, n_segs):
    """fused_segmented_sort == stable (seg, hi, lo) oracle at sizes
    around every padding boundary (pow2, block_rows multiples)."""
    if n < n_segs:
        pytest.skip("fewer records than segments")
    model = _model()
    keys = gensort.uniform_keys(n, seed=n)[:, : encoding.ENCODED_BYTES]
    bounds = np.linspace(0, n, n_segs + 1).astype(np.int64)
    seg = np.repeat(np.arange(n_segs, dtype=np.int32), np.diff(bounds))
    s_max = 8
    n_rows, capacity = fused.plan_batch(
        1 << max(0, (n - 1).bit_length()), s_max
    )
    sizes = np.diff(bounds)
    alloc = np.ones(n_segs, dtype=np.int64)
    alloc += (n_rows - n_segs) * sizes // n
    row_base = np.zeros(s_max, np.int32)
    rows_per_seg = np.zeros(s_max, np.int32)
    rows_per_seg[:n_segs] = alloc
    row_base[:n_segs] = np.concatenate([[0], np.cumsum(alloc)[:-1]])
    perm, _ = fused.fused_segmented_sort(
        model,
        jnp.asarray(keys),
        jnp.asarray(seg),
        jnp.asarray(row_base),
        jnp.asarray(rows_per_seg),
        n_rows=n_rows,
        capacity=capacity,
        use_kernels=False,
    )
    hi, lo = encoding.encode_np(keys)
    want = ref.segmented_sort_ref(seg, hi, lo)
    np.testing.assert_array_equal(np.asarray(perm), want)


@pytest.mark.parametrize("use_kernels", [False, True])
def test_fused_parity_all_duplicates(use_kernels):
    """A duplicate-saturated batch overflows every row capacity and must
    take the stable-fallback path — output still oracle-identical."""
    n, s_max = 512, 8
    model = _model()
    keys = np.tile(
        gensort.uniform_keys(1, seed=5)[:, : encoding.ENCODED_BYTES],
        (n, 1),
    )
    seg = np.zeros(n, np.int32)
    n_rows, capacity = fused.plan_batch(n, s_max)
    row_base = np.zeros(s_max, np.int32)
    rows_per_seg = np.zeros(s_max, np.int32)
    rows_per_seg[0] = n_rows
    perm, overflow = fused.fused_segmented_sort(
        model,
        jnp.asarray(keys),
        jnp.asarray(seg),
        jnp.asarray(row_base),
        jnp.asarray(rows_per_seg),
        n_rows=n_rows,
        capacity=capacity,
        use_kernels=use_kernels,
    )
    assert bool(np.asarray(overflow))
    hi, lo = encoding.encode_np(keys)
    np.testing.assert_array_equal(
        np.asarray(perm), ref.segmented_sort_ref(seg, hi, lo)
    )


# ---------------------------------------------------------------------------
# Executor-level parity vs the host path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "sizes",
    [
        [1, 2, 3],  # tiny partitions around the short-circuit
        [100, 1023, 1024, 1025, 7],  # padding boundaries
        [5000, 4, 3000],  # uneven occupancy
    ],
)
def test_batched_executor_matches_host(sizes):
    model = _model()
    blocks = _blocks(sizes, seed=1)
    ex = BatchedDeviceExecutor(model)
    got = dict(ex.sort_iter(enumerate(blocks)))
    for i, blk in enumerate(blocks):
        want = _host_sorted(model, blk)
        assert got[i].tobytes() == want.tobytes(), i


def test_batched_executor_duplicate_fallback_matches_host():
    """The grid path's overflow fallback (pinned via flat=False — on CPU
    the auto choice is the flat sort, which has no fallback to take)."""
    model = _model()
    blocks = _blocks([2000, 500], seed=2, dup=True)
    ex = BatchedDeviceExecutor(model, flat=False)
    got = dict(ex.sort_iter(enumerate(blocks)))
    assert ex.fallbacks >= 1  # one key per row saturates capacity
    for i, blk in enumerate(blocks):
        assert got[i].tobytes() == _host_sorted(model, blk).tobytes()


def test_flat_executor_duplicates_match_host():
    """The flat CPU dispatch is exact under duplicate saturation — no
    overflow concept, no fallback counter."""
    model = _model()
    blocks = _blocks([2000, 500], seed=2, dup=True)
    ex = BatchedDeviceExecutor(model, flat=True)
    got = dict(ex.sort_iter(enumerate(blocks)))
    assert ex.fallbacks == 0
    for i, blk in enumerate(blocks):
        assert got[i].tobytes() == _host_sorted(model, blk).tobytes()


@pytest.mark.parametrize("sizes", [[100, 1023, 1024, 1025, 7], [5000, 4, 3000]])
def test_flat_and_grid_paths_byte_identical(sizes):
    """Both dispatch shapes implement the same stable segmented order."""
    model = _model()
    blocks = _blocks(sizes, seed=6)
    outs = []
    for flat in (True, False):
        ex = BatchedDeviceExecutor(model, flat=flat)
        got = dict(ex.sort_iter(enumerate(blocks)))
        outs.append(b"".join(got[i].tobytes() for i in range(len(sizes))))
    assert outs[0] == outs[1]


def _prefix_tied_blocks(sizes, seed=0):
    """Gensort blocks whose keys come in groups of about six that share
    their first 8 bytes and differ in bytes 8-9: the device orders the
    8-byte prefix only, so the touch-up has to reorder every segment."""
    total = sum(sizes)
    recs = gensort.make_records(total, seed=seed)
    recs[:, :8] = recs[np.arange(total) % (total // 6), :8]
    ends = np.cumsum(sizes)
    return [GENSORT.parse_blob(recs[e - m : e].tobytes())
            for m, e in zip(sizes, ends)]


@pytest.mark.parametrize("ties", [False, True])
@pytest.mark.parametrize("kind", ["batched", "mesh"])
def test_batching_executors_emit_the_keys_of_their_records(kind, ties):
    """Each emitted block's keys are the touch-up's, in the final order:
    key i is the first 10 bytes of record i, also where prefix ties make
    the touch-up reorder.  The epilogue writes each record once and
    gathers each key once, twice where the order changed
    (``sort.gather_bytes``)."""
    from repro.launch.mesh import make_mesh

    model = _model()
    sizes = [1500, 2500]
    blocks = (_prefix_tied_blocks(sizes, seed=12) if ties
              else _blocks(sizes, seed=11))
    clock = PhaseClock()
    if kind == "mesh":
        mesh = make_mesh((1,), ("data",))
        ex = MeshBatchedExecutor(model, mesh=mesh, clock=clock)
    else:
        ex = BatchedDeviceExecutor(model, clock=clock)
    got = dict(ex.sort_iter(enumerate(blocks)))
    for i, blk in enumerate(blocks):
        out = got[i]
        assert out.tobytes() == _host_sorted(model, blk).tobytes()
        recs = np.frombuffer(out.tobytes(), np.uint8).reshape(-1, 100)
        np.testing.assert_array_equal(out.keys, recs[:, :10])
    input_bytes = sum(b.n_bytes for b in blocks)
    # 100-byte records, 10-byte keys: 1.1 the input, 1.2 with a reorder
    want = input_bytes * (12 if ties else 11) // 10
    assert clock.counters["sort.gather_bytes"] == want


def test_pad_target_waste_bounded():
    """Size-bucketed padding wastes <= 12.5% (vs up to 2x for pow2) and
    stays monotone with a bounded static-shape set per octave."""
    prev = 0
    for n in list(range(1, 600)) + [4097, 12_345, 50_000, (1 << 20) + 1]:
        t = fused.pad_target(n)
        assert t >= n
        assert t >= prev  # monotone over increasing n
        assert t - n <= max(t // 8, 8), (n, t)
        prev = t
    # eighth-octave quanta: at most 8 distinct targets per octave
    octave = {fused.pad_target(n) for n in range(4097, 8193)}
    assert len(octave) <= 8


def test_batched_executor_batches_partitions():
    """Many partitions collapse into few dispatches (the tentpole win)."""
    model = _model()
    blocks = _blocks([400] * 24, seed=3)
    ex = BatchedDeviceExecutor(model)
    got = dict(ex.sort_iter(enumerate(blocks)))
    assert len(got) == 24
    assert ex.dispatches <= 24 // 4  # >= 4x fewer than per-partition
    assert 0.0 < ex.occupancy <= 1.0


def test_jit_compiles_olog_across_many_partitions():
    """Across a many-partition run the distinct compiled static shapes
    grow O(log max-batch-records), not O(partitions)."""
    model = _model()
    rng = np.random.default_rng(7)
    sizes = [int(s) for s in rng.integers(2, 3000, size=64)]
    ex = BatchedDeviceExecutor(model, batch_slots=4096)
    list(ex.sort_iter(enumerate(_blocks(sizes, seed=4))))
    assert ex.dispatches >= 8  # genuinely a many-dispatch run
    bound = 2 * int(np.log2(max(sum(sizes), 2))) + 4
    assert ex.jit_compiles <= bound, (ex.jit_compiles, bound)
    assert ex.jit_compiles < ex.dispatches


# ---------------------------------------------------------------------------
# Empty / single-record partition short-circuit (regression)
# ---------------------------------------------------------------------------


def test_sort_partition_empty_and_single_no_dispatch(monkeypatch):
    """m == 0 used to pad to one sentinel row and launch the device
    chain; empty and single-record partitions must now short-circuit
    before any dispatch."""
    from repro.core import learned_sort

    def boom(*a, **k):
        raise AssertionError("device sort dispatched for m <= 1")

    monkeypatch.setattr(learned_sort, "sort_device", boom)
    monkeypatch.setattr(learned_sort, "sort_host", boom)
    model = _model()
    empty = GENSORT.parse_blob(b"")
    one = GENSORT.parse_blob(gensort.make_records(1, seed=9).tobytes())
    for blk in (empty, one):
        for device_sort in (False, True):
            out = sort_partition(
                model, blk, device_sort=device_sort, use_kernels=False
            )
            assert out.tobytes() == blk.tobytes()
    ex = BatchedDeviceExecutor(model)
    got = dict(ex.sort_iter([(0, empty), (1, one)]))
    assert ex.dispatches == 0
    assert got[1].tobytes() == one.tobytes()


# ---------------------------------------------------------------------------
# Differential: sort_file byte-identity, both formats x readers {1, 3}
# ---------------------------------------------------------------------------


def _sha(path):
    with open(path, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()


@pytest.mark.parametrize("skewed", [False, True])
def test_sort_file_fixed_byte_identity(tmp_path, skewed):
    n = 30_000
    inp = str(tmp_path / "in.bin")
    gensort.write_file(inp, n, skewed=skewed, seed=11)
    refsum = validate.checksum(gensort.read_records(inp, mmap=False))
    hashes = {}
    for executor, kw in [
        ("host", {}),
        ("batched", {"device_sort": True}),
        ("per_partition", {"device_sort": True,
                           "executor": "per_partition"}),
    ]:
        for readers in (1, 3):
            out = str(tmp_path / f"{executor}{readers}.bin")
            stats = external.sort_file(
                inp, out, memory_budget_bytes=2 << 20,
                batch_records=10_000, n_readers=readers, **kw,
            )
            assert validate.validate_file(out, refsum, n)["ok"]
            assert stats.executor == executor
            hashes[(executor, readers)] = _sha(out)
    assert len(set(hashes.values())) == 1, hashes


@pytest.mark.parametrize("kind", ["uniform", "dups"])
def test_sort_file_line_byte_identity(tmp_path, kind):
    fmt = LineFormat(max_key_bytes=16)
    inp = str(tmp_path / "in.txt")
    lines.write_lines(inp, 12_000, kind=kind, seed=13)
    refsum = validate.checksum_block(fmt.read_block(inp))
    hashes = {}
    for executor, kw in [("host", {}), ("batched", {"device_sort": True})]:
        for readers in (1, 3):
            out = str(tmp_path / f"{executor}{readers}.txt")
            stats = external.sort_file(
                inp, out, fmt=fmt, n_partitions=6, n_readers=readers,
                memory_budget_bytes=1 << 20, **kw,
            )
            res = validate.validate_file(
                out, refsum, stats.n_records, fmt=fmt
            )
            assert res["ok"], (executor, readers, res)
            hashes[(executor, readers)] = _sha(out)
    assert len(set(hashes.values())) == 1, hashes


def test_sort_file_dispatch_accounting(tmp_path):
    """SortStats carries the executor accounting the bench-smoke job
    diffs: batched needs >= 4x fewer dispatches than per-partition."""
    n = 50_000
    inp = str(tmp_path / "in.bin")
    gensort.write_file(inp, n, seed=17)
    out = str(tmp_path / "out.bin")
    per = external.sort_file(
        inp, out, n_partitions=16, device_sort=True,
        executor="per_partition",
    )
    bat = external.sort_file(
        inp, out, n_partitions=16, device_sort=True, executor="batched",
    )
    assert per.device_dispatches == 16
    assert bat.device_dispatches * 4 <= per.device_dispatches
    assert 0.0 < bat.batch_occupancy <= 1.0
    assert bat.jit_compiles >= 1
    # the fused fast path must actually run on uniform data — a fallback
    # here means the pow2 padding or row allocation regressed (padding
    # concentrated in one segment used to overflow its rows)
    assert bat.fallbacks == 0, bat.fallbacks


def test_make_executor_rejects_unknown():
    with pytest.raises(ValueError):
        make_executor(_model(), executor="warp_drive")


def test_terasort_executor_seam(tmp_path):
    """terasort's final pass shares the executor: batched output must be
    byte-identical to the host path."""
    import jax

    from repro.core import terasort
    from repro.launch.mesh import make_mesh

    if len(jax.devices()) < 1:
        pytest.skip("no devices")
    n = 20_000
    inp = str(tmp_path / "in.bin")
    gensort.write_file(inp, n, seed=19)
    refsum = validate.checksum(gensort.read_records(inp, mmap=False))
    mesh = make_mesh((1,), ("data",))
    outs = {}
    for name, kw in [("host", {}), ("batched", {"device_sort": True})]:
        out = str(tmp_path / f"{name}.bin")
        stats = terasort.sort_file_distributed(
            inp, out, mesh, chunk_records=1 << 13, **kw
        )
        assert validate.validate_file(out, refsum, n)["ok"]
        assert stats.executor == name
        outs[name] = _sha(out)
    assert len(set(outs.values())) == 1
