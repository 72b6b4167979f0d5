"""Streaming pod-scale external sort — the paper's stated future work
("make ELSAR a high-performing distributed sorting algorithm that can work
with datasets in the order of hundreds of terabytes", §8) built from the
two layers this framework already has:

  host file  --chunks-->  pod all-to-all partition  --spill-->  per-range
  host runs  --device LearnedSort per range-->  concatenate = sorted file

The key property carried over from the paper: every record is routed ONCE
to the device that owns its global equi-depth key range (one collective
per chunk), and per-range spills from different chunks need no merge —
each range is sorted once, at the end, when all its records have arrived.
Total I/O = 2 reads + 2 writes per record regardless of dataset size;
communication = 1-2 index crossings (pre-shuffle optional) — both
independent of how many chunks the dataset is split into.  Only row
*indices* cross the wire during routing: record bytes are gathered
host-side straight from the input block into per-range spill files.

Byte-identity with the single-device sorter (``external.sort_file``)
holds for ties too: each arriving fragment is rewritten in ascending
input order before spilling (equal full-window keys share a bucket, so
restoring input order *within* a range restores it globally), and the
final per-range sort is stable.

Record layout is pluggable through the ``fmt`` seam (``core/format``):
fixed-stride gensort records or delimiter-terminated lines stream through
the same chunk loop, and ``manifest=True`` emits the v3 sidecar so
``SortedFileIndex``/``QueryEngine`` serve the distributed output exactly
like a single-device one.

Scaling out: on this container "devices" are XLA host devices
(``--xla_force_host_platform_device_count=N`` in ``XLA_FLAGS``, set
before jax initializes) and the spill store is the local filesystem.  On
a real multi-host pod each process first calls
``launch.mesh.initialize_multiprocess(...)`` (a documented idempotent
wrapper over ``jax.distributed.initialize``), after which
``launch.mesh.make_data_mesh()`` spans every host and this module's
``shard_map`` programs run unchanged — per-host spills move to local
NVMe and each process writes the output ranges it owns.
"""

from __future__ import annotations

import contextlib
import os
import queue
import shutil
import tempfile
import threading

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import encoding, rmi
from repro.core import manifest as manifest_lib
from repro.core.executor import make_executor
from repro.core.format import GENSORT, RecordFormat
from repro.core.stages.queues import Abort, put
from repro.core.stages.reader import spill_root
from repro.core.stages.stats import PhaseClock, SortStats
from repro.core.stages.writer import WriterPool


def sort_file_distributed(
    input_path: str,
    output_path: str,
    mesh,
    axis_names=("data",),
    *,
    fmt: RecordFormat = GENSORT,
    chunk_records: int = 1 << 18,
    sample_frac: float = 0.01,
    capacity_factor: float = 1.6,
    workdir: str | None = None,
    device_sort: bool = False,
    use_kernels: bool = False,
    executor: str = "auto",
    manifest: bool = False,
    n_writers: int = 0,
) -> SortStats:
    """Sort a record file using the pod as the partitioning engine.

    ``executor`` selects the final-pass range sorter through the shared
    ``SortExecutor`` seam; ``"mesh"`` runs the fused batched graph per
    device inside a ``shard_map`` program over ``mesh`` itself.  Range
    spills land under ``workdir``, or the ``REPRO_SPILL_DIR``
    environment knob with a per-host subdir (NVMe-aware placement on
    multi-host pods), or the system tempdir.  The final range pass
    drains through the zero-copy :class:`WriterPool` (DESIGN.md §15);
    ``n_writers=0`` sizes the pool from the device count.  All temp
    state (range spills, the output fd) is cleaned up on any failure; a
    partial output file is removed rather than left behind.
    """
    stats = SortStats()
    clock = PhaseClock()
    n_dev = 1
    for a in axis_names:
        n_dev *= mesh.shape[a]
    src = fmt.read_block(input_path)
    n = src.n_records
    stats.n_records = n
    stats.input_bytes = src.n_bytes
    if n == 0:
        open(output_path, "wb").close()
        clock.finish(stats)
        return stats

    # --- train the CDF model on a striped sample (global key ranges)
    with clock.timer("train"):
        take = max(int(n * sample_frac), 4096)
        idx = np.linspace(0, n - 1, min(take, n)).astype(np.int64)
        model = rmi.fit(np.ascontiguousarray(src.keys[idx]))
        stats.bytes_read += int(idx.shape[0] * src.keys.shape[1])

    # --- chunk loop: pod partitions each chunk to its owner devices
    chunk_records = max((chunk_records // n_dev) * n_dev, n_dev)
    sh = NamedSharding(mesh, P(axis_names))
    # per-host spill placement (§15): REPRO_SPILL_DIR (or workdir) with
    # a host<k> subdir, so each process of a pod spills to storage it
    # owns — typically node-local NVMe — instead of a shared tempdir
    sroot = spill_root(workdir, per_host=True)
    tmp = tempfile.mkdtemp(prefix="terasort_", dir=sroot)
    range_paths = [os.path.join(tmp, f"r{d:05d}.bin") for d in range(n_dev)]
    range_files: list = []
    created_output = False
    ok = False
    try:
        range_files = [open(p, "wb", buffering=1 << 20) for p in range_paths]
        range_counts = [0] * n_dev
        range_bytes = [0] * n_dev

        # jit once per (chunk shape): route + balance, NO local sort yet
        # (the paper's insight — partitions sort once, after all arrivals)
        route_fns = {}  # capacity_factor -> jitted route fn (lazily built)

        def route(hi, lo, val, factor):
            if factor not in route_fns:
                route_fns[factor] = _make_route_fn(
                    mesh, axis_names, model, chunk_records // n_dev, factor
                )
            return route_fns[factor](hi, lo, val)

        with clock.timer("partition"):
            for off in range(0, n, chunk_records):
                cb = src.slice_records(off, min(off + chunk_records, n))
                m = cb.n_records
                stats.bytes_read += cb.n_bytes
                hi, lo = encoding.encode_np(cb.keys)
                pad = (-m) % n_dev
                if pad:  # sentinel rows: masked in the router, never sent
                    fill = np.full(pad, encoding.SENTINEL)
                    hi = np.concatenate([hi, fill])
                    lo = np.concatenate([lo, fill])
                val = np.arange(m + pad, dtype=np.int32)
                # straight from NumPy: no chunk is staged on device 0
                args = tuple(jax.device_put(a, sh) for a in (hi, lo, val))
                # graceful degradation: rare pathological chunks re-run
                # with a doubled capacity (lossless — overflow is always
                # detected before anything is dropped)
                factor = capacity_factor
                for _ in range(6):
                    out_val, n_valid, lost = route(*args, factor)
                    if int(np.asarray(lost).sum()) == 0:
                        break
                    stats.fallbacks += 1
                    factor *= 2.0
                else:
                    raise RuntimeError("capacity overflow persisted at 32x")
                # spill each device's received range to its range file,
                # in ascending input order (byte-identical tie handling:
                # equal keys share a bucket, so input order within a
                # range is input order globally)
                nv = np.asarray(n_valid).reshape(n_dev)
                ov = np.asarray(out_val).reshape(n_dev, -1)
                for d in range(n_dev):
                    rows = ov[d, : nv[d]]
                    rows = np.sort(rows[(rows >= 0) & (rows < m)])
                    if rows.size == 0:
                        continue
                    payload = cb.gather_bytes(rows)
                    range_files[d].write(payload)
                    range_counts[d] += int(rows.size)
                    range_bytes[d] += len(payload)
                    stats.bytes_written += len(payload)
        for f in range_files:
            f.close()

        # --- final pass: sort each range once, concatenate at offsets.
        # Ranges stream through the shared SortExecutor seam (DESIGN.md
        # §10): host LearnedSort by default, the batched device executor,
        # or the mesh executor (the same fused graph per device inside
        # shard_map) — ranges are consecutive key ranges of one model,
        # exactly the segment contract the fused graph packs into
        # super-batches, and its double-buffering overlaps range reads
        # with in-flight sorts.
        stats.partition_counts = list(range_counts)
        offsets = np.concatenate([[0], np.cumsum(range_bytes)[:-1]])

        ex = make_executor(
            model,
            device_sort=device_sort,
            use_kernels=use_kernels,
            executor=executor,
            mesh=mesh,
            axis_names=axis_names,
            clock=clock,
        )
        stats.executor = ex.name

        def ranges():
            for d in range(n_dev):
                if range_counts[d] == 0:
                    os.unlink(range_paths[d])
                    continue
                with clock.timer("sort_read"):
                    blob = np.fromfile(range_paths[d], dtype=np.uint8)
                    stats.bytes_read += blob.nbytes
                    os.unlink(range_paths[d])
                # parse_blob only needs the buffer protocol — no copy
                yield int(offsets[d]), fmt.parse_blob(blob)

        # the sorted ranges drain through the zero-copy writer pool
        # (§15): the pool owns creation + preallocation of the output,
        # and positioned pwrites let range d+1's write overlap range
        # d+2's sort — ranges are disjoint by construction, so any
        # arrival order is safe
        write_q: queue.Queue = queue.Queue(maxsize=4)
        abort = threading.Event()
        werrors: list = []
        pool = WriterPool(
            clock, output_path, write_q, 1, abort, werrors,
            n_writers=n_writers or max(1, min(4, n_dev)),
            out_bytes=int(sum(range_bytes)),
        )
        created_output = True
        pool.start()
        try:
            for at, block in ex.sort_iter(ranges()):
                put(write_q, (int(at), block), abort)
            put(write_q, None, abort)
        except Abort:
            pass  # a writer failed; its error re-raises below
        except BaseException:
            abort.set()  # release writers blocked on the queue
            raise
        finally:
            pool.join()
        if werrors:
            raise werrors[0]
        stats.n_writers = pool.n_writers
        stats.writer_bytes = list(pool.writer_bytes)
        stats.writer_stall_seconds = list(pool.writer_stall_seconds)
        stats.fallbacks += ex.fallbacks

        if manifest:
            with clock.timer("manifest"):
                m3 = manifest_lib.build(
                    model, range_counts, output_path, fmt=fmt
                )
                mp = manifest_lib.manifest_path(output_path)
                manifest_lib.save(m3, mp)
                stats.manifest_path = mp
        ok = True
    finally:
        # no resource outlives a failure: spill files and the spill dir
        # go unconditionally (the writer pool closes its own fd in
        # join), and a partial output file is removed rather than left
        # looking sorted
        for f in range_files:
            if not f.closed:
                f.close()
        shutil.rmtree(tmp, ignore_errors=True)
        if sroot is not None:
            # the host<k> subdir spill_root created is ours too; rmdir
            # only succeeds when empty, so concurrent runs keep theirs
            with contextlib.suppress(OSError):
                os.rmdir(sroot)
        if not ok and created_output:
            with contextlib.suppress(OSError):
                os.unlink(output_path)
    clock.finish(stats)
    return stats


def _make_route_fn(mesh, axis_names, model, n_per_device, capacity_factor):
    """Route-only variant of distributed.make_sort_fn (no device sort —
    ranges are spilled and sorted once at the end).  Only row indices
    (``val``) cross the wire; keys are used locally for bucketing and
    dropped.  Returns ``fn(hi, lo, val) -> (val_routed, n_valid, lost)``
    with ``val_routed`` per-device arrival-compacted row indices."""
    from repro.core import partition
    from repro.core.encoding import SENTINEL

    axis_names = tuple(axis_names)
    n_dev = 1
    for a in axis_names:
        n_dev *= mesh.shape[a]
    capacity = partition.route_capacity(n_per_device, n_dev, capacity_factor)

    def local_fn(hi, lo, val):
        def transpose_shuffle(x):
            blk = x.reshape(n_dev, -1)
            return jax.lax.all_to_all(
                blk, axis_names, split_axis=0, concat_axis=0, tiled=True
            ).reshape(-1)

        hi = transpose_shuffle(hi)
        lo = transpose_shuffle(lo)
        val = transpose_shuffle(val)
        bucket = rmi.predict_bucket(model, hi, lo, n_dev)
        # sentinel padding rows (short final chunk) must not consume real
        # bucket capacity: they used to route to the last device, where a
        # tiny tail chunk could trigger spurious capacity-doubling
        # retries and inflate stats.fallbacks.  Divert them to an extra
        # discard bucket that is sliced off before the all-to-all.
        is_pad = (hi == SENTINEL) & (lo == SENTINEL)
        bucket = jnp.where(is_pad, n_dev, bucket)
        gather_idx, valid, counts = partition.bucket_matrix(
            bucket, n_dev + 1, capacity
        )
        gather_idx = gather_idx[:n_dev]
        valid = valid[:n_dev]
        lost = jnp.maximum(counts[:n_dev] - capacity, 0).sum()
        send_val = jnp.where(valid, jnp.take(val, gather_idx), -1)
        recv_val = jax.lax.all_to_all(
            send_val, axis_names, 0, 0, tiled=True
        ).reshape(-1)
        n_valid = (recv_val >= 0).sum().astype(jnp.int32)
        # compact valid records to the front (stable by arrival)
        order = jnp.argsort(recv_val < 0, stable=True)
        return jnp.take(recv_val, order), n_valid[None], lost[None]

    spec = P(axis_names)
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec),
        check_vma=False,
    )
    return jax.jit(fn)
