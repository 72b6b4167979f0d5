"""Pluggable sort executors: the seam between the sorter stage and the
sort implementation (DESIGN.md §10).

An executor consumes a stream of ``(tag, RecordBlock)`` items and yields
``(tag, sorted RecordBlock)``; tags are opaque (the pipeline passes write
offsets).  Three implementations:

* :class:`HostSortExecutor` — the host LearnedSort (``sort_host``), one
  NumPy pass per partition, zero device dispatches.  The default when
  ``device_sort`` is off; its output defines byte-identity for the
  differential harness.
* :class:`PerPartitionDeviceExecutor` — the historical device path: one
  jitted encode→RMI→bitonic chain per partition with host-side key
  encoding.  Kept as the dispatch-count baseline
  (``executor="per_partition"``).
* :class:`BatchedDeviceExecutor` — the default device executor: packs
  partitions into fixed-shape super-batches with segment ids and runs
  one ``kernels/fused`` sort graph per batch — encode happens **on
  device**, and one dispatch covers up to ``max_segments`` partitions.
  Dispatches are **double-buffered**: while batch *k* computes, batch
  *k+1* is packed and dispatched and batch *k−1*'s permutation is
  fetched, so H2D, compute, and D2H overlap.

Every executor produces output byte-identical to the host path: the
stable memcmp order of the full key window, with the GNU-``strncmp``
touch-up beyond byte 8 applied in the executor's epilogue.

All executors record ``device_dispatches`` / ``batch_slots`` /
``batch_records`` / ``jit_compiles`` counters (on themselves and, when a
:class:`~repro.core.stages.stats.PhaseClock` is attached, on the clock so
``SortStats`` picks them up).
"""

from __future__ import annotations

import contextlib
from collections import deque

import numpy as np

from repro.core import rmi
from repro.core.encoding import ENCODED_BYTES
from repro.core.format import RecordBlock
from repro.kernels.fused import _next_pow2

# Partitions per super-batch: one dispatch covers up to this many
# segments.  32 keeps the row grid's per-segment allocation coarse
# enough that proportional rounding stays within the capacity headroom.
MAX_SEGMENTS = 32
# In-flight super-batches (pack k+1 / compute k / fetch k-1).
PIPELINE_DEPTH = 2


class SortExecutor:
    """Base class: stream protocol + shared instrumentation."""

    name = "base"
    # True when several sorter workers may drive sort_iter concurrently
    # (stateless executors); batching executors need a single driver.
    parallel_safe = True

    def __init__(self, model: rmi.RMIParams, clock=None):
        self.model = model
        self.clock = clock
        self.dispatches = 0
        self.fallbacks = 0
        self.batch_records = 0
        self.batch_slots = 0
        self.compile_keys: set = set()

    @property
    def jit_compiles(self) -> int:
        """Distinct static shapes dispatched (an upper bound on compiles:
        the process-level jit cache may already hold some of them)."""
        return len(self.compile_keys)

    @property
    def occupancy(self) -> float:
        """Mean fraction of super-batch slots holding real records."""
        return self.batch_records / self.batch_slots if self.batch_slots else 0.0

    def sort_iter(self, items):
        """``(tag, RecordBlock)`` stream in -> sorted stream out."""
        raise NotImplementedError

    # -- instrumentation helpers --------------------------------------
    def _timer(self, phase: str = "sort"):
        if self.clock is None:
            return contextlib.nullcontext()
        return self.clock.timer(phase)

    def _count_dispatch(self, slots: int, records: int, key) -> None:
        self.dispatches += 1
        self.batch_slots += slots
        self.batch_records += records
        new = key not in self.compile_keys
        self.compile_keys.add(key)
        if self.clock is not None:
            self.clock.add_counter("device_dispatches")
            self.clock.add_counter("batch_slots", slots)
            self.clock.add_counter("batch_records", records)
            if new:
                self.clock.add_counter("jit_compiles")


def _memcmp_touchup(keys: np.ndarray, perm: np.ndarray) -> np.ndarray:
    """Epilogue: fix order beyond the 8-byte embedding (paper's strncmp
    step, §4) over the full key window, stably."""
    k = keys[perm]
    kv = np.ascontiguousarray(k).view(
        [("k", f"S{k.shape[1]}")]
    )["k"].reshape(-1)
    if (kv[:-1] > kv[1:]).any():
        perm = perm[np.argsort(kv, kind="stable")]
    return perm


def sort_partition(
    model: rmi.RMIParams,
    block: RecordBlock,
    *,
    device_sort: bool,
    use_kernels: bool,
    executor: "SortExecutor | None" = None,
) -> RecordBlock:
    """Sort one partition's records (host LearnedSort or the historical
    per-partition device chain).

    Only the key-prefix matrix is sorted; the permutation then gathers
    the (possibly variable-length) record bodies in one ``take``.
    Empty and single-record partitions short-circuit before any device
    dispatch — a 0-record block used to be padded to one sentinel row
    and still launch the full kernel chain.
    """
    from repro.core import learned_sort

    if block.n_records <= 1:
        return block
    keys = np.ascontiguousarray(block.keys)
    if device_sort:
        import jax.numpy as jnp

        from repro.core import encoding
        from repro.core.encoding import SENTINEL

        m = block.n_records
        hi, lo = encoding.encode_np(keys)
        # pad to the next power of two so jit sees O(log) distinct
        # shapes across partitions, not one compile per partition
        m_pad = _next_pow2(m)
        if m_pad != m:
            hi = np.concatenate([hi, np.full(m_pad - m, SENTINEL)])
            lo = np.concatenate([lo, np.full(m_pad - m, SENTINEL)])
        if executor is not None:
            executor._count_dispatch(m_pad, m, ("per_partition", m_pad))
        _, _, perm = learned_sort.sort_device(
            model, jnp.asarray(hi), jnp.asarray(lo), use_kernels=use_kernels
        )
        perm = np.asarray(perm)
        perm = perm[perm < m]  # drop sentinel padding
        perm = _memcmp_touchup(keys, perm)
        return block.take(perm)
    # host LearnedSort (bucket + radix place + touch-up): no per-partition
    # device dispatch — see learned_sort.sort_host
    perm = learned_sort.sort_host(model, keys)
    return block.take(perm)


class HostSortExecutor(SortExecutor):
    """Host (NumPy) LearnedSort per partition — the reference path."""

    name = "host"
    parallel_safe = True

    def sort_iter(self, items):
        for tag, block in items:
            with self._timer():
                block = sort_partition(
                    self.model, block, device_sort=False, use_kernels=False
                )
            yield tag, block


class PerPartitionDeviceExecutor(SortExecutor):
    """Historical device path: one jitted chain per partition (the
    dispatch-count baseline the batched executor is measured against)."""

    name = "per_partition"
    parallel_safe = True

    def __init__(self, model, *, use_kernels=False, clock=None):
        super().__init__(rmi.device_params(model), clock=clock)
        self.use_kernels = use_kernels

    def sort_iter(self, items):
        for tag, block in items:
            with self._timer():
                block = sort_partition(
                    self.model,
                    block,
                    device_sort=True,
                    use_kernels=self.use_kernels,
                    executor=self,
                )
            yield tag, block


class BatchedDeviceExecutor(SortExecutor):
    """Device-resident batched executor: super-batch packing + one fused
    sort dispatch per batch, double-buffered across ``PIPELINE_DEPTH``
    in-flight dispatches (DESIGN.md §10, §12).

    Two dispatch shapes behind the same packing/epilogue protocol:

    * **flat** (the default on every backend): one stable ``lax.sort``
      over ``(seg, hi, lo)`` with pure-jnp encode — the grid path's
      overflow fallback promoted to the primary.  On CPU it runs and
      compiles several times faster than the scatter-grid graph (whose
      Pallas kernels run in interpret mode).  On TPU the grid graph
      holds this same full-size sort as its overflow branch plus two
      more (the bucket grouping and the per-row sort), so it can only
      compile slower: 245 s against 106 s at 2**20 rows for a v5e.
    * **grid** (``use_kernels``): Pallas encode → fused RMI →
      per-segment affine remap → segmented bitonic
      (``kernels/fused.fused_segmented_sort``).

    Both pack into size-bucketed static shapes (``fused.pad_target``:
    sixteenth-octave quanta, <= 12.5% padded slots vs up to 2x for plain
    pow2) so one dispatch is also the fastest dispatch."""

    name = "batched"
    parallel_safe = False  # one packer must own the super-batch

    def __init__(
        self,
        model,
        *,
        use_kernels: bool = False,
        batch_slots: int = 1 << 20,
        batch_bytes: int = 256 << 20,
        max_segments: int = MAX_SEGMENTS,
        depth: int = PIPELINE_DEPTH,
        flat: "bool | None" = None,
        clock=None,
    ):
        super().__init__(model, clock=clock)
        self.use_kernels = use_kernels
        # note: self.batch_slots (base class) is the instrumentation
        # counter; the packing bound lives in _slots_cap/_bytes_cap
        self._slots_cap = max(2, batch_slots)
        self._bytes_cap = max(1, batch_bytes)
        self.max_segments = max(1, min(max_segments, MAX_SEGMENTS))
        self.depth = max(1, depth)
        import jax

        from repro.kernels import fused

        on_cpu = jax.default_backend() == "cpu"
        # flat=None -> auto: the flat graph unless the kernels are asked
        # for (see the class docstring for why TPU takes it too)
        self.flat = (not use_kernels) if flat is None else flat
        if not self.flat:
            # one-time host->device upload; dispatches reuse the leaves
            self.model = rmi.device_params(model)
        self._fused = (
            fused.fused_segmented_sort
            if on_cpu
            else fused.fused_segmented_sort_donated
        )

    # -- packing -------------------------------------------------------

    def _dispatch(self, entries: list) -> tuple:
        """Pack ``entries`` into one device batch and launch the fused
        graph (asynchronously on real backends)."""
        import jax.numpy as jnp

        from repro.kernels import fused

        sizes = [b.n_records for _, b in entries]
        total = sum(sizes)
        n_pad = fused.pad_target(total)
        keys = np.zeros((n_pad, ENCODED_BYTES), dtype=np.uint8)
        seg = np.empty(n_pad, dtype=np.int32)
        off = 0
        for s, (_, b) in enumerate(entries):
            m = b.n_records
            w = min(b.keys.shape[1], ENCODED_BYTES)
            keys[off : off + m, :w] = b.keys[:, :w]
            seg[off : off + m] = s
            off += m
        k = len(entries)
        if self.flat:
            # padding sorts strictly after every real segment (seg = k)
            # and is dropped by the perm < total filter — no pad-share
            # recycling, no row planning, no model on the hot path
            if n_pad != total:
                keys[total:] = 0xFF
                seg[total:] = k
            self._count_dispatch(n_pad, total, ("flat", n_pad))
            perm_dev = fused.flat_segmented_sort(
                jnp.asarray(keys), jnp.asarray(seg)
            )
            return entries, sizes, total, perm_dev, None
        pad = n_pad - total
        pad_share = np.zeros(k, dtype=np.int64)
        if pad:
            # Padding is spread across the segments proportionally and
            # dropped by the perm < total filter in the epilogue.  Each
            # share recycles its own segment's keys, so padding spreads
            # over that segment's rows like its real data, stays inside
            # the segment's CDF band (foreign keys would stretch the
            # per-segment qmin/qmax frame and compress the real records
            # into a sliver of its rows), and the key-duplication factor
            # stays a uniform < 2x — concentrating the whole pow2 pad in
            # one segment amplified its per-row collision peaks past the
            # capacity headroom and forced the fallback.
            np_sizes = np.asarray(sizes, dtype=np.int64)
            pad_share = pad * np_sizes // total
            rem = np.argsort(
                pad * np_sizes % total, kind="stable"
            )[::-1][: pad - int(pad_share.sum())]
            pad_share[rem] += 1
            starts = np.concatenate([[0], np.cumsum(np_sizes)[:-1]])
            p = total
            for s in range(k):
                m = int(pad_share[s])
                if not m:
                    continue
                keys[p : p + m] = keys[
                    starts[s] + (np.arange(m) % np_sizes[s])
                ]
                seg[p : p + m] = s
                p += m
        n_rows, capacity = fused.plan_batch(n_pad, self.max_segments)
        # proportional row allocation: every segment gets >= 1 private
        # row, the rest go out by size (padding included)
        alloc_sizes = np.asarray(sizes, dtype=np.int64) + pad_share
        alloc = np.ones(k, dtype=np.int64)
        alloc += (n_rows - k) * alloc_sizes // n_pad
        row_base = np.zeros(self.max_segments, dtype=np.int32)
        rows_per_seg = np.zeros(self.max_segments, dtype=np.int32)
        rows_per_seg[:k] = alloc
        row_base[:k] = np.concatenate([[0], np.cumsum(alloc)[:-1]])
        self._count_dispatch(n_pad, total, (n_pad, n_rows, capacity))
        perm_dev, overflow_dev = self._fused(
            self.model,
            jnp.asarray(keys),
            jnp.asarray(seg),
            jnp.asarray(row_base),
            jnp.asarray(rows_per_seg),
            n_rows=n_rows,
            capacity=capacity,
            use_kernels=self.use_kernels,
        )
        return entries, sizes, total, perm_dev, overflow_dev

    def _finish(self, handle: tuple):
        """Fetch one batch's permutation and emit its sorted blocks."""
        entries, sizes, total, perm_dev, overflow_dev = handle
        perm = np.asarray(perm_dev)  # blocks until the device is done
        if overflow_dev is not None and bool(np.asarray(overflow_dev)):
            self.fallbacks += 1
        perm = perm[perm < total]  # drop the padding records
        bases = np.concatenate([[0], np.cumsum(sizes)])
        pos = 0
        for s, (tag, block) in enumerate(entries):
            m = sizes[s]
            local = perm[pos : pos + m] - bases[s]
            pos += m
            if local.size != m or (local < 0).any() or (local >= m).any():
                raise RuntimeError(
                    f"segmented sort mixed segments: segment {s} got "
                    f"indices outside [0, {m}) — executor invariant broken"
                )
            local = _memcmp_touchup(block.keys, local)
            yield tag, block.take(local)

    # -- stream protocol ----------------------------------------------

    def sort_iter(self, items):
        pending: deque = deque()
        cur: list = []
        cur_records = 0
        cur_bytes = 0
        for tag, block in items:
            if block.n_records <= 1:
                yield tag, block  # empty/single: never dispatched
                continue
            cur.append((tag, block))
            cur_records += block.n_records
            cur_bytes += block.n_bytes
            if (
                len(cur) >= self.max_segments
                or cur_records >= self._slots_cap
                or cur_bytes >= self._bytes_cap
            ):
                with self._timer():
                    pending.append(self._dispatch(cur))
                cur, cur_records, cur_bytes = [], 0, 0
                while len(pending) >= self.depth:
                    with self._timer():
                        yield from self._finish(pending.popleft())
        if cur:
            with self._timer():
                pending.append(self._dispatch(cur))
        while pending:
            with self._timer():
                yield from self._finish(pending.popleft())


class MeshBatchedExecutor(SortExecutor):
    """Mesh-sharded batched executor: the flat super-batch graph run
    *per device inside one ``shard_map`` program* (DESIGN.md §13).

    Where :class:`BatchedDeviceExecutor` packs up to ``max_segments``
    partitions into one device's dispatch, this executor additionally
    spreads the packed segments over every device of a jax mesh: block
    ``i`` of a dispatch group is assigned to the least-loaded device
    (ties resolve in device order, so ``n_dev`` equal-sized key ranges
    land on their owner devices), each device's shard is padded to a
    shared sixteenth-octave :func:`~repro.kernels.fused.pad_target`
    width, and ONE jitted ``shard_map`` launch sorts every device's
    segments locally — the flat stable ``(seg, hi, lo)`` comparison
    graph of DESIGN.md §12, which is byte-identical to the host path by
    the same argument (pure-jnp encode, stable ties, memcmp touch-up in
    the epilogue).  No collective runs inside the program: records were
    already routed to their owner ranges, so the sort is embarrassingly
    device-local — the paper's merge-free invariant at mesh scale.

    Occupancy/dispatch accounting matches the single-device executor:
    one dispatch covers ``n_dev * n_pad`` slots, and padded slots (both
    per-device tail pad and idle devices) count against occupancy.
    """

    name = "mesh"
    parallel_safe = False  # one packer owns the super-batch

    def __init__(
        self,
        model,
        *,
        mesh=None,
        axis_names=("data",),
        batch_slots: int = 1 << 20,
        batch_bytes: int = 256 << 20,
        max_segments: int = MAX_SEGMENTS,
        depth: int = PIPELINE_DEPTH,
        clock=None,
    ):
        super().__init__(model, clock=clock)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        if mesh is None:
            from repro.launch.mesh import make_data_mesh

            mesh = make_data_mesh()
            axis_names = ("data",)
        self.mesh = mesh
        self.axis_names = tuple(axis_names)
        self.n_dev = 1
        for a in self.axis_names:
            self.n_dev *= mesh.shape[a]
        self._slots_cap = max(2, batch_slots)
        self._bytes_cap = max(1, batch_bytes)
        self.max_segments = max(1, min(max_segments, MAX_SEGMENTS))
        self.depth = max(1, depth)
        self._sharding = NamedSharding(mesh, PartitionSpec(self.axis_names))
        self._fns: dict = {}  # n_pad -> jitted shard_map sort

    def _sort_fn(self, n_pad: int):
        fn = self._fns.get(n_pad)
        if fn is None:
            import jax
            from jax.sharding import PartitionSpec as P

            from repro.kernels import fused

            def local_fn(keys, seg):
                # local shapes: keys (1, n_pad, 8), seg (1, n_pad)
                perm = fused._flat_impl(
                    keys.reshape(n_pad, -1), seg.reshape(n_pad)
                )
                return perm.reshape(1, n_pad)

            spec = P(self.axis_names)
            fn = jax.jit(
                jax.shard_map(
                    local_fn,
                    mesh=self.mesh,
                    in_specs=(spec, spec),
                    out_specs=spec,
                    check_vma=False,
                )
            )
            self._fns[n_pad] = fn
        return fn

    # -- packing -------------------------------------------------------

    def _dispatch(self, entries: list) -> tuple:
        import jax

        from repro.kernels import fused

        # least-loaded device assignment, stable in arrival order: the
        # i-th of n_dev equal ranges lands on device i (its owner)
        dev_entries: list = [[] for _ in range(self.n_dev)]
        dev_load = [0] * self.n_dev
        for tag, b in entries:
            d = min(range(self.n_dev), key=lambda i: dev_load[i])
            dev_entries[d].append((tag, b))
            dev_load[d] += b.n_records
        total = sum(dev_load)
        n_pad = fused.pad_target(max(max(dev_load), 1))
        keys = np.zeros((self.n_dev, n_pad, ENCODED_BYTES), dtype=np.uint8)
        # pad rows carry seg = len(entries) — strictly after every real
        # local segment id, so they sort last and drop out of the perm
        seg = np.full((self.n_dev, n_pad), len(entries), dtype=np.int32)
        for d in range(self.n_dev):
            off = 0
            for s, (_, b) in enumerate(dev_entries[d]):
                m = b.n_records
                w = min(b.keys.shape[1], ENCODED_BYTES)
                keys[d, off : off + m, :w] = b.keys[:, :w]
                seg[d, off : off + m] = s
                off += m
        self._count_dispatch(
            self.n_dev * n_pad, total, ("mesh", self.n_dev, n_pad)
        )
        # straight from NumPy: each device receives only its own shard
        # (a jnp.asarray first would stage the whole batch on device 0)
        perm_dev = self._sort_fn(n_pad)(
            jax.device_put(keys, self._sharding),
            jax.device_put(seg, self._sharding),
        )
        return dev_entries, perm_dev

    def _finish(self, handle: tuple):
        dev_entries, perm_dev = handle
        perm = np.asarray(perm_dev)  # blocks until every device is done
        for d, entries in enumerate(dev_entries):
            sizes = [b.n_records for _, b in entries]
            local_total = sum(sizes)
            p = perm[d]
            p = p[p < local_total]  # pad rows pack after the real rows
            bases = np.concatenate([[0], np.cumsum(sizes)])
            pos = 0
            for s, (tag, block) in enumerate(entries):
                m = sizes[s]
                local = p[pos : pos + m] - bases[s]
                pos += m
                if (
                    local.size != m
                    or (local < 0).any()
                    or (local >= m).any()
                ):
                    raise RuntimeError(
                        f"mesh segmented sort mixed segments: device {d} "
                        f"segment {s} got indices outside [0, {m}) — "
                        "executor invariant broken"
                    )
                local = _memcmp_touchup(block.keys, local)
                yield tag, block.take(local)

    # -- stream protocol ----------------------------------------------

    def sort_iter(self, items):
        pending: deque = deque()
        cur: list = []
        cur_records = 0
        cur_bytes = 0
        for tag, block in items:
            if block.n_records <= 1:
                yield tag, block
                continue
            cur.append((tag, block))
            cur_records += block.n_records
            cur_bytes += block.n_bytes
            if (
                len(cur) >= self.n_dev * self.max_segments
                or cur_records >= self._slots_cap
                or cur_bytes >= self._bytes_cap
            ):
                with self._timer():
                    pending.append(self._dispatch(cur))
                cur, cur_records, cur_bytes = [], 0, 0
                while len(pending) >= self.depth:
                    with self._timer():
                        yield from self._finish(pending.popleft())
        if cur:
            with self._timer():
                pending.append(self._dispatch(cur))
        while pending:
            with self._timer():
                yield from self._finish(pending.popleft())


def make_executor(
    model: rmi.RMIParams,
    config=None,
    *,
    device_sort: bool = False,
    use_kernels: bool = False,
    executor: str = "auto",
    batch_slots: int = 0,
    batch_bytes: int = 0,
    max_segments: int = 0,
    mesh=None,
    axis_names=("data",),
    clock=None,
) -> SortExecutor:
    """Build the executor for a sort run.

    ``config`` is the public knob surface
    (``repro.core.config.ExecutorConfig``); the keyword arguments are
    the historical spelling and act as overrides on top of it (any
    non-default keyword wins over the config's value).  ``clock`` is a
    runtime object, not configuration, and stays a keyword.

    ``executor`` selects the implementation: ``"auto"`` (host unless
    ``device_sort``/``use_kernels`` asked for the device path, then
    batched), ``"host"``, ``"batched"``, ``"per_partition"`` (the
    historical device path, kept as the dispatch-count baseline), or
    ``"mesh"`` (the flat batched graph run per device of a jax mesh
    inside one ``shard_map`` program; ``mesh``/``axis_names`` supply the
    topology, defaulting to a 1-D mesh over every visible device).
    """
    if config is not None:
        device_sort = device_sort or config.device_sort
        use_kernels = use_kernels or config.use_kernels
        executor = executor if executor != "auto" else config.executor
        batch_slots = batch_slots or config.batch_slots
        batch_bytes = batch_bytes or config.batch_bytes
        max_segments = max_segments or config.max_segments
        mesh = mesh if mesh is not None else config.mesh
        axis_names = (
            axis_names if axis_names != ("data",) else config.axis_names
        )
    choice = executor or "auto"
    if choice == "auto":
        choice = "batched" if (device_sort or use_kernels) else "host"
    if choice == "host":
        return HostSortExecutor(model, clock=clock)
    if choice == "per_partition":
        return PerPartitionDeviceExecutor(
            model, use_kernels=use_kernels, clock=clock
        )
    if choice in ("batched", "mesh"):
        kw: dict = {"clock": clock}
        if batch_slots:
            kw["batch_slots"] = batch_slots
        if batch_bytes:
            kw["batch_bytes"] = batch_bytes
        if max_segments:
            kw["max_segments"] = min(max_segments, MAX_SEGMENTS)
        if choice == "mesh":
            return MeshBatchedExecutor(
                model, mesh=mesh, axis_names=axis_names, **kw
            )
        return BatchedDeviceExecutor(model, use_kernels=use_kernels, **kw)
    raise ValueError(
        f"unknown executor {executor!r} "
        "(expected auto|host|batched|per_partition|mesh)"
    )
