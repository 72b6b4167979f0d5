"""Pod-scale distributed sort: the paper's partition-and-concatenate as a
``shard_map`` + ``all_to_all`` program (DESIGN.md §2).

Mapping onto the paper:
  reader thread T_i            -> device i (one shard of the input)
  f partitions                 -> one partition per device (equi-depth by
                                  the learned CDF => balanced all-to-all)
  thread-local fragments       -> per-destination capacity-padded send rows
  flush fragments to files     -> ONE lax.all_to_all collective
  sorter thread per partition  -> device-local LearnedSort
  concatenate partitions       -> output is sharded by partition id: device
                                  i holds the i-th contiguous key range =>
                                  the global array is already sorted

The all-to-all needs equal splits, so each per-destination row is padded to
``capacity = ceil(n_local * capacity_factor / n_dev)`` with SENTINEL keys
that sort last and are reported via per-device valid counts.  The learned
equi-depth partitioning is precisely what keeps ``capacity_factor`` small;
the radix baseline overflows under gensort skew (benchmarks/partition_variance).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import learned_sort, partition, rmi
from repro.core.encoding import SENTINEL


def make_sort_fn(
    mesh: Mesh,
    axis_names: Sequence[str],
    model: rmi.RMIParams,
    n_per_device: int,
    *,
    capacity_factor: float = 1.5,
    use_kernels: bool = True,
    pre_shuffle: bool = True,
):
    """Build a jit-able global sort over ``mesh`` axes ``axis_names``.

    Returns ``fn(hi, lo, val) -> (hi_s, lo_s, val_s, valid_count)`` where the
    inputs/outputs are globally-shaped arrays sharded over ``axis_names``;
    outputs are per-device sorted segments of ascending key ranges, each
    padded with SENTINEL keys to a fixed per-device width.  Concatenating
    the valid prefixes of all devices (in device order) is the fully sorted
    sequence — this concatenation is O(1) metadata, exactly the paper's
    "no merge" claim.
    """
    axis_names = tuple(axis_names)
    n_dev = 1
    for a in axis_names:
        n_dev *= mesh.shape[a]
    capacity = partition.route_capacity(n_per_device, n_dev, capacity_factor)
    out_width = capacity * n_dev

    def local_fn(hi, lo, val):
        if pre_shuffle:
            # ---- decorrelation round (beyond-paper; DESIGN.md §2): input
            # stripes can be temporally correlated with the key distribution
            # (gensort -s is), concentrating per-(source,dest) traffic far
            # beyond the equi-depth average and overflowing `capacity`.  A
            # block-transpose all-to-all first gives every device a
            # position-stratified sample of the whole file, after which
            # per-destination counts concentrate around n_local/n_dev.  The
            # paper's disk fragments are unbounded so it never faces this;
            # fixed-shape collectives do.
            def transpose_shuffle(x):
                blk = x.reshape(n_dev, -1)
                return jax.lax.all_to_all(
                    blk, axis_names, split_axis=0, concat_axis=0, tiled=True
                ).reshape(-1)

            hi = transpose_shuffle(hi)
            lo = transpose_shuffle(lo)
            val = transpose_shuffle(val)

        # ---- partition: predict destination device (equi-depth bucket)
        bucket = rmi.predict_bucket(model, hi, lo, n_dev)
        gather_idx, valid, counts = partition.bucket_matrix(
            bucket, n_dev, capacity
        )
        # overflow records (beyond capacity) would be dropped; guard by
        # clamping to the fallback path at the caller level. Here we track
        # a loss counter so callers/tests can assert zero loss.
        lost = jnp.maximum(counts - capacity, 0).sum()

        send_hi = jnp.where(valid, jnp.take(hi, gather_idx), SENTINEL)
        send_lo = jnp.where(valid, jnp.take(lo, gather_idx), SENTINEL)
        send_val = jnp.where(valid, jnp.take(val, gather_idx), -1)

        # ---- shuffle: one all-to-all replaces all fragment-file I/O
        recv_hi = jax.lax.all_to_all(
            send_hi, axis_names, split_axis=0, concat_axis=0, tiled=True
        )
        recv_lo = jax.lax.all_to_all(
            send_lo, axis_names, split_axis=0, concat_axis=0, tiled=True
        )
        recv_val = jax.lax.all_to_all(
            send_val, axis_names, split_axis=0, concat_axis=0, tiled=True
        )
        recv_hi = recv_hi.reshape(out_width)
        recv_lo = recv_lo.reshape(out_width)
        recv_val = recv_val.reshape(out_width)

        # ---- local sort (LearnedSort; sentinels sort last)
        hi_s, lo_s, perm = learned_sort.sort_device(
            model,
            recv_hi,
            recv_lo,
            use_kernels=use_kernels,
        )
        val_s = jnp.take(recv_val, perm)
        n_valid = (recv_hi != SENTINEL).sum().astype(jnp.int32)
        return hi_s, lo_s, val_s, n_valid[None], lost[None]

    spec = P(axis_names)
    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=(spec, spec, spec, spec, spec),
        check_vma=False,
    )
    return jax.jit(fn)


def global_sorted_from_shards(hi_s, lo_s, val_s, n_valid, n_dev: int):
    """Host-side compaction: drop sentinel padding, concatenate shards."""
    import numpy as np

    hi_s = np.asarray(hi_s).reshape(n_dev, -1)
    lo_s = np.asarray(lo_s).reshape(n_dev, -1)
    val_s = np.asarray(val_s).reshape(n_dev, -1)
    n_valid = np.asarray(n_valid).reshape(n_dev)
    his, los, vals = [], [], []
    for d in range(n_dev):
        k = int(n_valid[d])
        his.append(hi_s[d, :k])
        los.append(lo_s[d, :k])
        vals.append(val_s[d, :k])
    return (
        np.concatenate(his),
        np.concatenate(los),
        np.concatenate(vals),
    )
