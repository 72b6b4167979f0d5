"""Fused device-resident segmented sort graphs (DESIGN.md §10, §12).

One jitted graph sorts a whole **super-batch** of partitions in a single
device dispatch.  Two graph shapes share the packing protocol:

* the **grid** graph (this module's namesake): encode (Pallas, on device
  — no host ``encode_np`` in the hot path) → fused RMI bucketing →
  scatter into a row grid → row-wise bitonic touch-up → compaction to a
  permutation — taken only when the kernels are asked for
  (``use_kernels``);
* the **flat** graph (:func:`flat_segmented_sort`): pure-jnp encode +
  one stable ``lax.sort`` over ``(seg, hi, lo)`` — the default on every
  backend: on CPU XLA's comparison sort beats the grid and the Pallas
  kernels would run in interpret mode (§12); on TPU the grid graph holds
  this same sort as its overflow branch, so it compiles slower.

Both replace the per-partition encode→RMI→bitonic chains of the
historical device path, whose launch overhead — not the hardware — set
the sort rate.

Segmentation
------------
Each record carries a segment id (its partition's slot in the batch).
Segments are mapped to **disjoint, contiguous row ranges** of the
``(n_rows, capacity)`` touch-up grid: segment ``s`` owns rows
``[row_base[s], row_base[s] + rows_per_seg[s])``, allocated on the host
proportionally to segment size (these are *device arrays*, not static
shapes, so per-batch allocation never recompiles).  A record's row is
its CDF position, quantized once at a fixed fine resolution and then
**re-centered on its segment's own band**::

    q    = rmi_bucket(model, hi, lo, Q_RES)        # one fused kernel pass
    row  = row_base[seg]
         + floor((q - qmin[seg]) / span[seg] * rows_per_seg[seg])

with ``qmin``/``span`` per-segment scatter-min/max reductions of ``q``.
The re-centering matters: a super-batch covers a *slice* of the key
space (a few consecutive equi-depth partitions), so raw global CDF
positions would collapse every segment into a handful of rows.  It is
the executor-level twin of the RMI's leaf-local-frame trick (DESIGN.md
§2) — spend the resolution inside the band the data actually occupies.
The model is monotone and a pure function of the key, and the affine
remap preserves that, so rows ascend with the key inside every segment;
concatenating rows in order yields every segment sorted, in segment
order — a segmented sort with no per-segment dispatch and no
cross-segment assumptions.

Static shapes are a pure function of the padded batch size
(:func:`plan_batch`), so a many-partition run compiles O(log) distinct
graphs, not one per partition.  Bucket overflow (extreme duplicate skew)
falls back to one stable ``lax.sort`` over ``(seg, hi, lo)`` via
``lax.cond`` — data-oblivious fast path, unconditionally correct result.

The remap runs in float32, which is safe by monotonicity: division and
multiplication by positive constants are weakly monotone under rounding,
and ``(span - 1) / span`` stays strictly below 1.0f for ``span <=
Q_RES = 2**20`` (f32 has 24 mantissa bits), so the scaled position never
escapes the segment's row range.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import encoding, partition, rmi as rmi_lib
from repro.core.encoding import SENTINEL
from repro.kernels import ops

# Target mean records per touch-up row (rows are sorted by one bitonic
# pass of width ``capacity``; ~4x headroom absorbs model error and the
# proportional row-allocation rounding).
ROW_TARGET = 256
# Row-count cap: bounds the bitonic grid (and keeps every f32 remap
# product comfortably inside the 24-bit mantissa).
MAX_ROWS = 1 << 14
# CDF quantization resolution.  Static and shape-independent; fine
# enough that a segment covering 1/1000th of the key space still
# resolves ~1000 distinct positions inside its band.
Q_RES = 1 << 20


def _next_pow2(x: int) -> int:
    return 1 << max(0, (x - 1)).bit_length()


def pad_target(n: int) -> int:
    """Size-bucketed static batch size: the next multiple of 1/16th of
    the enclosing power of two (min quantum 8).

    Plain pow2 padding wasted up to 2x the batch (0.763 occupancy on the
    bench corpus — every padded slot is packed, transferred, and sorted).
    Sixteenth-octave quanta cap the waste at 12.5% of the batch (worst
    case sits just past a pow2 boundary, where n ~ p/2 and the quantum is
    p/16) while adding at most 8 distinct static shapes per octave —
    still an O(log max-batch) compile set shared across similar batches.
    """
    p = _next_pow2(max(n, 8))
    q = max(p // 16, 8)
    return -(-n // q) * q


def plan_batch(n_pad: int, max_segments: int) -> tuple[int, int]:
    """Static grid shape for a padded batch: ``(n_rows, capacity)``.

    A pure function of ``n_pad`` (a sixteenth-octave :func:`pad_target`
    bucket), so the set of compiled shapes across a run stays
    O(log max-batch-records) with a small constant.
    ``n_rows >= max_segments`` guarantees every segment at least one
    private row (segments must never share a row).
    """
    n_rows = _next_pow2(
        max(max_segments, min(n_pad // ROW_TARGET, MAX_ROWS))
    )
    capacity = _next_pow2(max(8, 4 * max(1, n_pad // n_rows)))
    return n_rows, capacity


def _compact_perm(
    val_m: jnp.ndarray, counts: jnp.ndarray, n: int
) -> jnp.ndarray:
    """(n_rows, capacity) sorted rows + per-row counts -> (n,) permutation."""
    _, c = val_m.shape
    starts = jnp.concatenate(
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts)[:-1].astype(jnp.int32)]
    )
    pos = jnp.arange(n, dtype=jnp.int32)
    row = jnp.searchsorted(jnp.cumsum(counts), pos, side="right").astype(
        jnp.int32
    )
    col = pos - jnp.take(starts, row)
    return jnp.take(val_m.reshape(-1), row * c + col)


def _fused_impl(
    model: rmi_lib.RMIParams,
    keys: jnp.ndarray,  # (n_pad, 8) uint8 — ENCODED_BYTES key prefixes
    seg: jnp.ndarray,  # (n_pad,) int32 segment ids
    row_base: jnp.ndarray,  # (max_segments,) int32 first row per segment
    rows_per_seg: jnp.ndarray,  # (max_segments,) int32 rows per segment
    *,
    n_rows: int,
    capacity: int,
    use_kernels: bool,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns ``(perm, overflowed)``: output position -> batch row."""
    n = keys.shape[0]
    s_max = row_base.shape[0]
    hi, lo = ops.encode_keys(keys)  # Pallas encode, on device
    q = ops.rmi_bucket(model, hi, lo, Q_RES)  # fused RMI, on device
    # per-segment local frame: re-center q on the band the segment's
    # keys actually occupy (a batch sees a slice of the key space)
    qmin = jnp.full(s_max, Q_RES, jnp.int32).at[seg].min(q)
    qmax = jnp.zeros(s_max, jnp.int32).at[seg].max(q)
    span = jnp.maximum(qmax - qmin, 0) + 1
    frac = (q - jnp.take(qmin, seg)).astype(jnp.float32) / jnp.take(
        span, seg
    ).astype(jnp.float32)
    rps = jnp.take(rows_per_seg, seg)
    row = jnp.take(row_base, seg) + (frac * rps.astype(jnp.float32)).astype(
        jnp.int32
    )
    idx = jnp.arange(n, dtype=jnp.int32)
    gather_idx, valid, counts = partition.bucket_matrix(row, n_rows, capacity)
    overflow = (counts > capacity).any()

    def fast(_):
        hi_m = jnp.where(valid, jnp.take(hi, gather_idx), SENTINEL)
        lo_m = jnp.where(valid, jnp.take(lo, gather_idx), SENTINEL)
        # padding slots carry val = n so real records win every tiebreak
        val_m = jnp.where(valid, jnp.take(idx, gather_idx), jnp.int32(n))
        if use_kernels:
            _, _, val_s = ops.sort_rows(hi_m, lo_m, val_m)
        else:
            _, _, val_s = jax.lax.sort(
                (hi_m, lo_m, val_m), dimension=1, num_keys=3, is_stable=False
            )
        return _compact_perm(val_s, counts, n)

    def fallback(_):
        # stable 3-word comparison sort: correct under any skew/duplicates
        return segmented_perm(seg, hi, lo)

    perm = jax.lax.cond(overflow, fallback, fast, operand=None)
    return perm, overflow


def segmented_perm(
    seg: jnp.ndarray, hi: jnp.ndarray, lo: jnp.ndarray
) -> jnp.ndarray:
    """Stable ``(seg, hi, lo)``-ascending permutation.

    The row index is the sort's fourth key rather than a stably carried
    value: indices are unique, so the order is total and equal to the
    stable sort's, and the TPU compiler builds this unstable four-key
    sort in 106 s against 191 s for the stable three-key one (2**20
    rows, v5e).
    """
    idx = jnp.arange(seg.shape[0], dtype=jnp.int32)
    return jax.lax.sort(
        (seg, hi, lo, idx), num_keys=4, is_stable=False
    )[-1]


def _flat_impl(keys: jnp.ndarray, seg: jnp.ndarray) -> jnp.ndarray:
    """Flat stable segmented sort: one ``lax.sort`` over ``(seg, hi, lo)``
    (:func:`segmented_perm`).

    This is the overflow fallback of the grid path promoted to the
    primary dispatch: on CPU backends XLA's comparison sort beats the
    scatter-grid + per-row bitonic pass ~3x *and* compiles an order of
    magnitude faster (the Pallas encode/RMI kernels run in interpret mode
    on CPU, inlining the kernel body once per grid block).  Encoding is
    pure jnp — no model needed: the stable 3-word comparison is exact, so
    there is nothing for a CDF prediction to speed up here.  Semantics
    are identical to the grid path's fallback, hence byte-identical
    output by the same argument.
    """
    hi, lo = encoding.encode(keys)
    return segmented_perm(seg, hi, lo)


flat_segmented_sort = jax.jit(_flat_impl)


_STATIC = ("n_rows", "capacity", "use_kernels")

# The executor picks the donated variant off-CPU (the packed key/segment
# buffers are dead after the dispatch); CPU backends don't implement
# donation and would warn on every batch.
fused_segmented_sort = jax.jit(_fused_impl, static_argnames=_STATIC)
fused_segmented_sort_donated = jax.jit(
    _fused_impl, static_argnames=_STATIC, donate_argnums=(1, 2)
)
