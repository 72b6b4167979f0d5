"""Pallas TPU kernels: 2-level RMI CDF inference + bucket id.

The paper's per-record prediction hot path (§3.3) in two VMEM passes:

1. **route** — the global (routing) feature and the root linear model,
   emitting each key's leaf id;
2. **leaf** — the leaf-local feature reconstruction (per-leaf integer
   offset + scale — the hierarchical-precision scheme of core/rmi.py),
   the leaf FMA, the band clamp and the bucket id.

Between them the leaf rows are gathered by XLA: Mosaic has no vector
gather from a VMEM table (it refuses ``jnp.take`` on the ``(L, 5)`` leaf
table), and an XLA gather of seven words per key is cheap next to the
two elementwise passes.  The gathered rows arrive column-major, ``(5, N)
f32`` + ``(2, N) u32``, so every block keeps the key axis on the lanes.

Mosaic has no unsigned->float cast either; :func:`_u32_to_f32` converts
through two exact 16-bit halves, so the result is the correctly rounded
f32 — bit-identical to XLA's ``astype`` in the reference.

VMEM per grid step at block_rows=1024: 8 KiB of key words, 28 KiB of
gathered leaf rows (padded to 8 sublanes: 64 KiB) and 4 KiB out.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _u32_to_f32(x):
    """Correctly rounded uint32 -> float32 without an unsigned cast: both
    16-bit halves are exact in f32, so the sum rounds exactly once."""
    hi = (x >> 16).astype(jnp.int32).astype(jnp.float32)
    lo = (x & 0xFFFF).astype(jnp.int32).astype(jnp.float32)
    return hi * jnp.float32(65536.0) + lo


def _feature(hi, lo, min_hi, min_lo, inv_range):
    below = (hi < min_hi) | ((hi == min_hi) & (lo < min_lo))
    borrow = (lo < min_lo).astype(jnp.uint32)
    dlo = lo - min_lo
    dhi = hi - min_hi - borrow
    x = _u32_to_f32(dhi) * jnp.float32(4294967296.0) + _u32_to_f32(dlo)
    return jnp.where(below, 0.0, jnp.clip(x * inv_range, 0.0, 1.0))


def _route_kernel(n_leaf, hi_ref, lo_ref, ints_ref, consts_ref, leaf_ref):
    # root routing on the coarse global feature
    x = _feature(
        hi_ref[...], lo_ref[...], ints_ref[0], ints_ref[1], consts_ref[0]
    )
    leaf_ref[...] = jnp.clip(
        ((x * consts_ref[1] + consts_ref[2]) * n_leaf).astype(jnp.int32),
        0,
        n_leaf - 1,
    )


def _leaf_kernel(
    hi_ref, lo_ref, consts_ref,
    slope_ref, icept_ref, band_lo_ref, band_hi_ref, inv_ref,
    min_hi_ref, min_lo_ref,
    bucket_ref,
):
    n_buckets = consts_ref[3]
    # leaf-local feature (full f32 precision inside the leaf's key span)
    xl = _feature(
        hi_ref[...], lo_ref[...], min_hi_ref[...], min_lo_ref[...],
        inv_ref[...],
    )
    y = jnp.clip(
        xl * slope_ref[...] + icept_ref[...], band_lo_ref[...],
        band_hi_ref[...],
    )
    bucket_ref[...] = jnp.minimum(
        (y * n_buckets).astype(jnp.int32), n_buckets.astype(jnp.int32) - 1
    )


def rmi_bucket_pallas(
    hi: jnp.ndarray,
    lo: jnp.ndarray,
    ints: jnp.ndarray,  # (2,) uint32: [min_hi, min_lo]
    consts: jnp.ndarray,  # (4,) f32: [inv_range, root_slope, root_icept, n_buckets]
    ftable: jnp.ndarray,  # (L, 5) f32
    utable: jnp.ndarray,  # (L, 2) u32
    *,
    block_rows: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    n = hi.shape[0]
    assert n % block_rows == 0, (n, block_rows)
    n_leaf = ftable.shape[0]
    grid = (n // block_rows,)
    rows = pl.BlockSpec((block_rows,), lambda i: (i,))
    ints_spec = pl.BlockSpec((2,), lambda i: (0,))
    consts_spec = pl.BlockSpec((4,), lambda i: (0,))
    leaf = pl.pallas_call(
        lambda *refs: _route_kernel(n_leaf, *refs),
        grid=grid,
        in_specs=[rows, rows, ints_spec, consts_spec],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
    )(hi, lo, ints, consts)
    # one 1-D gather per leaf column: a gather of (L, 5) rows would land
    # as an (n, 5) array padded to 128 lanes (25x its size in HBM)
    cols = [jnp.take(ftable[:, c], leaf) for c in range(5)]
    cols += [jnp.take(utable[:, c], leaf) for c in range(2)]
    return pl.pallas_call(
        _leaf_kernel,
        grid=grid,
        in_specs=[rows, rows, consts_spec] + [rows] * len(cols),
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=interpret,
    )(hi, lo, consts, *cols)
