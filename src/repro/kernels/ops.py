"""Jit'd public wrappers around the Pallas kernels.

The backend alone decides the mode: on a TPU the kernels run compiled;
on the CPU backend they run in ``interpret=True`` mode (the kernel body
executed per block by XLA), which is how the tests check them against
ref.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import rmi as rmi_lib
from repro.core.encoding import ENCODED_BYTES, SENTINEL
from repro.kernels import bitonic, encode, histogram, rmi


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_rows(x: jnp.ndarray, multiple: int, fill) -> tuple[jnp.ndarray, int]:
    n = x.shape[0]
    padded = (n + multiple - 1) // multiple * multiple
    if padded == n:
        return x, n
    pad_width = [(0, padded - n)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad_width, constant_values=fill), n


@functools.partial(jax.jit, static_argnames=("block_rows",))
def encode_keys(
    keys: jnp.ndarray, *, block_rows: int = 1024
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """(N, K) u8 keys -> (hi, lo) u32 via the encode kernel."""
    n, w = keys.shape
    if w < ENCODED_BYTES:
        keys = jnp.pad(keys, ((0, 0), (0, ENCODED_BYTES - w)))
    else:
        keys = keys[:, :ENCODED_BYTES]
    keys, n_orig = _pad_rows(keys, block_rows, 0)
    hi, lo = encode.encode_pallas(
        keys, block_rows=block_rows, interpret=_interpret()
    )
    return hi[:n_orig], lo[:n_orig]


@functools.partial(jax.jit, static_argnames=("n_buckets", "block_rows"))
def rmi_bucket(
    params: rmi_lib.RMIParams,
    hi: jnp.ndarray,
    lo: jnp.ndarray,
    n_buckets: int,
    *,
    block_rows: int = 1024,
) -> jnp.ndarray:
    """Fused RMI inference + equi-depth bucket id."""
    ints = jnp.stack([params.min_hi, params.min_lo])
    consts = jnp.stack(
        [
            params.inv_range,
            params.root_slope,
            params.root_intercept,
            jnp.float32(n_buckets),
        ]
    )
    hi_p, n_orig = _pad_rows(hi, block_rows, 0)
    lo_p, _ = _pad_rows(lo, block_rows, 0)
    out = rmi.rmi_bucket_pallas(
        hi_p,
        lo_p,
        ints,
        consts,
        params.ftable(),
        params.utable(),
        block_rows=block_rows,
        interpret=_interpret(),
    )
    return out[:n_orig]


@functools.partial(jax.jit, static_argnames=("n_buckets", "block_rows"))
def rmi_bucket_pair(
    params: rmi_lib.RMIParams,
    hi_a: jnp.ndarray,
    lo_a: jnp.ndarray,
    hi_b: jnp.ndarray,
    lo_b: jnp.ndarray,
    n_buckets: int,
    *,
    block_rows: int = 1024,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Batched dual-input bucketing: both co-partitioned inputs' keys
    through ONE fused RMI launch (DESIGN.md §9).

    The bucket id is a function of the key alone, so the two inputs can
    share a single padded batch — one kernel dispatch covers both sides
    of a co-partitioned sort / operator alignment check instead of two
    half-empty ones.
    """
    n_a = hi_a.shape[0]
    hi = jnp.concatenate([hi_a, hi_b])
    lo = jnp.concatenate([lo_a, lo_b])
    out = rmi_bucket(params, hi, lo, n_buckets, block_rows=block_rows)
    return out[:n_a], out[n_a:]


def rmi_predict_pos(
    params: rmi_lib.RMIParams,
    hi: jnp.ndarray,
    lo: jnp.ndarray,
    n_records: int,
    *,
    block_rows: int = 1024,
) -> jnp.ndarray:
    """Predicted row of each key in a sorted ``n_records`` file.

    The serving hot path (DESIGN.md §7): the learned index's position
    prediction is exactly the equi-depth bucket id at ``n_buckets ==
    n_records``, so this reuses the fused RMI kernel unchanged.  f32
    arithmetic makes the row exact below 2**24 records; above that the
    rounding is absorbed by the manifest's error band.
    """
    return rmi_bucket(params, hi, lo, n_records, block_rows=block_rows)


@functools.partial(jax.jit, static_argnames=("n_buckets", "block_rows"))
def bucket_histogram(
    bucket_ids: jnp.ndarray, n_buckets: int, *, block_rows: int = 1024
) -> jnp.ndarray:
    # the kernel chunks the bucket axis, so the one-hot tile stays small
    # at any n_buckets without shrinking block_rows below the 1024 tile
    ids, _ = _pad_rows(bucket_ids, block_rows, -1)  # -1 never matches a bucket
    return histogram.histogram_pallas(
        ids, n_buckets, block_rows=block_rows, interpret=_interpret()
    )


@functools.partial(jax.jit, static_argnames=("block_rows",))
def sort_rows(
    hi: jnp.ndarray,
    lo: jnp.ndarray,
    val: jnp.ndarray,
    *,
    block_rows: int = 8,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Row-wise (hi, lo)-ascending bitonic sort; rows padded to pow2 width."""
    r, c = hi.shape
    c_pow2 = 1 << (c - 1).bit_length()
    if c_pow2 != c:
        padk = ((0, 0), (0, c_pow2 - c))
        hi = jnp.pad(hi, padk, constant_values=SENTINEL)
        lo = jnp.pad(lo, padk, constant_values=SENTINEL)
        # max-val padding loses every (key, val) tiebreak against real data
        val = jnp.pad(val, padk, constant_values=jnp.iinfo(jnp.int32).max)
    # rows are independent, so the grid just needs r to be a block_rows
    # multiple: pad with throwaway rows and slice them off (shrinking
    # block_rows until it divides r degenerated to block_rows=1 — one
    # grid step per row — whenever r was prime)
    block_rows = max(1, min(block_rows, r))
    hi, _ = _pad_rows(hi, block_rows, SENTINEL)
    lo, _ = _pad_rows(lo, block_rows, SENTINEL)
    val, _ = _pad_rows(val, block_rows, 0)
    hi_s, lo_s, val_s = bitonic.sort_rows_pallas(
        hi, lo, val, block_rows=block_rows, interpret=_interpret()
    )
    return hi_s[:r, :c], lo_s[:r, :c], val_s[:r, :c]
