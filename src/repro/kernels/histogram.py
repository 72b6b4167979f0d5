"""Pallas TPU kernel: bucket histogram via one-hot reduction.

TPUs have no fast scatter-add; the MXU-native idiom for counting is a
one-hot compare + reduction (an ``(R, B)`` one-hot contracted against ones).
The grid is ``(bucket_chunks, row_blocks)``: each output chunk is pinned
across the row axis and accumulated — the canonical Pallas reduction
pattern (init on the first row block).

Block shapes follow the TPU's 1-D int32 layout, which tiles by 1024:
``block_rows`` and every bucket chunk but a whole-array one are multiples
of 1024 (Mosaic refuses a ``(512,)`` block of an ``s32[N]`` operand).
VMEM per step: R*4 (ids) + R*Bc*4 (one-hot) + Bc*4 — 4 MiB at
R = Bc = 1024.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# largest bucket chunk one grid step counts (keeps the one-hot at 4 MiB)
BUCKET_CHUNK = 1024


def _hist_kernel(ids_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]  # (R,)
    chunk = out_ref.shape[0]
    first = pl.program_id(0) * chunk
    onehot = (
        ids[:, None]
        == first + jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)
    ).astype(jnp.int32)
    out_ref[...] += onehot.sum(axis=0)


def histogram_pallas(
    bucket_ids: jnp.ndarray,
    n_buckets: int,
    *,
    block_rows: int = 1024,
    interpret: bool = False,
) -> jnp.ndarray:
    n = bucket_ids.shape[0]
    assert n % block_rows == 0, (n, block_rows)
    if n_buckets <= BUCKET_CHUNK:
        chunk, n_out = n_buckets, n_buckets  # one whole-array chunk
    else:
        chunk = BUCKET_CHUNK
        n_out = -(-n_buckets // chunk) * chunk
    grid = (n_out // chunk, n // block_rows)
    out = pl.pallas_call(
        _hist_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows,), lambda j, i: (i,))],
        out_specs=pl.BlockSpec((chunk,), lambda j, i: (j,)),
        out_shape=jax.ShapeDtypeStruct((n_out,), jnp.int32),
        interpret=interpret,
    )(bucket_ids)
    return out[:n_buckets]
