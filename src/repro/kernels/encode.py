"""Pallas TPU kernel: ASCII key bytes -> (hi, lo) uint32 embedding.

This is the front of the paper's hot loop (encode -> RMI -> scatter,
23.5% of ELSAR's runtime, Fig. 6).  Row-tiled: each grid step loads an
``(8, block_rows)`` u8 tile of key bytes into VMEM and emits two
``(block_rows,)`` u32 words.

The keys arrive byte-major — ``(8, N)``, one XLA transpose of the
``(N, 8)`` key matrix — so each key byte position is a lane-dense row of
the tile.  The key-major layout needed column slices of an ``(R, 8)``
tile, which Mosaic compiles but gets wrong on a v5e (byte 1 of each word
read back as 0).

VMEM budget per step: 8*block_rows bytes in (padded to 32 sublanes:
32 KiB at the default block_rows=1024) + 8*block_rows out — far under
the ~16 MiB VMEM of a TPU v5e core; the tile is deliberately small so
several grid steps can be double-buffered by the Pallas pipeline.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.encoding import ENCODED_BYTES


def _encode_kernel(keys_ref, hi_ref, lo_ref):
    k = keys_ref[...].astype(jnp.uint32)  # (8, R): one key byte per row
    hi_ref[...] = (k[0] << 24) | (k[1] << 16) | (k[2] << 8) | k[3]
    lo_ref[...] = (k[4] << 24) | (k[5] << 16) | (k[6] << 8) | k[7]


def encode_pallas(
    keys: jnp.ndarray, *, block_rows: int = 1024, interpret: bool = False
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """keys: (N, 8) uint8 with N % block_rows == 0."""
    n, w = keys.shape
    assert w == ENCODED_BYTES, f"pad keys to {ENCODED_BYTES} bytes first"
    assert n % block_rows == 0, (n, block_rows)
    grid = (n // block_rows,)
    return pl.pallas_call(
        _encode_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((ENCODED_BYTES, block_rows), lambda i: (0, i))],
        out_specs=[
            pl.BlockSpec((block_rows,), lambda i: (i,)),
            pl.BlockSpec((block_rows,), lambda i: (i,)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n,), jnp.uint32),
            jax.ShapeDtypeStruct((n,), jnp.uint32),
        ],
        interpret=interpret,
    )(keys.T)
