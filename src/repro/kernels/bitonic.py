"""Pallas TPU kernel: row-wise bitonic sort of (hi, lo, val) tiles.

This is ELSAR's *touch-up* sorter, TPU-adapted (DESIGN.md §2): the paper
uses InsertionSort for last-mile fixing — a sequential, branchy CPU idiom.
The branch-free equivalent with the same role on a vector unit is a bitonic
network: every compare-exchange stage is two static lane rotations + a
select, which maps onto the 8x128 VPU lanes with no data-dependent
control flow.

Each grid step sorts ``block_rows`` independent rows of width C (a power of
two) entirely in VMEM.  Keys are 64-bit ``(hi, lo)`` word pairs compared
lexicographically; ``val`` carries the record index.  Sentinel keys
(0xFFFFFFFF, 0xFFFFFFFF) sort to the end of the row.

Stage count is log2(C)*(log2(C)+1)/2; all partner indices and direction
masks are compile-time constants (numpy), so the kernel unrolls into pure
vector ops.  VMEM per step: 3 arrays * block_rows * C * 4B (+ partner
temporaries); block_rows=8, C=2048 -> ~0.8 MiB.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _stage_list(c: int):
    """Static (k, j) stage schedule for width c."""
    stages = []
    k = 2
    while k <= c:
        j = k // 2
        while j >= 1:
            stages.append((k, j))
            j //= 2
        k *= 2
    return stages


def _partner_swap(x: jnp.ndarray, j: int, is_lower: jnp.ndarray) -> jnp.ndarray:
    """x[:, idx ^ j] without a gather: the lower slot of each pair reads
    j lanes ahead and the upper slot j lanes behind — two lane rotations
    and a select (Mosaic lowers no lane reversal, so no ``jnp.flip``)."""
    c = x.shape[1]
    ahead = pltpu.roll(x, c - j, 1)  # ahead[:, i] = x[:, (i + j) % c]
    behind = pltpu.roll(x, j, 1)  # behind[:, i] = x[:, (i - j) % c]
    return jnp.where(is_lower, ahead, behind)


def _make_kernel(c: int):
    stages = _stage_list(c)

    def kernel(hi_ref, lo_ref, val_ref, hi_out, lo_out, val_out):
        hi = hi_ref[...]
        lo = lo_ref[...]
        val = val_ref[...]
        idx = jax.lax.broadcasted_iota(jnp.int32, hi.shape, 1)
        for k, j in stages:
            # masks derived from iota with static k, j (no captured consts)
            is_lower = (idx & j) == 0  # idx < (idx ^ j)
            up = (idx & k) == 0
            # position holds the MIN of the pair iff (lower XNOR ascending)
            want_min = is_lower == up
            hi_p = _partner_swap(hi, j, is_lower)
            lo_p = _partner_swap(lo, j, is_lower)
            val_p = _partner_swap(val, j, is_lower)
            # Strict total order (val tiebreak) so that duplicate keys can
            # never be kept/taken by BOTH slots of a pair (which would
            # duplicate one payload and drop the other).
            gt = (
                (hi > hi_p)
                | ((hi == hi_p) & (lo > lo_p))
                | ((hi == hi_p) & (lo == lo_p) & (val > val_p))
            )
            # want_min slot: take partner when self > partner (strict)
            # want_max slot: take partner when self < partner.  Plain
            # and/or: Mosaic refuses a select between two bool arrays.
            take_p = (want_min & gt) | ~(want_min | gt)
            hi = jnp.where(take_p, hi_p, hi)
            lo = jnp.where(take_p, lo_p, lo)
            val = jnp.where(take_p, val_p, val)
        hi_out[...] = hi
        lo_out[...] = lo
        val_out[...] = val

    return kernel


def sort_rows_pallas(
    hi: jnp.ndarray,
    lo: jnp.ndarray,
    val: jnp.ndarray,
    *,
    block_rows: int = 8,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Sort each row of (R, C) arrays by (hi, lo) ascending; C power of 2."""
    r, c = hi.shape
    assert c & (c - 1) == 0, f"row width {c} must be a power of two"
    block_rows = min(block_rows, r)
    assert r % block_rows == 0, (r, block_rows)
    grid = (r // block_rows,)
    spec = pl.BlockSpec((block_rows, c), lambda i: (i, 0))
    return pl.pallas_call(
        _make_kernel(c),
        grid=grid,
        in_specs=[spec, spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((r, c), jnp.uint32),
            jax.ShapeDtypeStruct((r, c), jnp.uint32),
            jax.ShapeDtypeStruct((r, c), val.dtype),
        ],
        interpret=interpret,
    )(hi, lo, val)
