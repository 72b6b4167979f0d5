"""The general generator of open-loop request schedules, driven by a
mix's parameters.

Every seed gets the same work in another order.  The number of requests
is ``rate * seconds``; the gaps between arrivals are one fixed set of
quantiles of the arrival process, and the key ranks one fixed set of
quantiles of the key distribution.  Both stand in one constant order,
drawn once from :data:`TRACE_SEED`, so that every seed sends the same
bursts and lulls, and the seed rotates that order by one offset: request
``i`` of the constant order goes out as request ``i + offset`` (mod the
count), with its gap and its key rank.  A server near its knee queues
what a burst brings, so a median taken over a few hundred requests
swings with the order of the gaps; a rotation keeps that order and
moves only where the window cuts it.

Mix parameters read here:

* ``rate_per_s``: offered requests per second;
* ``arrivals``: ``{"process": "poisson"}``, the only process generated;
* ``keys``: ``{"distribution": "zipfian", "zipfian_constant": 0.99,
  "scrambled": true}`` — YCSB's zipfian generator (Gray et al., SIGMOD
  1994) over the ranks of the corpus's records, with YCSB's FNV-1a
  scramble of the rank so that hot keys spread over the key space; no
  other key distribution is generated;
* ``operations``: ``{"read": 1.0}`` — point reads of existing keys
  (YCSB workload C); no other operation is generated yet.
"""

from __future__ import annotations

import numpy as np

FNV_OFFSET_BASIS_64 = 0xCBF29CE484222325
FNV_PRIME_64 = 1099511628211
TRACE_SEED = 0  # draws the one order of gaps and ranks every seed rotates


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def _stratified(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def n_requests(mix: dict, seconds: float) -> int:
    return max(1, int(round(mix["rate_per_s"] * seconds)))


def _in_trace_order(values: np.ndarray, stream: int, seed: int) -> np.ndarray:
    """``values`` in the constant order, rotated by the seed's offset."""
    n = values.shape[0]
    offset = int(_rng(seed, 1).integers(n))
    return np.roll(_rng(TRACE_SEED, stream).permutation(values), offset)


def arrival_offsets(mix: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times of the requests, in seconds from the window's start,
    ascending and inside ``[0, seconds)``."""
    n = n_requests(mix, seconds)
    process = mix["arrivals"]["process"]
    if process != "poisson":
        raise ValueError(f"unknown arrival process {process!r}")
    gaps = -np.log1p(-_stratified(n))  # unit-mean exponential quantiles
    gaps = _in_trace_order(gaps, 1, seed)
    t = np.cumsum(gaps)
    return t / (t[-1] + gaps.mean()) * seconds


def fnv1a_64(values: np.ndarray) -> np.ndarray:
    """YCSB's ``Utils.fnvhash64`` of each int64, as non-negative int64."""
    v = values.astype(np.uint64)
    h = np.full(v.shape, FNV_OFFSET_BASIS_64, dtype=np.uint64)
    with np.errstate(over="ignore"):
        for _ in range(8):
            h ^= v & np.uint64(0xFF)
            h *= np.uint64(FNV_PRIME_64)
            v >>= np.uint64(8)
    return np.abs(h.view(np.int64))


def zipfian_ranks(u: np.ndarray, n_items: int, theta: float) -> np.ndarray:
    """YCSB ``ZipfianGenerator.nextLong`` for uniforms ``u`` over
    ``n_items`` items (rank 0 the most popular)."""
    i = np.arange(1, n_items + 1, dtype=np.float64)
    zetan = float(np.sum(i ** -theta))
    zeta2 = 1.0 + 0.5**theta
    alpha = 1.0 / (1.0 - theta)
    eta = (1.0 - (2.0 / n_items) ** (1.0 - theta)) / (1.0 - zeta2 / zetan)
    uz = u * zetan
    ranks = (n_items * (eta * u - eta + 1.0) ** alpha).astype(np.int64)
    ranks = np.where(uz < zeta2, 1, ranks)
    ranks = np.where(uz < 1.0, 0, ranks)
    return np.clip(ranks, 0, n_items - 1)


def key_rows(mix: dict, n: int, n_items: int, seed: int) -> np.ndarray:
    """Corpus rows whose keys the ``n`` requests ask for."""
    keys = mix["keys"]
    if keys["distribution"] != "zipfian" or not keys["scrambled"]:
        raise ValueError(f"only scrambled zipfian keys are generated, "
                         f"not {keys}")
    u = _in_trace_order(_stratified(n), 2, seed)
    ranks = zipfian_ranks(u, n_items, float(keys["zipfian_constant"]))
    return fnv1a_64(ranks) % n_items


def check_operations(mix: dict) -> None:
    ops = mix.get("operations", {"read": 1.0})
    if set(ops) != {"read"}:
        raise ValueError(f"only point reads are generated, not {sorted(ops)}")
