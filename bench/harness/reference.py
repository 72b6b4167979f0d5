"""The plain reference the benchmark holds the program to.

It imports nothing of the program and reads only the corpus the
benchmark made from the seed.

* A sort's answer is the stable memcmp order of the records by their
  10-byte key: equal keys keep their input order.  The keys are
  printable ASCII (never a NUL byte), so NumPy's ``S10`` comparison is
  exactly memcmp.
* A point lookup's answer is the first record, in that order, whose key
  equals the requested key.

``key_bytes`` below 10 gives the **control**: the same reference on a
key cut to its leading bytes.  It breaks the guarantee that the whole
key orders the output, as a sort on a narrower device key would, and
every comparison must fail it.
"""

from __future__ import annotations

import numpy as np

KEY_BYTES = 10
ROWS_PER_BLOCK = 1 << 20


def _key_view(records: np.ndarray, key_bytes: int) -> np.ndarray:
    keys = np.ascontiguousarray(records[:, :key_bytes])
    if not keys.all():
        raise ValueError("reference keys must not hold NUL bytes")
    return keys.view(f"S{key_bytes}").reshape(-1)


def stable_order(records: np.ndarray, key_bytes: int = KEY_BYTES):
    """``(order, sorted_keys)``: the stable sort order of ``records`` by
    their leading ``key_bytes`` bytes, and the keys in that order."""
    keys = _key_view(records, key_bytes)
    order = np.argsort(keys, kind="stable")
    return order, keys[order]


def mismatched_records(
    output: np.ndarray, records: np.ndarray, order: np.ndarray
) -> int:
    """Records of ``output`` that differ from the reference output
    ``records[order]`` at the same position; a missing or extra record
    counts as one that differs."""
    n = min(output.shape[0], order.shape[0])
    wrong = abs(output.shape[0] - order.shape[0])
    for a in range(0, n, ROWS_PER_BLOCK):
        b = min(a + ROWS_PER_BLOCK, n)
        want = records[order[a:b]].view(np.uint32)
        got = np.ascontiguousarray(output[a:b]).view(np.uint32)
        wrong += int((want != got).any(axis=1).sum())
    return wrong


def point_answers(
    records: np.ndarray, order: np.ndarray, sorted_keys: np.ndarray,
    query_keys: np.ndarray,
):
    """``(found, rows)`` for ``query_keys`` (``(m, key_bytes)`` u8): whether
    a record has the key, and the input row of the first such record in
    the reference order."""
    w = sorted_keys.dtype.itemsize
    q = np.ascontiguousarray(query_keys[:, :w]).view(f"S{w}").reshape(-1)
    pos = np.searchsorted(sorted_keys, q, side="left")
    clipped = np.minimum(pos, sorted_keys.shape[0] - 1)
    found = (pos < sorted_keys.shape[0]) & (sorted_keys[clipped] == q)
    return found, order[clipped]
