"""The benchmark's own gensort-layout corpus generator.

A copy of the program's generator (``repro.data.gensort``) kept with the
benchmark, so that no later change to the program moves the yardstick.
It keeps the Sort Benchmark layout: 100-byte records, a 10-byte key of
printable ASCII (codes 32..126, uniform and independent per byte), and a
90-byte payload that starts with the record's 8-byte index (so a lost or
doubled record shows) and continues with printable filler.

``skewed=True`` is gensort's ``-s`` rule (paper §7.1, Fig. 3): a table of
128 six-byte entries, and record ``i`` has its six leading key bytes
replaced by ``table[floor(log2(max(i, 1))) mod 128]``, so each group of
``2**k`` records shares six of the eight key-prefix bytes.  As in
gensort, the table is a constant, the same for every seed: the seed
draws the other key bytes, so every seed's corpus has the same groups.

Everything else is drawn from the seed alone and vectorised a million
records at a time, except where a mix asks for ``fixed_key_bytes``: the
leading bytes of every key then come from one constant stream, the same
for every seed, and only the bytes after them from the seed.  The
program plans its partitions on the leading bytes, so such a corpus
gives every seed the same partition sizes, and the seed changes the key
bytes that the searches and the comparison read, not the work.  The filler is a fixed printable pattern rotated by the
record index, not random bytes: the sort never reads it, and drawing 82
random bytes per record would make the corpus most of a run's set-up.
"""

from __future__ import annotations

import numpy as np

KEY_BYTES = 10
ID_BYTES = 8
RECORD_BYTES = 100
FILLER_BYTES = RECORD_BYTES - KEY_BYTES - ID_BYTES
ASCII_LO, ASCII_HI = 32, 126
SKEW_TABLE_SIZE = 128
SKEW_TABLE_BYTES = 6
SKEW_TABLE_SEED = 1234
FIXED_KEY_SEED = 0  # the stream of the key bytes a mix holds fixed
CHUNK = 1_000_000

# 95 rotations of the printable alphabet, one filler row per rotation
_ALPHABET = np.arange(ASCII_LO, ASCII_HI + 1, dtype=np.uint8)
_FILLER = np.stack(
    [np.resize(np.roll(_ALPHABET, -r), FILLER_BYTES)
     for r in range(_ALPHABET.size)]
)


def _chunk_rng(seed: int, chunk_index: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2**63 - 1), chunk_index])


def _chunk_keys(seed: int, chunk_index: int, width: int) -> np.ndarray:
    return _chunk_rng(seed, chunk_index).integers(
        ASCII_LO, ASCII_HI + 1, size=(CHUNK, width), dtype=np.uint8
    )


def skew_table() -> np.ndarray:
    """The ``-s`` table: 128 entries of 6 printable bytes."""
    rng = np.random.default_rng(SKEW_TABLE_SEED)
    return rng.integers(
        ASCII_LO, ASCII_HI + 1, size=(SKEW_TABLE_SIZE, SKEW_TABLE_BYTES),
        dtype=np.uint8,
    )


_SKEW_TABLE = skew_table()


def make_records(start: int, n: int, *, seed: int, skewed: bool,
                 fixed_key_bytes: int = 0) -> np.ndarray:
    """Records ``[start, start + n)`` of the corpus, as ``(n, 100)`` u8;
    the corpus is the same however it is cut.  The leading
    ``fixed_key_bytes`` of each key are the same for every seed."""
    out = np.empty((n, RECORD_BYTES), dtype=np.uint8)
    done = 0
    while done < n:
        idx0 = start + done
        c, off = divmod(idx0, CHUNK)
        m = min(n - done, CHUNK - off)
        f = fixed_key_bytes
        if f:
            keys = np.concatenate(
                [_chunk_keys(FIXED_KEY_SEED, c, f)[off : off + m],
                 _chunk_keys(seed, c, KEY_BYTES - f)[off : off + m]], axis=1)
        else:
            keys = _chunk_keys(seed, c, KEY_BYTES)[off : off + m]
        rows = out[done : done + m]
        rows[:, :KEY_BYTES] = keys
        idx = np.arange(idx0, idx0 + m, dtype=np.uint64)
        if skewed:
            g = np.floor(np.log2(np.maximum(idx, 1))).astype(np.int64)
            rows[:, :SKEW_TABLE_BYTES] = _SKEW_TABLE[g % SKEW_TABLE_SIZE]
        rows[:, KEY_BYTES : KEY_BYTES + ID_BYTES] = idx.view(np.uint8).reshape(
            m, ID_BYTES
        )
        rows[:, KEY_BYTES + ID_BYTES :] = _FILLER[idx % _ALPHABET.size]
        done += m
    return out


def write_corpus(path: str, n: int, *, seed: int, skewed: bool,
                 fixed_key_bytes: int = 0) -> None:
    """Write the ``n``-record corpus of ``seed`` to ``path``."""
    with open(path, "wb") as f:
        for start in range(0, n, CHUNK):
            m = min(CHUNK, n - start)
            f.write(make_records(start, m, seed=seed, skewed=skewed,
                                 fixed_key_bytes=fixed_key_bytes).data)


def read_corpus(path: str) -> np.ndarray:
    """Read-only ``(n, 100)`` memory map of a corpus or sorted file."""
    arr = np.memmap(path, dtype=np.uint8, mode="r")
    if arr.shape[0] % RECORD_BYTES:
        raise ValueError(f"{path}: {arr.shape[0]} bytes is not whole records")
    return arr.reshape(-1, RECORD_BYTES)
