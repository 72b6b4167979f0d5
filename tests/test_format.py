"""RecordFormat layer unit + property tests (core/format.py, DESIGN.md §8):
LineFormat round-trip identity, delimiter-boundary fragment splits at
every offset within a stripe, short-key encode order-equivalence, and the
strict (no-silent-truncation) fixed-file reader."""

import os
import tempfile

import numpy as np
import pytest

from repro.core import encoding, validate
from repro.core.format import FixedFormat, LineFormat, line_keys
from repro.data import gensort, lines
from repro.data.pipeline import Stripe, byte_stripes
from repro.testing.hypothesis_compat import given, settings, st

# strategy: a corpus as a list of lines, each a list of printable codes
# (the delimiter 0x0A can never appear in content by construction)
_line = st.lists(st.integers(32, 126), min_size=0, max_size=12)
_corpus = st.lists(_line, min_size=0, max_size=12)


def _raw(corpus: "list[list[int]]", terminated: bool) -> bytes:
    out = b"".join(bytes(l) + b"\n" for l in corpus)
    if not terminated and out:
        out = out[:-1]
    return out


def _records_of(raw: bytes) -> "list[bytes]":
    """The normalized records a raw byte string holds (an unterminated
    final line gains its delimiter; an empty file holds none)."""
    if not raw:
        return []
    ls = raw.split(b"\n")
    if raw.endswith(b"\n"):
        ls = ls[:-1]
    return [l + b"\n" for l in ls]


def _stripe_records(fmt: LineFormat, path: str, s: Stripe) -> "list[bytes]":
    recs = []
    for block in fmt.iter_batches(path, s, batch_records=3):
        recs.extend(block.record(i) for i in range(block.n_records))
    return recs


# ---------------------------------------------------------------------------
# Round-trip: parse -> serialize identity
# ---------------------------------------------------------------------------


@settings(max_examples=25)
@given(_corpus, st.integers(0, 1))
def test_line_roundtrip_parse_serialize_identity(corpus, terminated):
    """read_block(file).tobytes() == the normalized file bytes, record
    boundaries and keys exactly reconstructing every line."""
    fmt = LineFormat(max_key_bytes=8)
    raw = _raw(corpus, bool(terminated))
    want = _records_of(raw)
    with tempfile.NamedTemporaryFile() as f:
        f.write(raw)
        f.flush()
        block = fmt.read_block(f.name)
    assert block.n_records == len(want)
    assert block.tobytes() == b"".join(want)
    for i, l in enumerate(want):
        assert block.record(i) == l
        assert bytes(block.keys[i]) == l[:-1][:8].ljust(8, b"\x00")
    # spill blobs round-trip through parse_blob identically
    reparsed = fmt.parse_blob(block.tobytes())
    assert reparsed.n_records == block.n_records
    np.testing.assert_array_equal(reparsed.offsets, block.offsets)


@settings(max_examples=25)
@given(_corpus)
def test_line_take_permutation(corpus):
    """block.take(perm) reorders whole records (the gather the sorter and
    the partitioner both rely on)."""
    fmt = LineFormat(max_key_bytes=8)
    blob = b"".join(bytes(l) + b"\n" for l in corpus)
    block = fmt.parse_blob(blob)
    n = block.n_records
    perm = np.arange(n)[::-1].copy()
    took = block.take(perm)
    for i in range(n):
        assert took.record(i) == block.record(n - 1 - i)
    assert took.n_bytes == block.n_bytes


def _fixed_block(source: str, n: int, tmp_path):
    """An ``n``-record gensort block: owned, mmap-backed, or a
    ``slice_records`` view that starts 3 records into its parent."""
    fmt = FixedFormat(100, 10)
    if source == "owned":
        return fmt.parse_blob(gensort.make_records(n, seed=5).tobytes())
    if source == "mmap":
        p = str(tmp_path / "fixed.bin")
        gensort.make_records(n, seed=5).tofile(p)
        return fmt.read_block(p)
    parent = fmt.parse_blob(gensort.make_records(n + 5, seed=5).tobytes())
    return parent.slice_records(3, 3 + n)


@pytest.mark.parametrize("pass_keys", [False, True])
@pytest.mark.parametrize("n", [0, 1, 2, 57])
@pytest.mark.parametrize("source", ["owned", "mmap", "slice"])
def test_fixed_take_matches_row_by_row(source, n, pass_keys, tmp_path):
    """take(perm) on fixed-stride records: record i of the result is
    record perm[i], its key the record's first 10 bytes, whether the
    keys are the block's strided view gathered by take or passed in
    already gathered; the result owns its bytes."""
    block = _fixed_block(source, n, tmp_path)
    assert block.n_records == n
    perm = np.random.default_rng(n).permutation(n)
    keys = np.ascontiguousarray(block.keys[perm]) if pass_keys else None
    took = block.take(perm, keys)
    assert took.n_records == n and took.n_bytes == 100 * n
    assert type(took.data) is np.ndarray and took.data.dtype == np.uint8
    assert not np.shares_memory(took.data, block.data)
    assert took.tobytes() == b"".join(block.record(int(j)) for j in perm)
    assert took.keys.shape == (n, 10)
    for i, j in enumerate(perm):
        assert bytes(took.keys[i]) == block.record(int(j))[:10]
        assert bytes(took.keys[i]) == took.record(i)[:10]


# ---------------------------------------------------------------------------
# Delimiter-boundary fragment splits
# ---------------------------------------------------------------------------


def test_fragment_split_at_every_offset(tmp_path):
    """For every byte offset s, a 2-stripe split [0,s)+[s,size) yields
    exactly the file's records, in order, with no loss or duplication —
    the stripe-ownership rule lands every split on a record boundary."""
    fmt = LineFormat(max_key_bytes=6)
    for terminated in (True, False):
        path = str(tmp_path / f"c{terminated}.txt")
        corpus = [b"pear", b"", b"apple", b"fig", b"", b"x" * 9, b"kiwi"]
        raw = b"\n".join(corpus) + (b"\n" if terminated else b"")
        with open(path, "wb") as f:
            f.write(raw)
        want = [c + b"\n" for c in corpus]
        size = len(raw)
        for s in range(size + 1):
            got = _stripe_records(
                fmt, path, Stripe(0, 0, s)
            ) + _stripe_records(fmt, path, Stripe(1, s, size))
            assert got == want, (terminated, s)


def test_fragment_split_many_stripe_counts(tmp_path):
    """byte_stripes at any count reconstructs the input order."""
    fmt = LineFormat(max_key_bytes=4)
    path = str(tmp_path / "c.txt")
    lines.write_lines(path, 200, kind="empty", seed=1, terminate_last=False)
    full = [
        b
        for s in byte_stripes(os.path.getsize(path), 1)
        for b in _stripe_records(fmt, path, s)
    ]
    for n_stripes in (2, 3, 7, 64, 500):
        got = [
            b
            for s in byte_stripes(os.path.getsize(path), n_stripes)
            for b in _stripe_records(fmt, path, s)
        ]
        assert got == full, n_stripes


# ---------------------------------------------------------------------------
# Short-key encode order-equivalence
# ---------------------------------------------------------------------------


@settings(max_examples=50)
@given(st.binary(min_size=0, max_size=7), st.binary(min_size=0, max_size=7))
def test_encode_order_equivalence_short_keys(a, b):
    """For keys shorter than the 8-byte embedding, (hi, lo) order ==
    memcmp order of the zero-padded keys — the invariant that makes the
    padded LineFormat key window partition correctly."""
    ka = np.frombuffer(a.ljust(8, b"\x00"), np.uint8)[None, :]
    kb = np.frombuffer(b.ljust(8, b"\x00"), np.uint8)[None, :]
    # encode from the *short* width: encode_np zero-pads internally
    sa = np.frombuffer(a, np.uint8)[None, :] if a else np.zeros((1, 0), np.uint8)
    sb = np.frombuffer(b, np.uint8)[None, :] if b else np.zeros((1, 0), np.uint8)
    ea = tuple(int(w[0]) for w in encoding.encode_np(sa))
    eb = tuple(int(w[0]) for w in encoding.encode_np(sb))
    pa, pb = ka.tobytes(), kb.tobytes()
    assert (ea < eb) == (pa < pb)
    assert (ea == eb) == (pa == pb)


# ---------------------------------------------------------------------------
# Strict fixed reader + block validator
# ---------------------------------------------------------------------------


def test_read_records_rejects_truncated_file(tmp_path):
    """A file whose size is not a record multiple raises instead of
    silently dropping the tail."""
    p = str(tmp_path / "x.bin")
    gensort.write_file(p, 10)
    with open(p, "ab") as f:
        f.write(b"\x20" * 37)  # torn trailing record
    with pytest.raises(ValueError, match="not a multiple"):
        gensort.read_records(p)
    with pytest.raises(ValueError, match="not a multiple"):
        FixedFormat(100, 10).count_records(p)


def test_validate_block_detects_corruption(tmp_path):
    fmt = LineFormat(max_key_bytes=8)
    p = str(tmp_path / "c.txt")
    lines.write_lines(p, 500, kind="uniform", seed=4)
    block = fmt.read_block(p)
    refsum = validate.checksum_block(block)
    srt = block.take(
        np.argsort(validate.block_keys_view(block), kind="stable")
    )
    assert validate.validate_block(srt, refsum, 500)["ok"]
    # corrupt one content byte
    bad = fmt.parse_blob(srt.tobytes())
    data = np.array(bad.data)
    pos = int(bad.offsets[37])
    data[pos] = data[pos] ^ 0x01 if data[pos] != 0x0A else data[pos]
    corrupted = fmt.parse_blob(data.tobytes())
    if corrupted.n_records == 500:  # byte flip stayed inside a record
        assert not validate.validate_block(corrupted, refsum, 500)[
            "checksum_ok"
        ]
    # merging two records (dropping a delimiter) breaks conservation
    data2 = np.array(srt.data)
    delim_pos = int(srt.offsets[100]) - 1
    merged = np.delete(data2, delim_pos)
    mblock = fmt.parse_blob(merged.tobytes())
    res = validate.validate_block(mblock, refsum, 500)
    assert not res["count_ok"] or not res["checksum_ok"]


def test_line_keys_of_empty_and_short_lines():
    data = np.frombuffer(b"\nab\nabcdefgh\n", dtype=np.uint8)
    offsets = np.array([0, 1, 4, 13], dtype=np.int64)
    k = line_keys(data, offsets, 4)
    assert bytes(k[0]) == b"\x00\x00\x00\x00"  # empty line
    assert bytes(k[1]) == b"ab\x00\x00"  # short line, zero-padded
    assert bytes(k[2]) == b"abcd"  # truncated to the window


# ---------------------------------------------------------------------------
# make_lines edge cases (DESIGN.md §11 hardening)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", lines.KINDS)
def test_make_lines_empty_corpus(kind, tmp_path):
    """n=0 must yield a valid zero-line buffer for every kind — and the
    whole sort path must accept the resulting empty file."""
    assert lines.make_lines(0, kind).size == 0
    p = str(tmp_path / "e.txt")
    lines.write_lines(p, 0, kind=kind)
    assert os.path.getsize(p) == 0
    fmt = LineFormat(max_key_bytes=8)
    block = fmt.read_block(p)
    assert block.n_records == 0 and block.keys.shape == (0, 8)


def test_key_width_exceeding_longest_line(tmp_path):
    """A key window wider than any line must produce valid zero-padded
    keys and offsets (no degenerate windows), for every corpus kind."""
    fmt = LineFormat(max_key_bytes=64)  # wider than any 32-byte line
    for kind in lines.KINDS:
        p = str(tmp_path / f"{kind}.txt")
        lines.write_lines(p, 300, kind=kind, seed=7)
        block = fmt.read_block(p)
        assert block.keys.shape == (block.n_records, 64)
        assert int(block.offsets[-1]) == os.path.getsize(p)
        # zero padding beyond each line's content, content bytes intact
        for i in (0, block.n_records // 2, block.n_records - 1):
            raw = block.record(i)[:-1]  # strip delimiter
            want = raw[:64].ljust(64, b"\x00")
            assert bytes(block.keys[i]) == want
        # the sample path survives the wide window too
        sk = fmt.sample_keys(p, block.n_records, 0.5)
        assert sk.shape[1] == 64


@pytest.mark.parametrize("kind", lines.ADVERSARIAL_KINDS)
def test_adversarial_lines_well_formed(kind):
    """Adversarial corpora: n lines out, delimiter-terminated, and the
    per-kind key structure holds."""
    buf = lines.make_lines(400, kind, seed=3)
    ls = bytes(buf).split(b"\n")
    assert ls[-1] == b""
    ls = ls[:-1]
    assert len(ls) == 400
    if kind == "presorted":
        assert ls == sorted(ls)
    elif kind == "reverse":
        keys = [l[:12] for l in ls]
        assert keys == sorted(keys, reverse=True)
    elif kind == "allequal":
        assert len({l[:16] for l in ls}) == 1
    elif kind == "tiny":
        assert len({l[:16] for l in ls}) <= 5
    elif kind == "utf8":
        for l in ls:
            l.decode("utf-8")  # always valid 2-byte sequences
