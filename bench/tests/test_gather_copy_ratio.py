"""Tests of the reader ``sort.gather_copy_ratio``: the sort executor's
epilogue bytes (counter ``sort.gather_bytes``) over the input bytes.

    python -m pytest -q bench/tests
"""

from __future__ import annotations

import os
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import catalog  # noqa: E402


def _job(input_bytes, **counters):
    return types.SimpleNamespace(input_bytes=input_bytes, counters=counters)


@pytest.mark.parametrize("jobs, want", [
    ([], None),
    # a program that does not count the epilogue's bytes
    ([_job(1000, **{"partition.group_bytes": 1000})], None),
    ([_job(1000, **{"sort.gather_bytes": 1100}), _job(1000)], None),
    ([types.SimpleNamespace(input_bytes=1000)], None),
    ([_job(1000, **{"sort.gather_bytes": 1100})], 1.1),
    ([_job(1000, **{"sort.gather_bytes": 1100}),
      _job(3000, **{"sort.gather_bytes": 3600})], 1.175),
])
def test_gather_copy_ratio_reader(jobs, want):
    read = catalog.Catalog(CHECKOUT, BENCH).reader("sort.gather_copy_ratio")
    got = read(types.SimpleNamespace(layer={"jobs": jobs}))
    assert got == (None if want is None else pytest.approx(want))
