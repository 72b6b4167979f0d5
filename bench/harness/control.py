"""The control: the reference put in the program's place, on a key cut
to its leading :data:`CONTROL_KEY_BYTES` bytes.

The configurations state no numeric precision; they state that the
whole 10-byte key orders the output.  The control breaks exactly that
guarantee, as a later change that sorted or searched on a narrower
device key would, and the numbers a run compares must fail it:

* a sort's control output is the corpus in the stable order of the cut
  key, and ``wrong_records`` counts its records that differ from the
  reference output;
* a lookup's control answer is the first record whose cut key matches,
  and ``wrong_answers`` counts the answers that differ from the
  reference's.

Each driver computes the readings of its own comparison in its
``control_readings`` hook, with the functions below.
``python3 bench/control.py --workload <cell> --seeds a b c`` prints them
at the cell's own size.
"""

from __future__ import annotations

import os
import types

import numpy as np

from harness import gensort, loadgen, main, open_loop, reference

CONTROL_KEY_BYTES = 4


def sort_reading(records: np.ndarray, ordered=None) -> int:
    """``wrong_records`` of the control's sort of ``records``."""
    order, _ = ordered or reference.stable_order(records)
    cut, _ = reference.stable_order(records, CONTROL_KEY_BYTES)
    return reference.mismatched_records(records[cut], records, order)


def control_answers(records: np.ndarray, keys: np.ndarray) -> dict:
    """What the control answers for ``keys``, in the client's form."""
    order, sorted_keys = reference.stable_order(records, CONTROL_KEY_BYTES)
    found, rows = reference.point_answers(records, order, sorted_keys, keys)
    n = keys.shape[0]
    return {
        "status": np.full(n, loadgen.OK, dtype=np.int8),
        "found": found,
        "records": np.where(found[:, None], records[rows], 0).astype(np.uint8),
        "record_len": np.where(found, records.shape[1], 0),
    }


def serve_reading(records: np.ndarray, keys: np.ndarray) -> int:
    """``wrong_answers`` of the control's answers to ``keys``."""
    target = types.SimpleNamespace(records=records)
    wrong, _ = open_loop.wrong_answers(
        target, keys, control_answers(records, keys)
    )
    return wrong


def readings(config: dict, mix: dict, seed: int, seconds: float,
             tmp: str) -> dict:
    """The control's numbers for one seed of one cell, at its size: the
    corpus the run would make, read by the mix's driver's hook."""
    path = os.path.join(tmp, f"control_{seed}.bin")
    gensort.write_corpus(path, config["records"], seed=seed,
                         skewed=config["key_distribution"] == "skewed",
                         fixed_key_bytes=mix.get("fixed_key_bytes", 0))
    try:
        records = np.array(gensort.read_corpus(path))
    finally:
        os.unlink(path)
    return main.driver_for(mix).control_readings(config, mix, seed, seconds,
                                                 records)
