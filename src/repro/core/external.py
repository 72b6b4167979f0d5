"""ELSAR: the out-of-core, file-to-file external sort (paper Alg. 1).

This is the stable entry point; the runtime itself lives in
``repro.core.pipeline`` — a pipelined, parallel implementation of the
paper's control flow:

  line 1   sparse output file of |I| bytes           -> phase "setup"
  line 2   train CDF model on a sample               -> phase "train"
  lines 6-20  r parallel readers stream stripe-aligned batches, predict
              partition ids, and flush coalesced fragments to per-partition
              spill files (``n_readers`` maps the paper's r; the default 1
              preserves the historical sequential behavior byte-for-byte)
                                                     -> phase "partition"
  lines 22-31  per-partition: load fragments ("sort_read"), LearnedSort
              ("sort"), write at the precomputed offset ("write") — these
              run as queue-connected stages that overlap with each other
              and with the tail of partitioning

Instrumentation: every phase is timed (busy + wall seconds, and spans
for its parts) and every byte of file I/O counted, feeding the paper's
Fig. 6 (phase breakdown) and Fig. 7 (I/O load) benchmarks;
``SortStats.overlap_seconds`` exposes the pipelining effect.  See
DESIGN.md §1 for the stage graph.
"""

from __future__ import annotations

import time

from repro.core.config import SortConfig, coerce_sort_config

# Re-exported for compatibility: SortStats began life here and the
# mergesort/terasort baselines (and external callers) import it from
# this module.
from repro.core.pipeline import SortPipelineConfig, SortStats, run_pipeline

__all__ = ["SortConfig", "SortStats", "SortPipelineConfig", "sort_file"]


class _Timer:
    """Accumulating phase timer used by the sequential baselines
    (mergesort/terasort), which keep single-threaded accounting."""

    def __init__(self, stats: SortStats, phase: str):
        self.stats, self.phase = stats, phase

    def __enter__(self):
        self.t0 = time.perf_counter()

    def __exit__(self, *exc):
        self.stats.phase_seconds[self.phase] = self.stats.phase_seconds.get(
            self.phase, 0.0
        ) + (time.perf_counter() - self.t0)


def sort_file(
    input_path: str,
    output_path: str,
    config: "SortConfig | None" = None,
    **overrides,
) -> SortStats:
    """Sort a record file with ELSAR. Returns instrumentation stats.

    The supported call shape is ``sort_file(input, output,
    config=SortConfig(...), **overrides)`` — every knob lives on
    :class:`repro.core.config.SortConfig` and keywords on top of an
    explicit config act as per-call overrides
    (``dataclasses.replace`` semantics).  The historical bare-keyword
    shape (``sort_file(input, output, n_readers=2, ...)``) keeps
    working through :func:`repro.core.config.coerce_sort_config`,
    which warns ``DeprecationWarning`` once per process; behavior is
    identical (the legacy grid in ``tests/test_differential.py`` runs
    through this shim).

    ``n_readers`` is the paper's r (§3.2): the number of striped reader
    threads in the partition phase.  Output is byte-identical for every
    reader count; > 1 additionally overlaps the partition/sort/write
    phases (visible as ``stats.overlap_seconds > 0``).

    ``n_writers`` sizes the zero-copy positioned-write pool (DESIGN.md
    §15): partitions are mutually exclusive with precomputed offsets
    (§3.5), so N workers ``pwrite`` concurrently on one shared fd with
    no merge and no ordering constraint.  0 = planner-tuned from the
    partition count and spill pressure; output is byte-identical for
    every pool width (``SortStats.writer_bytes`` /
    ``writer_stall_seconds`` record the per-writer split).

    ``model`` supplies a pre-trained CDF model (``core/rmi.RMIParams``)
    and skips the sample/train phase.  Sorting several inputs under one
    shared model (with an explicit shared ``n_partitions``) makes their
    outputs **co-partitioned**: partition j of every output covers the
    same key range, which is what the merge-free join/dedup/group-by
    operators consume (``core/operators.py``, DESIGN.md §9).

    ``fmt`` selects the record layout (``repro.core.format``, DESIGN.md
    §8): ``None`` keeps the historical gensort layout
    (``FixedFormat(100, 10)``); ``LineFormat(max_key_bytes=...)`` sorts
    variable-length newline-delimited text in stable memcmp order of the
    zero-padded key window.

    ``manifest=True`` additionally emits ``<output>.manifest.npz`` — the
    trained model + partition map + error band that turns the sorted file
    into a servable learned index (``repro.serve.index``, DESIGN.md §7).

    ``executor`` selects the sort implementation behind the
    ``SortExecutor`` seam (``repro.core.executor``, DESIGN.md §10):
    ``"auto"`` uses the host LearnedSort unless ``device_sort`` /
    ``use_kernels`` request the device path, which now runs the batched
    device-resident executor (super-batches of partitions, one fused
    encode→RMI→bitonic dispatch each); ``"per_partition"`` forces the
    historical one-dispatch-per-partition device path;
    ``"host"``/``"batched"`` force those explicitly.  Output is
    byte-identical across executors.

    ``partitioner`` selects the pre-sort planner's routing path
    (``repro.core.planner``, DESIGN.md §11): ``"auto"`` diagnoses the
    training sample and falls back from the learned model to
    sample-splitter (quantile) partitioning on hostile inputs (tiny key
    universes, duplicate floods, distributions the model can't fit);
    ``"model"`` / ``"splitter"`` force a path.  Output is byte-identical
    either way — the planner only changes partition *boundaries*, never
    record order.  The decision, its reason, and the sample diagnostics
    land in ``SortStats.planner_*``.

    The knobs ``n_partitions``, ``flush_bytes`` and ``batch_segments``
    default to 0 = auto-tuned by the planner from the memory budget and
    the sample (``SortStats.tuned_knobs`` records the effective values);
    any explicit non-zero value is used verbatim.

    ``model_cache`` (``repro.core.model_cache.ModelCache``, DESIGN.md
    §12) warm-starts training across sorts: the fresh sample is checked
    against cached models under the planner's skew band and the train
    phase is skipped on a hit (``SortStats.model_cache`` records
    hit/miss, ``SortStats.model_hash`` the model that partitioned).
    Reuse never changes the output bytes — only where the partition
    boundaries fall.
    """
    cfg = coerce_sort_config(config, overrides)
    return run_pipeline(input_path, output_path, cfg.to_pipeline())
