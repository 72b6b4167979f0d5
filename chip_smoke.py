"""Smoke run of the ELSAR sorter and its query server on a TPU.

    python chip_smoke.py                     # one chip, 10,000,000 records
    python chip_smoke.py --records 200000    # a quicker run
    python chip_smoke.py --chips 4           # the four-chip mesh sort only

Everything runs in this one process (a chip belongs to one process at a
time).  With no arguments the phases are:

1. device check: the first JAX device must be a TPU, or the script exits
   1 before anything else runs;
2. kernels: each Pallas kernel runs compiled on a small input and must
   match its ``kernels/ref.py`` oracle;
3. corpus: a uniform gensort corpus made from ``--seed`` — 100-byte
   records with 10-byte keys, the Sort Benchmark layout.  10,000,000
   records are 1 GB, 4x the default 256 MB memory budget, so the planner
   picks ~16 partitions and the run spills;
4. sort: ``external.sort_file`` with ``device_sort=True`` and a
   manifest.  The output must validate and be byte-identical (sha256) to
   a host-executor sort of the same input; the batched executor must
   have dispatched to the device, with no overflow fallback;
5. serve: a ``QueryServer`` predicting through the Pallas RMI kernel
   answers 1,000 point lookups (half present keys, half absent) and 20
   range scans; every answer must be ok and identical to a
   ``QueryEngine`` predicting on the host.

``--chips 4`` runs instead only the distributed sort
(``terasort.sort_file_distributed`` with the mesh executor over a
4-device data mesh) and its comparison, a host-executor sort.

Every line but the last is ``key=value``.  The last line is the JSON
object ``{"ok": true, "device": {...}}``, printed only when every phase
passed; any failure exits non-zero.  This is a smoke run, not a
benchmark: its seconds include compilation and are not tuned.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
)

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import external, terasort, validate  # noqa: E402
from repro.core.config import ServeConfig, SortConfig  # noqa: E402
from repro.data import gensort  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402
from repro.launch.query import make_workload  # noqa: E402
from repro.serve.index import SortedFileIndex  # noqa: E402
from repro.serve.query_engine import QueryEngine  # noqa: E402
from repro.serve.server import QueryServer  # noqa: E402

REQUIRED_PLATFORM = "tpu"
DEFAULT_RECORDS = 10_000_000
N_POINTS = 1000
N_RANGES = 20
RANGE_RECORDS = 1000
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(key: str, value) -> None:
    print(f"{key}={value}", flush=True)


def sha256_file(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        while block := f.read(16 << 20):
            h.update(block)
    return h.hexdigest()


class CompileClock:
    """Sums XLA compile seconds (persistent-cache reads included) from
    JAX's monitoring events."""

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == BACKEND_COMPILE_EVENT:
            self.seconds += duration
            self.count += 1


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------


def kernel_phase(seed: int = 0) -> None:
    """Every Pallas kernel, run as the backend decides (compiled on a
    TPU), against its pure-jnp oracle on a small input."""
    import jax.numpy as jnp

    from repro.core import encoding, rmi
    from repro.kernels import ops, ref

    emit("kernel_interpret_mode", ops._interpret())
    rng = np.random.default_rng(seed)
    keys = jnp.asarray(gensort.uniform_keys(4096, seed=seed))
    got, want = ops.encode_keys(keys), ref.encode_ref(keys)
    check(
        all(np.array_equal(g, w) for g, w in zip(got, want)),
        "encode kernel disagrees with ref.encode_ref",
    )
    emit("kernel_encode", "ok")

    model = rmi.fit(gensort.uniform_keys(8192, seed=seed + 1), n_leaf=1024)
    hi, lo = (jnp.asarray(a) for a in encoding.encode_np(np.asarray(keys)))
    for n_buckets in (256, 4096):
        got = np.asarray(ops.rmi_bucket(model, hi, lo, n_buckets))
        want = np.asarray(ref.rmi_bucket_ref(model, hi, lo, n_buckets))
        check(
            np.array_equal(got, want),
            f"rmi kernel disagrees with ref.rmi_bucket_ref at {n_buckets} "
            f"buckets on {int((got != want).sum())} of {got.size} keys",
        )
    emit("kernel_rmi", "ok")

    for n_buckets in (1000, 3000):
        ids = jnp.asarray(rng.integers(0, n_buckets, 5000, dtype=np.int32))
        check(
            np.array_equal(
                ops.bucket_histogram(ids, n_buckets),
                ref.histogram_ref(ids, n_buckets),
            ),
            f"histogram kernel disagrees with ref at {n_buckets} buckets",
        )
    emit("kernel_histogram", "ok")

    r, c = 16, 256
    khi = jnp.asarray(rng.integers(0, 7, (r, c)).astype(np.uint32))
    klo = jnp.asarray(rng.integers(0, 1 << 32, (r, c)).astype(np.uint32))
    val = jnp.asarray(np.tile(np.arange(c, dtype=np.int32), (r, 1)))
    hs, ls, vs = ops.sort_rows(khi, klo, val)
    hr, lr, _ = ref.sort_rows_ref(khi, klo, val)
    check(
        np.array_equal(hs, hr) and np.array_equal(ls, lr),
        "bitonic kernel's keys disagree with ref.sort_rows_ref",
    )
    check(
        (np.sort(np.asarray(vs), axis=1) == np.arange(c)).all(),
        "bitonic kernel lost or duplicated a payload",
    )
    emit("kernel_bitonic", "ok")


def corpus_phase(path: str, records: int, seed: int) -> int:
    """Write the uniform gensort corpus; returns its content checksum."""
    gensort.write_file(path, records, seed=seed)
    emit("corpus_records", records)
    emit("corpus_bytes", os.path.getsize(path))
    return validate.checksum(gensort.read_records(path))


def host_sort_sha(inp: str, workdir: str) -> str:
    """sha256 of the host-executor sort of ``inp`` (the reference)."""
    out = os.path.join(workdir, "host_sorted.bin")
    external.sort_file(inp, out, SortConfig(executor="host", workdir=workdir))
    sha = sha256_file(out)
    os.unlink(out)
    emit("host_sha256", sha)
    return sha


def sort_phase(inp: str, workdir: str, records: int, chk: int) -> str:
    """Device sort through ``external.sort_file``; returns the output."""
    out = os.path.join(workdir, "sorted.bin")
    stats = external.sort_file(
        inp, out,
        SortConfig(device_sort=True, manifest=True, workdir=workdir),
    )
    emit("sort_executor", stats.executor)
    emit("sort_device_dispatches", stats.device_dispatches)
    emit("sort_fallbacks", stats.fallbacks)
    emit("sort_partitions", len(stats.partition_counts))
    emit("sort_spill_disk_bytes", stats.spill_disk_bytes)
    emit("sort_wall_seconds", stats.wall_seconds)
    res = validate.validate_file(out, chk, records)
    emit("sort_validate_ok", res["ok"])
    sha = sha256_file(out)
    emit("sort_sha256", sha)
    ref_sha = host_sort_sha(inp, workdir)
    emit("sort_sha256_matches_host", sha == ref_sha)
    check(stats.executor == "batched", f"executor {stats.executor!r}")
    check(stats.device_dispatches > 0, "no device dispatch")
    check(stats.fallbacks == 0, f"{stats.fallbacks} overflow fallbacks")
    check(res["ok"], f"validate_file: {res}")
    check(sha == ref_sha, "device sort differs from the host sort")
    return out


async def _serve(index, points, ranges, config):
    server = await QueryServer(index, config).start()
    try:
        pts = await asyncio.gather(
            *[server.point(k.tobytes()) for k in points]
        )
        rngs = await asyncio.gather(
            *[server.range_scan(lo, hi) for lo, hi in ranges]
        )
    finally:
        await server.stop()
    return pts + rngs


def serve_phase(sorted_path: str, seed: int) -> None:
    """QueryServer with kernel predicts vs a host-predict QueryEngine."""
    t0 = time.perf_counter()
    with SortedFileIndex.open(sorted_path) as ref_index:
        points, ranges = make_workload(
            ref_index, N_POINTS, N_RANGES, RANGE_RECORDS, seed
        )
        with QueryEngine(ref_index, use_kernels=False) as engine:
            records, _, found = engine.point(points)
            spans = engine.range(ranges)
    want = [
        {"ok": True, "found": bool(f),
         "record": np.ascontiguousarray(r).tobytes() if f else None}
        for r, f in zip(records, found)
    ] + [
        {"ok": True, "count": int(s.shape[0]),
         "data": np.ascontiguousarray(s).tobytes()}
        for s in spans
    ]
    t1 = time.perf_counter()
    got = asyncio.run(
        _serve(
            SortedFileIndex.open(sorted_path), points, ranges,
            ServeConfig(use_kernels=True),
        )
    )
    emit("serve_reference_seconds", t1 - t0)
    emit("serve_server_seconds", time.perf_counter() - t1)
    emit("serve_reference_band_fallbacks", engine.stats.fallbacks)
    n_ok = sum(1 for a in got if a.get("ok"))
    n_same = sum(1 for a, b in zip(got, want) if a == b)
    emit("serve_answers", len(got))
    emit("serve_ok", n_ok)
    emit("serve_identical_to_host_predict", n_same)
    emit("serve_points_found", int(found.sum()))
    check(len(got) == N_POINTS + N_RANGES, f"{len(got)} answers")
    check(n_ok == len(got), f"{len(got) - n_ok} answers not ok")
    check(n_same == len(got), f"{len(got) - n_same} answers differ")


def mesh_phase(
    inp: str, workdir: str, records: int, chk: int, n_chips: int
) -> None:
    """Distributed sort with the mesh executor vs the host sort."""
    out = os.path.join(workdir, "mesh_sorted.bin")
    stats = terasort.sort_file_distributed(
        inp, out, make_data_mesh(n_chips), executor="mesh", workdir=workdir
    )
    emit("mesh_devices", n_chips)
    emit("mesh_executor", stats.executor)
    emit("mesh_device_dispatches", stats.device_dispatches)
    emit("mesh_wall_seconds", stats.wall_seconds)
    res = validate.validate_file(out, chk, records)
    emit("mesh_validate_ok", res["ok"])
    sha = sha256_file(out)
    os.unlink(out)
    emit("mesh_sha256", sha)
    ref_sha = host_sort_sha(inp, workdir)
    emit("mesh_sha256_matches_host", sha == ref_sha)
    check(stats.executor == "mesh", f"executor {stats.executor!r}")
    check(stats.device_dispatches > 0, "no device dispatch")
    check(res["ok"], f"validate_file: {res}")
    check(sha == ref_sha, "mesh sort differs from the host sort")


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--records", type=int, default=DEFAULT_RECORDS)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh sort")
    ap.add_argument("--workdir",
                    help="scratch directory (default: a new tempdir)")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != REQUIRED_PLATFORM:
        print(f"chip_smoke: no {REQUIRED_PLATFORM} device; JAX found "
              f"{dev.platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    emit("platform", dev.platform)
    emit("device_kind", dev.device_kind)
    emit("device_count", len(devices))
    emit("compile_cache_dir", compile_cache.enable())
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)

    workdir = args.workdir or tempfile.mkdtemp(prefix="chip_smoke_")
    os.makedirs(workdir, exist_ok=True)
    inp = os.path.join(workdir, "input.bin")
    phase_seconds: dict = {}

    def timed(name, fn, *a):
        t0 = time.perf_counter()
        try:
            return fn(*a)
        finally:
            phase_seconds[name] = time.perf_counter() - t0

    try:
        if args.chips == 1:
            timed("kernels", kernel_phase, args.seed)
        chk = timed("corpus", corpus_phase, inp, args.records, args.seed)
        if args.chips == 1:
            out = timed("sort", sort_phase, inp, workdir, args.records, chk)
            timed("serve", serve_phase, out, args.seed)
        else:
            timed("mesh_sort", mesh_phase, inp, workdir, args.records, chk,
                  args.chips)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    finally:
        jax.monitoring.unregister_event_duration_listener(clock)
        for name, sec in phase_seconds.items():
            emit(f"phase_seconds.{name}", sec)
        if args.workdir is None:
            shutil.rmtree(workdir, ignore_errors=True)

    wall = sum(phase_seconds.values())
    emit("compiles", clock.count)
    emit("compile_seconds", clock.seconds)
    emit("run_seconds", wall - clock.seconds)
    mem = dev.memory_stats() or {}
    emit("peak_bytes_in_use", mem.get("peak_bytes_in_use", "not reported"))
    print(json.dumps({
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(devices),
        },
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
