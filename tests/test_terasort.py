"""Streaming pod-scale external sort (paper §8 future work): file -> pod
partition -> range spills -> sort-once -> concatenate.

Multi-device coverage runs in subprocesses with 8 fake XLA host devices
(``XLA_FLAGS`` must be set before jax initializes; conftest deliberately
leaves it unset for tier-1).  ``REPRO_TERASORT_RECORDS`` scales the
subprocess corpora (CI's mesh leg raises it).  Single-device (1-dev
mesh) properties — resource cleanup on failure, counter parity with the
executor, manifest serving — run in-process.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import tempfile
import numpy as np, jax
from repro.core import terasort, validate
from repro.data import gensort
from repro.launch.mesh import make_mesh

tmp = tempfile.mkdtemp()
for skew in (False, True):
    inp = os.path.join(tmp, f"in{skew}.bin")
    out = os.path.join(tmp, f"out{skew}.bin")
    N = int(os.environ.get("REPRO_TERASORT_RECORDS", "200000"))
    gensort.write_file(inp, N, skewed=skew)
    chk = validate.checksum(gensort.read_records(inp, mmap=False))
    mesh = make_mesh((8,), ("data",))
    stats = terasort.sort_file_distributed(
        inp, out, mesh, chunk_records=1 << 15
    )
    res = validate.validate_file(out, chk, N)
    assert res["ok"], (skew, res)
    c = np.array(stats.partition_counts)
    assert c.std() / c.mean() < 0.35, c  # equi-depth ranges
print("TERASORT_OK")
"""

# Mesh-executor + format + bugfix coverage at 8 devices: byte-identity
# against the single-device sorter (ties included), line-format corpora,
# counter parity through the clock protocol, and the sentinel-masking
# regression on the router itself.
SCRIPT2 = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import hashlib, tempfile
import numpy as np, jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import encoding, external, partition, rmi, terasort, validate
from repro.core.format import LineFormat
from repro.data import gensort
from repro.launch.mesh import make_data_mesh

N = int(os.environ.get("REPRO_TERASORT_RECORDS", "120000"))
tmp = tempfile.mkdtemp()
mesh = make_data_mesh()
assert mesh.shape["data"] == 8

def sha(p):
    with open(p, "rb") as f:
        return hashlib.sha256(f.read()).hexdigest()

# --- byte-identity vs the single-device sorter on a duplicate-heavy
# corpus (97-word key vocab => masses of full-key ties): input-order
# spill rewriting + stable range sorts must keep ties byte-identical
inp = os.path.join(tmp, "dups.bin")
rec = gensort.make_records(N, seed=11)
vocab = gensort.uniform_keys(97, seed=99)
rng = np.random.default_rng(17)
rec[:, : gensort.KEY_BYTES] = vocab[rng.integers(0, 97, N)]
with open(inp, "wb") as f:
    f.write(rec.tobytes())
ref = os.path.join(tmp, "ref.bin")
external.sort_file(inp, ref)
for ex in ("host", "mesh"):
    out = os.path.join(tmp, f"dups_{ex}.bin")
    stats = terasort.sort_file_distributed(
        inp, out, mesh, chunk_records=1 << 14, executor=ex
    )
    assert sha(out) == sha(ref), ex
    assert stats.executor == ex

# --- mesh executor: ONE shard_map dispatch covers all 8 ranges, and the
# clock-protocol counters land in the distributed SortStats (the old
# _StatsClock silently dropped them)
assert stats.device_dispatches == 1, stats.device_dispatches
assert stats.jit_compiles == 1, stats.jit_compiles
assert 0.0 < stats.batch_occupancy <= 1.0, stats.batch_occupancy

# --- LineFormat across 8 devices, byte-identical + servable v3 manifest
fmt = LineFormat(max_key_bytes=16)
inp_l = os.path.join(tmp, "in.txt")
ls = [
    bytes(rng.integers(33, 127, rng.integers(1, 28), dtype=np.uint8))
    for _ in range(max(N // 5, 20000))
]
with open(inp_l, "wb") as f:
    f.write(b"\n".join(ls))  # unterminated final line: normalization path
ref_l = os.path.join(tmp, "ref.txt")
external.sort_file(inp_l, ref_l, fmt=fmt)
out_l = os.path.join(tmp, "out.txt")
stats = terasort.sort_file_distributed(
    inp_l, out_l, mesh, fmt=fmt, chunk_records=1 << 13,
    executor="mesh", manifest=True,
)
assert sha(out_l) == sha(ref_l)
from repro.core import manifest as manifest_lib
from repro.serve.index import SortedFileIndex
m = manifest_lib.load(stats.manifest_path)
assert m.version == 3 and m.fmt == fmt and m.line_offsets is not None
index = SortedFileIndex.open(out_l)
probe = sorted(ls, key=lambda l: l[:16].ljust(16, b"\x00"))[len(ls) // 2]
rows, found = index.lookup(
    np.frombuffer(probe[:16].ljust(16, b"\x00"), np.uint8)[None, :]
)
assert bool(found[0])
assert index.record_at(int(rows[0]))[:-1] == probe

# --- sentinel-masking regression (crafted router call): a short final
# chunk's sentinel pad rows must NOT consume bucket capacity.  64-row
# chunk = 57 real + 7 sentinels; capacity = route_capacity(20, 8, 1.6)
# = 4 (exact power of two — the shared-formula fix; the old doubling
# formula gave 8 and hid the overflow).  Real keys give every device
# exactly 4 last-bucket rows; pre-fix, the sentinel each of devices 1..7
# receives after the block transpose also bucketed last -> count 5 > 4
# -> spurious lost/capacity-doubling retries.
assert partition.route_capacity(20, 8, 1.6) == 4
sample = gensort.uniform_keys(4096, seed=5)
model = rmi.fit(sample)
order = np.argsort(
    np.ascontiguousarray(sample).view("S10").reshape(-1), kind="stable"
)
klow, khigh = sample[order[0]], sample[order[-1]]
bh, bl = encoding.encode_np(np.stack([klow, khigh]))
b = rmi.predict_bucket_np(model, bh, bl, 8)
assert b[0] == 0 and b[1] == 7, b  # the construction's premise
m_real, n_dev = 57, 8
keys = np.empty((m_real, 10), np.uint8)
cnt = np.zeros(n_dev, int)
for r in range(m_real):
    d = r % n_dev  # device r lands on after the block transpose
    keys[r] = khigh if cnt[d] < 4 else klow
    cnt[d] += 1
hi, lo = encoding.encode_np(keys)
hi = np.concatenate([hi, np.full(7, encoding.SENTINEL)])
lo = np.concatenate([lo, np.full(7, encoding.SENTINEL)])
val = np.arange(64, dtype=np.int32)
sh = NamedSharding(mesh, P(("data",)))
route = terasort._make_route_fn(mesh, ("data",), model, 20, 1.6)
ov, nv, lost = route(
    jax.device_put(jnp.asarray(hi), sh),
    jax.device_put(jnp.asarray(lo), sh),
    jax.device_put(jnp.asarray(val), sh),
)
assert int(np.asarray(lost).sum()) == 0, (
    "sentinel pad rows consumed bucket capacity"
)
nv = np.asarray(nv).reshape(n_dev)
ov = np.asarray(ov).reshape(n_dev, -1)
got = np.concatenate([ov[d, : nv[d]] for d in range(n_dev)])
assert sorted(got.tolist()) == list(range(m_real))  # all real, no pads

print("TERASORT2_OK")
"""


# The route's spans and counters and the two device programs' names, at
# 4 devices: a seeded corpus in several chunks and a short tail chunk
# (sentinel-padded to the full chunk), sorted with the mesh executor.
# The script prints what the tests below check, as one JSON line.
SCRIPT_ROUTE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, tempfile
import numpy as np, jax, jax.numpy as jnp
from repro.core import executor, partition, rmi, terasort
from repro.data import gensort
from repro.launch.mesh import make_data_mesh

N, CHUNK, N_DEV = 200_003, 1 << 15, 4
tmp = tempfile.mkdtemp()
rec = gensort.make_records(N, seed=41)
inp, out = os.path.join(tmp, "in.bin"), os.path.join(tmp, "out.bin")
with open(inp, "wb") as f:
    f.write(rec.tobytes())
mesh = make_data_mesh(N_DEV)
stats = terasort.sort_file_distributed(
    inp, out, mesh, chunk_records=CHUNK, executor="mesh", workdir=tmp
)
keys = [rec[i, :10].tobytes() for i in range(N)]
want = rec[sorted(range(N), key=keys.__getitem__)].tobytes()
with open(out, "rb") as f:
    got = f.read()

per_device = CHUNK // N_DEV
model = rmi.fit(gensort.uniform_keys(4096, seed=5))
route = terasort._make_route_fn(mesh, ("data",), model, per_device, 1.6)
u32 = jnp.zeros(CHUNK, jnp.uint32)
route_hlo = route.lower(u32, u32, jnp.zeros(CHUNK, jnp.int32)).as_text()
mesh_sort = executor.MeshBatchedExecutor(model, mesh=mesh)._sort_fn(1024)
sort_hlo = mesh_sort.lower(
    jnp.zeros((N_DEV, 1024, 8), jnp.uint8),
    jnp.zeros((N_DEV, 1024), jnp.int32),
).as_text()
print(json.dumps({
    "identical": got == want,
    "executor": stats.executor,
    "dispatches": stats.device_dispatches,
    "fallbacks": stats.fallbacks,
    "counters": stats.counters,
    "span_counts": stats.span_counts,
    "span_seconds": stats.span_seconds,
    "partition_s": stats.phase_seconds["partition"],
    "capacity": partition.route_capacity(per_device, N_DEV, 1.6),
    "route_module": route_hlo.split("module @", 1)[1].split(" ", 1)[0],
    "sort_module": sort_hlo.split("module @", 1)[1].split(" ", 1)[0],
}))
"""

def _run_subprocess(script):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(__file__), "..", "src"
    ) + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True, text=True, env=env, timeout=900,
    )


def test_terasort_8dev():
    r = _run_subprocess(SCRIPT)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "TERASORT_OK" in r.stdout


def test_terasort_8dev_mesh_executor_and_formats():
    r = _run_subprocess(SCRIPT2)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    assert "TERASORT2_OK" in r.stdout


@pytest.fixture(scope="module")
def route_run():
    r = _run_subprocess(SCRIPT_ROUTE)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_terasort_4dev_mesh_executor_matches_stable_sort(route_run):
    assert route_run["identical"]
    assert route_run["executor"] == "mesh"
    assert route_run["dispatches"] >= 1


def test_terasort_4dev_one_exchange_span_per_chunk(route_run):
    chunks = -(-200_003 // (1 << 15))
    assert route_run["counters"]["route.chunks"] == chunks == 7
    for span in ("route.encode", "route.exchange", "route.spill"):
        assert route_run["span_counts"][span] == chunks, span
    assert route_run["counters"].get("route.retries", 0) == 0
    assert route_run["fallbacks"] == 0


def test_terasort_4dev_exchange_bytes_from_shapes(route_run):
    """Per chunk: three transposes of its rows' 4-byte words, and 4
    devices' (4, capacity) int32 send blocks; the tail chunk of
    200,003 - 6 * 32,768 = 3,395 rows is padded to the full 32,768, so
    all 7 chunks carry the same bytes."""
    cap = route_run["capacity"]
    assert cap == 4096  # next power of two >= 8,192 * 1.6 / 4
    want = 7 * 4 * (3 * (1 << 15) + 4 * 4 * cap)
    assert route_run["counters"]["route.exchange_bytes"] == want


def test_terasort_4dev_route_spans_within_partition(route_run):
    spans = route_run["span_seconds"]
    total = sum(spans[s] for s in
                ("route.encode", "route.exchange", "route.spill"))
    assert 0 < total <= route_run["partition_s"]


def test_terasort_4dev_route_and_mesh_sort_programs_named_apart(route_run):
    assert route_run["route_module"] == "jit_route_chunk"
    assert route_run["sort_module"] == "jit_mesh_flat_sort"


# ---------------------------------------------------------------------------
# In-process properties on a 1-device mesh (no XLA_FLAGS needed)
# ---------------------------------------------------------------------------


def _one_dev_mesh():
    from repro.launch.mesh import make_data_mesh

    return make_data_mesh(1)


def test_cleanup_on_forced_overflow(tmp_path):
    """A chunk that overflows at 32x raises — and leaves NOTHING behind:
    no range files, no spill dir, no output file."""
    from repro.core import terasort
    from repro.data import gensort

    inp = str(tmp_path / "in.bin")
    gensort.write_file(inp, 4096)
    out = str(tmp_path / "out.bin")
    work = tmp_path / "work"
    work.mkdir()
    with pytest.raises(RuntimeError, match="capacity overflow"):
        terasort.sort_file_distributed(
            inp, out, _one_dev_mesh(),
            chunk_records=2048,
            capacity_factor=1e-9,  # capacity 1: guaranteed overflow
            workdir=str(work),
        )
    assert list(work.iterdir()) == [], "spill state leaked"
    assert not os.path.exists(out)


def test_cleanup_on_final_pass_failure(tmp_path, monkeypatch):
    """A failure AFTER the output file exists (mid final pass) closes the
    r+b handle, removes the partial output, and clears the spill dir."""
    from repro.core import terasort
    from repro.data import gensort

    real = terasort.make_executor

    def broken(*args, **kwargs):
        ex = real(*args, **kwargs)

        def sort_iter(items):
            it = ex.__class__.sort_iter(ex, items)
            yield next(it)
            raise OSError("injected mid-sort failure")

        ex.sort_iter = sort_iter
        return ex

    monkeypatch.setattr(terasort, "make_executor", broken)
    inp = str(tmp_path / "in.bin")
    gensort.write_file(inp, 8192)
    out = str(tmp_path / "out.bin")
    work = tmp_path / "work"
    work.mkdir()
    with pytest.raises(OSError, match="injected"):
        terasort.sort_file_distributed(
            inp, out, _one_dev_mesh(),
            chunk_records=2048, workdir=str(work),
        )
    assert list(work.iterdir()) == [], "spill state leaked"
    assert not os.path.exists(out), "partial output left looking sorted"


def test_counter_parity_with_executor(tmp_path, monkeypatch):
    """Distributed SortStats must report the executor's OWN dispatch/
    occupancy/compile counters through the clock protocol (the old
    _StatsClock dropped add_counter on the floor)."""
    from repro.core import terasort
    from repro.data import gensort

    captured = {}
    real = terasort.make_executor

    def spy(*args, **kwargs):
        ex = real(*args, **kwargs)
        captured["ex"] = ex
        return ex

    monkeypatch.setattr(terasort, "make_executor", spy)
    inp = str(tmp_path / "in.bin")
    gensort.write_file(inp, 20_000, seed=23)
    out = str(tmp_path / "out.bin")
    stats = terasort.sort_file_distributed(
        inp, out, _one_dev_mesh(), chunk_records=1 << 13,
        executor="batched", workdir=str(tmp_path),
    )
    ex = captured["ex"]
    assert ex.dispatches > 0
    assert stats.device_dispatches == ex.dispatches
    assert stats.jit_compiles == ex.jit_compiles
    assert stats.batch_occupancy == pytest.approx(ex.occupancy)
    assert 0.0 < stats.batch_occupancy <= 1.0


def test_empty_input(tmp_path):
    """Zero records: empty output, zero stats, no temp state."""
    from repro.core import terasort

    inp = str(tmp_path / "in.bin")
    open(inp, "wb").close()
    out = str(tmp_path / "out.bin")
    work = tmp_path / "work"
    work.mkdir()
    stats = terasort.sort_file_distributed(
        inp, out, _one_dev_mesh(), workdir=str(work)
    )
    assert stats.n_records == 0
    assert os.path.getsize(out) == 0
    assert list(work.iterdir()) == []


def test_manifest_serves_distributed_output(tmp_path):
    """manifest=True over the distributed output: a v3 manifest whose
    partition counts are the per-range counts, servable point lookups."""
    from repro.core import manifest as manifest_lib
    from repro.core import terasort, validate
    from repro.data import gensort
    from repro.serve.index import SortedFileIndex

    inp = str(tmp_path / "in.bin")
    n = 20_000
    gensort.write_file(inp, n, seed=31)
    out = str(tmp_path / "out.bin")
    stats = terasort.sort_file_distributed(
        inp, out, _one_dev_mesh(), chunk_records=1 << 13, manifest=True
    )
    m = manifest_lib.load(stats.manifest_path)
    assert m.version == 3
    assert m.part_counts.tolist() == stats.partition_counts
    assert m.n_records == n
    index = SortedFileIndex.open(out)
    recs = gensort.read_records(out, mmap=False)
    pick = np.unique(np.random.default_rng(3).integers(0, n, 64))
    rows, found = index.lookup(recs[pick, : gensort.KEY_BYTES])
    assert found.all()
    kv = validate.keys_view(recs)
    for i, r in zip(pick, rows):
        assert kv[int(r)] == kv[int(i)]
