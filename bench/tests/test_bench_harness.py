"""Tests of the benchmark harness, on the CPU at tiny sizes.

    python -m pytest -q bench/tests

Each cell's mix runs through the same drivers as on the chip, on a
temporary copy of the checkout whose configurations are cut to a few
thousand records; the device check is steered in the test (a run that
finds no TPU exits non-zero, as another test shows).
"""

from __future__ import annotations

import asyncio
import contextlib
import hashlib
import io
import json
import os
import shutil
import subprocess
import sys
import types

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import (  # noqa: E402
    catalog, control, gensort, loadgen, main, open_loop, reference,
    runtime, shapes, sort_jobs, traffic, tracereduce,
)

SMALL_TRACE = os.path.join(BENCH, "tests", "data", "small.xplane.pb")
TINY_RECORDS = 20_000
TINY_BUDGET = 1 << 20  # 2 MB corpus, 2 partitions, spills
SORT_CELLS = ("indy_uniform_1g.sort_jobs", "indy_skew_1g.sort_jobs")
SERVE_CELL = "indy_uniform_1g.ycsb_c_zipf"
SEED = 3_000_000_017  # wider than 32 bits, as the driver's are


class FakeChip:
    """A CPU device that passes for one TPU v5e in the device check."""

    platform = "tpu"
    device_kind = "TPU v5 lite"

    def memory_stats(self):
        return {"peak_bytes_in_use": 1}


def fake_devices(chips):
    return [FakeChip() for _ in range(chips)]


@pytest.fixture()
def checkout(tmp_path):
    """A copy of BENCHMARK.json and bench/ with tiny configurations and
    a server batch of at most 4 (so set-up compiles four shapes)."""
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    spec = json.loads(open(os.path.join(CHECKOUT, "BENCHMARK.json")).read())
    for c in spec["configs"]:
        path = root / c["file"]
        cfg = json.loads(path.read_text())
        cfg.update(records=TINY_RECORDS, memory_budget_bytes=TINY_BUDGET)
        path.write_text(json.dumps(cfg))
    mix = root / "bench" / "mixes" / "ycsb_c_zipf.json"
    m = json.loads(mix.read_text())
    m["serve_config"] = dict(m["serve_config"], max_batch=4)
    m["rate_per_s"] = 50
    mix.write_text(json.dumps(m))
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    return root


def run_cell(checkout, workload, seconds=1.0, trace=0, seed=SEED):
    """One in-process run; returns (exit code, result line, stdout)."""
    cat = catalog.Catalog(str(checkout), str(checkout / "bench"))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = main.main(
            ["--workload", workload, "--seed", str(seed),
             "--seconds", str(seconds), "--trace", str(trace)],
            check_devices=fake_devices, cat=cat,
        )
    text = out.getvalue()
    last = text.strip().splitlines()[-1] if text.strip() else ""
    result = json.loads(last) if rc == 0 else None
    return rc, result, text


# ---------------------------------------------------------------------------
# each mix, through the timed path, at a tiny size
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("workload", SORT_CELLS)
def test_sort_mix_runs_tiny(checkout, workload):
    rc, result, text = run_cell(checkout, workload)
    assert rc == 0
    assert result["correct"] is True
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {"sort_mb_s", "setup_s"}
    assert result["metrics"]["sort_mb_s"]["value"] > 0
    assert list(result)[-1] == "checks"
    assert result["checks"]["wrong_records"] == {"value": 0, "limit": 0}
    assert "executor=['batched']" in text
    assert "compiles.window=0" in text


def test_serve_mix_runs_tiny(checkout):
    rc, result, text = run_cell(checkout, SERVE_CELL, seconds=1.5)
    assert rc == 0
    assert result["correct"] is True
    assert set(result["metrics"]) == {
        "serve_qps", "setup_s",
    }
    assert result["attempted"] == 75 and result["failed"] == 0
    assert result["checks"]["wrong_answers"]["value"] == 0
    assert result["checks"]["missing_answers"]["value"] == 0
    assert "client_imported_jax=False" in text
    assert "compiles.window=0" in text


@pytest.mark.parametrize("workload", SORT_CELLS + (SERVE_CELL,))
def test_traced_run_reports_every_per_layer_metric(checkout, monkeypatch,
                                                   workload):
    # a CPU trace has no TPU plane: reduce the recorded chip trace instead
    monkeypatch.setattr(
        runtime.Run, "reduce_trace",
        lambda self: setattr(self, "reduced",
                             tracereduce.reduce(SMALL_TRACE)),
    )
    rc, result, _ = run_cell(checkout, workload, seconds=1.5, trace=1)
    assert rc == 0 and result["correct"] is True
    cat = catalog.Catalog(str(checkout), str(checkout / "bench"))
    want = {m["name"] for m in cat.per_layer(workload)}
    assert set(result["metrics"]) == want
    assert {"busy_s", "window_s"} <= set(result["device"])
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


# ---------------------------------------------------------------------------
# device check, and a directory without the program
# ---------------------------------------------------------------------------


def _env_without_path():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_run_without_tpu_exits_nonzero():
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", SORT_CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=CHECKOUT, env=_env_without_path(), capture_output=True,
        text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == "" or "{" not in proc.stdout
    assert "no tpu device" in proc.stderr


def test_bench_files_alone_fail(checkout):
    """A directory with only BENCHMARK.json and bench/: even past the
    device check, the program is missing and the run fails."""
    snippet = (
        "import sys; sys.path.insert(0, 'bench');"
        "from harness import main, catalog;"
        "import types;"
        "dev = types.SimpleNamespace(platform='tpu', "
        "device_kind='TPU v5 lite', memory_stats=lambda: {});"
        f"sys.exit(main.main(['--workload', '{SORT_CELLS[0]}', '--seed', "
        "'1', '--seconds', '1', '--trace', '0'], "
        "check_devices=lambda n: [dev] * n, "
        "cat=catalog.Catalog('.', 'bench')))"
    )
    proc = subprocess.run(
        [sys.executable, "-c", snippet], cwd=checkout,
        env=_env_without_path(), capture_output=True, text=True,
        timeout=300,
    )
    assert proc.returncode != 0
    assert "repro" in proc.stderr
    assert '"correct"' not in proc.stdout


# ---------------------------------------------------------------------------
# new cells, configurations, mixes and metrics are files found by name
# ---------------------------------------------------------------------------


def _digests(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()
            ).hexdigest()
    return out


def test_new_config_mix_and_metric_found_by_name(checkout, monkeypatch):
    before = _digests(checkout / "bench")
    cfg = json.loads((checkout / "bench/configs/indy_uniform_1g.json")
                     .read_text())
    cfg.update(name="indy_uniform_tiny", records=5000)
    (checkout / "bench/configs/indy_uniform_tiny.json").write_text(
        json.dumps(cfg))
    (checkout / "bench/mixes/sort_jobs_twice.json").write_text(json.dumps({
        "driver": "sort_jobs", "sort_config": {"device_sort": True},
        "warmup_jobs": 2,
    }))
    (checkout / "bench/metrics/sort.jobs_in_window.py").write_text(
        "def read(run):\n    return len(run.layer['jobs'])\n"
    )
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "indy_uniform_tiny", "source": "test",
        "file": "bench/configs/indy_uniform_tiny.json", "reduced": [],
        "why": "test",
    })
    cell = "indy_uniform_tiny.sort_jobs_twice"
    spec["workloads"].append({
        "name": cell, "config": "indy_uniform_tiny",
        "traffic": "sort_jobs_twice", "chips": 1, "why": "test",
    })
    for m in spec["end_to_end"]:
        if m["name"] == "sort_mb_s":
            m["workloads"].append(cell)
    spec["per_layer"].append({
        "name": "sort.jobs_in_window", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "entry", "moves": "sort_mb_s",
        "workloads": [cell],
    })
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    # a CPU trace has no TPU plane: reduce the recorded chip trace instead
    monkeypatch.setattr(
        runtime.Run, "reduce_trace",
        lambda self: setattr(self, "reduced",
                             tracereduce.reduce(SMALL_TRACE)),
    )
    rc, result, _ = run_cell(checkout, cell, trace=1)
    assert rc == 0 and result["correct"] is True
    assert result["metrics"]["sort.jobs_in_window"]["value"] >= 1
    assert "breakdown" in result
    after = _digests(checkout / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


NEW_DRIVER = '''"""Whole distributed sorts of the corpus, on a one-device mesh."""

import os

from harness import control, gensort, reference, sort_jobs


def job(run, inp, out):
    from repro.core import terasort
    from repro.launch.mesh import make_data_mesh

    with run.span("bench.job"):
        return terasort.sort_file_distributed(
            inp, out, make_data_mesh(1), executor="mesh", workdir=run.tmp,
            chunk_records=1 << 13)


def drive(run, devices):
    inp = sort_jobs.make_corpus(run)
    out = os.path.join(run.tmp, "out.bin")
    with run.setup_phase("warmup"):
        job(run, inp, out)
    t0 = run.window_begin()
    stats = job(run, inp, out)
    run.window_end()
    run.read_memory(devices)
    records = gensort.read_corpus(inp)
    order, _ = reference.stable_order(records)
    wrong = reference.mismatched_records(gensort.read_corpus(out), records,
                                         order)
    run.attempted, run.failed = 1, int(wrong > 0)
    run.checks["wrong_records"] = (wrong, 0)
    run.checks["jobs_off_mesh"] = (int(stats.executor != "mesh"), 0)
    run.e2e["sort_mb_s"] = stats.input_bytes / 1e6 / (run.window[1] - t0)


def control_readings(config, mix, seed, seconds, records):
    return {"wrong_records": control.sort_reading(records)}
'''

RUN_NEW_DRIVER = f'''
import json, sys, tempfile, types
sys.path.insert(0, "bench")
sys.path.insert(1, {os.path.join(CHECKOUT, "src")!r})
from harness import catalog, control, main, runtime, tracereduce
cat = catalog.Catalog(".", "bench")
if sys.argv[2] == "control":
    w = cat.workload(sys.argv[1])
    with tempfile.TemporaryDirectory() as tmp:
        print(json.dumps(control.readings(
            cat.config(w["config"]), cat.mix(w["traffic"]), {SEED},
            cat.spec["run_seconds"], tmp)))
    sys.exit(0)
runtime.Run.reduce_trace = lambda self: setattr(
    self, "reduced", tracereduce.reduce({SMALL_TRACE!r}))
dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                            memory_stats=lambda: {{"peak_bytes_in_use": 1}})
sys.exit(main.main(
    ["--workload", sys.argv[1], "--seed", str({SEED}), "--seconds", "0.1",
     "--trace", sys.argv[2]],
    check_devices=lambda n: [dev] * n, cat=cat))
'''


def test_new_driver_found_by_name(checkout, tmp_path):
    """A driver that is a new file under ``bench/harness/``, run on a
    four-chip cell, from a checkout that holds it and nothing else new."""
    with pytest.raises(KeyError, match=r"no_such_driver\.py"):
        main.driver_for({"driver": "no_such_driver"})
    before = _digests(checkout / "bench")
    (checkout / "bench/harness/dist_sort_jobs.py").write_text(NEW_DRIVER)
    (checkout / "bench/mixes/dist_sort.json").write_text(json.dumps({
        "driver": "dist_sort_jobs", "what": "test",
    }))
    (checkout / "bench/metrics/sort.exposed_sort_s.py").write_text(
        "def read(run):\n"
        "    if run.reduced is None:\n"
        "        return None\n"
        "    return sum(run.reduced.exposed_seconds(r'sort'))\n"
    )
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    cell = "indy_skew_1g.dist_sort"  # key ties cross the mesh route
    spec["workloads"].append({
        "name": cell, "config": "indy_skew_1g", "traffic": "dist_sort",
        "chips": 4, "why": "test",
    })
    for m in spec["end_to_end"]:
        if m["name"] == "sort_mb_s":
            m["workloads"].append(cell)
    spec["per_layer"].append({
        "name": "sort.exposed_sort_s", "unit": "s", "better": "lower",
        "source": "device_trace", "layer": "device", "moves": "sort_mb_s",
        "workloads": [cell],
    })
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))
    script = tmp_path / "run_new_driver.py"
    script.write_text(RUN_NEW_DRIVER)
    results = {}
    for mode in ("0", "1", "control"):
        proc = subprocess.run(
            [sys.executable, str(script), cell, mode], cwd=checkout,
            env=_env_without_path(), capture_output=True, text=True,
            timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-4000:]
        results[mode] = json.loads(proc.stdout.strip().splitlines()[-1])
    for result in (results["0"], results["1"]):
        assert result["correct"] is True
        assert result["device"]["count"] == 4
        assert result["checks"]["wrong_records"] == {"value": 0, "limit": 0}
        assert result["checks"]["jobs_off_mesh"] == {"value": 0, "limit": 0}
    assert set(results["0"]["metrics"]) == {"sort_mb_s", "setup_s"}
    exposed = results["1"]["metrics"]["sort.exposed_sort_s"]["value"]
    assert exposed == pytest.approx(
        sum(tracereduce.reduce(SMALL_TRACE).exposed_seconds("sort")))
    assert exposed > 0
    # the new driver's own hook gives the control's sort reading
    assert results["control"] == {"wrong_records": 19_978}
    after = _digests(checkout / "bench")
    assert {k: v for k, v in after.items() if k in before} == before


# ---------------------------------------------------------------------------
# the open-loop client times each request from its due time
# ---------------------------------------------------------------------------


def test_latency_counts_from_due_not_from_send():
    due = np.array([0.0, 0.1])
    answers = {
        "t_sent": np.array([10.0, 10.4]),  # the second was sent 0.3 s late
        "t_done": np.array([10.05, 10.45]),
        "status": np.array([loadgen.OK, loadgen.OK], dtype=np.int8),
    }
    lat = open_loop.latencies(10.0, due, answers)
    np.testing.assert_allclose(lat, [0.05, 0.35])
    answers["status"][1] = loadgen.OVERLOADED
    assert np.isinf(open_loop.latencies(10.0, due, answers)[1])


def test_client_against_a_stalled_server():
    """A server that answers one request at a time, 40 ms each: later
    requests wait behind earlier ones, and their latency from the due
    time grows although each is sent on time."""

    async def scenario():
        lock = asyncio.Lock()

        async def handle(reader, writer):
            while line := await reader.readline():
                msg = json.loads(line)
                async with lock:
                    await asyncio.sleep(0.04)
                writer.write((json.dumps({
                    "id": msg["id"], "ok": True, "found": True,
                    "record": "61" * 100,
                }) + "\n").encode())
                await writer.drain()
            writer.close()

        server = await asyncio.start_server(handle, "127.0.0.1", 0)
        port = server.sockets[0].getsockname()[1]
        import time

        t0 = time.monotonic() + 0.2
        due = np.arange(5) * 0.005
        keys = np.full((5, 10), 65, dtype=np.uint8)
        res = await loadgen.drive({"due": due, "keys": keys}, "127.0.0.1",
                                  port, t0, t0 + 5, connections=5)
        server.close()
        await server.wait_closed()
        return t0, due, res

    t0, due, res = asyncio.run(scenario())
    assert (res["status"] == loadgen.OK).all()
    assert (res["t_sent"] >= t0 + due).all()
    lat = open_loop.latencies(t0, due, res)
    assert np.all(np.diff(np.sort(lat)) > 0.02)  # the queue grows
    assert lat.max() > 0.15
    assert (res["records"][:, 0] == ord("a")).all()


# ---------------------------------------------------------------------------
# the control fails every comparison; planted faults make correct false
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("skewed", [False, True])
def test_control_fails_the_sort_comparison(skewed):
    records = gensort.make_records(0, 200_000, seed=SEED, skewed=skewed)
    assert control.sort_reading(records) > 0
    order, _ = reference.stable_order(records)
    assert reference.mismatched_records(records[order], records, order) == 0


def test_control_fails_the_lookup_comparison():
    records = gensort.make_records(0, 1_000_000, seed=SEED, skewed=False)
    mix = json.loads(open(os.path.join(BENCH, "mixes",
                                       "ycsb_c_zipf.json")).read())
    rows = traffic.key_rows(mix, 4000, records.shape[0], SEED)
    keys = np.ascontiguousarray(records[rows, :10])
    assert control.serve_reading(records, keys) > 0
    target = types.SimpleNamespace(records=records)
    order, sorted_keys = reference.stable_order(records)
    found, ref_rows = reference.point_answers(records, order, sorted_keys,
                                              keys)
    exact = {
        "status": np.zeros(len(keys), np.int8), "found": found,
        "records": records[ref_rows], "record_len": np.full(len(keys), 100),
    }
    assert open_loop.wrong_answers(target, keys, exact) == (0, 0)


@pytest.mark.parametrize("workload,want", [
    (SORT_CELLS[0], {"wrong_records": 218}),
    (SORT_CELLS[1], {"wrong_records": 199_974}),
    (SERVE_CELL, {"wrong_answers": 1}),
])
def test_control_readings_through_the_drivers_hooks(checkout, tmp_path,
                                                    workload, want):
    """``control.readings`` asks the mix's driver, and reads what it read
    when it chose the comparison itself (200,000 records, 50 q/s)."""
    cat = catalog.Catalog(str(checkout), str(checkout / "bench"))
    w = cat.workload(workload)
    config = dict(cat.config(w["config"]), records=200_000)
    got = control.readings(config, cat.mix(w["traffic"]), SEED,
                           cat.spec["run_seconds"], str(tmp_path))
    assert got == want


def _corrupt(path):
    with open(path, "r+b") as f:
        f.seek(150)
        b = f.read(1)
        f.seek(150)
        f.write(bytes([b[0] ^ 1]))


def _swap_first_two(path):
    with open(path, "r+b") as f:
        a, b = f.read(100), f.read(100)
        f.seek(0)
        f.write(b + a)


def _halve(path):
    size = os.path.getsize(path)
    os.truncate(path, size // 200 * 100)


SORT_FAULTS = {
    "answer_altered": _corrupt,
    "records_swapped": _swap_first_two,
    "half_left_out": _halve,
    "state_unchanged": None,  # the output is the input, unsorted
}


@pytest.mark.parametrize("fault", sorted(SORT_FAULTS))
def test_sort_fault_makes_correct_false(checkout, monkeypatch, fault):
    real = sort_jobs.sort_job
    count = {"jobs": 0}

    def broken(run, inp, out, cfg):
        stats = real(run, inp, out, cfg)
        count["jobs"] += 1
        if count["jobs"] > run.mix["warmup_jobs"]:  # only the window's
            if SORT_FAULTS[fault] is None:
                shutil.copyfile(inp, out)
            else:
                SORT_FAULTS[fault](out)
        return stats

    monkeypatch.setattr(sort_jobs, "sort_job", broken)
    rc, result, _ = run_cell(checkout, SORT_CELLS[1])
    assert rc == 0
    assert result["correct"] is False
    assert result["checks"]["wrong_records"]["value"] > 0


def _alter_records(execute):
    def broken(self, batch):
        out = execute(self, batch)
        return [(req, dict(resp, record=b"x" + resp["record"][1:]))
                if resp.get("record") else (req, resp) for req, resp in out]
    return broken


def _leave_out_half(execute):
    def broken(self, batch):
        return execute(self, batch)[: max(1, len(batch) // 2)] \
            if len(batch) > 1 else execute(self, batch)
    return broken


@pytest.mark.parametrize("fault", ["answer_altered", "half_left_out"])
def test_serve_fault_makes_correct_false(checkout, monkeypatch, fault):
    from repro.serve.server import QueryServer

    wrap = {"answer_altered": _alter_records,
            "half_left_out": _leave_out_half}[fault]
    monkeypatch.setattr(QueryServer, "_execute", wrap(QueryServer._execute))
    monkeypatch.setattr(open_loop, "ANSWER_GRACE_S", 2.0)
    mix = checkout / "bench" / "mixes" / "ycsb_c_zipf.json"
    m = json.loads(mix.read_text())
    m["rate_per_s"] = 400  # enough arrivals that batches hold several
    mix.write_text(json.dumps(m))
    rc, result, _ = run_cell(checkout, SERVE_CELL, seconds=1.0)
    assert rc == 0
    assert result["correct"] is False
    checks = result["checks"]
    assert checks["wrong_answers"]["value"] + \
        checks["missing_answers"]["value"] > 0


# ---------------------------------------------------------------------------
# trace reduction, peaks and byte counts
# ---------------------------------------------------------------------------


def test_reduce_recorded_chip_trace():
    r = tracereduce.reduce(SMALL_TRACE)
    assert r.n_devices == 1
    assert 0 < r.busy_s < r.window_s
    assert 0 < r.idle_share < 1
    assert r.module_seconds(r"_flat_impl") > 0
    assert r.module_seconds(r"rmi_bucket") > 0
    assert r.module_seconds(r"no_such_module") is None
    labels = {label for label, _ in r.gaps}
    assert labels & {"bench.job", "bench.lookup"} or \
        "no benchmark span" in labels
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    # the recording sorted 8,192 rows twice and predicted 64 keys twice
    # over 1,024 leaves: both shares of the roofline lie in (0, 100]
    hbm = shapes.peaks("TPU v5 lite")["hbm_bytes_per_s"]
    sort_share = shapes.roofline_share(
        shapes.flat_sort_bytes(2 * 8192), r.module_seconds("_flat_impl"), hbm)
    rmi_share = shapes.roofline_share(
        2 * shapes.rmi_call_bytes(64, 1024), r.module_seconds("rmi_bucket"),
        hbm)
    assert 0 < sort_share <= 100 and 0 < rmi_share <= 100
    assert all(s > 0 for _, s in b["device_ops"])
    # the inputs of every metric reader, as the reduction read them
    # before it kept each device's ops
    assert r.window_s == 0.0179652 and r.busy_s == 9.2008e-05
    assert r.module_ns == {"jit_rmi_bucket": 85885.0, "jit__flat_impl": 10141.0}
    assert sum(r.op_ns.values()) == 92008.0 and r.op_ns["sort.21"] == 8842.0
    assert [s for _, s in r.gaps[:4]] == [
        0.004775648, 0.004687267, 0.004287686, 0.004118603]
    assert len(r.gaps) == 67 and len(r.device_ops) == 1
    assert sum(b - a for _, a, b in r.device_ops[0]) == 92008.0


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name,
        lines=[types.SimpleNamespace(name=n, events=evs)
               for n, evs in lines.items()],
    )


def test_reduce_arithmetic_on_a_synthetic_trace():
    host = _plane("/host:CPU", {
        "main": [
            _Ev("bench.window", 100, 1000),
            _Ev("bench.job", 100, 500),
            _Ev("bench.reference", 600, 400),
            _Ev("elsar.sort", 100, 490),
        ],
        # the program's spans on another thread: the shortest one open at
        # a gap's midpoint names the gap's phase
        "worker": [_Ev("elsar.sort.gather", 110, 30)],
    })
    dev = _plane("/device:TPU:0", {
        "XLA Modules": [_Ev("jit__flat_impl(7)", 150, 300),
                        _Ev("jit_rmi_bucket(2)", 900, 400)],
        "XLA Ops": [_Ev("sort.1", 150, 200), _Ev("fusion.2", 300, 150),
                    _Ev("custom-call.3", 900, 400), _Ev("early", 0, 50)],
    })
    r = tracereduce.reduce_profile(types.SimpleNamespace(planes=[host, dev]))
    assert r.window_s == pytest.approx(1000e-9)
    # ops: [150, 450) and [900, 1100) clipped to the window's end
    assert r.busy_s == pytest.approx(500e-9)
    assert r.idle_share == pytest.approx(0.5)
    assert r.module_seconds("_flat_impl") == pytest.approx(300e-9)
    assert r.module_seconds("rmi_bucket") == pytest.approx(200e-9)
    # [450, 900) lies in no elsar. span; [100, 150) in two
    assert r.gaps[0] == ("bench.reference", pytest.approx(450e-9))
    assert r.gaps[1] == ("bench.job/sort.gather", pytest.approx(50e-9))
    assert len(r.gaps) == 2
    ops = dict(r.breakdown()["device_ops"])
    assert ops["custom-call.3"] == pytest.approx(200e-9)
    assert "early" not in ops


def test_reduce_keeps_each_devices_ops():
    """Four device planes, listed out of order: ``device_ops`` per plane
    in device order, and the time of an ``all-to-all`` that a fusion
    half covers on device 0 and nothing covers on device 1."""
    host = _plane("/host:CPU", {"main": [_Ev("bench.window", 0, 1000)]})
    a2a = "%all-to-all.1 = (u32[64]{0}) all-to-all(u32[64]{0} %p), dims={0}"

    def tpu(n, ops):
        return _plane(f"/device:TPU:{n}", {"XLA Ops": ops})

    planes = [
        host,
        tpu(1, [_Ev(a2a, 100, 200), _Ev("fusion.2", 500, 200)]),
        tpu(0, [_Ev(a2a, 100, 200), _Ev("fusion.2", 200, 200)]),
        tpu(3, [_Ev(a2a, 900, 200)]),  # runs past the window's end
        tpu(2, [_Ev("fusion.2", 0, 250)]),
    ]
    r = tracereduce.reduce_profile(types.SimpleNamespace(planes=planes))
    assert r.n_devices == 4
    assert r.device_ops == [
        [("all-to-all.1", 100, 300), ("fusion.2", 200, 400)],
        [("all-to-all.1", 100, 300), ("fusion.2", 500, 700)],
        [("fusion.2", 0, 250)],
        [("all-to-all.1", 900, 1000)],
    ]
    assert r.op_seconds("all-to-all") == pytest.approx(
        [200e-9, 200e-9, 0.0, 100e-9])
    assert r.exposed_seconds("all-to-all") == pytest.approx(
        [100e-9, 200e-9, 0.0, 100e-9])
    assert r.op_seconds("fusion") == pytest.approx(
        [200e-9, 200e-9, 250e-9, 0.0])
    assert r.exposed_seconds("fusion") == pytest.approx(
        [100e-9, 200e-9, 250e-9, 0.0])
    # busy: [100, 400), [100, 300) + [500, 700), [0, 250), [900, 1000)
    assert r.busy_s == pytest.approx((300 + 400 + 250 + 100) / 4 * 1e-9)
    # summed over the planes, as before
    assert dict(r.breakdown()["device_ops"]) == pytest.approx(
        {"all-to-all.1": 500e-9, "fusion.2": 650e-9})
    # idle gaps are device 0's: [0, 100) and [400, 1000)
    assert [s for _, s in r.gaps] == pytest.approx([600e-9, 100e-9])


def test_peaks_table():
    p = shapes.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["bf16_flops_per_s"] == 197e12
    assert p["ici_bytes_per_s"] == 200e9 and "1,600 Gbps" in p["source"]
    with pytest.raises(KeyError):
        shapes.peaks("TPU v4")


def test_byte_counts_and_roofline_share():
    assert shapes.flat_sort_bytes(1_000_000) == 16_000_000
    assert shapes.rmi_call_bytes(64, 1024) == 64 * 12 + 1024 * 28
    # 819 MB at 819 GB/s takes 1 ms: measured at 2 ms, half the roofline
    assert shapes.roofline_share(819e6, 2e-3, 819e9) == pytest.approx(50.0)


# ---------------------------------------------------------------------------
# the generator and the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("skewed", [False, True])
def test_gensort_layout(skewed):
    n = 3 * gensort.CHUNK // 2
    whole = gensort.make_records(0, n, seed=SEED, skewed=skewed)
    keys = whole[:, :10]
    assert keys.min() >= 32 and keys.max() <= 126
    ids = whole[:, 10:18].copy().view(np.uint64).reshape(-1)
    np.testing.assert_array_equal(ids, np.arange(n))
    assert whole[:, 18:].min() >= 32
    piece = gensort.make_records(gensort.CHUNK - 5, 10, seed=SEED,
                                 skewed=skewed)
    np.testing.assert_array_equal(
        piece, whole[gensort.CHUNK - 5 : gensort.CHUNK + 5])
    if skewed:
        table = gensort.skew_table()
        for i in (1, 2, 3, 4, 1000, 2**20 + 7):
            g = int(np.floor(np.log2(i))) % 128
            np.testing.assert_array_equal(whole[i, :6], table[g])
        assert len({bytes(k) for k in keys[2**19 : 2**20, :6]}) == 1
    else:
        counts = np.bincount(keys.reshape(-1), minlength=127)[32:]
        assert counts.min() > 0.9 * counts.mean()


def test_gensort_same_seed_same_corpus(tmp_path):
    a, b = tmp_path / "a", tmp_path / "b"
    gensort.write_corpus(str(a), 1000, seed=SEED, skewed=True)
    gensort.write_corpus(str(b), 1000, seed=SEED, skewed=True)
    assert a.read_bytes() == b.read_bytes()
    gensort.write_corpus(str(b), 1000, seed=SEED + 1, skewed=True)
    assert a.read_bytes() != b.read_bytes()


def test_gensort_fixed_key_bytes_same_for_every_seed():
    a = gensort.make_records(0, 5000, seed=SEED, skewed=False,
                             fixed_key_bytes=8)
    b = gensort.make_records(0, 5000, seed=SEED + 1, skewed=False,
                             fixed_key_bytes=8)
    np.testing.assert_array_equal(a[:, :8], b[:, :8])
    assert (a[:, 8:10] != b[:, 8:10]).any(axis=1).mean() > 0.99
    np.testing.assert_array_equal(a[:, 10:], b[:, 10:])
    assert a[:, :10].min() >= gensort.ASCII_LO
    assert a[:, :10].max() <= gensort.ASCII_HI
    # cut anywhere, the corpus is the same
    piece = gensort.make_records(gensort.CHUNK - 3, 6, seed=SEED,
                                 skewed=False, fixed_key_bytes=8)
    tail = gensort.make_records(gensort.CHUNK, 3, seed=SEED,
                                skewed=False, fixed_key_bytes=8)
    np.testing.assert_array_equal(piece[3:], tail)
    plain = gensort.make_records(0, 5000, seed=SEED, skewed=False)
    assert not np.array_equal(plain[:, :8], a[:, :8])


def test_reference_is_a_stable_memcmp_sort():
    rng = np.random.default_rng(0)
    records = rng.integers(65, 68, size=(500, 100), dtype=np.uint8)
    order, sorted_keys = reference.stable_order(records)
    want = sorted(range(500), key=lambda i: (records[i, :10].tobytes(), i))
    np.testing.assert_array_equal(order, want)
    out = records[order].copy()
    assert reference.mismatched_records(out, records, order) == 0
    out[[3, 4]] = out[[4, 3]]
    assert reference.mismatched_records(out, records, order) == (
        0 if (out[3] == out[4]).all() else 2)
    assert reference.mismatched_records(out[:-10], records, order) >= 10
    q = records[[7, 7, 123], :10]
    found, rows = reference.point_answers(records, order, sorted_keys, q)
    assert found.all()
    first = min(i for i in range(500)
                if records[i, :10].tobytes() == q[0].tobytes())
    assert rows[0] == rows[1] == first
    missing = np.full((1, 10), 90, dtype=np.uint8)
    assert not reference.point_answers(records, order, sorted_keys,
                                       missing)[0][0]


def test_traffic_same_work_every_seed():
    mix = {"rate_per_s": 1000, "arrivals": {"process": "poisson"}}
    a = traffic.arrival_offsets(mix, 2.0, 1)
    b = traffic.arrival_offsets(mix, 2.0, 2)
    assert a.shape == b.shape == (2000,)
    assert (np.diff(a) > 0).all() and 0 <= a[0] and a[-1] < 2.0
    np.testing.assert_allclose(np.sort(np.diff(a, prepend=0.0)),
                               np.sort(np.diff(b, prepend=0.0)),
                               rtol=1e-9, atol=1e-12)
    assert not np.array_equal(a, b)
    # one constant order of gaps, rotated by the seed: b's gaps are a's,
    # cyclically shifted, and each key rank keeps its gap
    ga, gb = np.diff(a, prepend=0.0), np.diff(b, prepend=0.0)
    shift = int(np.argmin([np.abs(np.roll(ga / ga.mean(), k)
                                  - gb / gb.mean()).max()
                           for k in range(ga.size)]))
    np.testing.assert_allclose(np.roll(ga / ga.mean(), shift)[1:],
                               (gb / gb.mean())[1:], rtol=1e-6)
    keys = {"keys": {"distribution": "zipfian", "zipfian_constant": 0.99,
                     "scrambled": True}}
    np.testing.assert_array_equal(
        np.roll(traffic.key_rows(keys, 2000, 50_000, 1), shift),
        traffic.key_rows(keys, 2000, 50_000, 2))
    np.testing.assert_array_equal(traffic.arrival_offsets(mix, 2.0, 1), a)
    with pytest.raises(ValueError):
        traffic.arrival_offsets(dict(mix, arrivals={"process": "onoff"}),
                                2.0, 1)


def test_zipfian_keys_follow_ycsb():
    u = (np.arange(100_000) + 0.5) / 100_000
    ranks = traffic.zipfian_ranks(u, 1_000_000, 0.99)
    counts = np.bincount(ranks)
    assert counts[0] == counts.max() and counts[0] > counts[10] > 0
    assert ranks.max() < 1_000_000
    # Utils.fnvhash64 of 0 and 1, worked out byte by byte
    want = []
    for v in (0, 1):
        h = traffic.FNV_OFFSET_BASIS_64
        for _ in range(8):
            h ^= v & 0xFF
            h = (h * traffic.FNV_PRIME_64) % 2**64
            v >>= 8
        want.append(abs(h - 2**64 if h >= 2**63 else h))
    np.testing.assert_array_equal(traffic.fnv1a_64(np.array([0, 1])), want)
    mix = {"keys": {"distribution": "zipfian", "zipfian_constant": 0.99,
                    "scrambled": True}}
    rows = traffic.key_rows(mix, 10_000, 50_000, SEED)
    assert rows.min() >= 0 and rows.max() < 50_000
    assert len(np.unique(rows)) < 10_000  # hot keys repeat
    with pytest.raises(ValueError):
        traffic.key_rows({"keys": {"distribution": "uniform"}},
                         1000, 50_000, SEED)
