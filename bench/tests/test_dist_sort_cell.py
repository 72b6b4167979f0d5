"""Tests of the four-chip distributed sort cell, ``indy_uniform_4g.sort4``,
on the CPU at a small size.

    python -m pytest -q bench/tests

Its real configuration, mix, driver and readers run in a subprocess on
four virtual CPU devices that pass for TPU v5e chips in the device
check; the route's byte count and the readers of the collectives are
pinned on synthetic four-plane traces.
"""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import subprocess
import sys
import types

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECKOUT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import (  # noqa: E402
    catalog, control, route_shapes, tracereduce,
)

CELL = "indy_uniform_4g.sort4"
RECORDS = 300_000  # two route chunks: 262,144 records and a tail
SEED = 3_000_000_017  # wider than 32 bits, as benchmark seeds may be
NEW_METRICS = {
    "dist.exchange_s_per_gb", "dist.spill_s_per_gb",
    "dist.exchange_bytes_per_record", "dist.route_ici_roofline",
    "dist.a2a_exposed_s_per_gb", "dist.graph_roofline",
}
# the one-chip sort cells' readers that the final pass and the route's
# partition phase feed as well
SHARED_METRICS = {
    "sort.partition_s_per_gb", "sort.executor_s_per_gb",
    "sort.batch_occupancy", "sort.write_s_per_gb", "device_idle.sort",
    "sort.pack_s_per_gb", "sort.device_wait_s_per_gb",
    "sort.touchup_s_per_gb", "sort.gather_s_per_gb",
    "sort.handoff_s_per_gb",
}


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


def _plane(name, lines):
    return types.SimpleNamespace(
        name=name,
        lines=[types.SimpleNamespace(name=n, events=evs)
               for n, evs in lines.items()],
    )


def four_plane_trace(a2a_on_device_2: bool):
    """The four-plane synthetic trace of the harness tests: an
    ``all-to-all`` that a fusion half covers on device 0 and nothing
    covers on device 1, none on device 2 (unless asked for), and one cut
    by the window's end on device 3."""
    host = _plane("/host:CPU", {"main": [_Ev("bench.window", 0, 1000)]})
    a2a = "%all-to-all.1 = (u32[64]{0}) all-to-all(u32[64]{0} %p), dims={0}"

    def tpu(n, ops):
        return _plane(f"/device:TPU:{n}", {"XLA Ops": ops})

    dev2 = [_Ev("fusion.2", 0, 250)]
    if a2a_on_device_2:
        dev2.append(_Ev(a2a, 600, 400))
    planes = [
        host,
        tpu(1, [_Ev(a2a, 100, 200), _Ev("fusion.2", 500, 200)]),
        tpu(0, [_Ev(a2a, 100, 200), _Ev("fusion.2", 200, 200)]),
        tpu(3, [_Ev(a2a, 900, 200)]),
        tpu(2, dev2),
    ]
    return tracereduce.reduce_profile(types.SimpleNamespace(planes=planes))


def reader(name):
    path = os.path.join(BENCH, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "test_" + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def fake_run(reduced, records, input_gb=1.0):
    return types.SimpleNamespace(
        reduced=reduced,
        layer={"jobs": [], "records": records, "input_gb": input_gb,
               "n_dev": 4},
        peaks={"ici_bytes_per_s": 200e9, "hbm_bytes_per_s": 819e9},
    )


# ---------------------------------------------------------------------------
# the route's least byte count and the readers of its collectives
# ---------------------------------------------------------------------------


def test_route_least_bytes():
    # 3 of 4 records leave their chip, each as a 4-byte row index
    assert route_shapes.least_route_bytes(1000, 4) == 3000
    assert route_shapes.least_route_bytes_per_chip(1000, 4) == 750
    assert route_shapes.least_route_bytes(1000, 1) == 0
    # 750 B at 200 GB/s is 3.75 ns: measured over 7.5 ns, half the roofline
    assert route_shapes.route_roofline_share(
        1000, 4, 7.5e-9, 200e9) == pytest.approx(50.0)


def test_route_ici_roofline_on_four_planes():
    read = reader("dist.route_ici_roofline")
    # a chip that ran no all_to_all in the window has no share to give
    assert read(fake_run(four_plane_trace(False), 1000)) is None
    # op time per chip 200, 200, 400, 100 ns over 3.75 ns of least time
    got = read(fake_run(four_plane_trace(True), 1000))
    assert got == pytest.approx(
        (1.875 + 1.875 + 0.9375 + 3.75) / 4)
    assert read(fake_run(None, 1000)) is None


def test_a2a_exposed_on_four_planes():
    read = reader("dist.a2a_exposed_s_per_gb")
    # exposed per chip: 100, 200, 0, 100 ns; mean 100 ns, over 0.5 GB
    got = read(fake_run(four_plane_trace(False), 1000, input_gb=0.5))
    assert got == pytest.approx(200e-9)
    # device 2's all_to_all starts after its fusion ends: all 400 ns
    got = read(fake_run(four_plane_trace(True), 1000))
    assert got == pytest.approx((100 + 200 + 400 + 100) / 4 * 1e-9)
    empty = tracereduce.reduce_profile(types.SimpleNamespace(planes=[
        _plane("/host:CPU", {"main": [_Ev("bench.window", 0, 1000)]}),
        _plane("/device:TPU:0", {"XLA Ops": [_Ev("fusion.1", 0, 10)]}),
    ]))
    assert read(fake_run(empty, 1000)) is None


# ---------------------------------------------------------------------------
# the cell's own files, on four virtual devices
# ---------------------------------------------------------------------------


RUN_CELL = f'''
import json, os, sys, tempfile, types
sys.path.insert(0, "bench")
sys.path.insert(1, {os.path.join(CHECKOUT, "src")!r})
sys.path.insert(2, {os.path.join(BENCH, "tests")!r})
from harness import catalog, control, main, runtime
cat = catalog.Catalog(".", "bench")
if sys.argv[2] == "control":
    w = cat.workload(sys.argv[1])
    with tempfile.TemporaryDirectory() as tmp:
        print(json.dumps(control.readings(
            cat.config(w["config"]), cat.mix(w["traffic"]), {SEED},
            cat.spec["run_seconds"], tmp)))
    sys.exit(0)


def synthetic_trace(self):
    import test_dist_sort_cell as t
    reduced = t.four_plane_trace(True)
    reduced.module_ns = {{"jit_mesh_flat_sort": 4000.0,
                         "jit_route_chunk": 2000.0}}
    self.reduced = reduced


runtime.Run.reduce_trace = synthetic_trace
dev = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite",
                            memory_stats=lambda: {{"peak_bytes_in_use": 1}})
sys.exit(main.main(
    ["--workload", sys.argv[1], "--seed", str({SEED}), "--seconds", "0.1",
     "--trace", sys.argv[2]],
    check_devices=lambda n: [dev] * n, cat=cat))
'''


@pytest.fixture(scope="module")
def cell_results(tmp_path_factory):
    """The cell run from a copy of ``BENCHMARK.json`` and ``bench/`` with
    its configuration cut to :data:`RECORDS`: untraced, traced (on a
    synthetic trace) and under the control."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(os.path.join(CHECKOUT, "BENCHMARK.json"), root)
    cat = catalog.Catalog(str(root), str(root / "bench"))
    path = root / "bench" / "configs" / "indy_uniform_4g.json"
    path.write_text(json.dumps(dict(cat.config("indy_uniform_4g"),
                                    records=RECORDS)))
    script = root / "run_cell.py"
    script.write_text(RUN_CELL)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    results = {}
    for mode in ("0", "1", "control"):
        proc = subprocess.run(
            [sys.executable, str(script), CELL, mode], cwd=root, env=env,
            capture_output=True, text=True, timeout=600,
        )
        assert proc.returncode == 0, proc.stderr[-4000:]
        results[mode] = (json.loads(proc.stdout.strip().splitlines()[-1]),
                         proc.stdout)
    return results


@pytest.mark.parametrize("trace", ["0", "1"])
def test_cell_runs_on_four_devices(cell_results, trace):
    result, text = cell_results[trace]
    assert result["correct"] is True
    assert result["device"]["count"] == 4
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["checks"] == {
        "wrong_records": {"value": 0, "limit": 0},
        "jobs_off_mesh": {"value": 0, "limit": 0},
        "jobs_unrouted": {"value": 0, "limit": 0},
    }
    assert "executor=['mesh']" in text
    assert "route_chunks=[2" in text


def test_cell_reports_its_metrics(cell_results):
    untraced, _ = cell_results["0"]
    assert set(untraced["metrics"]) == {"sort_mb_s", "setup_s"}
    traced, _ = cell_results["1"]
    assert set(traced["metrics"]) == NEW_METRICS | SHARED_METRICS
    for name in ("dist.route_ici_roofline", "dist.graph_roofline"):
        assert traced["metrics"][name]["value"] > 0
    # two chunks of 262,144 records (the 37,856-record tail padded to the
    # full chunk), each 3 transposes of its 4-byte words and 4 send
    # blocks of (4, 32,768) int32
    want = 2 * 4 * (3 * 262_144 + 16 * 32_768) / RECORDS
    got = traced["metrics"]["dist.exchange_bytes_per_record"]["value"]
    assert got == pytest.approx(want)


def test_cell_control_through_the_drivers_hook(cell_results, tmp_path):
    """The new driver's hook reads the corpus as the one-chip sort
    driver's does."""
    got, _ = cell_results["control"]
    cat = catalog.Catalog(CHECKOUT, BENCH)
    w = cat.workload(CELL)
    config = dict(cat.config(w["config"]), records=RECORDS)
    mix = dict(cat.mix(w["traffic"]), driver="sort_jobs")
    want = control.readings(config, mix, SEED, 30, str(tmp_path))
    assert got == want and got["wrong_records"] > 0
