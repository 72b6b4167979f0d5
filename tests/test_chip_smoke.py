"""chip_smoke.py on the CPU: its phases at 20,000 records, the platform
check in ``main``, the four-chip phase on 4 virtual devices (in a
subprocess: the device count is fixed before JAX initializes), and the
compile-cache helper every device entry point calls."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import chip_smoke
from repro.launch import compile_cache

RECORDS = 20_000
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _last_json(out: str) -> dict:
    return json.loads(out.strip().splitlines()[-1])


def test_kernel_phase_matches_refs():
    chip_smoke.kernel_phase(seed=0)


def test_corpus_sort_serve_phases(tmp_path):
    inp = str(tmp_path / "input.bin")
    chk = chip_smoke.corpus_phase(inp, RECORDS, seed=3)
    out = chip_smoke.sort_phase(inp, str(tmp_path), RECORDS, chk)
    assert os.path.exists(out + ".manifest.npz")
    chip_smoke.serve_phase(out, seed=3)


def test_sort_phase_rejects_a_wrong_checksum(tmp_path):
    inp = str(tmp_path / "input.bin")
    chk = chip_smoke.corpus_phase(inp, RECORDS, seed=4)
    with pytest.raises(chip_smoke.SmokeFailure, match="validate_file"):
        chip_smoke.sort_phase(inp, str(tmp_path), RECORDS, chk + 1)


def test_main_refuses_the_cpu(capsys):
    assert chip_smoke.main(["--records", "1000"]) != 0
    assert '"ok"' not in capsys.readouterr().out


def test_main_end_to_end_when_steered_to_the_cpu(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "REQUIRED_PLATFORM", "cpu")
    # keep the test's own process off the in-repo cache directory
    monkeypatch.setattr(
        chip_smoke.compile_cache, "enable", compile_cache.cache_dir
    )
    assert chip_smoke.main(["--records", str(RECORDS)]) == 0
    out = capsys.readouterr().out
    assert _last_json(out) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    for line in (
        "sort_executor=batched",
        "sort_fallbacks=0",
        "sort_validate_ok=True",
        "sort_sha256_matches_host=True",
        "serve_answers=1020",
        "serve_ok=1020",
        "serve_identical_to_host_predict=1020",
    ):
        assert line in out.splitlines(), line


FOUR_CHIPS = r"""
import os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
sys.path.insert(0, sys.argv[1])
import chip_smoke
chip_smoke.REQUIRED_PLATFORM = "cpu"
chip_smoke.compile_cache.enable = chip_smoke.compile_cache.cache_dir
sys.exit(chip_smoke.main(["--chips", "4", "--records", sys.argv[2]]))
"""


def _python(args, cwd=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, *args], capture_output=True, text=True, env=env,
        cwd=cwd, timeout=600,
    )


def test_four_chip_phase_on_virtual_devices():
    r = _python(["-c", FOUR_CHIPS, ROOT, str(RECORDS)])
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    lines = r.stdout.splitlines()
    assert "mesh_devices=4" in lines
    assert "mesh_executor=mesh" in lines
    assert "mesh_sha256_matches_host=True" in lines
    assert _last_json(r.stdout)["device"]["count"] == 4
    assert not any(ln.startswith("kernel_") for ln in lines)  # no 1-chip phase


def test_script_alone_fails_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _python(["chip_smoke.py", "--records", "1000"], cwd=tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_compile_cache_honours_the_environment(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cache"))
    assert compile_cache.cache_dir() == str(tmp_path / "cache")


def test_compile_cache_default_is_fixed_inside_the_checkout(
    monkeypatch, tmp_path
):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    here = compile_cache.cache_dir()
    monkeypatch.chdir(tmp_path)
    assert compile_cache.cache_dir() == here
    assert here == os.path.join(ROOT, ".jax_cache")
