"""chip_smoke.py off the chip: its phases at the reduced gemma3-1b config on
the CPU (Pallas kernels interpreted), its refusal to run anywhere but a TPU,
and its four-chip fleet phase on four virtual CPU devices."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import get_config
from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@pytest.fixture()
def no_compile_cache(monkeypatch, tmp_path):
    """serve.main turns the persistent cache on; with the variable set (after
    JAX read its environment) it leaves the cache as it found it: off."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "unused"))


def _cpu_env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"))
    env.update(extra)
    return env


def test_kernel_phase_matches_oracle_at_reduced_widths(chip_smoke):
    chip_smoke.kernel_phase(get_config("gemma3-1b", reduced=True))


def test_serve_phase_through_serve_main(chip_smoke, no_compile_cache):
    out = chip_smoke.serve_phase(reduced=True)
    # float32 on the CPU: every path is exact, so nothing may diverge
    assert out["differ"] == []
    assert out["agree"] == chip_smoke.NEW_TOKENS * 12
    assert out["serial_agree"] == chip_smoke.NEW_TOKENS * 2
    assert out["prefix_hits"] == 4  # the four shared prompts after the first batch


def test_refuses_a_cpu_device():
    proc = subprocess.run(
        [sys.executable, str(SCRIPT)], env=_cpu_env(), cwd=ROOT,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("device: ") and '"platform": "cpu"' in lines[0]
    assert '"ok"' not in proc.stdout


def test_fails_without_the_repo(tmp_path):
    shutil.copy(SCRIPT, tmp_path)
    env = _cpu_env()
    del env["PYTHONPATH"]
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout


def test_four_chip_phase_on_virtual_devices(tmp_path):
    """Fleet tokens equal the one-device run, four workers sit on four
    distinct devices, and the compiled programs land in the cache directory
    the environment names."""
    cache = tmp_path / "cache"
    code = (
        "import json, chip_smoke; "
        "print(json.dumps(chip_smoke.fleet_phase(reduced=True, workers=4)))"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=ROOT, capture_output=True, text=True,
        timeout=900,
        env=_cpu_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4",
            JAX_COMPILATION_CACHE_DIR=str(cache),
            JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        ),
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert sorted(out["devices"].values()) == [f"jax-cpu-{i}" for i in range(4)]
    assert all(n > 0 for n in out["settled"].values()), out["settled"]
    assert any(cache.iterdir())


def test_compile_cache_defaults_to_the_repo(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert REPO_CACHE_DIR.parent == ROOT
    assert f"{REPO_CACHE_DIR.name}/" in (ROOT / ".gitignore").read_text().split()


def test_compile_cache_placed_from_outside(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
