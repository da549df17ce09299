"""``chip_smoke.py``: refuses to fall back to the CPU, runs end to end as a
CPU rehearsal, and places the compile cache where its entry points say.

The script runs in subprocesses (each a fresh JAX on the CPU), with the
compile cache sent to a temporary directory.
"""
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from repro import compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"
TINY = ["--rehearse", "--docs", "300", "--queries", "8", "--batch", "4",
        "--sharded-batch", "4", "--chunk-tokens", "4096"]


def _run(args, tmp_path, cwd=ROOT, script=SCRIPT, devices=1):
    env = {k: v for k, v in os.environ.items()
           if k not in ("PYTHONPATH", "REPRO_FORCE_INTERPRET")}
    env.update(
        JAX_PLATFORMS="cpu",
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
        XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
    )
    return subprocess.run(
        [sys.executable, str(script), *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=600,
    )


def _last_json(stdout: str):
    lines = stdout.strip().splitlines()
    try:
        return json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        return None


def test_refuses_to_run_without_a_tpu(tmp_path):
    r = _run([], tmp_path)
    assert r.returncode != 0
    assert "no TPU visible" in r.stderr
    assert _last_json(r.stdout) is None


def test_fails_alone_in_a_directory(tmp_path):
    lone = tmp_path / "lone"
    lone.mkdir()
    shutil.copy(SCRIPT, lone / "chip_smoke.py")
    r = _run([], tmp_path, cwd=lone, script=lone / "chip_smoke.py")
    assert r.returncode != 0
    assert _last_json(r.stdout) is None


def test_refuses_forced_interpret_on_a_tpu(monkeypatch):
    """With a TPU visible, ``REPRO_FORCE_INTERPRET`` still stops the run:
    the smoke run must compile its kernels with Mosaic."""
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    class FakeTpu:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [FakeTpu()])
    args = mod.argparse.Namespace(rehearse=False, chips=1)
    assert mod._check_device(args)[0].platform == "tpu"
    monkeypatch.setenv("REPRO_FORCE_INTERPRET", "0")
    with pytest.raises(SystemExit, match="REPRO_FORCE_INTERPRET"):
        mod._check_device(args)


@pytest.mark.parametrize("chips", [1, 4])
def test_cpu_rehearsal_end_to_end(tmp_path, chips):
    r = _run([*TINY, "--chips", str(chips)], tmp_path, devices=chips)
    assert r.returncode == 0, r.stderr[-3000:]
    out = r.stdout
    assert _last_json(out) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": chips},
    }
    if chips == 1:
        for phase in ("[encoder]", "[index]", "[search]", "[serving]"):
            assert phase in out
        assert "pids_identical=True" in out
        assert "identical_to_direct=True" in out
    else:
        assert "[sharded] shards=4" in out and "pids_identical=True" in out


def test_compile_cache_placement(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv(compile_cache.ENV, "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert compile_cache.configure() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None  # left to JAX
        monkeypatch.delenv(compile_cache.ENV)
        assert compile_cache.configure() == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
