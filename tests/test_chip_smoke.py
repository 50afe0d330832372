"""``chip_smoke.py`` off the chip: it refuses to run without a TPU or
without the repository, and its phases pass at a tiny size on the CPU
(the same functions the chip run calls, handed a reduced config)."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parent.parent
PROGS = Path(__file__).parent / "distributed_progs"
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402
from repro.configs.registry import get_config  # noqa: E402
from repro.launch import compile_cache  # noqa: E402

TINY_SERVE = [
    "--arch", "yi-9b", "--smoke", "--layers", "2", "--kernel-backend",
    "fused", "--requests", "3", "--slots", "2", "--max-len", "128",
    "--page-size", "16", "--num-pages", "64",
]


def _run_alone(cwd: Path, script: Path):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _has_ok_line(stdout: str) -> bool:
    return any(line.startswith("{") and json.loads(line).get("ok")
               for line in stdout.splitlines())


def test_refuses_without_tpu():
    r = _run_alone(ROOT, ROOT / "chip_smoke.py")
    assert r.returncode != 0
    assert "no TPU" in r.stderr
    assert not _has_ok_line(r.stdout), r.stdout


def test_fails_without_the_repository(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run_alone(tmp_path, tmp_path / "chip_smoke.py")
    assert r.returncode != 0
    assert not _has_ok_line(r.stdout), r.stdout


def test_one_chip_phases_tiny(monkeypatch, capsys):
    # a set JAX_COMPILATION_CACHE_DIR leaves this process's cache alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "unused")
    chip_smoke.smoke_one_chip(TINY_SERVE,
                              attn_cfg=get_config("yi-9b", smoke=True))
    out = capsys.readouterr().out
    assert out.count("pool pages bit-identical: True") == 2
    assert "[griffin tier 0.5] served 3 requests, 45 tokens" in out
    assert "[dense] served 3 requests, 45 tokens" in out


@pytest.mark.slow
def test_mesh_path_tiny(tmp_path):
    """``--mesh model=4``'s comparison on 4 emulated host devices."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, str(PROGS / "prog_chip_smoke_mesh.py")],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, r.stdout + r.stderr[-3000:]
    assert "split over 4 devices" in r.stdout
    assert "OK" in r.stdout, r.stdout


def test_compile_cache_location(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        assert compile_cache.use_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = compile_cache.use_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
