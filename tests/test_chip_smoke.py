"""CPU rehearsal of ``chip_smoke.py`` and the guards it relies on.

The smoke's phases run here at the smoke config with interpret-mode
kernels; only its device check (a TPU) is left out, by calling
``run_phases`` directly. The script itself must refuse to run on the CPU.
"""

import importlib.util
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.configs import smoke_config

ROOT = Path(__file__).resolve().parents[1]


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # its dataclasses look it up
    spec.loader.exec_module(mod)
    return mod


def test_phases_run_end_to_end_at_smoke_config(capsys):
    cs = _chip_smoke()
    cs.run_phases(smoke_config("stablelm-3b"), smoke_config("mamba2-2.7b"),
                  batch=2, seq=32, steps=3, prompt_lens=(5, 9, 16, 24),
                  new_tokens=4, interpret=True)
    out = capsys.readouterr().out
    for n, name in enumerate(("kernels", "train", "ckpt", "resume", "serve"),
                             start=2):
        assert f"phase {n} {name}: " in out
    assert "params byte-identical" in out


def test_check_fails_loudly():
    cs = _chip_smoke()
    with pytest.raises(cs.SmokeError):
        cs.require(False, "a failed check")


def _run_script(script: Path, cwd: Path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_script_refuses_cpu():
    r = _run_script(ROOT / "chip_smoke.py", ROOT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout
    assert "needs a TPU" in r.stderr


def test_script_alone_refuses(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    r = _run_script(tmp_path / "chip_smoke.py", tmp_path)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


# ---------------------------------------------------------- compile cache
@pytest.fixture()
def restore_cache_dir():
    from jax.experimental.compilation_cache import compilation_cache as cc
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    cc.reset_cache()


def test_compile_cache_honours_env(monkeypatch, tmp_path, restore_cache_dir):
    from repro.launch.compile_cache import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_fixed_in_checkout(monkeypatch, restore_cache_dir):
    from repro.launch.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first, second = enable_compile_cache(), enable_compile_cache()
    assert first == second == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().splitlines()
