"""CPU guards for the chip smoke run's contract and the compile cache.

``chip_smoke.py`` must refuse to report anything without a TPU (no
silent CPU fallback), and the persistent compile cache must sit where
the entry points say: ``$JAX_COMPILATION_CACHE_DIR``, or one fixed
directory in the checkout.
"""

import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("where", ["checkout", "script_alone"])
def test_chip_smoke_fails_without_a_tpu(where, tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = ROOT / "chip_smoke.py"
    if where == "script_alone":
        script = Path(shutil.copy(script, tmp_path / "chip_smoke.py"))
        env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    assert proc.stdout.strip() == "", proc.stdout


def test_compile_cache_dir_honours_env_else_fixed_checkout_path(
        monkeypatch, tmp_path):
    outer = os.environ.get(compile_cache.ENV_VAR)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path / "cc"))
    assert compile_cache.compile_cache_dir() == tmp_path / "cc"
    monkeypatch.delenv(compile_cache.ENV_VAR)
    first = compile_cache.compile_cache_dir()
    assert first == compile_cache.compile_cache_dir() == \
        ROOT / ".jax_cache"
    # importing the entry points leaves JAX's own setting alone
    import jax

    import repro.launch.simulate  # noqa: F401

    assert jax.config.jax_compilation_cache_dir == outer
