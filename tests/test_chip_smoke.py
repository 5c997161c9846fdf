"""chip_smoke.py on the CPU: it refuses to run without a TPU, and its
phases pass at a reduced size when a test calls them directly."""

import dataclasses
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from repro.configs import get_reduced

ROOT = Path(__file__).resolve().parents[1]


def _env(**extra):
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.update(extra)
    return env


def test_refuses_to_run_without_tpu():
    proc = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                          cwd=ROOT, env=_env(), capture_output=True,
                          text=True, timeout=120)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert '"ok": true' not in proc.stdout


def test_phases_pass_on_reduced_config(capsys):
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    chip_smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(chip_smoke)
    cfg = get_reduced(chip_smoke.ARCH)
    # the published expert layout (32 experts, top-8) at reduced widths,
    # so the profile-guided policy defers what it defers on the chip
    cfg = cfg.with_(moe=dataclasses.replace(cfg.moe, n_experts=32,
                                            top_k=8))
    chip_smoke.smoke(cfg)  # the device check is skipped: phases only
    lines = [json.loads(s) for s in capsys.readouterr().out.splitlines()]
    paths = [r["path"] for r in lines if "path" in r]
    assert paths == ["cold", "warm", "warm", "warm", "warm"]
    ref = next(r for r in lines if r["phase"] == "reference")
    assert ref["rel_err"] < ref["bound"]
    guided = next(r for r in lines if r["phase"] == "guided")
    assert "compile.score" in guided["lazy"]
    assert guided["deferred_compile_s"] > 0


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_placement(tmp_path, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` places the cache where set; else it
    is the checkout's fixed ``.jax_cache``."""
    code = (
        "import jax, jax.numpy as jnp\n"
        "from repro.launch.serve import enable_compile_cache\n"
        "d = enable_compile_cache()\n"
        "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()\n"
        "print(d, jax.config.jax_compilation_cache_dir)\n")
    env = _env(JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_dir:
        env["JAX_COMPILATION_CACHE_DIR"] = str(tmp_path / "cc")
        want = tmp_path / "cc"
    else:
        want = ROOT / ".jax_cache"
    checkout_cache = ROOT / ".jax_cache"
    before = set(os.listdir(checkout_cache)) if checkout_cache.exists() \
        else set()
    out = subprocess.run([sys.executable, "-c", code], cwd=tmp_path,
                         env=env, capture_output=True, text=True,
                         timeout=120, check=True).stdout.split()
    assert out == [str(want), str(want)]
    assert any(want.iterdir())
    if env_dir:
        after = set(os.listdir(checkout_cache)) if checkout_cache.exists() \
            else set()
        assert after == before
