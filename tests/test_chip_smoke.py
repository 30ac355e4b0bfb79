"""``chip_smoke.py`` rehearsed on the CPU: every phase at the reduced size,
the refusal to run without a TPU, and where the compile cache goes."""
import os
import subprocess
import sys

import jax
import pytest

import chip_smoke
from repro.launch import compile_cache

ROOT = os.path.dirname(os.path.abspath(chip_smoke.__file__))


@pytest.fixture
def no_cache_side_effects(monkeypatch, tmp_path):
    # the entry points turn the persistent cache on for their process;
    # with the variable set they leave JAX's config alone
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


@pytest.mark.parametrize("phase", ["mine_cc", "mine_pagerank_failures",
                                   "kernel"])
def test_phase_reduced(no_cache_side_effects, phase):
    obs = getattr(chip_smoke, f"phase_{phase}")(reduced=True)
    assert obs["build_s"] >= 0


def test_serve_phase_reduced(no_cache_side_effects, tmp_path):
    obs = chip_smoke.phase_serve(reduced=True,
                                 store_dir=str(tmp_path / "store"))
    assert obs["deltas"] > 0 and obs["queries"] > 0


def test_dist_phase_one_device():
    obs = chip_smoke.phase_dist_vs_local(jax.devices()[:1], reduced=True)
    assert obs["dist_ticks"] == obs["local_ticks"] > 0


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout


def test_compile_cache_follows_env(monkeypatch):
    calls = []
    monkeypatch.setattr(compile_cache.jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.use_compile_cache() == "/elsewhere"
    keyed = ("jax_compilation_cache_include_metadata_in_key", True)
    assert calls == [keyed]  # the directory is left to JAX
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.use_compile_cache()
    assert path == os.path.join(ROOT, ".jax_cache")
    assert calls == [keyed, keyed, ("jax_compilation_cache_dir", path)]
