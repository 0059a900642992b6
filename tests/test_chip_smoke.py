"""chip_smoke.py on the CPU: it must refuse to run without a TPU, and its
train and serve phases must drive the real CLIs end to end (at reduced
width here; the kernels phase and the full-width run need the chip)."""

import importlib.util
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_tpu_and_names_the_platform():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "'cpu'" in r.stderr
    assert '"ok"' not in r.stdout


def test_train_then_serve_phases_reduced(tmp_path, monkeypatch):
    from repro.launch import compile_cache
    calls = []
    # the entry points ask for the persistent cache; tests keep it off
    monkeypatch.setattr(compile_cache, "enable", lambda: calls.append(1))
    smoke = _load_smoke()
    ckpt = str(tmp_path / "ckpt")
    t = smoke.train_phase(ckpt, reduced=True, steps=2, batch=2, seq=16)
    assert len(t["losses"]) == 2
    assert os.path.exists(os.path.join(ckpt, "replay.jsonl"))
    s = smoke.serve_phase(ckpt, reduced=True, requests=3, slots=2,
                          prompt_len=20, gen=5)
    assert [c.tokens.size for c in s["completions"]] == [5, 5, 5]
    assert {c.user for c in s["completions"]} == {smoke.USER}
    # off the chip the kernels run interpreted / as jnp references
    assert set(t["kernels"]) | set(s["kernels"]) == {
        "train_step", "serve_decode", "serve_verify", "serve_prefill"}
    assert len(calls) == 2


@pytest.mark.parametrize("env_dir", [None, "/elsewhere/cache"])
def test_compile_cache_dir(monkeypatch, env_dir):
    from repro.launch import compile_cache
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert compile_cache.cache_dir() == os.path.join(ROOT, ".jax_cache")
        with open(os.path.join(ROOT, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        assert compile_cache.cache_dir() == env_dir


def test_timed_reads_the_program_compile_counter(capsys):
    """A phase's log line counts the compiles it made, from
    ``repro.obs.compiles()``."""
    import jax
    import jax.numpy as jnp
    smoke = _load_smoke()
    x = jnp.ones(3)

    def phase():
        return jax.jit(lambda v: v * 5 - 2)(x).block_until_ready()

    smoke.timed("phase", phase)
    assert "of which 1 backend compiles or cache loads" in \
        capsys.readouterr().out
