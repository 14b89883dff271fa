"""Rules that keep the device visible: the Pallas mode resolver, the
compile-cache placement, launch/serve.py's exit code, and chip_smoke's
refusal to run anywhere but a TPU."""
from __future__ import annotations

import os
import pathlib
import subprocess
import sys

import pytest

from repro import compile_cache
from repro.kernels import resolve_interpret

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("backend,interpret,expected", [
    ("cpu", None, True),
    ("cpu", True, True),
    ("cpu", False, False),      # compiling for a described TPU from a CPU
    ("tpu", None, False),
    ("tpu", False, False),
    ("gpu", None, False),
])
def test_resolve_interpret(backend, interpret, expected):
    assert resolve_interpret(interpret, backend) is expected


def test_resolve_interpret_refuses_interpreter_on_tpu():
    with pytest.raises(ValueError, match="interpret=True on a TPU"):
        resolve_interpret(True, "tpu")


def test_resolve_interpret_defaults_to_jax_backend():
    import jax
    assert resolve_interpret(None) is (jax.default_backend() == "cpu")


def test_cache_dir_env_wins_else_fixed_in_repo():
    assert compile_cache.cache_dir(
        {compile_cache.ENV_VAR: "/elsewhere/cache"}) == "/elsewhere/cache"
    fixed = compile_cache.cache_dir({})
    assert fixed == str(REPO / ".jax_cache")
    assert fixed == compile_cache.cache_dir({})          # no per-run name
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def _python(code: str, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)


def _env(**extra) -> dict:
    env = {k: v for k, v in os.environ.items()
           if k != compile_cache.ENV_VAR}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"), **extra)
    return env


def test_enable_writes_under_env_dir(tmp_path):
    code = ("from repro import compile_cache; compile_cache.enable(); "
            "import jax, jax.numpy as jnp; "
            "jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready(); "
            "print(jax.config.jax_compilation_cache_dir)")
    out = _python(code, _env(
        **{compile_cache.ENV_VAR: str(tmp_path),
           "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0"}))
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(tmp_path)
    assert any(tmp_path.iterdir()), "nothing cached under the env dir"


def test_enable_without_env_uses_repo_dir():
    code = ("from repro import compile_cache; compile_cache.enable(); "
            "import jax; print(jax.config.jax_compilation_cache_dir)")
    out = _python(code, _env())
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip().splitlines()[-1] == str(REPO / ".jax_cache")


@pytest.mark.parametrize("prefill_raises,rc", [(False, 0), (True, 1)])
def test_serve_exit_code(monkeypatch, capsys, prefill_raises, rc):
    """An ``error`` finish with no FaultPlan set makes serve exit 1; the
    engine itself still contains the exception per request."""
    from repro import steps
    from repro.launch import serve

    if prefill_raises:
        def boom(*args, **kwargs):
            raise RuntimeError("prefill broke")
        monkeypatch.setattr(steps, "prefill_step", boom)
    argv = ["--arch", "qwen2-0.5b", "--reduced", "--requests", "2",
            "--prompt-len", "4", "--max-new", "2", "--batch-size", "2"]
    assert serve.main(argv) == rc
    err = capsys.readouterr().err
    assert ("FAILED: 2 request(s) ended in ['error']" in err) is prefill_raises


def test_chip_smoke_refuses_cpu():
    out = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                         env=_env(), capture_output=True, text=True,
                         timeout=120)
    assert out.returncode != 0
    assert "no TPU" in out.stderr
    lines = out.stdout.strip().splitlines()
    assert not any(line.startswith('{"phase"') for line in lines)
    assert not lines or '"ok": true' not in lines[-1]
