"""What the entry points promise about devices and compiled code: the chip
smoke refuses a machine without a TPU, the kernels never fall back to the
interpreter on a backend other than the CPU, and the persistent compile
cache is turned on by entry points only, in the directory the environment
names or else in the checkout's ``.jax_cache/``."""
import json
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from repro.kernels import ops
from repro.launch import compile_cache

ROOT = pathlib.Path(__file__).resolve().parent.parent
ENV = compile_cache.ENV_VAR


def _run(code_or_args, env_extra, drop=()):
    env = {k: v for k, v in os.environ.items() if k not in drop}
    env.update(PYTHONPATH="src", JAX_PLATFORMS="cpu", **env_extra)
    args = (code_or_args if isinstance(code_or_args, list)
            else ["-c", code_or_args])
    return subprocess.run([sys.executable, *args], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=120)


def test_chip_smoke_refuses_the_cpu():
    run = _run(["chip_smoke.py"], {})
    assert run.returncode != 0
    assert '"ok": true' not in run.stdout
    assert "no TPU" in run.stderr


def test_kernels_interpret_on_cpu_only(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert ops._interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops._interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()


@pytest.mark.parametrize("environ,want", [
    ({ENV: "/srv/jax-cache"}, "/srv/jax-cache"),
    ({}, str(ROOT / ".jax_cache")),
    ({ENV: ""}, str(ROOT / ".jax_cache")),
], ids=["env", "checkout", "empty-env"])
def test_cache_dir(environ, want):
    assert compile_cache.cache_dir(environ) == want


def test_chip_path_import_leaves_the_process_alone():
    """Importing the chip path turns no cache on and pulls in no module
    that sets XLA_FLAGS (launch/dryrun.py does, when imported)."""
    run = _run("import json, os, sys, jax, chip_smoke, repro.core, "
               "repro.launch.serve, repro.launch.compile_cache; "
               "print(json.dumps([jax.config.jax_compilation_cache_dir, "
               "'repro.launch.dryrun' in sys.modules, "
               "os.environ.get('XLA_FLAGS')]))",
               {}, drop=(ENV, "XLA_FLAGS"))
    assert run.returncode == 0, run.stderr
    assert json.loads(run.stdout) == [None, False, None]


def test_enable_writes_entries_where_the_env_says(tmp_path):
    code = ("import json, jax, jax.numpy as jnp; "
            "from repro.launch import compile_cache; "
            "path = compile_cache.enable(); "
            "jax.jit(lambda x: jnp.tanh(x) * 3)(jnp.ones(8)).block_until_ready(); "
            "print(json.dumps([path, jax.config.jax_compilation_cache_dir]))")
    run = _run(code, {ENV: str(tmp_path)})
    assert run.returncode == 0, run.stderr
    path, configured = json.loads(run.stdout.strip().splitlines()[-1])
    assert path == configured == str(tmp_path)
    assert any(tmp_path.iterdir())
