"""The harness's persistent compilation cache: a second process finds
every program the first one compiled.

Each case runs two processes on the CPU that turn the cache on as a chip
run does (``checkout_compile_cache`` before JAX starts, then
``enable_compile_cache``) in a fresh checkout, and compile the same
program.  The environment may set a size limit, and the directory may
already hold an entry written with no limit (no access-time file beside
it): under JAX's size limit that entry made every later write fail.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

import run

PROBE = """
import json, sys
sys.path.insert(0, sys.argv[1])
import run
run.checkout_compile_cache()
import jax, jax.numpy as jnp
counter = run.CompileCounter()
print(run.enable_compile_cache(), file=sys.stderr)
f = jax.jit(lambda x: jnp.sin(x) @ x.T + 3.0)
f(jnp.ones((64, 64))).block_until_ready()
print(json.dumps({"hits": counter.hits, "writes": counter.misses}))
"""


def _probe(root, env):
    proc = subprocess.run([sys.executable, "-c", PROBE, str(root / "bench")],
                          cwd=root, env=env, capture_output=True, text=True,
                          timeout=120)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("limit", [None, "1000000000"])
@pytest.mark.parametrize("stale_entry", [False, True])
def test_second_process_hits(tmp_path, limit, stale_entry):
    root = tmp_path / "checkout"
    shutil.copytree(run.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(run.ROOT / "src", root / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "elsewhere"))
    env.pop("JAX_COMPILATION_CACHE_MAX_SIZE", None)
    if limit:
        env["JAX_COMPILATION_CACHE_MAX_SIZE"] = limit
    if stale_entry:
        (root / ".jax_cache").mkdir()
        (root / ".jax_cache" / "jit_other-0123-cache").write_bytes(b"x" * 64)
    first = _probe(root, env)
    second = _probe(root, env)
    assert first["writes"] > 0 and first["hits"] == 0
    assert second == {"hits": first["writes"], "writes": 0}
    assert any((root / ".jax_cache").glob("jit__lambda-*-cache"))
    assert not (tmp_path / "elsewhere").exists()
