"""chip_smoke.py off the chip, and the compile-cache helper it shares with
every entry point.

On the CPU the smoke must FAIL: the worker it starts refuses to come up
without a TPU (``--platform tpu``), nothing falls back, and no result line is
printed. The parent must stay off JAX altogether — a process that has
touched JAX holds the chip its children need."""

import os
import subprocess
import sys
import time

import jax
import pytest

from dynamo_tpu.runtime import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the script's own main, in-process, then: did the PARENT import jax?
_RUN = (
    "import runpy, sys\n"
    "try:\n"
    "    runpy.run_path('chip_smoke.py', run_name='__main__')\n"
    "finally:\n"
    "    print('PARENT_IMPORTED_JAX=%s' % ('jax' in sys.modules), flush=True)\n"
)


def _marked_processes(mark: str) -> set:
    """PIDs of running processes that carry ``mark`` in their environment:
    the script's own descendants, whatever other tests run meanwhile."""
    pids = set()
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/environ", "rb") as f:
                if mark.encode() in f.read():
                    pids.add(int(pid))
        except OSError:
            pass  # exited while we looked, or not ours to read
    return pids


def test_chip_smoke_fails_without_a_tpu():
    run_id = f"{os.getpid()}-{time.monotonic_ns()}"
    mark = f"CHIP_SMOKE_TEST_RUN={run_id}"
    env = dict(os.environ, JAX_PLATFORMS="cpu", CHIP_SMOKE_TEST_RUN=run_id)
    t0 = time.monotonic()
    proc = subprocess.run(
        [sys.executable, "-c", _RUN], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=240,
    )
    took = time.monotonic() - t0
    assert proc.returncode != 0, proc.stdout
    assert '"ok": true' not in proc.stdout
    # the worker itself refused: JAX found no TPU behind --platform tpu
    assert "serve-worker: exited with" in proc.stderr, proc.stderr[-2000:]
    assert "Unable to initialize backend 'tpu'" in proc.stderr
    assert "PARENT_IMPORTED_JAX=False" in proc.stdout
    assert took < 120, f"took {took:.0f}s to notice there is no chip"
    # it stops every process it started, also when the stack never came up
    assert not _marked_processes(mark)


@pytest.mark.parametrize("env_value", ["/some/dir", "", None],
                         ids=["env-set", "env-empty", "env-unset"])
def test_compile_cache_dir(monkeypatch, env_value):
    """JAX_COMPILATION_CACHE_DIR wins and nothing else is set in code (empty
    = cache off, how this suite runs); unset, one fixed in-checkout path."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_compilation_cache_dir
    if env_value is None:
        monkeypatch.delenv(device.ENV_COMPILE_CACHE, raising=False)
        want = os.path.join(REPO, ".jax_cache")
    else:
        monkeypatch.setenv(device.ENV_COMPILE_CACHE, env_value)
        want = env_value or None
    assert device.compile_cache_dir() == want
    try:
        assert device.enable_compile_cache() == want
        if env_value is None:
            assert jax.config.jax_compilation_cache_dir == want
        else:
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        cc.reset_cache()
