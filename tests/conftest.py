"""Test harness config.

All JAX tests run on a virtual 8-device CPU mesh (the multi-chip sharding path
is validated without TPU hardware, mirroring the reference's mocker-based
GPU-free test strategy, reference tests/README.md). Set env BEFORE jax import.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"  # the suite runs on the CPU, chip or no chip
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    flags = (flags + " --xla_force_host_platform_device_count=8").strip()
if "xla_cpu_parallel_codegen_split_count" not in flags:
    # XLA's parallel LLVM codegen intermittently SIGABRTs mid-compile on
    # this image (~50% per multi-engine session; one abort kills the whole
    # pytest process). Serial codegen is rock-stable (measured 0 crashes)
    # and the compile-time cost is amortized by the persistent cache.
    flags = (flags + " --xla_cpu_parallel_codegen_split_count=1").strip()
os.environ["XLA_FLAGS"] = flags
# The persistent compile cache is DISABLED for tests: on this image the
# cache's native load/store path segfaults or aborts the whole pytest
# process (measured: test_guided crashed at the same test 8/8 runs with a
# warm cache and passed 18/18 tests with the cache off; same for the
# chunked-prefill engine tests). Recompiling costs ~30-60s per engine-heavy
# file; a single segfault costs every test after it in the session.
os.environ["JAX_COMPILATION_CACHE_DIR"] = ""
# Mixed continuous batching (engine mixed_step) compiles ONE extra fused
# program the first time a prefill overlaps resident decodes; across the
# suite's dozens of tiny engines that is minutes of serial XLA compile for a
# path tests/test_mixed_batching.py pins explicitly (engines there opt in
# via TpuEngineConfig(mixed_admission=True)). Default off for the suite;
# setdefault so DTPU_MIXED=1 can still force it everywhere.
os.environ.setdefault("DTPU_MIXED", "0")

import jax  # noqa: E402

# the config knob wins over whatever the environment exported: CPU backend,
# 8 virtual devices
jax.config.update("jax_platforms", "cpu")

import asyncio  # noqa: E402
import inspect  # noqa: E402

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: compile-heavy tests (multi-engine spec-decode builds) excluded "
        "from the tier-1 run (-m 'not slow'); run them serially via "
        "-m slow — they time out under parallel/xdist runs on this image",
    )


@pytest.hookimpl(tryfirst=True)
def pytest_pyfunc_call(pyfuncitem):
    """Run ``async def`` tests on a fresh event loop (no pytest-asyncio here)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name] for name in pyfuncitem._fixtureinfo.argnames
        }
        # a hang's limit, not a budget: the slowest honest test (an indexer
        # engine with its kernels interpreted, twice against the reference)
        # takes 85-95 s alone and met 120 s beside five other workers
        asyncio.run(asyncio.wait_for(fn(**kwargs), timeout=300))
        return True
    return None


@pytest.fixture
def tmp_store_path(tmp_path):
    return str(tmp_path / "store")


@pytest.fixture(scope="session")
def repo_analysis():
    """ONE whole-tree tools/analysis run (dynamo_tpu/, every pass, no
    baseline) shared by every current-tree pin in test_analysis.py /
    test_analysis_flows.py — each used to reload and re-analyze the tree
    themselves, which multiplied ~7s per test into the tier-1 clock.
    Returns (modules, parse_findings, findings)."""
    from tools.analysis import core

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    modules, parse = core.load_modules([os.path.join(repo, "dynamo_tpu")])
    findings = core.collect_findings(modules, parse)
    return modules, parse, findings


@pytest.fixture(scope="session")
def repo_analysis_full():
    """ONE run over the FULL gated tree (dynamo_tpu/ + tools/ + tests/) for
    the cross-plane contract pins: the contract spec table registers
    consumer sites that live under tests/ (the /debug/requests schema
    pins), so the dynamo_tpu-only ``repo_analysis`` view would report
    direction drift a full run doesn't. Returns (modules, parse,
    findings)."""
    from tools.analysis import core

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    modules, parse = core.load_modules(
        [os.path.join(repo, p) for p in ("dynamo_tpu", "tools", "tests")]
    )
    findings = core.collect_findings(modules, parse)
    return modules, parse, findings
