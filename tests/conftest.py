"""Test harness: force an 8-device virtual CPU mesh.

This is the reference-free way to test multi-worker DiLoCo semantics
(SURVEY §4): collectives over a mesh of fake devices exercise the same
SPMD partitioning XLA uses on a real slice.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# No network in CI: fail tokenizer-hub lookups instantly instead of
# waiting out connect timeouts (~52 s on the offline-fallback test).
os.environ.setdefault("HF_HUB_OFFLINE", "1")
os.environ.setdefault("TRANSFORMERS_OFFLINE", "1")
# The suite compiles cold and writes nothing into the checkout: the CLI
# entry points place a persistent compile cache
# (utils.enable_compile_cache), and children started by tests inherit
# this switch — JAX's own — unless the caller placed a cache with
# JAX_COMPILATION_CACHE_DIR. (Crash/resume with a shared cache handed
# resumed programs wrong executables on jax 0.4.37; PERF.md Findings,
# PR 21, has the re-test on 0.9.0.)
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import pytest  # noqa: E402


def pytest_collection_modifyitems(config, items):
    """Skip ``slow``-marked tests in the default run, but never when the
    user asked for them — via ``-m`` or an explicit ``::`` node id."""
    if config.getoption("-m") or any("::" in a for a in config.args):
        return
    skip = pytest.mark.skip(reason="slow parity test; run with -m slow or by node id")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    return devs


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_backend():
    assert jax.default_backend() == "cpu", (
        "tests must run on the virtual CPU mesh, not real accelerators; "
        f"got {jax.default_backend()}"
    )
