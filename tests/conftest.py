"""Test bootstrap: force an 8-device virtual CPU backend BEFORE jax imports.

This is the TPU-world stand-in for a multi-chip test rig (SURVEY.md §4):
``--xla_force_host_platform_device_count=8`` gives 8 CPU "devices", so
mesh/sharding/collective tests (the ``multigpu.py`` tier of the reference)
run on one host in CI.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

# JAX_PLATFORMS only takes effect if set before jax is imported; a plugin or
# an earlier import may already have read another value, so pin the config
# too, before any backend is initialized.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# Make the repo importable without installation.
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


import contextlib  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture
def dispatcher_off():
    """A context manager under which ``obs/xla.py``'s ``jax.monitoring``
    listeners are not registered: a process before its
    ``enable_compile_cache()``, for the tests that hold a run with the
    set-up timeline against one without."""
    from jax import monitoring

    from distributed_pytorch_tpu.obs import xla

    @contextlib.contextmanager
    def off():
        was = xla._dispatcher_installed
        if was:
            monitoring.unregister_event_duration_listener(
                xla._on_duration_event)
            monitoring.unregister_event_listener(xla._on_event)
            xla._dispatcher_installed = False
        try:
            yield
        finally:
            if was:
                assert xla.install_dispatcher()

    return off


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-process / subprocess integration tests"
    )
    config.addinivalue_line(
        "markers",
        "chaos: deterministic fault-injection tests (the chaos harness); "
        "fast CPU-only injections run in tier-1, long drills are also "
        "marked slow",
    )
