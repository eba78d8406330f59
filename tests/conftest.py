"""Test-session guards.

The dry-run sets XLA_FLAGS=--xla_force_host_platform_device_count=512 in
its OWN process only; tests must run with the default single-device view
(multi-device tests spawn subprocesses). Fail fast if the env leaks.

The persistent compile cache stays off for the session and every
subprocess it starts: entry points default ``JAX_COMPILATION_CACHE_DIR``
to ``<checkout>/.jax_cache``, and tests must not write there.
"""
import os


def pytest_configure(config):
    os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    flags = os.environ.get("XLA_FLAGS", "")
    assert "xla_force_host_platform_device_count" not in flags, (
        "XLA_FLAGS device-count override leaked into the test session; "
        "the dry-run must set it only inside launch/dryrun.py")
