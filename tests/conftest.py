"""Test config: force an 8-device virtual CPU mesh before JAX import.

Multi-chip shardings are validated on virtual CPU devices; the chip path
is exercised by `chip_smoke.py` (and `--mesh` on a four-chip host).
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# cache every program, however small: repeat test runs skip XLA compiles
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "0")
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# an interpreter that imported jax before this file ran has already
# snapshotted JAX_PLATFORMS — set it through the config API as well
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

from emqx_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`; chaos is the ISSUE-6 deterministic
    # fault-injection matrix and deliberately NOT slow-marked, so the
    # injection matrix gates every tier-1 run
    config.addinivalue_line(
        "markers", "slow: long-running benchmarks/stress (excluded "
        "from tier-1)")
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection matrix "
        "(ISSUE 6 supervision layer)")
