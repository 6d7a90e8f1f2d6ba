"""Test env: force a virtual 8-device CPU mesh BEFORE jax initialises.

Mirrors SURVEY.md §4 — distributed tests validate dp/tp/pp/fsdp sharding
semantics on host devices; the driver separately dry-runs multichip.
"""
import os

# Force the CPU backend, before any backend initialisation.
os.environ['JAX_PLATFORMS'] = 'cpu'
prev = os.environ.get('XLA_FLAGS', '')
if 'xla_force_host_platform_device_count' not in prev:
    os.environ['XLA_FLAGS'] = (
        prev + ' --xla_force_host_platform_device_count=8'
    ).strip()

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as pt

    pt.seed(1234)
    np.random.seed(1234)
    yield


@pytest.fixture(autouse=True, scope='module')
def _bound_code_mappings():
    """Every compiled CPU executable maps its code, and the jit caches keep
    executables for the life of a worker process. Near `vm.max_map_count`
    LLVM cannot map another section and aborts the process ("Unable to
    allocate section memory"), which takes an xdist worker down late in a
    tier-1 run and leaves the session hanging. Once a worker has used half
    of the limit, drop the caches at the next module boundary (measured:
    `jax.clear_caches()` after test_serving_tp.py returns 10430 mappings to
    694)."""
    yield
    try:
        with open('/proc/sys/vm/max_map_count') as f:
            limit = int(f.read())
        with open('/proc/self/maps') as f:
            used = sum(1 for _ in f)
    except OSError:             # not Linux: nothing to bound
        return
    if 2 * used > limit:
        jax.clear_caches()
