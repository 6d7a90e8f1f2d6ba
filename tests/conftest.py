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


def _mappings_share():
    """This process's memory mappings as a share of `vm.max_map_count`
    (0.0 where there is no /proc)."""
    try:
        with open('/proc/sys/vm/max_map_count') as f:
            limit = int(f.read())
        with open('/proc/self/maps') as f:
            return sum(1 for _ in f) / limit
    except OSError:             # not Linux: nothing to bound
        return 0.0


@pytest.fixture(autouse=True, scope='module')
def _bound_code_mappings():
    """Every compiled CPU executable maps its code, and the jit caches keep
    executables for the life of a worker process. Near `vm.max_map_count`
    LLVM cannot map another section and the process dies inside
    `backend_compile_and_load` ("Unable to allocate section memory", or a
    bare segmentation fault), which takes an xdist worker down late in a
    tier-1 run. Once a worker has used a quarter of the limit, drop the
    caches at the next module boundary (measured: `jax.clear_caches()`
    after test_serving_tp.py returns 10430 mappings to 694). A quarter, not
    half: test_serving_spec.py alone maps 41,521 of the 65,530 (PR 29), so
    whether it survived a start from 32,000 was a matter of which files
    its worker had been handed before it."""
    yield
    if _mappings_share() > 0.25:
        jax.clear_caches()


@pytest.fixture(autouse=True)
def _bound_code_mappings_inside_a_module():
    """The last resort inside one long module: past nine tenths of the
    limit the caches go between two tests (the next test compiles again
    what it needs; a test must not count on another's compilations)."""
    yield
    if _mappings_share() > 0.9:
        jax.clear_caches()
