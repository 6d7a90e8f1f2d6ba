"""Mesh & strategy (ref: python/paddle/distributed/fleet/fleet.py::init,
base/topology.py::HybridCommunicateGroup).

Paddle builds NCCL process groups per parallel dimension (dp/mp/pp/
sharding) from `DistributedStrategy.hybrid_configs`. TPU-native: the
same topology is ONE `jax.sharding.Mesh` with named axes; GSPMD lowers
array shardings to ICI collectives — no process groups to manage.

Axis names (canonical order, outermost first):
    'dp'   data parallel (pure replica of params)
    'fsdp' fully-sharded data parallel / ZeRO-3 (params sharded too)
    'pp'   pipeline stages
    'tp'   tensor (model) parallel
    'sp'   sequence/context parallel (ring attention)
    'ep'   expert parallel (MoE) — usually aliases dp×fsdp in size
"""
from __future__ import annotations

import dataclasses
import typing

import jax
import numpy as np
from jax.sharding import Mesh

MESH_AXES = ('dp', 'fsdp', 'pp', 'tp', 'sp', 'ep')


@dataclasses.dataclass
class DistributedStrategy:
    """ref: paddle.distributed.fleet.DistributedStrategy (hybrid_configs).

    Degrees of -1 mean "absorb all remaining devices" (at most one).
    """

    dp_degree: int = -1
    fsdp_degree: int = 1
    pp_degree: int = 1
    tp_degree: int = 1
    sp_degree: int = 1
    ep_degree: int = 1
    # non-topology knobs (consumed elsewhere)
    amp: bool = False
    amp_dtype: str = 'bfloat16'
    gradient_merge_steps: int = 1
    sharding_stage: int = 0        # 0=off, 1/2/3 ≈ ZeRO stages

    def degrees(self) -> typing.Dict[str, int]:
        return {
            'dp': self.dp_degree, 'fsdp': self.fsdp_degree,
            'pp': self.pp_degree, 'tp': self.tp_degree, 'sp': self.sp_degree,
            'ep': self.ep_degree,
        }


_global_mesh: typing.Optional[Mesh] = None


def build_mesh(strategy: DistributedStrategy | None = None,
               devices=None, **degree_overrides) -> Mesh:
    """Factor `devices` into a named mesh per the strategy's degrees."""
    strategy = strategy or DistributedStrategy()
    for k, v in degree_overrides.items():
        setattr(strategy, f'{k}_degree', v)
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    degrees = strategy.degrees()
    fixed = {k: v for k, v in degrees.items() if v != -1}
    free = [k for k, v in degrees.items() if v == -1]
    prod = int(np.prod(list(fixed.values()))) if fixed else 1
    if n % prod != 0:
        raise ValueError(f'{n} devices not divisible by fixed degrees {fixed}')
    rest = n // prod
    if len(free) > 1:
        raise ValueError(f'at most one axis may be -1, got {free}')
    if free:
        fixed[free[0]] = rest
    elif prod != n:
        raise ValueError(f'degrees {fixed} (={prod}) != device count {n}')
    shape = tuple(fixed[a] for a in MESH_AXES)
    arr = np.asarray(devices).reshape(shape)
    return Mesh(arr, MESH_AXES)


def init_parallel_env(strategy: DistributedStrategy | None = None,
                      devices=None, **degree_overrides) -> Mesh:
    """ref: paddle.distributed.init_parallel_env / fleet.init.

    Builds the global mesh. For true multi-host, call
    `jax.distributed.initialize()` before this (see distributed/launch.py).
    """
    global _global_mesh
    _global_mesh = build_mesh(strategy, devices, **degree_overrides)
    return _global_mesh


def get_mesh() -> typing.Optional[Mesh]:
    return _global_mesh


def set_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh


def force_virtual_devices(n: int) -> None:
    """Append `--xla_force_host_platform_device_count=max(n, 8)` to
    XLA_FLAGS unless a count is already forced. Only effective BEFORE
    the backend initialises (and ignored by jax afterwards) — callers
    that need the devices to actually exist must still count them.
    The 8 floor matches the shardlint / test-rig virtual mesh.

    A crutch for the CPU rig only: a no-op unless jax is pinned to the
    CPU (`JAX_PLATFORMS=cpu`). On a TPU host the mesh is built from the
    real chips and nothing about the process is changed."""
    import os

    if (jax.config.jax_platforms
            or os.environ.get('JAX_PLATFORMS', '')) != 'cpu':
        return
    flags = os.environ.get('XLA_FLAGS', '')
    if 'xla_force_host_platform_device_count' not in flags:
        os.environ['XLA_FLAGS'] = (
            flags + f' --xla_force_host_platform_device_count='
                    f'{max(int(n), 8)}').strip()


def serving_mesh(tp: int, devices=None) -> Mesh:
    """1-D tensor-parallel mesh for a TP-sharded `ServingEngine`
    (`ServingEngine(model, tp=4)` builds one of these internally; pass
    an explicit `devices` slice to pin which chips serve).

    The mesh is the first `tp` of `jax.devices()` — real chips on a TPU
    host. Where jax is pinned to the CPU and has not initialised a
    backend yet, the host-platform device-count flag is forced first
    (to at least `tp`, and at least the 8 the shardlint / test rig
    uses) so CPU dev boxes can stand up a tp>1 engine without
    exporting XLA_FLAGS by hand. A backend with fewer than `tp`
    devices raises instead of silently serving single-device."""
    tp = int(tp)
    if tp < 1:
        raise ValueError(f'tp must be >= 1, got {tp}')
    if devices is None:
        # the len() check below is the real gate either way
        force_virtual_devices(tp)
        devices = jax.devices()
    devices = list(devices)
    if len(devices) < tp:
        raise ValueError(
            f'serving_mesh(tp={tp}) needs {tp} devices, found '
            f'{len(devices)} on backend {jax.default_backend()!r} — '
            f'for a virtual CPU mesh run with JAX_PLATFORMS=cpu XLA_FLAGS='
            f'--xla_force_host_platform_device_count={max(tp, 8)} set '
            f'before jax initialises')
    return build_mesh(devices=devices[:tp], tp=tp)


def get_world_size() -> int:
    return jax.device_count()


def get_rank() -> int:
    return jax.process_index()
