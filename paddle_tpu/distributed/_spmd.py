"""The shard_map surface the distributed layer uses, in one place.

ring_attention, ulysses, pipeline and llama's sharded decode dispatch
call `jax.shard_map(..., axis_names=..., check_vma=...)`,
`lax.axis_size` and `lax.pcast` through these three names.
"""
from __future__ import annotations

import jax
from jax import lax

axis_size = lax.axis_size


def pvary(x, axis):
    """Promote a replicated value to varying over `axis`."""
    return lax.pcast(x, axis, to='varying')


def shard_map(f, mesh=None, in_specs=None, out_specs=None,
              axis_names=None, check_vma=None):
    """`jax.shard_map`; `axis_names` is the set of MANUAL axes (None =
    all mesh axes), `check_vma=None` keeps jax's default."""
    kwargs = {}
    if axis_names is not None:
        kwargs['axis_names'] = set(axis_names)
    if check_vma is not None:
        kwargs['check_vma'] = check_vma
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, **kwargs)
