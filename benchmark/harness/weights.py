"""Weights from `--seed`, one leaf at a time, the same for the program and
for the plain reference.

A leaf is named by its layer (-1 for the embedding, the final norm and the
head) and its role (`self_attn.q_proj`, `mlp.down_proj`, ...). Its key is
folded from the seed, the layer and the name, so the reference can make one
layer's weights when it needs them and never holds the program's arrays.
Which leaves a layer has, their shapes and how noise becomes a leaf's
values are the configuration's family's (`fam`: `benchmark/families`);
the fold is here alone. Matrices are (in, out): `x @ w`.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp


def base_key(seed):
    """Any whole number up to 2**62 or so: the low 31 bits seed the key and
    the rest is folded in, so seeds past 2**31 are distinct and legal."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def make_leaf(fam, base, layer, name, shape, dtype):
    key = jax.random.fold_in(jax.random.fold_in(base, layer + 1),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    noise = jax.random.normal(key, shape, jnp.float32)
    return fam.init(name, noise).astype(dtype)


PROBES = 8


def probe_dots(base, layer, name, grad):
    """The gradient of one leaf against PROBES seeded standard-normal
    directions: over the directions, the mean square of a difference of
    such dots is the squared norm of the difference of two gradients,
    which the norms alone cannot show."""
    key = jax.random.fold_in(jax.random.fold_in(base, layer + 1),
                             zlib.crc32(f'{name}#probe'.encode())
                             & 0x7FFFFFFF)
    g = grad.astype(jnp.float32)
    return jnp.stack([
        jnp.vdot(g, jax.random.normal(jax.random.fold_in(key, k), g.shape,
                                      jnp.float32))
        for k in range(PROBES)])


def make_layer(fam, base, cfg, layer, like):
    """The leaves of layer `layer`, which may be traced: `like`, the index
    of a layer of its kind (`fam.layer_like`), says which leaves those are."""
    return {n: make_leaf(fam, base, layer, n, s, dt)
            for n, (s, dt) in fam.layer_shapes(cfg, like).items()}


def make_globals(fam, base, cfg):
    return {n: make_leaf(fam, base, -1, n, s, dt)
            for n, (s, dt) in fam.global_shapes(cfg).items()}


_FILLS = {}


def fill_model(fam, cfg, struct, seed):
    """The program's model pytree (as `jax.eval_shape` gives it) with every
    leaf made on the device, in the type it is served in, in one jitted
    call (traced once per model shape, whatever the seed). Ends the run
    where the model's leaves are not the configuration's."""
    paths = jax.tree_util.tree_flatten_with_path(struct)[0]
    ids = {jax.tree_util.keystr(p): fam.leaf_id(jax.tree_util.keystr(p))
           for p, _ in paths}
    shapes = {ids[jax.tree_util.keystr(p)]: s.shape for p, s in paths}
    expect = {(-1, n): s for n, (s, _) in fam.global_shapes(cfg).items()}
    for layer in range(cfg['num_hidden_layers']):
        expect.update({(layer, n): s for n, (s, _) in
                       fam.layer_shapes(cfg, layer).items()})
    if shapes != expect:
        odd = set(shapes.items()) ^ set(expect.items())
        raise SystemExit(f'benchmark: the model\'s leaves are not the '
                         f'configuration\'s: {sorted(odd)[:6]}')
    key = (fam,) + tuple((k, s.shape, str(s.dtype)) for (k, _), (_, s) in
                         zip(ids.items(), paths))
    if key not in _FILLS:
        _FILLS[key] = jax.jit(lambda base: jax.tree_util.tree_map_with_path(
            lambda p, s: make_leaf(fam, base, *ids[jax.tree_util.keystr(p)],
                                   s.shape, s.dtype), struct))
    return _FILLS[key](base_key(seed))
