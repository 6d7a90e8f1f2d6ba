"""Weights from `--seed`, one leaf at a time, the same for the program and
for the plain reference.

A leaf is named by its layer (-1 for the embedding, the final norm and the
head) and its role (`self_attn.q_proj`, `mlp.down_proj`, ...). Its key is
folded from the seed, the layer and the name, so the reference can make one
layer's weights when it needs them and never holds the program's arrays.
Matrices are (in, out): `x @ w`.
"""
from __future__ import annotations

import re
import zlib

import jax
import jax.numpy as jnp

def base_key(seed):
    """Any whole number up to 2**62 or so: the low 31 bits seed the key and
    the rest is folded in, so seeds past 2**31 are distinct and legal."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def make_leaf(base, layer, name, shape, dtype):
    key = jax.random.fold_in(jax.random.fold_in(base, layer + 1),
                             zlib.crc32(name.encode()) & 0x7FFFFFFF)
    noise = jax.random.normal(key, shape, jnp.float32)
    if name.endswith('norm.weight'):
        return (1.0 + 0.05 * noise).astype(dtype)
    return (0.02 * noise).astype(dtype)


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


def layer_shapes(cfg):
    """{leaf name: (shape, dtype)} of one decoder layer of `cfg` (the
    configuration file's keys)."""
    h, f = cfg['hidden_size'], cfg['intermediate_size']
    d = cfg['head_dim']
    q, kv = cfg['num_attention_heads'] * d, cfg['num_key_value_heads'] * d
    dt = jnp.dtype(cfg['torch_dtype'])
    shapes = {
        'input_layernorm.weight': ((h,), jnp.float32),
        'post_attention_layernorm.weight': ((h,), jnp.float32),
        'self_attn.q_proj': ((h, q), dt), 'self_attn.k_proj': ((h, kv), dt),
        'self_attn.v_proj': ((h, kv), dt), 'self_attn.o_proj': ((q, h), dt),
        'mlp.gate_proj': ((h, f), dt), 'mlp.up_proj': ((h, f), dt),
        'mlp.down_proj': ((f, h), dt)}
    if cfg['attention_bias']:
        shapes.update({'self_attn.q_bias': ((q,), dt),
                       'self_attn.k_bias': ((kv,), dt),
                       'self_attn.v_bias': ((kv,), dt)})
    return shapes


def global_shapes(cfg):
    h, v = cfg['hidden_size'], cfg['vocab_size']
    dt = jnp.dtype(cfg['torch_dtype'])
    shapes = {'embed_tokens': ((v, h), dt), 'norm.weight': ((h,), jnp.float32)}
    if not cfg['tie_word_embeddings']:
        shapes['lm_head'] = ((h, v), dt)
    return shapes


def make_layer(base, cfg, layer):
    return {n: make_leaf(base, layer, n, s, dt)
            for n, (s, dt) in layer_shapes(cfg).items()}


def make_globals(base, cfg):
    return {n: make_leaf(base, -1, n, s, dt)
            for n, (s, dt) in global_shapes(cfg).items()}


_PATH = re.compile(r'(?:layers\.L?(\d+)\.)?([A-Za-z_\.]+)$')


def leaf_id(path_str):
    """(layer, name) of a leaf of the program's model pytree, from its
    path as `jax.tree_util.keystr` prints it."""
    tail = path_str.lstrip('.')
    tail = tail[len('model.'):] if tail.startswith('model.') else tail
    m = _PATH.match(tail)
    if m is None:
        raise ValueError(f'benchmark: cannot name the model leaf {path_str!r}')
    return (-1 if m.group(1) is None else int(m.group(1))), m.group(2)


_FILLS = {}


def fill_model(struct, seed):
    """The program's model pytree (as `jax.eval_shape` gives it) with every
    leaf made on the device, in the type it is served in, in one jitted
    call (traced once per model shape, whatever the seed). Returns
    (model, {(layer, name): shape})."""
    paths = jax.tree_util.tree_flatten_with_path(struct)[0]
    ids = {jax.tree_util.keystr(p): leaf_id(jax.tree_util.keystr(p))
           for p, _ in paths}
    shapes = {ids[jax.tree_util.keystr(p)]: s.shape for p, s in paths}
    key = tuple((k, s.shape, str(s.dtype)) for (k, _), (_, s) in
                zip(ids.items(), paths))
    if key not in _FILLS:
        _FILLS[key] = jax.jit(lambda base: jax.tree_util.tree_map_with_path(
            lambda p, s: make_leaf(base, *ids[jax.tree_util.keystr(p)],
                                   s.shape, s.dtype), struct))
    return _FILLS[key](base_key(seed)), shapes
