"""A second family, which lives in the tests alone: the `llama` family's
decoder described layer by layer. `cfg['layer_types'][layer]` names each
layer's kind; leaves, reference and counts all go through `KINDS` by the
layer's index. The program attends every earlier position in every layer,
so a table of `full` alone is sound, and one with `self` in it (a window
of one: a query attends its own position only) describes a model the
program does not run: `correct` has to come out false."""
import os
import sys

import numpy as np

from benchmark.harness import common, weights

_llama = common.load_module(os.path.join(common.ROOT, 'benchmark',
                                         'families', 'llama.py'))

# kind -> (keys a query may attend, None for all; whose leaves they are)
KINDS = {'full': (None, _llama), 'self': (1, _llama)}


def kind(cfg, layer):
    return KINDS[cfg['layer_types'][layer]]


def make_model(cfg, seed, max_positions):
    if len(cfg['layer_types']) != cfg['num_hidden_layers']:
        raise SystemExit('benchmark: layer_types names not every layer')
    return weights.fill_model(sys.modules[__name__], cfg,
                              _llama.struct(cfg, max_positions), seed)


leaf_id, global_shapes, init = (_llama.leaf_id, _llama.global_shapes,
                                _llama.init)
head_params = _llama.head_params


def layer_shapes(cfg, layer):
    return kind(cfg, layer)[1].layer_shapes(cfg, layer)


def layer_like(cfg, layer):
    return cfg['layer_types'].index(cfg['layer_types'][layer])


def matmul_params(cfg, layer):
    return kind(cfg, layer)[1].matmul_params(cfg, layer)


def attn_keys(cfg, layer, context):
    window = kind(cfg, layer)[0]
    return context if window is None else np.minimum(context, window)


def attn_flops_key(cfg, layer):
    return kind(cfg, layer)[1].attn_flops_key(cfg, layer)


def cache_bytes_token(cfg, layer):
    return kind(cfg, layer)[1].cache_bytes_token(cfg, layer)


def query_bytes_token(cfg, layer):
    return kind(cfg, layer)[1].query_bytes_token(cfg, layer)


def needed_full_attn(ctx):
    """A count that arrives with the family, as a new kernel's would:
    (flops, bytes) the decode attention of the `full` layers needs for the
    delivered tokens."""
    cfg, flops, nbytes = ctx['cfg'], 0, 0
    full = [l for l, k in enumerate(cfg['layer_types']) if k == 'full']
    for prompt_len, first, n in ctx['deliveries']:
        for j in range(max(first, 1), first + n):
            for layer in full:
                keys = attn_keys(cfg, layer, prompt_len + j)
                flops += keys * attn_flops_key(cfg, layer)
                nbytes += (keys * cache_bytes_token(cfg, layer)
                           + query_bytes_token(cfg, layer))
    return flops, nbytes
