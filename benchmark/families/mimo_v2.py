"""The `mimo_v2` family: Xiaomi's MiMo-V2 decoders (`model_type: mimo_v2`),
which models/mimo_v2.py's `MimoV2ForCausalLM` runs. Three kinds of layer: a
dense SwiGLU under full attention (the leading layer), sparse experts under
windowed attention with a sink, sparse experts under full attention
(`hybrid_layer_pattern`, `moe_layer_freq`). The two kinds of attention
have their own kv heads and thetas; a K row is `head_dim` wide and a V row
`v_head_dim`. The contract is `benchmark/families`'s docstring.

The chip's share. `n_routed_experts` counts the experts HELD here, from
`expert_offset` on; the router's width is `published.n_routed_experts`,
read from there by the builder, the counts and the reference alike.
`vocab_size` is the slice of the vocabulary held here.
"""
from __future__ import annotations

import importlib.util
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights

if importlib.util.find_spec('paddle_tpu.models.mimo_v2') is None:
    raise SystemExit('benchmark: this program has no models/mimo_v2.py: it '
                     'cannot run a configuration of the mimo_v2 family')


def router_width(cfg):
    return cfg['published']['n_routed_experts']


def is_dense(cfg, layer):
    return cfg['moe_layer_freq'][layer] == 0


def is_window(cfg, layer):
    return cfg['hybrid_layer_pattern'][layer] == 1


def heads(cfg, layer):
    """(query heads, kv heads, q/k width, v width) of the layer's kind."""
    pre = 'swa_' if is_window(cfg, layer) else ''
    return tuple(cfg[pre + k] for k in (
        'num_attention_heads', 'num_key_value_heads', 'head_dim',
        'v_head_dim'))


def struct(cfg, max_positions):
    """The program's model at the configuration's sizes, as shapes."""
    from paddle_tpu.models.mimo_v2 import MimoV2Config, MimoV2ForCausalLM

    if cfg['hidden_act'] != 'silu' or cfg['scoring_func'] != 'sigmoid' \
            or cfg['n_group'] != 1 or cfg['topk_method'] != 'noaux_tc' \
            or cfg['n_shared_experts'] or cfg['attention_bias'] \
            or cfg['tie_word_embeddings'] \
            or cfg['rope_scaling']['rope_type'] != 'default':
        raise SystemExit('benchmark: the mimo_v2 family runs silu, sigmoid '
                         'routing in one group with a selection-only bias, '
                         'no shared expert, no qkv bias, plain RoPE and an '
                         'untied head only')
    mc = MimoV2Config(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        intermediate_size=cfg['intermediate_size'],
        moe_intermediate_size=cfg['moe_intermediate_size'],
        num_hidden_layers=cfg['num_hidden_layers'],
        hybrid_layer_pattern=cfg['hybrid_layer_pattern'],
        moe_layer_freq=cfg['moe_layer_freq'],
        sliding_window=cfg['sliding_window'],
        partial_rotary_factor=cfg['partial_rotary_factor'],
        attention_value_scale=cfg['attention_value_scale'],
        layernorm_epsilon=cfg['layernorm_epsilon'],
        n_routed_experts=router_width(cfg),
        num_experts_per_tok=cfg['num_experts_per_tok'],
        norm_topk_prob=cfg['norm_topk_prob'],
        routed_scaling_factor=cfg['routed_scaling_factor'],
        experts_held=cfg['n_routed_experts'],
        expert_offset=cfg['expert_offset'],
        max_position_embeddings=max_positions, dtype=cfg['torch_dtype'],
        **{pre + k: cfg[pre + k] for pre in ('', 'swa_') for k in (
            'num_attention_heads', 'num_key_value_heads', 'head_dim',
            'v_head_dim', 'rope_theta')},
        **{f'add_{kind}_attention_sink_bias':
           cfg[f'add_{kind}_attention_sink_bias']
           for kind in ('full', 'swa')})
    return jax.eval_shape(lambda: MimoV2ForCausalLM(mc))


def make_model(cfg, seed, max_positions):
    return weights.fill_model(sys.modules[__name__], cfg,
                              struct(cfg, max_positions), seed)


_PATH = re.compile(r'(?:layers\.L?(\d+)\.)?([A-Za-z_\.]+)$')


def leaf_id(path):
    m = _PATH.match(path.lstrip('.'))
    if m is None:
        raise ValueError(f'benchmark: cannot name the model leaf {path!r}')
    return (-1 if m.group(1) is None else int(m.group(1))), m.group(2)


def has_sink(cfg, layer):
    return cfg['add_swa_attention_sink_bias' if is_window(cfg, layer)
               else 'add_full_attention_sink_bias']


def layer_shapes(cfg, layer):
    h = cfg['hidden_size']
    nq, nkv, d, dv = heads(cfg, layer)
    dt, f32 = jnp.dtype(cfg['torch_dtype']), jnp.float32
    shapes = {f'{n}.weight': ((h,), f32) for n in (
        'input_layernorm', 'post_attention_layernorm')}
    shapes.update({
        'self_attn.q_proj': ((h, nq * d), dt),
        'self_attn.k_proj': ((h, nkv * d), dt),
        'self_attn.v_proj': ((h, nkv * dv), dt),
        'self_attn.o_proj': ((nq * dv, h), dt)})
    if has_sink(cfg, layer):
        shapes['self_attn.attention_sink_bias'] = ((nq,), f32)
    if is_dense(cfg, layer):
        f = cfg['intermediate_size']
        shapes.update({'mlp.gate_proj': ((h, f), dt),
                       'mlp.up_proj': ((h, f), dt),
                       'mlp.down_proj': ((f, h), dt)})
    else:
        m, e = cfg['moe_intermediate_size'], cfg['n_routed_experts']
        shapes.update({
            'mlp.router': ((h, router_width(cfg)), f32),
            'mlp.expert_bias': ((router_width(cfg),), f32),
            'mlp.w_gate': ((e, h, m), dt), 'mlp.w_up': ((e, h, m), dt),
            'mlp.w_down': ((e, m, h), dt)})
    return shapes


def global_shapes(cfg):
    h, v = cfg['hidden_size'], cfg['vocab_size']
    dt = jnp.dtype(cfg['torch_dtype'])
    return {'embed_tokens': ((v, h), dt), 'norm.weight': ((h,), jnp.float32),
            'lm_head': ((h, v), dt)}


SINK_MEAN = 5.0


def init(name, noise):
    """Gains near 1; the routing bias small but not zero, so that choosing
    and weighing differ; the sink well above a score's usual size, so that
    it holds about half of a window layer's mass (`assumed.sink`: at a mean
    of 3 it held ~15 % and a reference without it read 0.29-0.35, under
    the cell's limit: chip, PR 33)."""
    if name.endswith('norm.weight'):
        return 1.0 + 0.05 * noise
    if name.endswith('attention_sink_bias'):
        return SINK_MEAN + noise
    return 0.02 * noise


def _kind(cfg, layer):
    return is_dense(cfg, layer), is_window(cfg, layer)


def layer_like(cfg, layer):
    return next(l for l in range(cfg['num_hidden_layers'])
                if _kind(cfg, l) == _kind(cfg, layer))


def expert_params(cfg):
    return 3 * cfg['hidden_size'] * cfg['moe_intermediate_size']


def matmul_params(cfg, layer):
    """Attention of the layer's kind, then the dense SwiGLU or, in an
    expert layer, the router and the chip's share of the experts a token
    goes through: `num_experts_per_tok` x held / width."""
    h = cfg['hidden_size']
    nq, nkv, d, dv = heads(cfg, layer)
    attn = h * nq * d + h * nkv * (d + dv) + nq * dv * h
    if is_dense(cfg, layer):
        return attn + 3 * h * cfg['intermediate_size']
    routed = (cfg['num_experts_per_tok'] * cfg['n_routed_experts']
              * expert_params(cfg)) // router_width(cfg)
    return attn + h * router_width(cfg) + routed


def head_params(cfg):
    return cfg['hidden_size'] * cfg['vocab_size']


def attn_keys(cfg, layer, context):
    return (np.minimum(context, cfg['sliding_window'])
            if is_window(cfg, layer) else context)


def attn_flops_key(cfg, layer):
    nq, _, d, dv = heads(cfg, layer)
    return 2 * nq * (d + dv)


def cache_bytes_token(cfg, layer):
    """A K row and a V row of every kv head of the layer's kind, at their
    own widths, in the pages' type (bfloat16)."""
    _, nkv, d, dv = heads(cfg, layer)
    return nkv * (d + dv) * 2


def query_bytes_token(cfg, layer):
    """q read at a K row's width, the output written at a V row's."""
    nq, _, d, dv = heads(cfg, layer)
    return nq * (d + dv) * 2


def needed_expert_matmuls(ctx):
    """(flops, bytes) the routed experts' grouped products of the traced
    decode windows need, from the program's own routing counts
    (`serve.routing`, the ring): the three matrices of every held expert
    that was HIT read once a layer and token-step, and the local picks'
    products. The router is no grouped product and is not counted. The
    same work whatever computes it; (0, 0) where the program counts no
    routing."""
    try:
        from paddle_tpu.observability.tracing import TRACER

        rows = [e['args'] for e in TRACER.traced()
                if e['name'] == 'serve.routing']
    except (ImportError, AttributeError):
        return 0, 0
    one = expert_params(ctx['cfg'])
    hit = sum(a['experts_hit'] for a in rows)
    return (2 * one * sum(a['picks_local'] for a in rows),
            2 * one * hit)          # bfloat16 weights
