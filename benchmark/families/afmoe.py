"""The `afmoe` family: Arcee's Trinity decoders (`model_type: afmoe`), which
models/afmoe.py's `AfmoeForCausalLM` runs. Three kinds of layer: a dense
SwiGLU under windowed attention (the leading `num_dense_layers`), sparse
experts under windowed attention, sparse experts under full attention
(`layer_types`). Every layer has gated grouped-query attention with
per-head q/k RMSNorm and four RMSNorms; a window layer rotates q and k, a
full layer does not. The contract is `benchmark/families`'s docstring.

The chip's share. `num_experts` counts the experts HELD here, from
`expert_offset` on; the router's width is `published.num_experts`, read
from there by the builder, the counts and the reference alike.
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

if importlib.util.find_spec('paddle_tpu.models.afmoe') is None:
    raise SystemExit('benchmark: this program has no models/afmoe.py: it '
                     'cannot run a configuration of the afmoe family')

SLIDING = 'sliding_attention'


def router_width(cfg):
    return cfg['published']['num_experts']


def is_dense(cfg, layer):
    return layer < cfg['num_dense_layers']


def is_sliding(cfg, layer):
    return cfg['layer_types'][layer] == SLIDING


def struct(cfg, max_positions):
    """The program's model at the configuration's sizes, as shapes."""
    from paddle_tpu.models.afmoe import AfmoeConfig, AfmoeForCausalLM

    if cfg['hidden_act'] != 'silu' or cfg['score_func'] != 'sigmoid' \
            or cfg['n_group'] != 1 or cfg['rope_scaling'] is not None \
            or cfg['tie_word_embeddings'] or not cfg['mup_enabled']:
        raise SystemExit('benchmark: the afmoe family runs silu, sigmoid '
                         'routing in one group, plain RoPE, the muP '
                         'embedding scale and an untied head only')
    ac = AfmoeConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        intermediate_size=cfg['intermediate_size'],
        moe_intermediate_size=cfg['moe_intermediate_size'],
        num_hidden_layers=cfg['num_hidden_layers'],
        num_dense_layers=cfg['num_dense_layers'],
        num_attention_heads=cfg['num_attention_heads'],
        num_key_value_heads=cfg['num_key_value_heads'],
        head_dim=cfg['head_dim'], layer_types=cfg['layer_types'],
        sliding_window=cfg['sliding_window'], rope_theta=cfg['rope_theta'],
        rms_norm_eps=cfg['rms_norm_eps'], num_experts=router_width(cfg),
        num_experts_per_tok=cfg['num_experts_per_tok'],
        num_shared_experts=cfg['num_shared_experts'],
        route_norm=cfg['route_norm'], route_scale=cfg['route_scale'],
        experts_held=cfg['num_experts'], expert_offset=cfg['expert_offset'],
        mup_enabled=cfg['mup_enabled'], max_position_embeddings=max_positions,
        dtype=cfg['torch_dtype'])
    return jax.eval_shape(lambda: AfmoeForCausalLM(ac))


def make_model(cfg, seed, max_positions):
    return weights.fill_model(sys.modules[__name__], cfg,
                              struct(cfg, max_positions), seed)


_PATH = re.compile(r'(?:layers\.L?(\d+)\.)?([A-Za-z_\.]+)$')


def leaf_id(path):
    m = _PATH.match(path.lstrip('.'))
    if m is None:
        raise ValueError(f'benchmark: cannot name the model leaf {path!r}')
    return (-1 if m.group(1) is None else int(m.group(1))), m.group(2)


def layer_shapes(cfg, layer):
    h, d = cfg['hidden_size'], cfg['head_dim']
    q, kv = cfg['num_attention_heads'] * d, cfg['num_key_value_heads'] * d
    dt, f32 = jnp.dtype(cfg['torch_dtype']), jnp.float32
    shapes = {f'{n}.weight': ((h,), f32) for n in (
        'input_layernorm', 'post_attention_layernorm', 'pre_mlp_layernorm',
        'post_mlp_layernorm')}
    shapes.update({
        'self_attn.q_proj': ((h, q), dt), 'self_attn.k_proj': ((h, kv), dt),
        'self_attn.v_proj': ((h, kv), dt), 'self_attn.gate_proj': ((h, q), dt),
        'self_attn.o_proj': ((q, h), dt), 'self_attn.q_norm': ((d,), f32),
        'self_attn.k_norm': ((d,), f32)})
    if is_dense(cfg, layer):
        f = cfg['intermediate_size']
        shapes.update({'mlp.gate_proj': ((h, f), dt),
                       'mlp.up_proj': ((h, f), dt),
                       'mlp.down_proj': ((f, h), dt)})
    else:
        m, e = cfg['moe_intermediate_size'], cfg['num_experts']
        s = m * cfg['num_shared_experts']
        shapes.update({
            'mlp.router': ((h, router_width(cfg)), f32),
            'mlp.expert_bias': ((router_width(cfg),), f32),
            'mlp.w_gate': ((e, h, m), dt), 'mlp.w_up': ((e, h, m), dt),
            'mlp.w_down': ((e, m, h), dt), 'mlp.shared_gate': ((h, s), dt),
            'mlp.shared_up': ((h, s), dt), 'mlp.shared_down': ((s, h), dt)})
    return shapes


def global_shapes(cfg):
    h, v = cfg['hidden_size'], cfg['vocab_size']
    dt = jnp.dtype(cfg['torch_dtype'])
    return {'embed_tokens': ((v, h), dt), 'norm.weight': ((h,), jnp.float32),
            'lm_head': ((h, v), dt)}


def init(name, noise):
    """Gains near 1 (the "depth-scaled" of the model card is a gain's
    initial value: with seeded weights it is no equation); the routing
    bias small but not zero, so that choosing and weighing differ."""
    if name.endswith(('norm.weight', 'q_norm', 'k_norm')):
        return 1.0 + 0.05 * noise
    return 0.02 * noise


def layer_like(cfg, layer):
    kind = (is_dense(cfg, layer), cfg['layer_types'][layer])
    return next(l for l in range(cfg['num_hidden_layers'])
                if (is_dense(cfg, l), cfg['layer_types'][l]) == kind)


def expert_params(cfg):
    return 3 * cfg['hidden_size'] * cfg['moe_intermediate_size']


def matmul_params(cfg, layer):
    """Attention with its gate, then the dense SwiGLU or, in an expert
    layer, the router, the shared expert and the chip's share of the
    experts a token goes through: `num_experts_per_tok` x held / width."""
    h, d = cfg['hidden_size'], cfg['head_dim']
    q, kv = cfg['num_attention_heads'] * d, cfg['num_key_value_heads'] * d
    attn = 3 * h * q + 2 * h * kv
    if is_dense(cfg, layer):
        return attn + 3 * h * cfg['intermediate_size']
    routed = (cfg['num_experts_per_tok'] * cfg['num_experts']
              * expert_params(cfg)) // router_width(cfg)
    return (attn + h * router_width(cfg)
            + cfg['num_shared_experts'] * expert_params(cfg) + routed)


def head_params(cfg):
    return cfg['hidden_size'] * cfg['vocab_size']


def attn_keys(cfg, layer, context):
    return (np.minimum(context, cfg['sliding_window'])
            if is_sliding(cfg, layer) else context)


def attn_flops_key(cfg, layer):
    return 4 * cfg['num_attention_heads'] * cfg['head_dim']


def cache_bytes_token(cfg, layer):
    """A K and a V row of every kv head, in the pages' type (bfloat16)."""
    return 2 * cfg['num_key_value_heads'] * cfg['head_dim'] * 2


def query_bytes_token(cfg, layer):
    return 2 * cfg['num_attention_heads'] * cfg['head_dim'] * 2


def needed_expert_matmuls(ctx):
    """(flops, bytes) the routed experts' grouped products of the traced
    decode windows need, from the program's own routing counts
    (`serve.routing`, the ring): the three matrices of every held expert
    that was HIT read once a layer and token-step, and the local picks'
    products. The shared expert and the router are not grouped products
    and are not counted. The same work whatever computes it; (0, 0) where
    the program counts no routing."""
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
