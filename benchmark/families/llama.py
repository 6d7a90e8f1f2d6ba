"""The `llama` family: pre-norm decoders that models/llama.py's
`LlamaForCausalLM` runs (RMSNorm, grouped-query attention with rotate-half
RoPE, optional q/k/v biases, SwiGLU, a head of its own or the embedding's).
Every layer is the same; the contract is `benchmark/families`'s docstring.
"""
from __future__ import annotations

import re
import sys

import jax
import jax.numpy as jnp

from benchmark.harness import weights


def struct(cfg, max_positions):
    """The program's model at the configuration's sizes, as shapes."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    if cfg['hidden_act'] != 'silu' or cfg['sliding_window'] is not None:
        raise SystemExit('benchmark: models/llama.py runs silu and full '
                         'attention only')
    if cfg['head_dim'] * cfg['num_attention_heads'] != cfg['hidden_size']:
        raise SystemExit('benchmark: LlamaConfig derives head_dim from '
                         'hidden_size / heads')
    lc = LlamaConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        intermediate_size=cfg['intermediate_size'],
        num_hidden_layers=cfg['num_hidden_layers'],
        num_attention_heads=cfg['num_attention_heads'],
        num_key_value_heads=cfg['num_key_value_heads'],
        max_position_embeddings=max_positions,
        rms_norm_eps=cfg['rms_norm_eps'], rope_theta=cfg['rope_theta'],
        tie_word_embeddings=cfg['tie_word_embeddings'],
        attention_bias=cfg['attention_bias'], dtype=cfg['torch_dtype'])
    return jax.eval_shape(lambda: LlamaForCausalLM(lc))


def make_model(cfg, seed, max_positions):
    return weights.fill_model(sys.modules[__name__], cfg,
                              struct(cfg, max_positions), seed)


_PATH = re.compile(r'(?:layers\.L?(\d+)\.)?([A-Za-z_\.]+)$')


def leaf_id(path):
    tail = path.lstrip('.')
    tail = tail[len('model.'):] if tail.startswith('model.') else tail
    m = _PATH.match(tail)
    if m is None:
        raise ValueError(f'benchmark: cannot name the model leaf {path!r}')
    return (-1 if m.group(1) is None else int(m.group(1))), m.group(2)


def layer_shapes(cfg, layer):
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


def init(name, noise):
    if name.endswith('norm.weight'):
        return 1.0 + 0.05 * noise
    return 0.02 * noise


def layer_like(cfg, layer):
    return 0


def matmul_params(cfg, layer):
    h, f, d = cfg['hidden_size'], cfg['intermediate_size'], cfg['head_dim']
    q, kv = cfg['num_attention_heads'] * d, cfg['num_key_value_heads'] * d
    return h * q + 2 * h * kv + q * h + 3 * h * f


def head_params(cfg):
    return cfg['hidden_size'] * cfg['vocab_size']


def attn_keys(cfg, layer, context):
    return context


def attn_flops_key(cfg, layer):
    return 4 * cfg['num_attention_heads'] * cfg['head_dim']


def cache_bytes_token(cfg, layer):
    """A K and a V row of every kv head, in the pages' type (bfloat16)."""
    return 2 * cfg['num_key_value_heads'] * cfg['head_dim'] * 2


def query_bytes_token(cfg, layer):
    return 2 * cfg['num_attention_heads'] * cfg['head_dim'] * 2
