"""The `layered` family's plain reference: the `llama` family's decoder
with each layer's kind read from `cfg['layer_types'][layer]`: in a `full`
layer a query attends every position up to its own, in a `self` layer its
own alone."""
import os

import jax
import jax.numpy as jnp

from benchmark.harness import common
from benchmark.reference.decoder import HIGHEST, linear, rms_norm, rope

_llama = common.load_module(os.path.join(common.ROOT, 'benchmark',
                                         'reference', 'families', 'llama.py'))
embed, logits, faults = _llama.embed, _llama.logits, _llama.faults

WINDOW = {'full': None, 'self': 1}


def layer_forward(cfg, lp, x, layer, quant=None):
    window = WINDOW[cfg['layer_types'][layer]]
    b, s, _ = x.shape
    nq, nkv, d = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                  cfg['head_dim'])
    h = rms_norm(x, lp['input_layernorm.weight'], cfg['rms_norm_eps'])
    q = linear(h, lp['self_attn.q_proj'], quant, lp.get('self_attn.q_bias'))
    k = linear(h, lp['self_attn.k_proj'], quant, lp.get('self_attn.k_bias'))
    v = linear(h, lp['self_attn.v_proj'], quant, lp.get('self_attn.v_bias'))
    q = rope(q.reshape(b, s, nq, d), cfg['rope_theta'])
    k = rope(k.reshape(b, s, nkv, d), cfg['rope_theta'])
    v = v.reshape(b, s, nkv, d)
    q = q.reshape(b, s, nkv, nq // nkv, d)
    scores = jnp.einsum('bsngd,btnd->bngst', q, k,
                        precision=HIGHEST) / (d ** 0.5)
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = (ahead >= 0) if window is None else (ahead >= 0) & (ahead < window)
    probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
    att = jnp.einsum('bngst,btnd->bsngd', probs, v, precision=HIGHEST)
    x = x + linear(att.reshape(b, s, nq * d), lp['self_attn.o_proj'], quant)
    h = rms_norm(x, lp['post_attention_layernorm.weight'],
                 cfg['rms_norm_eps'])
    gate = linear(h, lp['mlp.gate_proj'], quant)
    up = linear(h, lp['mlp.up_proj'], quant)
    return x + linear(jax.nn.silu(gate) * up, lp['mlp.down_proj'], quant)
