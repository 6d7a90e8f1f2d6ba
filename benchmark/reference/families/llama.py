"""The `llama` family's decoder in plain `jax.numpy` float32.

Mistral-7B-v0.3 and Qwen2.5-3B as their model cards and `config.json`
describe them: token embedding; per layer RMSNorm, grouped-query attention
with rotate-half RoPE (biases on q, k, v where `attention_bias`), residual,
RMSNorm, SwiGLU, residual; final RMSNorm; a head that is its own matrix or
the embedding transposed. Every layer is the same, so `layer` is not read.
No cache, no pages, no kernels, no batching tricks.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import HIGHEST, linear, rms_norm, rope


def layer_forward(cfg, lp, x, layer, quant=None):
    """x (B, S, hidden) float32 -> the same, through one decoder layer."""
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
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    att = jnp.einsum('bngst,btnd->bsngd', probs, v, precision=HIGHEST)
    x = x + linear(att.reshape(b, s, nq * d), lp['self_attn.o_proj'], quant)
    h = rms_norm(x, lp['post_attention_layernorm.weight'],
                 cfg['rms_norm_eps'])
    gate = linear(h, lp['mlp.gate_proj'], quant)
    up = linear(h, lp['mlp.up_proj'], quant)
    return x + linear(jax.nn.silu(gate) * up, lp['mlp.down_proj'], quant)


def embed(gp, ids):
    return gp['embed_tokens'].astype(jnp.float32)[ids]


def logits(cfg, gp, x, quant=None):
    h = rms_norm(x, gp['norm.weight'], cfg['rms_norm_eps'])
    w = (gp['embed_tokens'].T if cfg['tie_word_embeddings']
         else gp['lm_head'])
    return linear(h, w, quant)


def faults(cfg):
    return ('half_batch', 'frozen') + (
        ('no_bias_grad',) if cfg['attention_bias'] else ())
