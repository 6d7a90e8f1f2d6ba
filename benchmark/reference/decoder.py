"""The decoder both configurations share, in plain `jax.numpy` float32.

Mistral-7B-v0.3 and Qwen2.5-3B as their model cards and `config.json`
describe them: token embedding; per layer RMSNorm, grouped-query attention
with rotate-half RoPE (biases on q, k, v where `attention_bias`), residual,
RMSNorm, SwiGLU, residual; final RMSNorm; a head that is its own matrix or
the embedding transposed. No cache, no pages, no kernels, no batching
tricks. `quant` switches on the control of "How correct is decided": every
linear layer's inputs and weights are rounded to int8 or to fp8 (e4m3),
scaled per token and per output channel, before the product: the step
below bfloat16 that would tempt a later PR.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def _round_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    return jnp.round(x / scale) * scale


def _round_fp8(x, axis):
    """e4m3, the row or column scaled so that its largest entry is the
    format's largest finite number, 448."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


ROUNDINGS = {'int8': _round_int8, 'fp8': _round_fp8}


def _rounded(x, axis, quant):
    """x as the control precision holds it; gradients pass straight
    through the rounding."""
    safe = jnp.where(jnp.max(jnp.abs(x), axis=axis, keepdims=True) == 0,
                     1.0, x)
    return x + jax.lax.stop_gradient(ROUNDINGS[quant](safe, axis) - safe)


def linear(x, w, quant=None, bias=None):
    w = w.astype(jnp.float32)
    if quant is not None:
        x, w = _rounded(x, -1, quant), _rounded(w, 0, quant)
    y = jnp.matmul(x, w, precision=HIGHEST)
    return y if bias is None else y + bias.astype(jnp.float32)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x (B, S, N, D), positions 0..S-1, rotate-half form."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer_forward(cfg, lp, x, quant=None):
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


def frozen(cfg):
    """The configuration as a hashable static argument."""
    return tuple(sorted((k, v) for k, v in cfg.items()
                        if isinstance(v, (int, float, bool, str, type(None)))))


@functools.partial(jax.jit, static_argnames=('cfg_items', 'quant'))
def layer_step(lp, x, *, cfg_items, quant=None):
    return layer_forward(dict(cfg_items), lp, x, quant)
