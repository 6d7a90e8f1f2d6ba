"""The `afmoe` family's decoder in plain `jax.numpy` float32, as the
configuration's source describes it (huggingface.co/arcee-ai/
Trinity-Large-Preview, `config.json`): the embedding times sqrt(hidden)
(`mup_enabled`); per layer RMSNorm, gated grouped-query attention with
per-head q/k RMSNorm, rotate-half RoPE and a window of `sliding_window` on
`sliding_attention` layers and neither on `full_attention` layers, RMSNorm
of the result, residual; RMSNorm, a dense SwiGLU (the first
`num_dense_layers`) or sigmoid top-k routed experts with a shared expert,
RMSNorm of the result, residual; final RMSNorm and an untied head. No
cache, no pages, no kernels, no sorting: every held expert runs on every
token and is weighed by the router's weight, which is zero unless chosen.

The chip's share, as the program has it: the router chooses and
normalises over all `published.num_experts`; the experts held are
`num_experts` from `expert_offset` on; what the others would add is left
out. The router is not rounded by the control (a lower-precision
deployment keeps its router whole: `ExpertShare.no_quantize`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import HIGHEST, linear, rms_norm, rope

SLIDING = 'sliding_attention'


def attention(cfg, lp, h, layer, quant):
    b, s, _ = h.shape
    nq, nkv, d = (cfg['num_attention_heads'], cfg['num_key_value_heads'],
                  cfg['head_dim'])
    eps = cfg['rms_norm_eps']
    q = linear(h, lp['self_attn.q_proj'], quant).reshape(b, s, nq, d)
    k = linear(h, lp['self_attn.k_proj'], quant).reshape(b, s, nkv, d)
    v = linear(h, lp['self_attn.v_proj'], quant).reshape(b, s, nkv, d)
    gate = linear(h, lp['self_attn.gate_proj'], quant)
    q = rms_norm(q, lp['self_attn.q_norm'], eps)
    k = rms_norm(k, lp['self_attn.k_norm'], eps)
    ahead = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    seen = ahead >= 0
    if cfg['layer_types'][layer] == SLIDING:
        q, k = rope(q, cfg['rope_theta']), rope(k, cfg['rope_theta'])
        if cfg['sliding_window'] is not None:
            seen = seen & (ahead < cfg['sliding_window'])
    q = q.reshape(b, s, nkv, nq // nkv, d)

    def one_kv_head(qkv):
        """A kv head's group of queries: (b, s, g, d), (b, s, d) twice. One
        head's scores at a time, so that a long context fits."""
        q_h, k_h, v_h = qkv
        scores = jnp.einsum('bsgd,btd->bgst', q_h, k_h,
                            precision=HIGHEST) / (d ** 0.5)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum('bgst,btd->bsgd', probs, v_h, precision=HIGHEST)

    att = jax.lax.map(one_kv_head, (jnp.moveaxis(q, 2, 0),
                                    jnp.moveaxis(k, 2, 0),
                                    jnp.moveaxis(v, 2, 0)))
    att = jnp.moveaxis(att, 0, 2)                   # (b, s, nkv, g, d)
    att = att.reshape(b, s, nq * d) * jax.nn.sigmoid(gate)
    return linear(att, lp['self_attn.o_proj'], quant)


def swiglu(m, gate, up, down, quant):
    return linear(jax.nn.silu(linear(m, gate, quant)) * linear(m, up, quant),
                  down, quant)


def route(cfg, lp, m):
    """(T.., width) weights: 0 but for the top-k of s + b, where s over
    their sum times `route_scale`."""
    s = jax.nn.sigmoid(jnp.matmul(m, lp['mlp.router'], precision=HIGHEST))
    _, chosen = jax.lax.top_k(s + lp['mlp.expert_bias'],
                              cfg['num_experts_per_tok'])
    picked = jax.nn.one_hot(chosen, s.shape[-1], dtype=s.dtype).sum(-2)
    w = s * picked
    if cfg['route_norm']:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * cfg['route_scale']


def experts(cfg, lp, m, quant):
    """shared(m) + the held experts' weighed parts."""
    w = route(cfg, lp, m)
    first = cfg['expert_offset']
    w = w[..., first:first + cfg['num_experts']]

    def one(total, xs):
        gate, up, down, w_e = xs
        return total + w_e[..., None] * swiglu(m, gate, up, down, quant), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (lp['mlp.w_gate'], lp['mlp.w_up'], lp['mlp.w_down'],
         jnp.moveaxis(w, -1, 0)))
    return routed + swiglu(m, lp['mlp.shared_gate'], lp['mlp.shared_up'],
                           lp['mlp.shared_down'], quant)


def layer_forward(cfg, lp, x, layer, quant=None):
    """x (B, S, hidden) float32 -> the same, through one decoder layer."""
    eps = cfg['rms_norm_eps']
    a = attention(cfg, lp, rms_norm(x, lp['input_layernorm.weight'], eps),
                  layer, quant)
    x = x + rms_norm(a, lp['post_attention_layernorm.weight'], eps)
    m = rms_norm(x, lp['pre_mlp_layernorm.weight'], eps)
    if layer < cfg['num_dense_layers']:
        f = swiglu(m, lp['mlp.gate_proj'], lp['mlp.up_proj'],
                   lp['mlp.down_proj'], quant)
    else:
        f = experts(cfg, lp, m, quant)
    return x + rms_norm(f, lp['post_mlp_layernorm.weight'], eps)


def embed(gp, ids):
    table = gp['embed_tokens'].astype(jnp.float32)
    return table[ids] * (table.shape[-1] ** 0.5)


def logits(cfg, gp, x, quant=None):
    return linear(rms_norm(x, gp['norm.weight'], cfg['rms_norm_eps']),
                  gp['lm_head'], quant)


def faults(cfg):
    return ('half_batch', 'frozen')
