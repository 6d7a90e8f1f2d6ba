"""The `mimo_v2` family's decoder in plain `jax.numpy` float32, as the
configuration's source describes it (huggingface.co/XiaomiMiMo/MiMo-V2.5,
`config.json`): the language model alone. Per layer RMSNorm, grouped-query
attention, residual; RMSNorm, a dense SwiGLU (`moe_layer_freq` 0) or
sigmoid top-k routed experts with no shared expert, residual; final RMSNorm
and an untied head. Attention by kind (`hybrid_layer_pattern`): a full
layer (0) has `num_key_value_heads` kv heads, `rope_theta` and a plain
causal softmax; a window layer (1) has `swa_num_key_value_heads`,
`swa_rope_theta`, attends its last `sliding_window` positions, and its
softmax has one more column, a learned logit a query head (the sink), which
is dropped before the values are weighed. q and k are `head_dim` wide and v
`v_head_dim`; v is scaled by `attention_value_scale`; the rotary embedding
turns the leading `partial_rotary_factor` of a head (the even floor) and the
rest passes. No cache, no pages, no kernels, no sorting: every held expert
runs on every token and is weighed by the router's weight, which is zero
unless chosen; attention runs a kv head and a block of at most 512 queries
at a time, so that a long context fits.

Departures from the source, each also in the configuration's `assumed`: the
vision and audio towers and the multi-token-prediction layers are left out
(the catalog gives no key of either); rotary on the LEADING dims in
rotate-half form; the value scale on both kinds of layer;
`attention_chunk_size` read as the window, no chunked mask; no q/k norm.

The chip's share, as the program has it: the router chooses and normalises
over all `published.n_routed_experts`; the experts held are
`n_routed_experts` from `expert_offset` on; what the others would add is
left out. The router is not rounded by the control.

`cfg['fault']` plants one of SERVE_FAULTS, what a program that lacks one
part of the model would compute; the readings that set a serving cell's
limit hold each to it (`benchmark/tests/chip_faults.py`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference.decoder import HIGHEST, linear, rms_norm, rope

SERVE_FAULTS = ('no_sink', 'no_window', 'full_theta_on_window',
                'no_value_scale', 'rotary_all_dims', 'choice_by_s')
QUERY_BLOCK = 512


def is_window(cfg, layer):
    return cfg['hybrid_layer_pattern'][layer] == 1


def geometry(cfg, layer):
    """(query heads, kv heads, q/k width, v width, theta) of the layer."""
    pre = 'swa_' if is_window(cfg, layer) else ''
    return tuple(cfg[pre + k] for k in (
        'num_attention_heads', 'num_key_value_heads', 'head_dim',
        'v_head_dim', 'rope_theta'))


def rotary_dim(cfg, d):
    return int(d * cfg['partial_rotary_factor']) // 2 * 2


def attention(cfg, lp, h, layer, quant):
    b, s, _ = h.shape
    fault = cfg.get('fault')
    nq, nkv, d, dv, theta = geometry(cfg, layer)
    window = is_window(cfg, layer)
    if window and fault == 'full_theta_on_window':
        theta = cfg['rope_theta']
    q = linear(h, lp['self_attn.q_proj'], quant).reshape(b, s, nq, d)
    k = linear(h, lp['self_attn.k_proj'], quant).reshape(b, s, nkv, d)
    v = linear(h, lp['self_attn.v_proj'], quant).reshape(b, s, nkv, dv)
    if fault != 'no_value_scale':
        v = v * cfg['attention_value_scale']
    r = d if fault == 'rotary_all_dims' else rotary_dim(cfg, d)
    q = jnp.concatenate([rope(q[..., :r], theta), q[..., r:]], -1)
    k = jnp.concatenate([rope(k[..., :r], theta), k[..., r:]], -1)
    sink = None
    if window and cfg['add_swa_attention_sink_bias'] and fault != 'no_sink':
        sink = lp['self_attn.attention_sink_bias'].reshape(nkv, nq // nkv)
    span = cfg['sliding_window'] if window and fault != 'no_window' else None
    blk = next((n for n in range(min(s, QUERY_BLOCK), 0, -1) if s % n == 0))
    q = q.reshape(b, s // blk, blk, nkv, nq // nkv, d)
    keys = jnp.arange(s)

    def one_kv_head(head):
        """A kv head's group of queries, a block of them at a time:
        (b, blocks, blk, g, d), (b, s, d), (b, s, dv), (g,) or None."""
        q_h, k_h, v_h, sink_h = head

        def one_block(block):
            q_b, first = block                      # (b, blk, g, d)
            ahead = (first + jnp.arange(blk))[:, None] - keys[None, :]
            seen = ahead >= 0
            if span is not None:
                seen = seen & (ahead < span)
            scores = jnp.einsum('bsgd,btd->bgst', q_b, k_h,
                                precision=HIGHEST) / (d ** 0.5)
            scores = jnp.where(seen, scores, -jnp.inf)
            if sink_h is not None:
                # the sink: one more column of the softmax, then dropped
                col = jnp.broadcast_to(sink_h[None, :, None, None],
                                       scores.shape[:3] + (1,))
                scores = jnp.concatenate([scores, col], -1)
            probs = jax.nn.softmax(scores, -1)[..., :s]
            return jnp.einsum('bgst,btd->bsgd', probs, v_h,
                              precision=HIGHEST)

        return jax.lax.map(one_block, (jnp.moveaxis(q_h, 1, 0),
                                       jnp.arange(0, s, blk)))

    heads = (jnp.moveaxis(q, 3, 0), jnp.moveaxis(k, 2, 0),
             jnp.moveaxis(v, 2, 0))
    att = jax.lax.map(one_kv_head, heads + (sink,)) if sink is not None \
        else jax.lax.map(lambda hd: one_kv_head(hd + (None,)), heads)
    # (nkv, blocks, b, blk, g, dv) -> (b, s, nq * dv)
    att = jnp.transpose(att, (2, 1, 3, 0, 4, 5)).reshape(b, s, nq * dv)
    return linear(att, lp['self_attn.o_proj'], quant)


def swiglu(m, gate, up, down, quant):
    return linear(jax.nn.silu(linear(m, gate, quant)) * linear(m, up, quant),
                  down, quant)


def route(cfg, lp, m):
    """(T.., width) weights: 0 but for the top-k of s + b (`noaux_tc`, one
    group), where s over the chosen's sum (`norm_topk_prob`) times
    `routed_scaling_factor` (null = 1)."""
    s = jax.nn.sigmoid(jnp.matmul(m, lp['mlp.router'], precision=HIGHEST))
    by = s if cfg.get('fault') == 'choice_by_s' else s + lp['mlp.expert_bias']
    _, chosen = jax.lax.top_k(by, cfg['num_experts_per_tok'])
    picked = jax.nn.one_hot(chosen, s.shape[-1], dtype=s.dtype).sum(-2)
    w = s * picked
    if cfg['norm_topk_prob']:
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * (cfg['routed_scaling_factor'] or 1.0)


def experts(cfg, lp, m, quant):
    """The held experts' weighed parts, and nothing else."""
    w = route(cfg, lp, m)
    first = cfg['expert_offset']
    w = w[..., first:first + cfg['n_routed_experts']]

    def one(total, xs):
        gate, up, down, w_e = xs
        return total + w_e[..., None] * swiglu(m, gate, up, down, quant), None

    routed, _ = jax.lax.scan(
        one, jnp.zeros_like(m),
        (lp['mlp.w_gate'], lp['mlp.w_up'], lp['mlp.w_down'],
         jnp.moveaxis(w, -1, 0)))
    return routed


def layer_forward(cfg, lp, x, layer, quant=None):
    """x (B, S, hidden) float32 -> the same, through one decoder layer."""
    eps = cfg['layernorm_epsilon']
    x = x + attention(cfg, lp, rms_norm(x, lp['input_layernorm.weight'],
                                        eps), layer, quant)
    m = rms_norm(x, lp['post_attention_layernorm.weight'], eps)
    if cfg['moe_layer_freq'][layer]:
        return x + experts(cfg, lp, m, quant)
    return x + swiglu(m, lp['mlp.gate_proj'], lp['mlp.up_proj'],
                      lp['mlp.down_proj'], quant)


def embed(gp, ids):
    return gp['embed_tokens'].astype(jnp.float32)[ids]


def logits(cfg, gp, x, quant=None):
    return linear(rms_norm(x, gp['norm.weight'], cfg['layernorm_epsilon']),
                  gp['lm_head'], quant)


def faults(cfg):
    return ('half_batch', 'frozen')
