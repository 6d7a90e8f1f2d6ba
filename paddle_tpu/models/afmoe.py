"""AFMoE — Arcee's Trinity decoder (`model_type: afmoe`; ref:
huggingface.co/arcee-ai/Trinity-Large-Preview config.json).

A pre- and post-normed decoder with sparse experts:

  - `h = E[ids] * sqrt(hidden)` when `mup_enabled`;
  - attention: q, k, v and an output GATE from the normed input; q and k
    RMS-normed per head; rotate-half RoPE and a sliding window on the
    layers `layer_types` calls "sliding_attention", NO rotary and plain
    causal attention on its "full_attention" layers; `o = (softmax(q kᵀ /
    sqrt(d)) v * sigmoid(gate)) Wo`; `h += RMSNorm(o)`;
  - feed-forward on `RMSNorm(h)`: a dense SwiGLU on the first
    `num_dense_layers` layers, else `distributed.moe.ExpertShare`: a
    sigmoid router over `num_experts` with a selection-only bias, top-k
    weights normalised and scaled, a shared expert, and the experts this
    rank HOLDS (`experts_held` from `expert_offset`; all of them by
    default); `h += RMSNorm(f)`;
  - final RMSNorm and an untied head.

The cached forward takes `block_tables`/`kv_write_pos` as
`LlamaForCausalLM`'s does (it shares `cached_attention`), so
`ServingEngine` serves it: window layers through the paged kernel's
window, full layers through its plain form, on one kind of page.
"""
from __future__ import annotations

import dataclasses
import math
import typing

import jax
import jax.numpy as jnp

from .. import nn
from ..distributed.moe import ExpertShare
from ..nn import functional as F
from ..nn import initializer as I
from ..nn.functional.norm import rms_norm
from ..nn.layer.base import Layer, Parameter
from .generation import GenerationMixin, default_positions
from .llama import LlamaMLP, apply_rotary, cached_attention, rope_cos_sin

SLIDING, FULL = 'sliding_attention', 'full_attention'


@dataclasses.dataclass
class AfmoeConfig:
    vocab_size: int = 200192
    hidden_size: int = 3072
    intermediate_size: int = 12288         # the dense layers' SwiGLU
    moe_intermediate_size: int = 3072      # one expert's, and the shared
    num_hidden_layers: int = 60
    num_dense_layers: int = 6
    num_attention_heads: int = 48
    num_key_value_heads: int = 8
    head_dim: int = 128
    # per layer, SLIDING or FULL; None = every fourth layer full
    layer_types: typing.Optional[typing.Sequence[str]] = None
    sliding_window: int = 4096
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    num_experts: int = 256                 # the router's width
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.448
    # this rank's share of every expert layer (None = all the experts)
    experts_held: typing.Optional[int] = None
    expert_offset: int = 0
    mup_enabled: bool = True
    max_position_embeddings: int = 4096
    initializer_range: float = 0.02
    dtype: str = 'float32'

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = [FULL if (i + 1) % 4 == 0 else SLIDING
                                for i in range(self.num_hidden_layers)]
        self.layer_types = list(self.layer_types)
        odd = set(self.layer_types) - {SLIDING, FULL}
        if odd or len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f'layer_types needs one of {SLIDING!r}/{FULL!r} for each '
                f'of the {self.num_hidden_layers} layers, got '
                f'{self.layer_types}')


def afmoe_tiny(**kw) -> AfmoeConfig:
    """Tiny config for tests: one dense layer, then one whole period."""
    defaults = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=5, num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        layer_types=[SLIDING] * 4 + [FULL], sliding_window=8,
        num_experts=16, num_experts_per_tok=4, max_position_embeddings=128)
    defaults.update(kw)
    return AfmoeConfig(**defaults)


def _gated(out, gate):
    """The attention output under its sigmoid gate."""
    return out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)


class AfmoeAttention(Layer):
    """Gated grouped-query attention with per-head q/k RMSNorm; RoPE and
    a window on sliding layers, neither on full layers."""

    def __init__(self, config: AfmoeConfig, layer_idx: int):
        super().__init__()
        self.sliding = config.layer_types[layer_idx] == SLIDING
        self.sliding_window = config.sliding_window if self.sliding else None
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.head_dim
        self.rope_theta = config.rope_theta
        self.eps = config.rms_norm_eps
        init = I.Normal(0.0, config.initializer_range)
        h, d = config.hidden_size, config.head_dim
        q, kv = self.num_heads * d, self.num_kv_heads * d
        self.q_proj = Parameter(init((h, q), config.dtype))
        self.k_proj = Parameter(init((h, kv), config.dtype))
        self.v_proj = Parameter(init((h, kv), config.dtype))
        self.gate_proj = Parameter(init((h, q), config.dtype))
        self.o_proj = Parameter(init((q, h), config.dtype))
        self.q_norm = Parameter(jnp.ones((d,), jnp.float32))
        self.k_norm = Parameter(jnp.ones((d,), jnp.float32))

    def forward(self, x, positions, cache=None, cache_index=None,
                kvalid=None, kv_start=None, kv_write_pos=None,
                block_tables=None):
        B, S, _ = x.shape
        q = (x @ self.q_proj).reshape(B, S, self.num_heads, self.head_dim)
        k = (x @ self.k_proj).reshape(B, S, self.num_kv_heads, self.head_dim)
        v = (x @ self.v_proj).reshape(B, S, self.num_kv_heads, self.head_dim)
        gate = x @ self.gate_proj
        q = rms_norm(q, self.q_norm, self.eps)
        k = rms_norm(k, self.k_norm, self.eps)
        if self.sliding:
            cos, sin = rope_cos_sin(positions, self.head_dim, self.rope_theta)
            q, k = apply_rotary(q, cos, sin), apply_rotary(k, cos, sin)
        if cache is None:
            out = F.scaled_dot_product_attention(
                q, k, v, is_causal=True, window_size=self.sliding_window)
            new_cache = None
        else:
            out, new_cache = cached_attention(
                q, k, v, cache, cache_index, kvalid=kvalid,
                kv_start=kv_start, kv_write_pos=kv_write_pos,
                window=self.sliding_window, block_tables=block_tables)
        out = out.reshape(B, S, self.num_heads * self.head_dim)
        return _gated(out, gate) @ self.o_proj, new_cache


class AfmoeDecoderLayer(Layer):
    def __init__(self, config: AfmoeConfig, layer_idx: int):
        super().__init__()
        h, eps = config.hidden_size, config.rms_norm_eps
        self.input_layernorm = nn.RMSNorm(h, epsilon=eps)
        self.self_attn = AfmoeAttention(config, layer_idx)
        self.post_attention_layernorm = nn.RMSNorm(h, epsilon=eps)
        self.pre_mlp_layernorm = nn.RMSNorm(h, epsilon=eps)
        if layer_idx < config.num_dense_layers:
            self.mlp = LlamaMLP(config)    # the dense layers' SwiGLU
        else:
            self.mlp = ExpertShare(
                h, config.moe_intermediate_size, config.num_experts,
                config.num_experts_per_tok,
                experts_held=config.experts_held,
                expert_offset=config.expert_offset,
                shared_intermediate=(config.moe_intermediate_size
                                     * config.num_shared_experts),
                route_norm=config.route_norm,
                route_scale=config.route_scale, dtype=config.dtype)
        self.post_mlp_layernorm = nn.RMSNorm(h, epsilon=eps)

    def forward(self, x, positions, cache=None, cache_index=None,
                kvalid=None, kv_start=None, kv_write_pos=None,
                block_tables=None):
        attn, new_cache = self.self_attn(
            self.input_layernorm(x), positions, cache, cache_index, kvalid,
            kv_start, kv_write_pos, block_tables)
        x = x + self.post_attention_layernorm(attn)
        x = x + self.post_mlp_layernorm(self.mlp(self.pre_mlp_layernorm(x)))
        return x, new_cache


class AfmoeForCausalLM(GenerationMixin, Layer):
    # the vocabulary table is gathered, not multiplied
    no_quantize = ('embed_tokens',)

    def __init__(self, config: AfmoeConfig):
        super().__init__()
        self.config = config
        init = I.Normal(0.0, config.initializer_range)
        self.embed_tokens = Parameter(
            init((config.vocab_size, config.hidden_size), config.dtype))
        self.layers = nn.LayerList(
            [AfmoeDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.rms_norm_eps)
        self.lm_head = Parameter(
            init((config.hidden_size, config.vocab_size), config.dtype))

    def forward(self, input_ids, positions=None, caches=None,
                cache_index=None, kvalid=None, kv_start=None,
                kv_write_pos=None, block_tables=None):
        """Logits, or (logits, new_caches) with a KV cache: the
        GenerationMixin cached-call contract, paged caches included."""
        B, S = input_ids.shape
        if positions is None:
            positions = default_positions(B, S, cache_index, kv_write_pos)
        x = self.embed_tokens[input_ids]
        if self.config.mup_enabled:
            x = x * jnp.asarray(math.sqrt(self.config.hidden_size), x.dtype)
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            x, nc = layer(x, positions,
                          caches[i] if caches is not None else None,
                          cache_index, kvalid, kv_start, kv_write_pos,
                          block_tables)
            if new_caches is not None:
                new_caches.append(nc)
        logits = self.norm(x) @ self.lm_head
        return logits if caches is None else (logits, new_caches)

    def loss(self, input_ids, labels=None):
        """Next-token cross-entropy. No balancing term: the routing bias
        is moved by its own rule outside the loss, which is not here."""
        from ..ops import softmax_cross_entropy

        if labels is None:
            labels = input_ids[:, 1:]
            input_ids = input_ids[:, :-1]
        return softmax_cross_entropy(self(input_ids), labels).mean()

    def cache_dtype(self):
        return self.embed_tokens.dtype
