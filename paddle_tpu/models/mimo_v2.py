"""MiMo-V2 — Xiaomi's hybrid-attention sparse decoder (`model_type:
mimo_v2`; ref: huggingface.co/XiaomiMiMo/MiMo-V2.5 config.json), the
language model only (no vision or audio tower, no multi-token-prediction
layers).

A pre-normed decoder whose layers are of two attention kinds
(`hybrid_layer_pattern`: 0 full, 1 sliding window) with their OWN head
geometries, and of two feed-forward kinds (`moe_layer_freq`: 0 dense):

  - `h = E[ids]`;
  - attention on `RMSNorm(h)`: q, k `head_dim` wide and v `v_head_dim`
    wide (K rows 192, V rows 128 as published), no biases; `v` times
    `attention_value_scale`; rotate-half RoPE on the leading
    `partial_rotary_factor` of each q/k head (the even floor: 64 of 192),
    the rest passes; a full layer has `num_key_value_heads` kv heads and
    `rope_theta`, a window layer `swa_num_key_value_heads`,
    `swa_rope_theta` and attends its last `sliding_window` positions
    through a learned per-head SINK logit that takes mass and adds no
    value (`add_swa_attention_sink_bias`); scores over sqrt(head_dim);
    `h += concat(o) Wo`;
  - feed-forward on `RMSNorm(h)`: a dense SwiGLU, or
    `distributed.moe.ExpertShare` — a sigmoid router over
    `n_routed_experts` with a selection-only bias, top-k weights
    normalised (`norm_topk_prob`), no shared expert, and the experts
    this rank HOLDS (`experts_held` from `expert_offset`); `h += f`;
  - final RMSNorm and an untied head.

The model keeps TWO kinds of KV page (`page_kinds`): the full layers'
(every page of the context) and the window layers' (other kv heads; pages
wholly behind the window may be recycled). The cached forward takes
`block_tables` as one table a kind, in `page_kinds`' order, so
`ServingEngine` serves it on two pool groups.
"""
from __future__ import annotations

import dataclasses
import typing

import jax.numpy as jnp

from .. import nn
from ..distributed.moe import ExpertShare
from ..nn import initializer as I
from ..nn.layer.base import Layer, Parameter
from .generation import (GenerationMixin, PageKind, default_positions,
                         layer_tables)
from .llama import (LlamaMLP, apply_rotary, cached_attention,
                    masked_attention, rope_cos_sin)


@dataclasses.dataclass
class MimoV2Config:
    vocab_size: int = 152576
    hidden_size: int = 4096
    intermediate_size: int = 16384         # the dense layers' SwiGLU
    moe_intermediate_size: int = 2048      # one expert's
    num_hidden_layers: int = 48
    # full-attention layers
    num_attention_heads: int = 64
    num_key_value_heads: int = 4
    head_dim: int = 192
    v_head_dim: int = 128
    rope_theta: float = 1e7
    add_full_attention_sink_bias: bool = False
    # sliding-window layers
    swa_num_attention_heads: int = 64
    swa_num_key_value_heads: int = 8
    swa_head_dim: int = 192
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 1e4
    add_swa_attention_sink_bias: bool = True
    sliding_window: int = 128
    # per layer: 0 full, 1 window; None = every sixth layer full
    hybrid_layer_pattern: typing.Optional[typing.Sequence[int]] = None
    # per layer: 0 dense, 1 experts; None = layer 0 dense
    moe_layer_freq: typing.Optional[typing.Sequence[int]] = None
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    layernorm_epsilon: float = 1e-5
    n_routed_experts: int = 256            # the router's width
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: typing.Optional[float] = None
    # this rank's share of every expert layer (None = all the experts)
    experts_held: typing.Optional[int] = None
    expert_offset: int = 0
    max_position_embeddings: int = 4096
    initializer_range: float = 0.02
    dtype: str = 'float32'

    def __post_init__(self):
        n = self.num_hidden_layers
        if self.hybrid_layer_pattern is None:
            self.hybrid_layer_pattern = [
                0 if i == 0 or (i + 1) % 6 == 0 else 1 for i in range(n)]
        if self.moe_layer_freq is None:
            self.moe_layer_freq = [int(i > 0) for i in range(n)]
        for name in ('hybrid_layer_pattern', 'moe_layer_freq'):
            flags = list(getattr(self, name))
            if len(flags) != n or set(flags) - {0, 1}:
                raise ValueError(f'{name} needs a 0 or a 1 for each of the '
                                 f'{n} layers, got {flags}')
            setattr(self, name, flags)


def mimo_v2_tiny(**kw) -> MimoV2Config:
    """Tiny config for tests: the leading dense full layer, then one
    period (window x 3, full), kv heads 2 / 4, K 24 and V 16 wide."""
    defaults = dict(
        vocab_size=256, hidden_size=64, intermediate_size=128,
        moe_intermediate_size=32, num_hidden_layers=5,
        num_attention_heads=4, num_key_value_heads=2, head_dim=24,
        v_head_dim=16, swa_num_attention_heads=4, swa_num_key_value_heads=4,
        swa_head_dim=24, swa_v_head_dim=16, sliding_window=8,
        hybrid_layer_pattern=[0, 1, 1, 1, 0], moe_layer_freq=[0, 1, 1, 1, 1],
        n_routed_experts=16, num_experts_per_tok=4,
        max_position_embeddings=128)
    defaults.update(kw)
    return MimoV2Config(**defaults)


class MimoV2Attention(Layer):
    """Grouped-query attention of one kind: its own head counts, widths
    and theta; partial rotary; the value scale; a window and a sink on
    the layers that have them."""

    def __init__(self, config: MimoV2Config, layer_idx: int):
        super().__init__()
        c, swa = config, bool(config.hybrid_layer_pattern[layer_idx])
        pre = 'swa_' if swa else ''
        self.num_heads = getattr(c, pre + 'num_attention_heads')
        self.num_kv_heads = getattr(c, pre + 'num_key_value_heads')
        self.head_dim = getattr(c, pre + 'head_dim')
        self.v_head_dim = getattr(c, pre + 'v_head_dim')
        self.rope_theta = getattr(c, pre + 'rope_theta')
        self.sliding_window = c.sliding_window if swa else None
        self.rotary_dim = int(self.head_dim * c.partial_rotary_factor) // 2 * 2
        self.value_scale = c.attention_value_scale
        init = I.Normal(0.0, c.initializer_range)
        h, d, dv = c.hidden_size, self.head_dim, self.v_head_dim
        self.q_proj = Parameter(init((h, self.num_heads * d), c.dtype))
        self.k_proj = Parameter(init((h, self.num_kv_heads * d), c.dtype))
        self.v_proj = Parameter(init((h, self.num_kv_heads * dv), c.dtype))
        self.o_proj = Parameter(init((self.num_heads * dv, h), c.dtype))
        has_sink = (c.add_swa_attention_sink_bias if swa
                    else c.add_full_attention_sink_bias)
        self.attention_sink_bias = (
            Parameter(jnp.zeros((self.num_heads,), jnp.float32))
            if has_sink else None)

    def _rotate(self, x, cos, sin):
        r = self.rotary_dim
        return jnp.concatenate(
            [apply_rotary(x[..., :r], cos, sin), x[..., r:]], axis=-1)

    def forward(self, x, positions, cache=None, cache_index=None,
                kvalid=None, kv_start=None, kv_write_pos=None,
                block_tables=None):
        B, S, _ = x.shape
        q = (x @ self.q_proj).reshape(B, S, self.num_heads, self.head_dim)
        k = (x @ self.k_proj).reshape(B, S, self.num_kv_heads, self.head_dim)
        v = (x @ self.v_proj).reshape(B, S, self.num_kv_heads,
                                      self.v_head_dim)
        v = v * jnp.asarray(self.value_scale, v.dtype)
        cos, sin = rope_cos_sin(positions, self.rotary_dim, self.rope_theta)
        q, k = self._rotate(q, cos, sin), self._rotate(k, cos, sin)
        if cache is None:
            ahead = jnp.arange(S)[:, None] - jnp.arange(S)[None, :]
            seen = ahead >= 0
            if self.sliding_window is not None:
                seen = seen & (ahead < self.sliding_window)
            out = masked_attention(q, k, v, seen[None, None],
                                   self.attention_sink_bias)
            new_cache = None
        else:
            out, new_cache = cached_attention(
                q, k, v, cache, cache_index, kvalid=kvalid,
                kv_start=kv_start, kv_write_pos=kv_write_pos,
                window=self.sliding_window, block_tables=block_tables,
                sink=self.attention_sink_bias)
        out = out.reshape(B, S, self.num_heads * self.v_head_dim)
        return out @ self.o_proj, new_cache


class MimoV2DecoderLayer(Layer):
    def __init__(self, config: MimoV2Config, layer_idx: int):
        super().__init__()
        h, eps = config.hidden_size, config.layernorm_epsilon
        self.input_layernorm = nn.RMSNorm(h, epsilon=eps)
        self.self_attn = MimoV2Attention(config, layer_idx)
        self.post_attention_layernorm = nn.RMSNorm(h, epsilon=eps)
        if config.moe_layer_freq[layer_idx]:
            self.mlp = ExpertShare(
                h, config.moe_intermediate_size, config.n_routed_experts,
                config.num_experts_per_tok,
                experts_held=config.experts_held,
                expert_offset=config.expert_offset,
                route_norm=config.norm_topk_prob,
                route_scale=config.routed_scaling_factor or 1.0,
                dtype=config.dtype)
        else:
            self.mlp = LlamaMLP(config)    # the dense layers' SwiGLU

    def forward(self, x, positions, cache=None, cache_index=None,
                kvalid=None, kv_start=None, kv_write_pos=None,
                block_tables=None):
        attn, new_cache = self.self_attn(
            self.input_layernorm(x), positions, cache, cache_index, kvalid,
            kv_start, kv_write_pos, block_tables)
        x = x + attn
        return x + self.mlp(self.post_attention_layernorm(x)), new_cache


class MimoV2ForCausalLM(GenerationMixin, Layer):
    # the vocabulary table is gathered, not multiplied
    no_quantize = ('embed_tokens',)

    def __init__(self, config: MimoV2Config):
        super().__init__()
        self.config = config
        init = I.Normal(0.0, config.initializer_range)
        self.embed_tokens = Parameter(
            init((config.vocab_size, config.hidden_size), config.dtype))
        self.layers = nn.LayerList(
            [MimoV2DecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = nn.RMSNorm(config.hidden_size,
                               epsilon=config.layernorm_epsilon)
        self.lm_head = Parameter(
            init((config.hidden_size, config.vocab_size), config.dtype))

    def page_kinds(self):
        """The full layers' pages, then the window layers': other kv
        heads, and nothing behind the window is ever read."""
        c = self.config
        of = {flag: tuple(l for l, p in enumerate(c.hybrid_layer_pattern)
                          if p == flag) for flag in (0, 1)}
        kinds = (PageKind('full', of[0], c.num_key_value_heads, c.head_dim,
                          c.v_head_dim),
                 PageKind('window', of[1], c.swa_num_key_value_heads,
                          c.swa_head_dim, c.swa_v_head_dim,
                          c.sliding_window))
        return tuple(k for k in kinds if k.layers)

    def forward(self, input_ids, positions=None, caches=None,
                cache_index=None, kvalid=None, kv_start=None,
                kv_write_pos=None, block_tables=None):
        """Logits, or (logits, new_caches) with a KV cache: the
        GenerationMixin cached-call contract; `block_tables` is one table
        a kind of page (`page_kinds`' order), or the one table where the
        configuration has one kind."""
        B, S = input_ids.shape
        if positions is None:
            positions = default_positions(B, S, cache_index, kv_write_pos)
        n = self.config.num_hidden_layers
        tables = ([None] * n if block_tables is None
                  else layer_tables(self.page_kinds(), block_tables, n))
        x = self.embed_tokens[input_ids]
        new_caches = [] if caches is not None else None
        for i, layer in enumerate(self.layers):
            x, nc = layer(x, positions,
                          caches[i] if caches is not None else None,
                          cache_index, kvalid, kv_start, kv_write_pos,
                          tables[i])
            if new_caches is not None:
                new_caches.append(nc)
        logits = self.norm(x) @ self.lm_head
        return logits if caches is None else (logits, new_caches)

    def loss(self, input_ids, labels=None):
        """Next-token cross-entropy (no balancing term: the routing bias
        is moved by its own rule outside the loss, which is not here)."""
        from ..ops import softmax_cross_entropy

        if labels is None:
            labels = input_ids[:, 1:]
            input_ids = input_ids[:, :-1]
        return softmax_cross_entropy(self(input_ids), labels).mean()

    def cache_dtype(self):
        return self.embed_tokens.dtype
