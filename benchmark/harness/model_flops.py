"""The work a model needs, from the configuration file and the request log
alone: the same number whatever implements the step.

Needed work, not implemented work: padding to a bucket, pages walked past
a row's context, recomputed scores and rematerialised layers are not here.
"""
from __future__ import annotations


def layer_matmul_params(cfg):
    h, f, d = cfg['hidden_size'], cfg['intermediate_size'], cfg['head_dim']
    q, kv = cfg['num_attention_heads'] * d, cfg['num_key_value_heads'] * d
    return h * q + 2 * h * kv + q * h + 3 * h * f


def head_params(cfg):
    return cfg['hidden_size'] * cfg['vocab_size']


def attn_flops_token(cfg, context):
    """QK^T and PV of one query token over `context` keys, every layer."""
    return (4 * context * cfg['num_attention_heads'] * cfg['head_dim']
            * cfg['num_hidden_layers'])


def prefill_flops(cfg, prompt_len):
    """A prompt of `prompt_len` tokens: matmuls for every token, causal
    attention, the head for the last position only (the one whose logits
    are needed). The embedding lookup is no matmul."""
    causal = attn_flops_token(cfg, 1) * prompt_len * (prompt_len + 1) // 2
    return (2 * layer_matmul_params(cfg) * cfg['num_hidden_layers'] * prompt_len
            + causal
            + 2 * head_params(cfg))


def decode_flops(cfg, context):
    """One output token whose query attends `context` keys (itself
    included); its logits choose the next token, so the head counts."""
    return (2 * layer_matmul_params(cfg) * cfg['num_hidden_layers']
            + attn_flops_token(cfg, context) + 2 * head_params(cfg))


def serve_flops(cfg, deliveries):
    """`deliveries`: (prompt_len, first, n) per request and step, the n
    output tokens numbered first.. that one step() delivered. The prompt
    counts when output token 0 is delivered; that token's logits come from
    the prefill, every later one from a decode step at its own context."""
    total = 0
    for prompt_len, first, n in deliveries:
        for j in range(first, first + n):
            total += (prefill_flops(cfg, prompt_len) if j == 0
                      else decode_flops(cfg, prompt_len + j))
    return total


def paged_attn_needed(cfg, deliveries):
    """(flops, bytes) the decode attention needs for the delivered tokens:
    per token at context c, K and V rows of c positions in the pages' type
    plus q in and out, per layer."""
    layers, d = cfg['num_hidden_layers'], cfg['head_dim']
    kvh, qh = cfg['num_key_value_heads'], cfg['num_attention_heads']
    flops = nbytes = 0
    for prompt_len, first, n in deliveries:
        for j in range(max(first, 1), first + n):
            c = prompt_len + j
            flops += attn_flops_token(cfg, c)
            nbytes += layers * (c * 2 * kvh * d * 2 + 2 * qh * d * 2)
    return flops, nbytes


def train_flops(cfg, batch, seq):
    """Forward and backward of `batch` sequences of `seq` tokens: three
    times the forward's matmuls (head included: every position's logits
    are needed) and causal attention. Recomputation counts for nothing."""
    tokens = batch * seq
    fwd = (2 * (layer_matmul_params(cfg) * cfg['num_hidden_layers']
                + head_params(cfg)) * tokens
           + batch * flash_attn_flops(cfg, seq, backward=False))
    return 3 * fwd


def flash_attn_flops(cfg, seq, backward):
    """Causal half of 4 s^2 heads head_dim a layer forward; the backward
    has four matrix products to the forward's two."""
    fwd = (4 * seq * seq * cfg['num_attention_heads'] * cfg['head_dim']
           * cfg['num_hidden_layers']) // 2
    return 2 * fwd if backward else fwd


def flash_attn_bytes(cfg, seq, backward):
    """q, k, v read and out written once a layer (bf16); the backward
    reads those and dout and writes dq, dk, dv."""
    d, layers = cfg['head_dim'], cfg['num_hidden_layers']
    qo = seq * cfg['num_attention_heads'] * d * 2
    kv = seq * cfg['num_key_value_heads'] * d * 2
    fwd = layers * (2 * qo + 2 * kv)
    return layers * (4 * qo + 4 * kv) if backward else fwd


def needed_paged_attn(ctx):
    """(flops, bytes) for the roofline reader, from the request log."""
    return paged_attn_needed(ctx['cfg'], ctx['deliveries'])


def needed_flash_attn(ctx):
    """Forward and backward calls together, every step of the window."""
    calls = ctx['train_steps'] * ctx['batch']
    cfg, seq = ctx['cfg'], ctx['seq']
    return (calls * (flash_attn_flops(cfg, seq, False)
                     + flash_attn_flops(cfg, seq, True)),
            calls * (flash_attn_bytes(cfg, seq, False)
                     + flash_attn_bytes(cfg, seq, True)))
