"""The work a model needs, from the configuration file and the request log
alone: the same number whatever implements the step.

Needed work, not implemented work: padding to a bucket, pages walked past
a row's context, recomputed scores and rematerialised layers are not here.
What one token needs in one layer is the configuration's family's
(`fam`: `benchmark/families`); here those are summed over the layers, the
tokens and the steps. The readers get a cell's counts as `Work(fam)`.
"""
from __future__ import annotations

import functools

import numpy as np


class Work:
    """A cell's counts under the names below, as `ctx['flops']`: a name is
    the family's own function where its file has one (a new kernel's
    `needed_<kernel>(ctx)`, or a count that these sums do not fit), and
    otherwise this module's, over the family's layers."""

    def __init__(self, fam):
        self.fam = fam

    def __getattr__(self, name):
        own = getattr(self.fam, name, None)
        if own is not None:
            return own
        shared = globals().get(name)
        if name.startswith('_') or not callable(shared) or shared is Work:
            raise AttributeError(f'neither {self.fam.__name__} nor '
                                 f'{__name__} counts {name!r}')
        return functools.partial(shared, self.fam)


def _layers(cfg):
    return range(cfg['num_hidden_layers'])


def layers_matmul_params(fam, cfg):
    return sum(fam.matmul_params(cfg, l) for l in _layers(cfg))


def attn_flops_token(fam, cfg, context):
    """QK^T and PV of one query token at `context`, every layer."""
    return sum(fam.attn_flops_key(cfg, l) * fam.attn_keys(cfg, l, context)
               for l in _layers(cfg))


def causal_keys(fam, cfg, layer, seq):
    """Keys that the queries at contexts 1..seq attend, together."""
    return int(np.sum(fam.attn_keys(cfg, layer,
                                    np.arange(1, seq + 1, dtype=np.int64))))


def prefill_flops(fam, cfg, prompt_len):
    """A prompt of `prompt_len` tokens: matmuls for every token, causal
    attention, the head for the last position only (the one whose logits
    are needed). The embedding lookup is no matmul."""
    causal = sum(fam.attn_flops_key(cfg, l)
                 * causal_keys(fam, cfg, l, prompt_len) for l in _layers(cfg))
    return (2 * layers_matmul_params(fam, cfg) * prompt_len + causal
            + 2 * fam.head_params(cfg))


def decode_flops(fam, cfg, context):
    """One output token whose query attends from `context` (itself
    included); its logits choose the next token, so the head counts."""
    return (2 * layers_matmul_params(fam, cfg)
            + attn_flops_token(fam, cfg, context) + 2 * fam.head_params(cfg))


def serve_flops(fam, cfg, deliveries):
    """`deliveries`: (prompt_len, first, n) per request and step, the n
    output tokens numbered first.. that one step() delivered. The prompt
    counts when output token 0 is delivered; that token's logits come from
    the prefill, every later one from a decode step at its own context."""
    total = 0
    for prompt_len, first, n in deliveries:
        for j in range(first, first + n):
            total += (prefill_flops(fam, cfg, prompt_len) if j == 0
                      else decode_flops(fam, cfg, prompt_len + j))
    return total


def paged_attn_needed(fam, cfg, deliveries):
    """(flops, bytes) the decode attention needs for the delivered tokens:
    per token at context c and per layer, what the keys it attends keep in
    the cache, plus q in and out."""
    flops = nbytes = 0
    for prompt_len, first, n in deliveries:
        for j in range(max(first, 1), first + n):
            c = prompt_len + j
            flops += attn_flops_token(fam, cfg, c)
            nbytes += sum(fam.attn_keys(cfg, l, c)
                          * fam.cache_bytes_token(cfg, l)
                          + fam.query_bytes_token(cfg, l)
                          for l in _layers(cfg))
    return flops, nbytes


def train_flops(fam, cfg, batch, seq):
    """Forward and backward of `batch` sequences of `seq` tokens: three
    times the forward's matmuls (head included: every position's logits
    are needed) and causal attention. Recomputation counts for nothing."""
    tokens = batch * seq
    fwd = (2 * (layers_matmul_params(fam, cfg) + fam.head_params(cfg))
           * tokens + batch * flash_attn_flops(fam, cfg, seq, backward=False))
    return 3 * fwd


def flash_attn_flops(fam, cfg, seq, backward):
    """The causal half of a layer's score matrix, the diagonal counted
    half (4 s^2 heads head_dim / 2 where every key is attended); the
    backward has four matrix products to the forward's two."""
    fwd = sum(fam.attn_flops_key(cfg, l)
              * (2 * causal_keys(fam, cfg, l, seq) - seq) // 2
              for l in _layers(cfg))
    return 2 * fwd if backward else fwd


def flash_attn_bytes(fam, cfg, seq, backward):
    """q, k, v read and out written once a layer; the backward reads
    those and dout and writes dq, dk, dv."""
    fwd = sum(seq * (fam.query_bytes_token(cfg, l)
                     + fam.cache_bytes_token(cfg, l)) for l in _layers(cfg))
    return 2 * fwd if backward else fwd


def needed_paged_attn(fam, ctx):
    """(flops, bytes) for the roofline reader, from the request log."""
    return paged_attn_needed(fam, ctx['cfg'], ctx['deliveries'])


def needed_flash_attn(fam, ctx):
    """Forward and backward calls together, every step of the window."""
    calls = ctx['train_steps'] * ctx['batch']
    cfg, seq = ctx['cfg'], ctx['seq']
    return (calls * (flash_attn_flops(fam, cfg, seq, False)
                     + flash_attn_flops(fam, cfg, seq, True)),
            calls * (flash_attn_bytes(fam, cfg, seq, False)
                     + flash_attn_bytes(fam, cfg, seq, True)))
