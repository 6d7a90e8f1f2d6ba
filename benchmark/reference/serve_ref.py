"""What a served token is held to: one teacher-forced float32 forward
pass over each sampled request's prompt and served tokens.

For every served token, how far its logit lies below the reference's best
at that position. With a `control` precision the same prompts and tokens
also go through the reference at that precision, and the gap read is that
of the token the control puts first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights
from benchmark.reference import decoder


@functools.partial(jax.jit, static_argnames=('logits', 'cfg_items', 'quant'))
def _read(gp, x, where, *, logits, cfg_items, quant):
    rows = jnp.take_along_axis(x, where[:, :, None], axis=1)   # (R, O, H)
    return logits(decoder.thawed(cfg_items), gp, rows, quant)


@jax.jit
def _gaps(ref_logits, tokens):
    best = ref_logits.max(-1)
    got = jnp.take_along_axis(ref_logits, tokens[:, :, None], -1)[..., 0]
    return best - got


def _forward(fam, cfg, seed, ids, where, quant):
    ref, items = fam.reference, decoder.frozen(cfg)
    base = weights.base_key(seed)
    make_layer = jax.jit(lambda b, l, like: weights.make_layer(
        fam, b, cfg, l, like), static_argnums=2)
    gp = jax.jit(lambda b: weights.make_globals(fam, b, cfg))(base)
    x = ref.embed(gp, ids)
    for layer in range(cfg['num_hidden_layers']):
        like = fam.layer_like(cfg, layer)
        x = decoder.layer_step(make_layer(base, layer, like), x,
                               forward=ref.layer_forward, cfg_items=items,
                               layer=like, quant=quant)
    return _read(gp, x, where, logits=ref.logits, cfg_items=items,
                 quant=quant)


def served_gaps(fam, cfg, seed, requests, pad_to, control=None):
    """`fam`: the configuration's family (`common.family`); `requests`:
    [(prompt ids, served output ids)]. Returns the widest
    gap of a served token, and with `control` ('int8', 'fp8') also the
    widest gap of the control's own first choices at the same positions."""
    n_out = max(len(o) for _, o in requests)
    ids = np.zeros((len(requests), pad_to), np.int32)
    where = np.zeros((len(requests), n_out), np.int32)
    toks = np.zeros((len(requests), n_out), np.int32)
    real = np.zeros((len(requests), n_out), bool)
    for r, (prompt, out) in enumerate(requests):
        seq = np.concatenate([prompt, out])
        if len(seq) > pad_to:
            raise ValueError(f'request of {len(seq)} tokens over the '
                             f'reference length {pad_to}')
        ids[r, :len(seq)] = seq
        # output token j is chosen from the logits at position p - 1 + j
        where[r, :len(out)] = len(prompt) - 1 + np.arange(len(out))
        toks[r, :len(out)] = out
        real[r, :len(out)] = True
    with jax.default_matmul_precision('highest'):
        ref = _forward(fam, cfg, seed, jnp.asarray(ids), jnp.asarray(where),
                       None)
        served = np.asarray(_gaps(ref, jnp.asarray(toks)))
        result = {'served_gap': float(served[real].max()),
                  'served_tokens': int(real.sum())}
        if control:
            low = _forward(fam, cfg, seed, jnp.asarray(ids),
                           jnp.asarray(where), control)
            first = jnp.argmax(low, -1).astype(jnp.int32)
            result['control_gap'] = float(
                np.asarray(_gaps(ref, first))[real].max())
    return result
