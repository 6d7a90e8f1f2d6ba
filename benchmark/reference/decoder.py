"""The library a family's plain reference is written with, in `jax.numpy`
float32: `linear`, `rms_norm`, `rope`, and the static form of a
configuration. The layers themselves are the families'
(`benchmark/reference/families/`).

`quant` switches on the control of "How correct is decided": every linear
layer's inputs and weights are rounded to int8 or to fp8 (e4m3), scaled
per token and per output channel, before the product: the step below
bfloat16 that would tempt a later PR.
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


def frozen(cfg):
    """The configuration as a hashable static argument, lists and nested
    groups (`layer_types`, `rope_scaling`) with it; `thawed` gives it
    back."""
    if isinstance(cfg, dict):
        return ('dict', tuple(sorted((k, frozen(v)) for k, v in cfg.items())))
    if isinstance(cfg, (list, tuple)):
        return ('list', tuple(frozen(v) for v in cfg))
    return cfg


def thawed(items):
    if isinstance(items, tuple):
        kind, values = items
        return ({k: thawed(v) for k, v in values} if kind == 'dict'
                else [thawed(v) for v in values])
    return items


@functools.partial(jax.jit, static_argnames=('forward', 'cfg_items', 'layer',
                                             'quant'))
def layer_step(lp, x, *, forward, cfg_items, layer, quant=None):
    """A family's `layer_forward`, compiled once for each kind of layer
    (`layer` is what the family's `layer_like` gives)."""
    return forward(thawed(cfg_items), lp, x, layer, quant)
