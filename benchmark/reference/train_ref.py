"""Three AdamW steps of a family's reference in plain float32, layer by
layer so that parameters, two moments and one layer's gradient fit one
chip.

Loss is the mean next-token cross-entropy over all positions. AdamW as the
paper and Paddle's `adamw` state it: decoupled decay `p -= lr * wd * p`,
bias-corrected moments, `p -= lr * mhat / (sqrt(vhat) + eps)`, on every
leaf. `fault` plants what a broken step would do (`half_batch`: the second
half of the tokens left out, the mean taken over the rest; `frozen`: a
step that returns its state unchanged; `no_bias_grad`: the gradients of
the leaves named `*_bias` dropped); the family's `faults(cfg)` says which
of them a configuration can have. `quant` runs the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.harness import weights
from benchmark.reference import decoder


@functools.partial(jax.jit, static_argnames=('forward', 'cfg_items', 'layer',
                                             'quant'))
def _layer_back(lp, x, dy, *, forward, cfg_items, layer, quant):
    cfg = decoder.thawed(cfg_items)
    _, vjp = jax.vjp(lambda p, h: forward(cfg, p, h, layer, quant), lp, x)
    return vjp(dy)


@functools.partial(jax.jit, static_argnames=('logits', 'cfg_items', 'quant'))
def _head_block(gp, x, labels, weight, *, logits, cfg_items, quant):
    """Weighted sum of the block's token losses and its gradients with
    respect to the globals and the block's hidden rows."""
    cfg = decoder.thawed(cfg_items)

    def loss(gp, x):
        z = logits(cfg, gp, x, quant)
        nll = jax.nn.logsumexp(z, -1) - jnp.take_along_axis(
            z, labels[..., None], -1)[..., 0]
        return jnp.sum(nll * weight)

    return jax.value_and_grad(loss, argnums=(0, 1))(gp, x)


@functools.partial(jax.jit, donate_argnums=(0, 2, 3))
def _adamw(p, g, m, v, t, hp):
    lr, wd, b1, b2, eps = hp

    def one(p, g, m, v):
        p = p - lr * wd * p
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mhat, vhat = m / (1 - b1 ** t), v / (1 - b2 ** t)
        return p - lr * mhat / (jnp.sqrt(vhat) + eps), m, v

    out = jax.tree.map(one, p, g, m, v)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,          # noqa: E731
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


@jax.jit
def _norms(tree):
    return jax.tree.map(lambda g: jnp.sqrt(jnp.sum(g * g)), tree)


@jax.jit
def _probes(tree, base, layer):
    return {k: weights.probe_dots(base, layer, k, g)
            for k, g in tree.items()}


@functools.partial(jax.jit, static_argnames='embed', donate_argnums=3)
def _embed_back(gp, ids, dx, g_gp, *, embed):
    """`g_gp` and what `dx` sends back through the family's `embed`."""
    _, vjp = jax.vjp(lambda g: embed(g, ids), gp)
    return jax.tree.map(jnp.add, g_gp, vjp(dx)[0])


def run(fam, cfg, seed, batches, hp, fault=None, quant=None, row_block=1024):
    """`fam`: the configuration's family (`common.family`). `batches`: the
    first steps' (batch, seq + 1) id arrays. `hp`: (lr, weight decay,
    beta1, beta2, eps). Returns losses, the first gradient's norm per leaf
    and the norm of each leaf's change."""
    ref, items = fam.reference, decoder.frozen(cfg)
    base = weights.base_key(seed)
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa: E731
    n_layers = cfg['num_hidden_layers']
    likes = [fam.layer_like(cfg, l) for l in range(n_layers)]
    make = jax.jit(lambda b, l, like: f32(weights.make_layer(
        fam, b, cfg, l, like)), static_argnums=2)
    make_layer = lambda b, l: make(b, l, likes[l])                  # noqa: E731
    make_globals = jax.jit(lambda b: f32(weights.make_globals(fam, b, cfg)))
    zeros = lambda t: jax.tree.map(jnp.zeros_like, t)               # noqa: E731
    with jax.default_matmul_precision('highest'):
        gp = make_globals(base)
        lps = [make_layer(base, l) for l in range(n_layers)]
        gm, gv = zeros(gp), zeros(gp)
        lms, lvs = [zeros(p) for p in lps], [zeros(p) for p in lps]
        losses, grad_norm, grad_dots = [], {}, {}
        for t, batch in enumerate(batches, start=1):
            ids = jnp.asarray(batch[:, :-1])
            labels = jnp.asarray(batch[:, 1:])
            b, s = ids.shape
            n = b * s
            weight = np.full((b, s), 1.0 / n, np.float32)
            if fault == 'half_batch':
                weight = weight.reshape(-1)
                weight[n // 2:] = 0.0
                weight[:n // 2] = 2.0 / n
                weight = weight.reshape(b, s)
            elif fault not in (None, *ref.faults(cfg)):
                raise ValueError(f'unknown fault {fault!r}')
            xs = [ref.embed(gp, ids)]
            for lp, like in zip(lps, likes):
                xs.append(decoder.layer_step(
                    lp, xs[-1], forward=ref.layer_forward, cfg_items=items,
                    layer=like, quant=quant))
            loss, g_gp, dx = 0.0, zeros(gp), []
            for r0 in range(0, s, row_block):
                sl = slice(r0, r0 + row_block)
                part, (g_part, dx_part) = _head_block(
                    gp, xs[-1][:, sl], labels[:, sl],
                    jnp.asarray(weight[:, sl]), logits=ref.logits,
                    cfg_items=items, quant=quant)
                loss += float(part)
                g_gp = jax.tree.map(jnp.add, g_gp, g_part)
                dx.append(dx_part)
            dx = jnp.concatenate(dx, axis=1)
            losses.append(loss)
            tt = jnp.float32(t)
            for l in reversed(range(n_layers)):
                g_lp, dx = _layer_back(
                    lps[l], xs[l], dx, forward=ref.layer_forward,
                    cfg_items=items, layer=likes[l], quant=quant)
                if fault == 'no_bias_grad':
                    g_lp = {k: jnp.zeros_like(g) if k.endswith('_bias')
                            else g for k, g in g_lp.items()}
                if t == 1:
                    grad_norm.update({(l, k): float(v) for k, v in
                                      _norms(g_lp).items()})
                    grad_dots.update({(l, k): np.asarray(v) for k, v in
                                      _probes(g_lp, base, l).items()})
                if fault != 'frozen':
                    lps[l], lms[l], lvs[l] = _adamw(lps[l], g_lp, lms[l],
                                                    lvs[l], tt, hp)
                xs[l + 1] = None
            g_gp = _embed_back(gp, ids, dx, g_gp, embed=ref.embed)
            if t == 1:
                grad_norm.update({(-1, k): float(v) for k, v in
                                  _norms(g_gp).items()})
                grad_dots.update({(-1, k): np.asarray(v) for k, v in
                                  _probes(g_gp, base, -1).items()})
            if fault != 'frozen':
                gp, gm, gv = _adamw(gp, g_gp, gm, gv, tt, hp)
            del xs, dx, g_gp
        change = {}
        for l, lp in enumerate([gp] + lps, start=-1):
            start = make_globals(base) if l < 0 else make_layer(base, l)
            diff = jax.tree.map(jnp.subtract, lp, start)
            change.update({(l, k): float(v)
                           for k, v in _norms(diff).items()})
    return {'loss': losses, 'grad_norm': grad_norm, 'grad_dots': grad_dots,
            'change_norm': change}


def compare(got, ref):
    """The numbers that decide a training cell, by the worst leaf.
    `grad_norm_gap` and `change_norm_gap`: the gap between the program's
    norm and the reference's, against the reference's norm of that leaf or
    of the median leaf, whichever is larger. `grad_proj_gap` is the one
    number of first order in a rounding error, and the one that sees a
    small leaf that is wholly wrong: the first gradient's difference from
    the reference's, estimated through `weights.PROBES` seeded directions
    a leaf, against that leaf's own reference norm. Leaves whose reference
    gradient is under a thousandth of the median leaf's are left out of
    the change and of the probed difference: they are nought to rounding.
    `leaves` is the table the three are the worst rows of: per leaf the
    reference's gradient norm, then the three gaps against the leaf's own
    reference norms."""
    out = {'loss_gap': max(abs(a - b) / abs(b)
                           for a, b in zip(got['loss'], ref['loss']))}
    g_ref = ref['grad_norm']
    g_med = float(np.median(list(g_ref.values())))
    live = [k for k, g in g_ref.items() if g >= 1e-3 * g_med]
    proj = {k: float(np.sqrt(np.mean(np.square(
        np.asarray(got['grad_dots'][k]) - ref['grad_dots'][k]))))
        for k in g_ref}
    c_med = float(np.median([ref['change_norm'][k] for k in live]))
    gaps = {
        'grad_norm': {k: abs(got['grad_norm'][k] - g) / max(g, g_med)
                      for k, g in g_ref.items()},
        'change_norm': {k: abs(got['change_norm'][k] - ref['change_norm'][k])
                        / max(ref['change_norm'][k], c_med) for k in live},
        'grad_proj': {k: proj[k] / g_ref[k] for k in live}}
    for name, by_leaf in gaps.items():
        worst = max(by_leaf, key=by_leaf.get)
        out[f'{name}_gap'] = by_leaf[worst]
        out[f'{name}_worst_leaf'] = f'{worst[0]}:{worst[1]}'
    out['left_out'] = sorted(f'{k[0]}:{k[1]}' for k in g_ref if k not in live)
    tiny = 1e-30
    out['leaves'] = {f'{k[0]}:{k[1]}': [
        g, abs(got['grad_norm'][k] - g) / max(g, tiny),
        proj[k] / max(g, tiny),
        abs(got['change_norm'][k] - ref['change_norm'][k])
        / max(ref['change_norm'][k], tiny)] for k, g in g_ref.items()}
    return out
