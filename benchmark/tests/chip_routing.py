#!/usr/bin/env python3
"""Routing near a tie, measured: bfloat16 and float32 can choose another
fourth expert where `s + b` nearly ties, and where one of the two is held
on this chip the token's hidden state moves by a whole expert's part.

Serves a traffic mix for a few seconds at this script's geometry (that of
the cell `trinity_ep8_reason_decode`), takes the sample of finished
requests a benchmark run would check, and reads, over their served tokens and
every expert layer: the share of (token, layer) top-k sets on which the
program (its own model, teacher-forced over the served tokens, in its own
precision) agrees with the float32 reference, and the share on which the
set of experts HELD here agrees; the same for the control precision; and
the served tokens' logit gaps split by whether a held expert flipped. The
program's choices are read, never handed to the reference. One JSON line;
not part of a benchmark run.

    python benchmark/tests/chip_routing.py --config trinity-large-preview --traffic reason_decode --seed 11 --seconds 8
"""
import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def reference_pass(fam, cfg, seed, ids, quant):
    """(logits (R, S, V), [sorted chosen experts (R*S, k) per expert
    layer]) of the plain reference, its router's choices recorded."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import weights

    ref, chosen = fam.reference, []
    route, k = ref.route, cfg['num_experts_per_tok']

    def layer_forward(lp, x, like):
        seen = []

        def recording(cfg_, lp_, m):
            w = route(cfg_, lp_, m)
            seen.append(jnp.sort(jax.lax.top_k(w, k)[1], -1))
            return w

        ref.route = recording
        try:
            return ref.layer_forward(cfg, lp, x, like, quant), seen
        finally:
            ref.route = route

    base = weights.base_key(seed)
    with jax.default_matmul_precision('highest'):
        gp = weights.make_globals(fam, base, cfg)
        x = ref.embed(gp, ids)
        for layer in range(cfg['num_hidden_layers']):
            like = fam.layer_like(cfg, layer)
            lp = jax.jit(lambda b, l: weights.make_layer(
                fam, b, cfg, l, like))(base, layer)
            x, seen = jax.jit(layer_forward, static_argnums=2)(lp, x, like)
            chosen += seen
        logits = ref.logits(cfg, gp, x, quant)
    return np.asarray(logits), [np.asarray(c).reshape(-1, k) for c in chosen]


def program_pass(model, ids):
    """The same of the program's own model, one uncached forward."""
    import jax.numpy as jnp

    from paddle_tpu.distributed import moe

    chosen, route = [], moe.ExpertShare.route

    def recording(self, tokens):
        w, idx = route(self, tokens)
        chosen.append(jnp.sort(idx, -1))
        return w, idx

    moe.ExpertShare.route = recording
    try:
        logits = model(ids).astype(jnp.float32)
    finally:
        moe.ExpertShare.route = route
    return np.asarray(logits), [np.asarray(c) for c in chosen]


def agreement(got, want, real, first, held):
    """Shares of real (token, layer) pairs on which the chosen sets, and
    the sets of experts held here, are the reference's; and per token
    whether a held expert flipped in any layer."""
    def local(sets):
        inside = (sets >= first) & (sets < first + held)
        return np.where(inside, sets, -1)

    same = np.stack([(g == w).all(-1) for g, w in zip(got, want)])[:, real]
    same_held = np.stack([
        (np.sort(local(g), -1) == np.sort(local(w), -1)).all(-1)
        for g, w in zip(got, want)])[:, real]
    return float(same.mean()), float(same_held.mean()), ~same_held.all(0)


def named(common, kind, value):
    """A name under benchmark/<kind>, or a file (the CPU rehearsal's)."""
    if not os.path.exists(value):
        return common.load(kind, value)
    with open(value) as f:
        return json.load(f)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument('--config', required=True)
    ap.add_argument('--traffic', required=True, help='a closed-loop mix')
    ap.add_argument('--seed', type=int, default=11)
    ap.add_argument('--seconds', type=float, default=8)
    ap.add_argument('--slots', type=int, default=64)
    ap.add_argument('--context', type=int, default=1024)
    ap.add_argument('--check', type=int, default=4,
                    help='requests in the sample')
    ap.add_argument('--control', default='int8')
    args = ap.parse_args()

    import jax.numpy as jnp

    from benchmark.harness import common, loadgen, serve_driver

    cfg = named(common, 'configs', args.config)
    traffic = named(common, 'traffic', args.traffic)
    fam, seed = common.family(cfg), args.seed
    geometry = {'max_slots': args.slots, 'block_size': 16,
                'max_context_len': args.context, 'decode_window': 8,
                'max_new_tokens': traffic['output']['max']}
    engine = serve_driver.build_engine(fam, cfg, geometry, seed)
    serve_driver.warm(engine, traffic['buckets'])
    source = serve_driver.ClosedSource(
        loadgen.closed_loop(traffic, cfg['vocab_size'], seed),
        traffic['clients'], traffic['lead_in_s'], args.seconds)
    records, _, _ = serve_driver.drive(engine, source, args.seconds,
                                       traffic['drain_limit_s'])
    outs = serve_driver.collect(engine, records)
    sample = serve_driver.sample_for_check(records, outs, seed,
                                           args.check)
    model = engine.model
    del engine, source
    common.free_device()            # the pools go, the model stays
    pad_to = -(-loadgen.longest(traffic) // 128) * 128
    ids = np.zeros((len(sample), pad_to), np.int32)
    real = np.zeros((len(sample), pad_to), bool)    # positions that chose
    for r, (p, o, _) in enumerate(sample):          # a served token
        ids[r, :len(o)] = o
        real[r, len(p) - 1:len(o) - 1] = True
    nxt = np.roll(ids, -1, 1)
    plog, pchosen = program_pass(model, jnp.asarray(ids))
    del model
    common.free_device()
    rlog, rchosen = reference_pass(fam, cfg, seed, jnp.asarray(ids), None)
    clog, cchosen = reference_pass(fam, cfg, seed, jnp.asarray(ids),
                                   args.control)
    flat = real.reshape(-1)
    first, held = cfg['expert_offset'], cfg['num_experts']

    def gaps(tokens):
        return (rlog.max(-1) - np.take_along_axis(
            rlog, tokens[..., None], -1)[..., 0]).reshape(-1)[flat]

    out = {'tokens': int(flat.sum()), 'layers': len(rchosen)}
    for name, chosen, tokens in (
            ('program', pchosen, nxt),              # the tokens it served
            (args.control, cchosen, clog.argmax(-1))):
        sets, sets_held, flipped = agreement(chosen, rchosen, flat, first,
                                             held)
        g = gaps(tokens)
        out[name] = {
            'sets_agree_share': sets, 'held_sets_agree_share': sets_held,
            'tokens_with_a_held_flip': int(flipped.sum()),
            'gap_max': float(g.max()),
            'gap_max_no_held_flip': float(g[~flipped].max()),
            'gap_p99': float(np.quantile(g, 0.99)),
            'gap_p50': float(np.quantile(g, 0.5))}
    # the program's own first choices, teacher-forced, against the tokens
    # the engine served: the two paths of one model
    out['program']['uncached_first_choice_is_the_served_token'] = float(
        (plog.argmax(-1) == nxt)[real].mean())
    print(json.dumps(out), flush=True)


if __name__ == '__main__':
    main()
