"""Drives `TrainEngine.prefetch/step/sync` on new batches made from the
seed by a host iterator, and hands the first three steps' readings to the
comparison with the plain reference.

Set-up builds one engine, takes steps 1 to 3 through the same call and
feed as the window (reading the first gradient's norms out of AdamW's
first moment after step 1, and the parameters' change after step 3), a few
warm steps, and then hands that same engine to the window.
"""
from __future__ import annotations

import time

import numpy as np

from benchmark.harness import loadgen

BETA1, BETA2, EPS = 0.9, 0.999, 1e-8      # AdamW's published defaults


def _leaf_ids(fam, tree):
    import jax

    return {jax.tree_util.keystr(p): fam.leaf_id(jax.tree_util.keystr(p))
            for p, _ in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _host(tree, ids):
    """{(layer, name): numpy value} of a pytree of small arrays."""
    import jax

    flat = jax.tree_util.tree_flatten_with_path(jax.device_get(tree))[0]
    return {ids[jax.tree_util.keystr(p)]: np.asarray(v) for p, v in flat}


def first_steps(fam, engine, feed, seed, n=3):
    """Steps 1..n through the window's own call and feed. Returns the
    losses, the first gradient's norm per leaf as the optimizer got it
    (its first moment after one step is (1 - beta1) g) and the norm of
    each leaf's change after step n."""
    import jax
    import jax.numpy as jnp

    from benchmark.harness import weights

    ids = _leaf_ids(fam, engine.model)
    norms = jax.jit(lambda t: jax.tree.map(
        lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), t))
    change = jax.jit(lambda model, base: jax.tree_util.tree_map_with_path(
        lambda p, x: jnp.sqrt(jnp.sum(jnp.square(
            x.astype(jnp.float32) - weights.make_leaf(
                fam, base, *ids[jax.tree_util.keystr(p)], x.shape,
                x.dtype).astype(jnp.float32)))), model))
    dots = jax.jit(lambda m, base: jax.tree_util.tree_map_with_path(
        lambda p, x: weights.probe_dots(
            base, *ids[jax.tree_util.keystr(p)], x), m))
    base = weights.base_key(seed)
    losses, grad_norm, grad_dots = [], None, None
    for step in range(1, n + 1):
        engine.step((next(feed),))
        losses.append(float(engine.sync()['loss']))
        if step == 1:
            m = engine.opt_state['slots']['m']
            grad_norm = {k: float(v) / (1.0 - BETA1)
                         for k, v in _host(norms(m), ids).items()}
            grad_dots = {k: v / (1.0 - BETA1)
                         for k, v in _host(dots(m, base), ids).items()}
    change_norm = {k: float(v) for k, v in
                   _host(change(engine.model, base), ids).items()}
    return {'loss': losses, 'grad_norm': grad_norm, 'grad_dots': grad_dots,
            'change_norm': change_norm}


def kernel_names(engine, traffic):
    """{program: {pallas module: instruction names}} of the fused step,
    with its memory_analysis() printed. In a function of its own so that
    nothing here keeps the engine's arrays alive."""
    from paddle_tpu.aot import geometry

    from benchmark.harness import programs

    kernels = {}
    shape = (traffic['batch'], traffic['seq'] + 1)
    for g in geometry.for_train_engine(engine, shape):
        for fn, a, kw in engine._cost_specs(g):
            d = programs.describe(g.label(), fn, a, kw)
            kernels[d['module']] = d['kernels']
            print(f'{d["label"]}: needs {d["needs_gib"]:.2f} GiB '
                  f'(arguments {d["arguments_gib"]:.2f}, temporaries '
                  f'{d["temporaries_gib"]:.2f})', flush=True)
    return kernels


def run_cell(cell, cfg, traffic, env, control=False):

    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.training.engine import TrainEngine

    from benchmark.harness import common, verdict
    from benchmark.reference import train_ref

    seconds = (min(env.seconds, cell['trace_seconds']) if env.trace
               else env.seconds)
    opt = cell['optimizer']
    fam = common.family(cfg)
    model = fam.make_model(cfg, env.seed, traffic['seq'])
    engine = TrainEngine(
        model, AdamW(learning_rate=opt['learning_rate'],
                     weight_decay=opt['weight_decay'], beta1=BETA1,
                     beta2=BETA2, epsilon=EPS), log_window=10 ** 9)
    del model
    kept = []

    def host_batches():
        for batch in loadgen.token_batches(traffic, cfg['vocab_size'],
                                           env.seed):
            if len(kept) < 3:
                kept.append(batch)
            yield batch

    feed = iter(engine.prefetch(host_batches()))
    got = first_steps(fam, engine, feed, env.seed)
    for _ in range(cell['warm_steps']):
        engine.step((next(feed),))
    engine.sync()
    print(f'first losses {got["loss"]}; {env.compiles.misses} programs '
          f'compiled, {env.compiles.hits} from the cache', flush=True)
    tokens = traffic['batch'] * traffic['seq']
    profile = common.Profile(env.trace_dir) if env.trace else None
    setup_s = time.perf_counter() - env.t_start
    compiles = env.compiles.requests
    if profile:
        profile.start()
    t0, steps, slowest, mark = time.perf_counter(), 0, 0.0, 0.0
    while True:
        for _ in range(cell['sync_every']):
            with common.span('bench.step'):
                engine.step((next(feed),))
            steps += 1
        with common.span('bench.sync'):
            last = engine.sync()['loss']
        elapsed = time.perf_counter() - t0
        slowest, mark = max(slowest, elapsed - mark), elapsed
        if elapsed >= seconds:
            break
    if profile:
        profile.stop()
    in_window = env.compiles.requests - compiles
    peak_bytes = common.memory_peak([env.device])
    print(f'{steps} steps of {tokens} tokens in {elapsed:.3f} s (the slowest '
          f'{cell["sync_every"]} between two syncs took {slowest:.3f} s); '
          f'last loss {last:.4f}', flush=True)
    if in_window:
        raise SystemExit(f'benchmark: {in_window} program(s) went through '
                         f'the compiler inside the window')
    metrics = {'setup_s': {'value': setup_s, 'unit': 's'},
               'train_tok_s': {'value': steps * tokens / elapsed,
                               'unit': 'tokens/s'}}
    device = common.device_line(env, cell['chips'], peak_bytes)
    line = {}
    if profile:
        metrics, device, line['breakdown'] = common.traced_line(
            env, cell['chips'], profile, peak_bytes,
            {'cfg': cfg, 'seconds': elapsed, 'train_steps': steps,
             'batch': traffic['batch'], 'seq': traffic['seq'],
             'kernels': kernel_names(engine, traffic)})
    feed.close()
    del engine, feed
    common.free_device()
    hp = (opt['learning_rate'], opt['weight_decay'], BETA1, BETA2, EPS)
    t_ref = time.perf_counter()
    ref = train_ref.run(fam, cfg, env.seed, kept, hp)
    numbers = train_ref.compare(got, ref)
    held = verdict.Verdict()
    for name, limit in cell['limits'].items():
        held.hold(name, numbers[name], limit)
    held.hold('nonfinite_loss', 0 if np.isfinite(last) else 1, 0)
    print(f'reference took {time.perf_counter() - t_ref:.1f} s; its losses '
          f'{ref["loss"]}; worst leaves: gradient '
          f'{numbers["grad_norm_worst_leaf"]}, change '
          f'{numbers["change_norm_worst_leaf"]}, probed difference '
          f'{numbers["grad_proj_worst_leaf"]}; left out: '
          f'{numbers["left_out"]}', flush=True)
    if control:
        # the control and each planted fault in the program's place: the
        # reference's own steps at the lower precision, or broken, held to
        # the cell's limits as the program's are
        readings = {
            name: train_ref.compare(train_ref.run(fam, cfg, env.seed, kept,
                                                  hp, **how), ref)
            for name, how in [(cell['control'], {'quant': cell['control']})]
            + [(f, {'fault': f}) for f in fam.reference.faults(cfg)]}
        line['control'] = verdict.judged(readings, cell['limits'])
        line['leaves'] = {'program': numbers['leaves'],
                          **{n: r['leaves'] for n, r in readings.items()}}
    held.report()
    return {'correct': held.correct, 'attempted': steps, 'failed': 0,
            'metrics': metrics, 'device': device, **line,
            'compared': held.compared()}
