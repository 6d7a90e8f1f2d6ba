"""Drives `ServingEngine.submit/step/result` under an open or a closed
loop, from the client's side, and times every token's delivery.

One thread. Before each `step()` every request now due is submitted; after
it returns, each live request's newly committed tokens are stamped with
this module's clock (`len(request.generated)`, read only: the engine has
no public per-step delivery hook yet).
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from benchmark.harness import loadgen

TERMINAL = ('finished', 'failed', 'expired', 'cancelled')


@dataclasses.dataclass
class Record:
    plan: loadgen.Planned
    rid: int
    req: object                     # the engine's request, read only
    submitted: float
    seen: int = 0
    stamps: list = dataclasses.field(default_factory=list)   # (t, first, n)
    done: float | None = None
    client: int | None = None

    def token_times(self):
        return [t for t, _, n in self.stamps for _ in range(n)]


def build_engine(fam, cfg, geometry, seed):
    """The engine at the cell's geometry, every key of it the engine's own
    option (`tp` only over 1), over the family's model with weights made
    on the device from the seed."""
    from paddle_tpu.inference.serving import ServingEngine

    model = fam.make_model(cfg, seed, geometry['max_context_len'])
    return ServingEngine(model, **{k: v for k, v in geometry.items()
                                   if k != 'tp' or v > 1})


def warm(engine, buckets):
    """Every program the cell's buckets can dispatch: the fused admit +
    decode step and the standalone prefill per bucket, and the window."""
    from paddle_tpu.aot import geometry

    return engine.warmup(geometries=geometry.for_serving_engine(
        engine, prompt_lens=list(buckets)))


class OpenSource:
    """Arrivals on a schedule, whatever the system does."""

    def __init__(self, plan, seconds):
        self.plan = sorted(plan, key=lambda p: p.due)
        self.next, self.seconds = 0, seconds

    def due(self, now):
        out = []
        while self.next < len(self.plan) and self.plan[self.next].due <= now:
            out.append((self.plan[self.next], None))
            self.next += 1
        return out

    def next_due(self):
        return (self.plan[self.next].due if self.next < len(self.plan)
                else None)

    def finished(self, record, now):
        pass

    def first_due(self):
        return self.plan[0].due


class ClosedSource:
    """`clients` callers that each send their next request when the last
    is answered, until the window closes."""

    def __init__(self, supply, clients, lead_in, seconds):
        self.supply, self.seconds = supply, seconds
        self.idle = [(-float(lead_in), c) for c in range(clients)]

    def due(self, now):
        ready = [(t, c) for t, c in self.idle if t <= now]
        self.idle = [x for x in self.idle if x[0] > now]
        out = []
        for t, c in ready:
            if t >= self.seconds:
                continue                       # the window has closed
            p = next(self.supply)
            p.due, p.measured = t, t >= 0.0
            out.append((p, c))
        return out

    def next_due(self):
        live = [t for t, _ in self.idle if t < self.seconds]
        return min(live) if live else None

    def finished(self, record, now):
        self.idle.append((now, record.client))

    def first_due(self):
        return min(t for t, _ in self.idle)


def drive(engine, source, seconds, drain_limit, annotate=None,
          on_window=None):
    """Runs lead-in, window and drain. Time 0 is the window's first
    instant. `on_window(opening: bool)` is called at its two ends, off the
    clock: what it takes (the profiler's start and stop) is neither the
    window's nor the drain's.
    Returns (records, steps [(t0, t1)], lateness [s])."""
    annotate, clock = annotate or _no_span, time.perf_counter
    origin = clock() - source.first_due()
    records, live, steps, late = [], [], [], []
    opened = closed = False

    def window_end(opening):
        """`on_window` with the clock stopped; returns the time after."""
        nonlocal origin
        if on_window:
            t = clock()
            on_window(opening)
            origin += clock() - t
        return clock() - origin

    while True:
        now = clock() - origin
        if not opened and now >= 0.0:
            opened, now = True, window_end(True)
        if opened and not closed and now >= seconds:
            closed, now = True, window_end(False)
        if closed and now > seconds + drain_limit:
            break
        with annotate('bench.submit'):
            for plan, client in source.due(now):
                if plan.due >= seconds:
                    continue
                rid = engine.submit(plan.prompt, plan.new_tokens)
                rec = Record(plan, rid, engine._live[rid], now,
                             client=client)
                late.append(now - plan.due)
                records.append(rec)
                live.append(rec)
        if not live:
            nxt = source.next_due()
            if nxt is None or nxt >= seconds:
                if closed:
                    break
                nxt = seconds
            with annotate('bench.wait'):
                time.sleep(max(0.0, min(nxt - (clock() - origin), 0.002)))
            continue
        with annotate('bench.step'):
            t0 = clock() - origin
            engine.step()
            t1 = clock() - origin
        steps.append((t0, t1))
        with annotate('bench.stamp'):
            still = []
            for rec in live:
                n = len(rec.req.generated)
                if n > rec.seen:
                    rec.stamps.append((t1, rec.seen, n - rec.seen))
                    rec.seen = n
                if rec.req.state in TERMINAL:
                    rec.done = t1
                    source.finished(rec, t1)
                else:
                    still.append(rec)
            live = still
    return records, steps, late


class _no_span:
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def collect(engine, records):
    """Hands every terminal request's outcome over, once: the ids for a
    finished one, None for one that failed or never ended."""
    from paddle_tpu.inference.serving import RequestError

    outs = {}
    for rec in records:
        try:
            out = engine.result(rec.rid)
        except (RequestError, KeyError):
            out = None
        outs[rec.rid] = None if out is None else np.asarray(out)
    return outs


def p95(values):
    return float(np.percentile(np.asarray(values, np.float64), 95))


def end_to_end(records, outs, seconds, drain_limit):
    """The window's user-visible numbers, over every request due in it.
    One that failed, was refused or never finished counts as failed and
    as the worst: the drain limit past the window's end."""
    measured = [r for r in records if r.plan.measured]
    worst = seconds + drain_limit
    ttft, gaps, failed = [], [], 0
    for r in measured:
        ok = outs.get(r.rid) is not None and r.seen == r.plan.new_tokens
        failed += not ok
        times = r.token_times()
        ttft.append(times[0] - r.plan.due if ok else worst - r.plan.due)
        gaps += list(np.diff(times)) if ok else [worst]
    served = 0
    for r in records:
        for t, first, n in r.stamps:
            if 0.0 <= t < seconds:
                served += n + (len(r.plan.prompt) if first == 0 else 0)
    out = {'attempted': len(measured), 'failed': failed,
           'serve_tok_s': served / seconds, 'gaps': gaps}
    if measured:
        out['ttft_p95_s'] = p95(ttft)
        out['itl_p95_ms'] = 1e3 * p95(gaps)
    return out


def deliveries(records, t0, t1):
    """(prompt_len, first, n) of every delivery stamped in [t0, t1): the
    request log that the FLOP and byte counts read."""
    return [(len(r.plan.prompt), first, n) for r in records
            for t, first, n in r.stamps if t0 <= t < t1]


def kernel_names(engine, buckets):
    """{program: {pallas module: instruction names}} over the programs the
    cell's buckets dispatch (served from the compile cache). In a function
    of its own so that nothing here keeps the engine's arrays alive."""
    from paddle_tpu.aot import geometry

    from benchmark.harness import programs

    kernels = {}
    for g in geometry.for_serving_engine(engine, prompt_lens=list(buckets)):
        for fn, a, kw in engine._cost_specs(g):
            d = programs.describe(g.label(), fn, a, kw)
            for k, names in d['kernels'].items():
                kernels.setdefault(d['module'], {}).setdefault(
                    k, set()).update(names)
    return kernels


def sample_for_check(records, outs, seed, k):
    """k finished window requests drawn from the seed, the longest among
    them, as (prompt, ids handed over, new tokens asked for)."""
    ok = [r for r in records if r.plan.measured and outs.get(r.rid)
          is not None and r.seen == r.plan.new_tokens]
    if not ok:
        return []
    rng = np.random.default_rng(int(seed) + 1)
    longest_one = max(ok, key=lambda r: len(r.plan.prompt) + r.seen)
    rest = [r for r in ok if r is not longest_one]
    picked = [longest_one] + [rest[i] for i in rng.permutation(len(rest))[
        :max(0, k - 1)]]
    return [(r.plan.prompt, outs[r.rid], r.plan.new_tokens) for r in picked]


def run_cell(cell, cfg, traffic, env, control=False):
    """Set-up, lead-in, window, drain, then the comparison that decides
    `correct`. Returns the result line as a dict."""
    from benchmark.harness import common, verdict
    from benchmark.reference import serve_ref

    seconds = (min(env.seconds, cell['trace_seconds']) if env.trace
               else env.seconds)
    fam, vocab = common.family(cfg), cfg['vocab_size']
    engine = build_engine(fam, cfg, cell['geometry'], env.seed)
    report = warm(engine, traffic['buckets'])
    print(f'warmed {report["geometries"]} geometries in '
          f'{report["seconds"]} s ({env.compiles.misses} compiled, '
          f'{env.compiles.hits} from the cache)', flush=True)
    if traffic['loop'] == 'open':
        source = OpenSource(
            loadgen.open_loop(traffic, vocab, env.seed, seconds), seconds)
    else:
        source = ClosedSource(
            loadgen.closed_loop(traffic, vocab, env.seed),
            traffic['clients'], traffic['lead_in_s'], seconds)
    profile = common.Profile(env.trace_dir) if env.trace else None
    marks = {}

    def on_window(opening):
        if opening:
            marks['setup_s'] = time.perf_counter() - env.t_start
            marks['compiles'] = env.compiles.requests
            if profile:
                profile.start()
        elif profile:
            profile.stop()

    records, steps, late = drive(
        engine, source, seconds, traffic['drain_limit_s'],
        annotate=common.span, on_window=on_window)
    in_window = env.compiles.requests - marks['compiles']
    outs = collect(engine, records)
    e2e = end_to_end(records, outs, seconds, traffic['drain_limit_s'])
    peak_bytes = common.memory_peak([env.device])
    print(f'{len(records)} requests sent ({e2e["attempted"]} due in the '
          f'window, {e2e["failed"]} failed), {len(steps)} steps; the '
          f'generator ran late by {1e3 * float(np.mean(late)):.2f} ms on '
          f'average, {1e3 * float(np.max(late)):.2f} ms at worst', flush=True)
    if in_window:
        raise SystemExit(f'benchmark: {in_window} program(s) went through '
                         f'the compiler inside the window or the drain')
    metrics = {'setup_s': {'value': marks['setup_s'], 'unit': 's'}}
    for name, unit in (('ttft_p95_s', 's'), ('itl_p95_ms', 'ms'),
                       ('serve_tok_s', 'tokens/s')):
        if name in cell['end_to_end']:
            metrics[name] = {'value': e2e[name], 'unit': unit}
    device = common.device_line(env, cell['chips'], peak_bytes)
    line = {}
    if profile:
        metrics, device, line['breakdown'] = common.traced_line(
            env, cell['chips'], profile, peak_bytes,
            {'cfg': cfg, 'seconds': seconds,
             'steps': [s for s in steps if 0.0 <= s[0] < seconds],
             'deliveries': deliveries(records, 0.0, seconds),
             'gaps': e2e['gaps'],
             'kernels': kernel_names(engine, traffic['buckets'])})
    sample = sample_for_check(records, outs, env.seed, cell['check_requests'])
    del engine, source
    common.free_device()
    held = verdict.Verdict()
    pad_to = -(-loadgen.longest(traffic) // 128) * 128
    limit = cell['limits']['served_logit_gap']
    if sample:
        t_ref = time.perf_counter()
        got = serve_ref.served_gaps(
            fam, cfg, env.seed, [(p, o[len(p):]) for p, o, _ in sample],
            pad_to, control=cell['control'] if control else None)
        held.hold('served_logit_gap', got['served_gap'], limit)
        wrong = sum(len(o) != len(p) + n or not np.array_equal(o[:len(p)], p)
                    for p, o, n in sample)
        held.hold('wrong_prompt_echo', wrong, 0)
        if control:
            # the control in the program's place: the tokens the lower
            # precision puts first, held to the cell's own limit
            line['control'] = verdict.judged(
                {cell['control']: {'served_logit_gap': got['control_gap']}},
                {'served_logit_gap': limit})
        line['served_tokens_checked'] = got['served_tokens']
        print(f'reference over {len(sample)} requests took '
              f'{time.perf_counter() - t_ref:.1f} s', flush=True)
    else:
        held.hold('requests_finished_in_window', float('nan'), 0)
    held.report()
    return {'correct': held.correct, 'attempted': e2e['attempted'],
            'failed': e2e['failed'], 'metrics': metrics, 'device': device,
            **line, 'compared': held.compared()}
