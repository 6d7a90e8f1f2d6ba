"""One general traffic generator; a traffic mix is a data file it reads.

Every seed gets the same work in another order. Lengths and gaps between
arrivals (a Poisson process's, so exponential) are the quantiles of the
mix's distributions at evenly spaced points; one fixed shuffle (SCHEDULE)
makes them a cycle of (gap, prompt length, output length), and `--seed`
chooses where in the cycle the window starts and draws the token ids. The
lead-in is the stretch of the cycle just before the window. So a run
differs from another in its inputs and its phase, not in what meets what: a
tail read off one window is the tail of this one schedule, not of every
order the mix could arrive in (PERF.md, section 2, has what other shuffles
read), and is steady enough to hold a later PR to.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np

SCHEDULE = 24       # seeds the one shuffle that every mix's cycle is cut from


@dataclasses.dataclass
class Planned:
    due: float              # seconds from the window's first instant
    prompt: np.ndarray      # int32 ids
    new_tokens: int
    measured: bool          # due inside the window


def quantiles(spec, n):
    """n values of the distribution `spec`, at (i + 1/2) / n."""
    u = (np.arange(n) + 0.5) / n
    if spec['dist'] == 'lognormal':
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in u])
        x = spec['median'] * np.exp(spec['sigma'] * z)
    elif spec['dist'] == 'uniform':
        x = spec['min'] + u * (spec['max'] - spec['min'])
    elif spec['dist'] == 'exponential':
        x = -np.log1p(-u)
    else:
        raise ValueError(f'unknown distribution {spec["dist"]!r}')
    if 'min' in spec:
        x = np.clip(x, spec['min'], spec['max'])
    return x


def _cycle(traffic, n, start):
    """The mix's n (gap, prompt length, new tokens) in the schedule's
    order, the cycle turned to begin at `start`. Gaps have mean 1."""
    order = np.random.default_rng(SCHEDULE)
    gaps = order.permutation(quantiles({'dist': 'exponential'}, n))
    prompts = np.rint(order.permutation(quantiles(traffic['prompt'], n)))
    outs = np.rint(order.permutation(quantiles(traffic['output'], n)))
    turn = lambda a: np.roll(a, -int(start) % n)           # noqa: E731
    return turn(gaps / gaps.mean()), turn(prompts), turn(outs)


def open_loop(traffic, vocab, seed, seconds):
    """The window's arrivals over [0, seconds) at `rate_rps` and, before
    them, the lead-in: the arrivals that precede the window in the cycle,
    reaching back `lead_in_s`."""
    rng = np.random.default_rng(int(seed))
    n = max(1, round(traffic['rate_rps'] * seconds))
    gaps, prompts, outs = _cycle(traffic, n, rng.integers(n))
    gaps = gaps / traffic['rate_rps']
    due = np.cumsum(gaps) - gaps
    lead = min(n, max(1, round(traffic['rate_rps'] * traffic['lead_in_s'])))
    before = -np.cumsum(gaps[::-1][:lead])[::-1]
    plan = []
    for t, i, measured in (
            [(t, n - lead + j, False) for j, t in enumerate(before)]
            + [(t, i, True) for i, t in enumerate(due)]):
        plan.append(Planned(
            float(t), rng.integers(0, vocab, int(prompts[i])).astype(
                np.int32), int(outs[i]), measured))
    return plan


def closed_loop(traffic, vocab, seed):
    """An endless supply for `clients` callers that each wait for their
    answer: the mix's cycle of `pool` requests, begun where the seed says."""
    rng = np.random.default_rng(int(seed))
    n = traffic['pool']
    _, prompts, outs = _cycle(traffic, n, rng.integers(n))
    while True:
        for p, o in zip(prompts, outs):
            yield Planned(0.0, rng.integers(0, vocab, int(p)).astype(
                np.int32), int(o), True)


def token_batches(traffic, vocab, seed):
    """Training batches of (batch, seq + 1) ids, a new one each step, rows
    that all differ, made on the host so that the input path is timed."""
    rng = np.random.default_rng(int(seed))
    shape = (traffic['batch'], traffic['seq'] + 1)
    while True:
        yield rng.integers(0, vocab, shape).astype(np.int32)


def longest(traffic):
    """The most tokens one request of the mix can hold."""
    return sum(math.ceil(traffic[k]['max']) for k in ('prompt', 'output'))
