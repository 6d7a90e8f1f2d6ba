"""SL002 — collective census vs the suite's declared communication
budget.

The #1 multichip perf killer is a collective nobody asked for: GSPMD
inserts an all-gather of a sharded weight inside the decode loop
because one activation constraint went missing, and tok/s quietly
drops 10x — on the chip, where nobody is looking.  Every registered suite
therefore DECLARES its communication budget ({kind: count} or
{kind: {'count': n, 'bytes': b}}, per-device call-site payloads as
counted by `collective_census`), and this rule errors on:

  - an emitted collective kind the budget does not declare at all,
  - more call sites of a kind than declared,
  - more payload bytes of a kind than the declared byte ceiling,

and warns when a declared kind no longer occurs (stale budget — the
suite got cheaper, ratchet the declaration down).  `budget=None` opts
a suite out (fixtures); `budget={}` means "zero collectives allowed".
"""
from __future__ import annotations

from ..engine import ShardRule
from . import register


def _norm(budget):
    out = {}
    for kind, v in budget.items():
        if isinstance(v, dict):
            out[kind] = {'count': int(v.get('count', 0)),
                         'bytes': v.get('bytes')}
        else:
            out[kind] = {'count': int(v), 'bytes': None}
    return out


def _mb(n):
    return n / (1024 * 1024)


@register
class CommBudget(ShardRule):
    id = 'SL002'
    name = 'communication-budget'
    severity = 'error'
    description = ('the post-SPMD collective census (kind x call '
                   'sites x per-device bytes) must stay within the '
                   "suite's declared communication budget; undeclared "
                   'collectives error, unused declarations warn.')

    def check(self, ctx):
        budget = ctx.entry.budget
        if budget is None or ctx.census is None:
            return
        budget = _norm(budget)
        for kind, rec in sorted(ctx.census.items()):
            declared = budget.get(kind)
            if declared is None:
                yield self.violation(
                    ctx,
                    f'undeclared collective: {rec["count"]} {kind} '
                    f'call site(s) ({_mb(rec["bytes"]):.2f} MB/device) '
                    f'with no {kind} entry in the communication '
                    f'budget — declare it or kill the resharding that '
                    f'introduced it')
                continue
            if rec['count'] > declared['count']:
                yield self.violation(
                    ctx,
                    f'{kind} over budget: {rec["count"]} call site(s) '
                    f'vs {declared["count"]} declared')
            if (declared['bytes'] is not None
                    and rec['bytes'] > declared['bytes']):
                yield self.violation(
                    ctx,
                    f'{kind} payload over budget: '
                    f'{_mb(rec["bytes"]):.2f} MB/device vs '
                    f'{_mb(declared["bytes"]):.2f} MB declared')
        for kind, declared in sorted(budget.items()):
            if declared['count'] > 0 and kind not in ctx.census:
                yield self.violation(
                    ctx,
                    f'declared {kind} budget '
                    f'({declared["count"]} site(s)) is unused — the '
                    f'suite got cheaper; ratchet the declaration down',
                    severity='warning')
