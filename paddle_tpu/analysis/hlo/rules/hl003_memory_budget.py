"""HL003 — peak device memory per program vs the suite's HBM budget.

Without this rule the first time a role-aware AOT geometry
set meets real HBM is in production — and an import-fed decode pool
that fits at ctx=512 can OOM at ctx=2048 purely from the temp buffers
XLA materialises for the gather/scatter, which no jaxpr-level analyzer
sees. The compiled memory analysis (argument + output + temp bytes)
is the closest static proxy for on-chip peak that exists off-chip, so
every registered suite DECLARES a byte budget and this rule holds
every program of the suite under it:

  - peak over budget: error (the geometry will not fit — shrink it or
    re-budget consciously),
  - peak inside the top quarter of the budget (>= 75%): warning (the
    next bucket up probably does not fit — headroom is about to run
    out),
  - no budget declared on a registered suite: error — an un-budgeted
    geometry is exactly the silent-OOM this rule exists to prevent.

Budgets are declared at the suite's own (tiny, CPU-compiled) shapes:
the structure of the memory bill — which temps XLA keeps live — is
what the rule pins; absolute chip-scale numbers are the job of a
run on the chip.
"""
from __future__ import annotations

from ..engine import HloRule
from . import register

WARN_FRACTION = 0.75


def _mb(n):
    return n / (1024 * 1024)


@register
class MemoryBudget(HloRule):
    id = 'HL003'
    name = 'memory-budget'
    severity = 'error'
    description = ('peak device memory (argument+output+temp bytes of '
                   'the compiled module) of every program must stay '
                   "under the suite's declared HBM budget; undeclared "
                   'budgets error.')

    def check(self, ctx):
        budget = ctx.entry.hbm_budget
        if budget is None:
            yield self.violation(
                ctx,
                'no hbm_budget declared — every registered suite must '
                'budget its peak device memory (measure once with '
                '`hlolint --format json`, declare with headroom)')
            return
        budget = int(budget)
        for a in ctx.programs:
            peak = a.peak_bytes()
            if not a.memory:
                yield self.violation(
                    ctx,
                    f'{a.label}: compiled memory analysis unavailable '
                    f'— the budget cannot be checked on this backend',
                    severity='warning')
                continue
            if peak > budget:
                yield self.violation(
                    ctx,
                    f'{a.label}: peak device memory {_mb(peak):.2f} MB '
                    f'exceeds the declared {_mb(budget):.2f} MB budget '
                    f'— this geometry will not fit; shrink it or '
                    f're-budget consciously')
            elif peak >= WARN_FRACTION * budget:
                yield self.violation(
                    ctx,
                    f'{a.label}: peak device memory {_mb(peak):.2f} MB '
                    f'is inside the top quarter of the '
                    f'{_mb(budget):.2f} MB budget — headroom is about '
                    f'to run out',
                    severity='warning')
