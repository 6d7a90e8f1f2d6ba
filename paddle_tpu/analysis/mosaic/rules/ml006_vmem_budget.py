"""ML006 — per-pallas_call VMEM budget vs the limit the call runs under.

Every input/output block lives in VMEM twice (the pallas pipeline
double-buffers: the DMA for grid step i+1 overlaps compute on step i)
and scratch lives there once.  A kernel whose working set exceeds the
~16 MB core VMEM fails allocation at compile time on the chip — after
interpret mode happily ran it.

The estimate is blocks*2 + scratch, the same arithmetic the kernels'
own `_pick_block`/`_block_rows` budget comments use.  It undercounts
compiler temporaries (dequant copies, relayouts), so the rule warns
from 75% of the limit and errors past 100%.  bench.py stamps the
per-kernel estimates into its detail blob so footprint regressions
show up in the bench history, not just at the gate.

~16 MB is what Mosaic gives a call that asks for nothing. A call that
states its own `CompilerParams(vmem_limit_bytes=...)` (a weight stream
in blocks of several MB, `ops/pallas/grouped_matmul.py`) is held to
that, and never to more than a core has (`VMEM_BYTES_PHYSICAL`).
"""
from __future__ import annotations

from ..engine import MosaicRule
from . import register

WARN_FRACTION = 0.75


def _mb(n):
    return n / (1024 * 1024)


@register
class VmemBudget(MosaicRule):
    id = 'ML006'
    name = 'vmem-budget'
    severity = 'error'
    description = ('estimated VMEM working set (double-buffered blocks '
                   '+ scratch) must fit the ~16 MB/core budget; warns '
                   'from 75%.')

    def check(self, ctx):
        for call in ctx.calls:
            est, budget = call.vmem_estimate(), call.vmem_budget()
            if est > budget:
                yield self.violation(
                    ctx,
                    f'{call.name}: estimated VMEM working set '
                    f'{_mb(est):.1f} MB (2x blocks + scratch) exceeds '
                    f'the ~{_mb(budget):.0f} MB/core '
                    f'budget — shrink the blocks')
            elif est > WARN_FRACTION * budget:
                yield self.violation(
                    ctx,
                    f'{call.name}: estimated VMEM working set '
                    f'{_mb(est):.1f} MB is within '
                    f'{100 * (1 - WARN_FRACTION):.0f}% of the '
                    f'~{_mb(budget):.0f} MB/core budget — '
                    f'compiler temporaries may tip it over',
                    severity='warning')
