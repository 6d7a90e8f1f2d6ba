"""SL006 — shard_map body collectives over axes the body cannot vary
over.

Inside `shard_map` the collectives are hand-written, and the classic
silent bug is a collective over the WRONG axis: `psum(x, 'tp')` where
nothing in the body varies over 'tp' multiplies every value by the
axis size; a ppermute over it is an expensive identity.  The repo's
sequence-parallel wrappers (ring attention, Ulysses) run with jax's
varying-axes checker off (`check_vma=False`), so nothing at trace
time catches it there.  This rule re-derives the check
statically from the traced jaxpr: for each shard_map equation it
collects the axes the body CAN vary over — axes an in_spec splits,
axes promoted by pvary/pcast, axes branched on via axis_index — and
errors on any psum/ppermute/all_to_all/... whose axis is

  - not a mesh axis at all (typo),
  - GSPMD-managed ('auto', not manually scheduled) — the partitioner
    owns that axis; a manual collective over it is undefined,
  - or provably constant over the body (the x-axis-size bug above).
"""
from __future__ import annotations

from ..engine import ShardRule
from . import register


@register
class ShardMapCollectives(ShardRule):
    id = 'SL006'
    name = 'shardmap-collective-axes'
    severity = 'error'
    description = ('shard_map body collectives must run over manually '
                   'scheduled mesh axes the body actually varies over '
                   '(split input, pvary, or axis_index) — anything '
                   'else is a typo, an auto-axis conflict, or a '
                   'silent x-axis-size scale bug.')

    def check(self, ctx):
        for sm in ctx.shard_maps:
            known = set(sm.mesh_axes)
            for prim, axes in sm.collectives:
                for axis in axes:
                    if axis not in known:
                        yield self.violation(
                            ctx,
                            f"{prim} over axis '{axis}' which does not "
                            f'exist in the shard_map mesh '
                            f'{sm.mesh_axes} (typo?)')
                    elif axis not in sm.manual:
                        yield self.violation(
                            ctx,
                            f"{prim} over GSPMD-managed axis '{axis}' "
                            f'(not in the shard_map\'s manual axes '
                            f'{tuple(sorted(sm.manual))}) — the '
                            f'partitioner owns it')
                    elif axis not in sm.varying:
                        yield self.violation(
                            ctx,
                            f"{prim} over axis '{axis}' but the body "
                            f'is constant over it (no in_spec splits '
                            f'it, no pvary/axis_index touches it): '
                            f'psum scales by the axis size, ppermute '
                            f'is an identity — almost certainly the '
                            f'wrong axis name')
