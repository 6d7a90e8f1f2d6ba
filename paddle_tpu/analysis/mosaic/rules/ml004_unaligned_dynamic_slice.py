"""ML004 — dynamic slices (`pl.ds`) at unprovably-aligned offsets.

Inside a kernel, `ref[pl.ds(start, size), :]` lowers to a VMEM slice.
On the tiled trailing dims that slice must land on tile boundaries:
the lane (minor) dim at multiples of 128, the sublane (second-minor)
dim at multiples of the dtype's sublane count.  A traced `start` the
compiler cannot prove aligned either refuses to lower or lowers to a
catastrophic per-element relayout.

The check walks every `get`/`swap` equation's NDIndexer.  A Slice on a
trailing-two dim passes when

  - its start is a constant multiple of the dim's requirement, or
  - its start is a traced value PROVABLY a multiple: a literal, a
    `mul` by an aligned literal (the `i * BLOCK` idiom), or sums/
    min/max of provable values (followed through convert_element_type),

and its size is a multiple of the requirement (or runs to the end of a
constant-start slice, or covers the whole dim).  Integer indices
(`m_scr[:, 0]`) are skipped: single-element extracts lower as scalar
reads, not slices.  `pl.multiple_of` hints are invisible in the jaxpr
— restructure to the `i * BLOCK` form or suppress in the registry.
"""
from __future__ import annotations

from ..engine import MosaicRule, iter_eqns, sublane_multiple
from . import register


def _const_val(atom):
    if isinstance(atom, int):
        return atom
    if not hasattr(atom, 'val'):        # not a jax.core.Literal
        return None
    import numpy as np

    # literals carry 0-d typed arrays (TypedNdArray(64, dtype=int32))
    val = np.asarray(atom.val)
    if val.ndim == 0 and np.issubdtype(val.dtype, np.integer):
        return int(val)
    return None


def _producers(body):
    out = {}
    for eqn in iter_eqns(body):
        for v in eqn.outvars:
            out[v] = eqn
    return out


def _provable_multiple(atom, k, producers, depth=0):
    val = _const_val(atom)
    if val is not None:
        return val % k == 0
    if depth > 8 or hasattr(atom, 'val'):
        return False                     # non-int Literal / depth cap
    eqn = producers.get(atom)
    if eqn is None:
        return False
    prim = eqn.primitive.name
    if prim in ('convert_element_type', 'squeeze', 'broadcast_in_dim'):
        return _provable_multiple(eqn.invars[0], k, producers, depth + 1)
    if prim == 'mul':
        a, b = eqn.invars[:2]
        for x in (a, b):
            v = _const_val(x)
            if v is not None and v % k == 0:
                return True
        return any(_provable_multiple(x, k, producers, depth + 1)
                   for x in (a, b))
    if prim in ('add', 'sub', 'max', 'min', 'rem'):
        return all(_provable_multiple(x, k, producers, depth + 1)
                   for x in eqn.invars[:2])
    return False


@register
class UnalignedDynamicSlice(MosaicRule):
    id = 'ML004'
    name = 'unaligned-dynamic-slice'
    severity = 'error'
    description = ('pl.ds on the tiled trailing dims needs starts/sizes '
                   'provably aligned to (sublane, 128); unprovable '
                   'traced starts fail or force relayouts.')

    def check(self, ctx):
        from jax import tree_util

        for call in ctx.calls:
            cache = {}                   # producer map built once per call
            for eqn in iter_eqns(call.body):
                if eqn.primitive.name not in ('get', 'swap'):
                    continue
                skip = 1 if eqn.primitive.name == 'get' else 2
                tree = eqn.params.get('tree')
                if tree is None:
                    continue
                try:
                    indexers = tree_util.tree_unflatten(
                        tree, eqn.invars[skip:skip + tree.num_leaves])
                except Exception:  # noqa: BLE001 - unknown layout: skip
                    continue
                ref_shape = tuple(getattr(eqn.invars[0].aval, 'shape', ()))
                ref_dtype = getattr(eqn.invars[0].aval, 'dtype', None)
                for nd in indexers:
                    indices = getattr(nd, 'indices', None)
                    if indices is None:
                        continue
                    yield from self._check_indexer(
                        ctx, call, indices, ref_shape, ref_dtype, cache)

    def _check_indexer(self, ctx, call, indices, ref_shape, ref_dtype,
                       cache):
        rank = len(indices)
        for dpos, idx in enumerate(indices):
            if not hasattr(idx, 'size'):   # int index: scalar extract
                continue
            trailing = rank - dpos         # 1 = lane, 2 = sublane
            if trailing > 2 or dpos >= len(ref_shape):
                continue
            dim = ref_shape[dpos]
            req = 128 if trailing == 1 else sublane_multiple(ref_dtype)
            start, size = idx.start, idx.size
            cstart = _const_val(start)
            if cstart == 0 and size == dim:
                continue                   # full cover
            if 'producers' not in cache:
                cache['producers'] = _producers(call.body)
            producers = cache['producers']
            axis = 'lane' if trailing == 1 else 'sublane'
            if not _provable_multiple(start, req, producers):
                where = (f'constant start {cstart}' if cstart is not None
                         else 'traced start (pl.ds)')
                yield self.violation(
                    ctx,
                    f'{call.name}: {axis}-dim slice of a '
                    f'{tuple(ref_shape)} {ref_dtype} ref has {where} '
                    f'not provably a multiple of {req}')
            size_ok = (size % req == 0 or size == dim
                       or (cstart is not None and cstart + size == dim))
            if not size_ok:
                yield self.violation(
                    ctx,
                    f'{call.name}: {axis}-dim slice size {size} of a '
                    f'{tuple(ref_shape)} {ref_dtype} ref is not a '
                    f'multiple of {req} (and does not run to the dim '
                    f'end)')
