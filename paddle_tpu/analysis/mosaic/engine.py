"""The mosaiclint engine: trace kernels, extract pallas_calls, run rules.

tracelint proves source-level contracts with `ast`; this engine proves
Mosaic/TPU lowering constraints at the level the compiler actually
sees: the closed jaxpr of each `pl.pallas_call` and its `GridMapping`
(block shapes, operand shapes/dtypes, grid, scratch).  Tracing is
abstract — `jax.make_jaxpr` over `ShapeDtypeStruct`s — so no kernel
executes and no backend is touched; it runs on CPU in tier-1.

The pieces:

  - `force_tpu_variant()`: kernels pick block sizes and dispatch paths
    off `ops.pallas.interpret_mode()`; patching it to False makes the
    trace capture the exact variant that would lower on the chip
    (tracing never lowers, so this is safe on CPU),
  - `trace_entry(entry)`: build the entry's suite, `make_jaxpr` it, and
    walk the jaxpr (including pjit/cond/scan/custom-vjp sub-jaxprs) for
    `pallas_call` equations, normalised into `PallasCall` records so
    rules never touch jax internals directly,
  - `MosaicRule` + `lint_entries`: the rule loop, reusing tracelint's
    `Violation`, severity, and baseline machinery — mosaic violations
    key on the kernel's source file, so `tools/mosaiclint_baseline.json`
    round-trips through the same load/write/filter_new,
  - suppression: jaxpr nodes carry no comments, so suppression lives in
    the registry — `Entry.suppress = {'ML00x': 'reason'}` — and every
    suppression must carry its reason (enforced here).

jax is imported lazily inside functions: importing
`paddle_tpu.analysis` (which tracelint's stdlib-only contract covers)
must not drag the backend in.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import inspect
import math
import os

from ..engine import Violation

# what Mosaic gives a kernel that asks for nothing, and the most one may
# ask for through `CompilerParams(vmem_limit_bytes=...)`: a v5e core's
# physical VMEM
VMEM_BYTES_PER_CORE = 16 * 1024 * 1024
VMEM_BYTES_PHYSICAL = 128 * 1024 * 1024

# Mosaic min-tile second-minor (sublane) size by dtype itemsize; the
# minor (lane) dim is always 128.
SUBLANE_BY_ITEMSIZE = {8: 8, 4: 8, 2: 16, 1: 32}


def sublane_multiple(dtype):
    """Required sublane multiple for `dtype` (8/f32, 16/bf16, 32/int8
    and fp8)."""
    itemsize = getattr(dtype, 'itemsize', None)
    if itemsize is None:
        import numpy as np

        itemsize = np.dtype(dtype).itemsize
    return SUBLANE_BY_ITEMSIZE.get(itemsize, 8)


@contextlib.contextmanager
def force_tpu_variant():
    """Trace the kernels' TPU code paths on any backend.

    Block-size policies (`_pick_block`, `quant_matmul`'s XLA fallback)
    branch on `ops.pallas.interpret_mode()`; analyzing the interpret
    variant would check block shapes the chip never sees.  Tracing
    stops at jaxpr construction, so forcing the TPU branch never asks
    for a TPU.
    """
    from paddle_tpu.ops import pallas as pallas_pkg

    orig = pallas_pkg.interpret_mode
    pallas_pkg.interpret_mode = lambda: False
    try:
        yield
    finally:
        pallas_pkg.interpret_mode = orig


# ---------------------------------------------------------------------------
# Normalised pallas_call view
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockInfo:
    """One operand of a pallas_call: its VMEM block vs the HBM array."""

    kind: str                    # 'input' | 'output'
    origin: str                  # pallas' name for the ref, best-effort
    block_shape: tuple
    array_shape: tuple
    dtype: object

    def block_bytes(self):
        return (math.prod(s for s in self.block_shape if s)
                * self.dtype.itemsize)


@dataclasses.dataclass(frozen=True)
class ScratchInfo:
    shape: tuple
    dtype: object
    memory_space: str            # 'vmem' | 'smem' | ...

    def nbytes(self):
        if self.memory_space == 'semaphore_mem':
            return 0
        return math.prod(self.shape) * self.dtype.itemsize


@dataclasses.dataclass
class PallasCall:
    """Everything the ML rules need about one pallas_call equation."""

    name: str
    grid: tuple
    blocks: list                 # [BlockInfo] inputs then outputs
    scratch: list                # [ScratchInfo]
    num_scalar_prefetch: int
    body: object                 # the kernel jaxpr (jax.core.Jaxpr)
    vmem_limit: int = None       # the call's own vmem_limit_bytes, if any

    def input_blocks(self):
        return [b for b in self.blocks if b.kind == 'input']

    def vmem_estimate(self):
        """Blocks are double-buffered by the pallas pipeline (the DMA
        for step i+1 overlaps compute on step i), scratch is single."""
        est = 2 * sum(b.block_bytes() for b in self.blocks)
        est += sum(s.nbytes() for s in self.scratch
                   if s.memory_space != 'smem')
        return est

    def vmem_budget(self):
        """What the working set has to fit: the limit the call states
        for itself (never more than the core has), else the default."""
        if self.vmem_limit is None:
            return VMEM_BYTES_PER_CORE
        return min(int(self.vmem_limit), VMEM_BYTES_PHYSICAL)


def iter_eqns(jaxpr):
    """All equations of `jaxpr`, recursing into sub-jaxprs carried in
    params (pjit, cond branches, scan/while bodies, custom-vjp calls).
    Duck-typed on `.eqns` / `.jaxpr` so no jax.core helper is needed."""
    stack = [jaxpr]
    seen = set()
    while stack:
        j = stack.pop()
        if id(j) in seen:
            continue
        seen.add(id(j))
        for eqn in j.eqns:
            yield eqn
            for v in eqn.params.values():
                for sub in _as_jaxprs(v):
                    stack.append(sub)


def _as_jaxprs(value):
    if hasattr(value, 'eqns'):
        return [value]
    if hasattr(value, 'jaxpr') and hasattr(value.jaxpr, 'eqns'):
        return [value.jaxpr]
    if isinstance(value, (tuple, list)):
        out = []
        for v in value:
            out.extend(_as_jaxprs(v))
        return out
    return []


def _block_dim(dim):
    """One entry of BlockMapping.block_shape as the rules read it: an int
    block size, or None for a squeezed dim."""
    from jax._src.pallas import core as pallas_core

    if isinstance(dim, pallas_core.Squeezed):
        return None
    if isinstance(dim, (pallas_core.Blocked, pallas_core.Element,
                        pallas_core.BoundedSlice)):
        return int(dim.block_size)
    raise TypeError(f'unrecognised pallas block dim {dim!r}')


def _normalize(eqn):
    """PallasCall from one pallas_call equation (the GridMapping /
    BlockMapping layout of the installed jax; anything unrecognised
    raises and surfaces as an ML000 trace-error instead of a silent
    pass)."""
    gm = eqn.params['grid_mapping']
    body = eqn.params['jaxpr']
    blocks = []
    kinds = (['input'] * gm.num_inputs) + (['output'] * gm.num_outputs)
    for kind, bm in zip(kinds, gm.block_mappings):
        if str(getattr(bm.block_aval, 'memory_space', None)) in ('any',
                                                                 'hbm'):
            # left in HBM (the paged kernel's pools): no block, no
            # pipeline; what the kernel copies of it lands in scratch,
            # which is counted there
            continue
        blocks.append(BlockInfo(
            kind=kind,
            origin=str(bm.origin or ''),
            block_shape=tuple(_block_dim(d) for d in bm.block_shape),
            array_shape=tuple(bm.array_aval.shape),
            dtype=bm.array_aval.dtype,
        ))
    n_lead = gm.num_index_operands + gm.num_inputs + gm.num_outputs
    scratch = []
    for var in body.invars[n_lead:]:
        aval = var.aval
        scratch.append(ScratchInfo(
            shape=tuple(getattr(aval, 'shape', ())),
            dtype=getattr(aval, 'dtype', None),
            memory_space=str(getattr(aval, 'memory_space', 'vmem')),
        ))
    return PallasCall(
        name=eqn.params['name'] or body.debug_info.func_name,
        grid=tuple(gm.grid),
        blocks=blocks,
        scratch=scratch,
        num_scalar_prefetch=gm.num_index_operands,
        body=body,
        vmem_limit=getattr((eqn.params.get('compiler_params') or {}).get(
            'mosaic_tpu'), 'vmem_limit_bytes', None),
    )


def extract_pallas_calls(fn, args, kwargs=None):
    """Trace `fn(*args, **kwargs)` abstractly and return every
    pallas_call in the jaxpr as a normalised PallasCall."""
    import jax

    closed = jax.make_jaxpr(lambda *a: fn(*a, **(kwargs or {})))(*args)
    calls = []
    for eqn in iter_eqns(closed.jaxpr):
        if eqn.primitive.name == 'pallas_call':
            calls.append(_normalize(eqn))
    return calls


# ---------------------------------------------------------------------------
# Registry entry + kernel context
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Entry:
    """One registered kernel suite.

    `anchor` is 'module:attr' of the public entry point — violations
    point at its def site.  `build()` returns (fn, args, kwargs) with
    `jax.ShapeDtypeStruct` args shaped like the bench suites.
    `suppress` maps rule id -> REASON (a reason is mandatory; an empty
    one raises at lint time).  `onchip` optionally runs the kernel with
    real data against its reference — tools/mosaic_check.py's job.
    """

    name: str
    anchor: str
    build: object
    suppress: dict = dataclasses.field(default_factory=dict)
    onchip: object = None

    def resolve_anchor(self, root=None):
        """(relpath, lineno) of the anchored entry point."""
        mod_name, _, attr = self.anchor.partition(':')
        mod = importlib.import_module(mod_name)
        fn = getattr(mod, attr)
        fn = inspect.unwrap(fn)
        path = inspect.getsourcefile(fn) or mod.__file__
        try:
            line = inspect.getsourcelines(fn)[1]
        except (OSError, TypeError):
            line = 1
        root = root or os.getcwd()
        try:
            path = os.path.relpath(path, root)
        except ValueError:
            pass
        return path.replace(os.sep, '/'), line


@dataclasses.dataclass
class KernelContext:
    """What a MosaicRule sees: one entry, its traced pallas_calls, and
    the anchor for violation positions."""

    entry: Entry
    calls: list
    path: str
    line: int


class MosaicRule:
    """Base class mirroring tracelint's Rule, but checking a traced
    KernelContext instead of a parsed file."""

    id = 'ML000'
    name = 'abstract'
    severity = 'error'
    description = ''

    def check(self, ctx):
        raise NotImplementedError

    def violation(self, ctx, message, severity=None):
        return Violation(
            path=ctx.path,
            line=ctx.line,
            col=0,
            rule=self.id,
            severity=severity or self.severity,
            message=f'[{ctx.entry.name}] {message}',
        )


# ---------------------------------------------------------------------------
# Lint loop
# ---------------------------------------------------------------------------

def trace_entry(entry, root=None):
    """KernelContext for one entry (TPU-variant forced), or an ML000
    Violation when the suite itself fails to trace."""
    path, line = entry.resolve_anchor(root=root)
    fn, args, kwargs = entry.build()
    with force_tpu_variant():
        calls = extract_pallas_calls(fn, args, kwargs)
    return KernelContext(entry=entry, calls=calls, path=path, line=line)


def lint_and_report(entries, rules=None, root=None):
    """Run every rule over every entry, tracing each suite ONCE.

    Returns (violations, suppressed, vmem): `violations` are live,
    `suppressed` pairs each registry-suppressed Violation with its
    reason, and `vmem` maps entry name -> peak VMEM estimate in bytes
    over its pallas_calls (-1 when the suite failed to trace — never
    mistaken for a small footprint).  A suppression without a reason
    raises — undocumented waivers are how static checks rot.
    """
    if rules is None:
        from .rules import all_rules

        rules = all_rules()
    violations, suppressed, vmem = [], [], {}
    for entry in entries:
        for rule_id, reason in entry.suppress.items():
            if not (isinstance(reason, str) and reason.strip()):
                raise ValueError(
                    f'{entry.name}: suppression of {rule_id} must carry '
                    f'a non-empty reason')
        try:
            ctx = trace_entry(entry, root=root)
        except Exception as e:  # noqa: BLE001 - any trace failure is a finding
            vmem[entry.name] = -1
            path, line = '<registry>', 1
            try:
                path, line = entry.resolve_anchor(root=root)
            except Exception:  # noqa: BLE001
                pass
            violations.append(Violation(
                path=path, line=line, col=0, rule='ML000',
                severity='error',
                message=f'[{entry.name}] suite failed to trace: '
                        f'{type(e).__name__}: {e}'))
            continue
        vmem[entry.name] = max(
            (c.vmem_estimate() for c in ctx.calls), default=0)
        for rule in rules:
            for v in rule.check(ctx):
                if v.rule in entry.suppress:
                    suppressed.append((v, entry.suppress[v.rule]))
                else:
                    violations.append(v)
    return sorted(violations), suppressed, vmem


def lint_entries(entries, rules=None, root=None):
    """(violations, suppressed) — see lint_and_report."""
    violations, suppressed, _ = lint_and_report(entries, rules=rules,
                                                root=root)
    return violations, suppressed


def vmem_report(entries, root=None):
    """{entry name: peak VMEM estimate} without running any rules —
    the number bench.py stamps into the detail blob."""
    return lint_and_report(entries, rules=[], root=root)[2]
