"""The shardlint engine: trace suites on a virtual mesh, run SL rules.

tracelint proves source-level contracts with `ast`; mosaiclint proves
Mosaic lowering legality at the jaxpr level; this engine proves the
SHARDING contract at the level GSPMD actually decides it: each
registered suite is `jax.jit(...).lower().compile()`d over
`ShapeDtypeStruct`s under a virtual 8-device mesh
(`--xla_force_host_platform_device_count=8`, SURVEY §4), and the rules
read three kinds of evidence out of that one compile:

  - the POST-SPMD HLO text: every `all-reduce` / `all-gather` /
    `reduce-scatter` / `all-to-all` / `collective-permute` the
    partitioner emitted, with per-call payload bytes — the collective
    census SL002 checks against the suite's declared communication
    budget and bench.py stamps as `shardlint_comm`,
  - the compiled input/output shardings and avals: SL003's replication
    blowup and SL005's donation/sharding aliasing checks,
  - the (pre-partitioning) jaxpr: every `shard_map` equation with its
    mesh, manual/auto axis split, in/out specs and body collectives —
    SL006's evidence.

Two trace-time audit seams catch what the compiled artifact cannot
show because production code CLAMPS before the compiler ever sees it:

  - `spec_audit()` patches `distributed.parallel._valid_spec` (plus
    `sharding.data_sharding` / `sharding.zero_spec` axis filters) to
    record every PartitionSpec entry they silently drop — an axis name
    missing from the mesh is exactly the typo-silently-replicates bug
    SL001 exists for, and it is invisible downstream of the clamp,
  - `host_transfer_audit()` patches `jax.device_get` so a suite's
    optional eager `host_probe` records transfers of sharded globals
    (SL004's implicit full gather).

Like mosaiclint: violations reuse tracelint's Violation/severity/
baseline machinery keyed on the suite's anchor file, suppression lives
in the registry with a MANDATORY reason, and a suite that fails to
trace or compile surfaces as SL000 — never as a silent pass.  jax is
imported lazily; importing `paddle_tpu.analysis` stays stdlib-only.
"""
from __future__ import annotations

import contextlib
import dataclasses
import re

from ..engine import Violation
from ..mosaic.engine import Entry as _MosaicEntry
from ..mosaic.engine import iter_eqns

DEFAULT_VIRTUAL_DEVICES = 8

# SL003: a fully-replicated array at/above this many bytes on a >1
# device mesh is a blowup finding (per-entry override on the Entry)
REPLICATION_THRESHOLD_BYTES = 4 * 1024 * 1024

# GSPMD/XLA collective op kinds the census counts (async `-start`
# halves are folded into their base kind; `-done` halves are skipped)
COLLECTIVE_KINDS = ('all-reduce', 'all-gather', 'reduce-scatter',
                    'all-to-all', 'collective-permute',
                    'collective-broadcast')

# jaxpr-level collective primitives (inside shard_map bodies)
COLLECTIVE_PRIMITIVES = ('psum', 'pmax', 'pmin', 'ppermute', 'all_to_all',
                         'all_gather', 'psum_scatter', 'pgather',
                         'reduce_scatter')

_HLO_ITEMSIZE = {
    'pred': 1, 's4': 1, 'u4': 1, 's8': 1, 'u8': 1,
    's16': 2, 'u16': 2, 'f16': 2, 'bf16': 2,
    's32': 4, 'u32': 4, 'f32': 4,
    's64': 8, 'u64': 8, 'f64': 8,
    'f8e4m3fn': 1, 'f8e5m2': 1, 'f8e4m3b11fnuz': 1,
    'c64': 8, 'c128': 16,
}

_COLLECTIVE_LINE_RE = re.compile(
    r'=\s+(.*?)\s+(' + '|'.join(COLLECTIVE_KINDS) + r')(?:-start)?\(')
_HLO_SHAPE_RE = re.compile(r'([a-z][a-z0-9]*)\[([0-9,]*)\]')


# ---------------------------------------------------------------------------
# Virtual mesh
# ---------------------------------------------------------------------------

def ensure_virtual_devices(n=DEFAULT_VIRTUAL_DEVICES):
    """True when >= n devices are available, forcing the host-platform
    device-count flag BEFORE the backend initialises when possible.

    Harmless after paddle_tpu import (importing the package does not
    initialise a backend); a process that already woke jax up with
    fewer devices gets False — the CLI turns that into rc 2 with a
    recipe, never a fake pass.  The platform itself is respected: pin
    `JAX_PLATFORMS=cpu` (tests/bench do) so the analyzers never claim
    a chip.
    """
    import os

    flags = os.environ.get('XLA_FLAGS', '')
    if 'xla_force_host_platform_device_count' not in flags:
        os.environ['XLA_FLAGS'] = (
            flags + f' --xla_force_host_platform_device_count={n}').strip()
    import jax

    return jax.device_count() >= n


def virtual_mesh(n=DEFAULT_VIRTUAL_DEVICES, **degrees):
    """`distributed.mesh.build_mesh` over the first `n` virtual
    devices with the given axis degrees (e.g. ``virtual_mesh(tp=8)``)."""
    if not ensure_virtual_devices(n):
        import jax

        raise RuntimeError(
            f'shardlint needs {n} devices, found {jax.device_count()}: '
            f'the backend initialised before the virtual-device flag '
            f'could be set — run with XLA_FLAGS='
            f'--xla_force_host_platform_device_count={n} (and '
            f'JAX_PLATFORMS=cpu)')
    import jax

    from paddle_tpu.distributed.mesh import build_mesh

    return build_mesh(devices=jax.devices()[:n], **degrees)


@contextlib.contextmanager
def _mesh_context(mesh):
    """Set the process-global mesh (layers reach it via `get_mesh()` in
    `sharding_constraint`) for the duration of a suite trace."""
    from paddle_tpu.distributed import mesh as mesh_mod

    prev = mesh_mod.get_mesh()
    mesh_mod.set_mesh(mesh)
    try:
        yield
    finally:
        mesh_mod.set_mesh(prev)


# ---------------------------------------------------------------------------
# Audit seams
# ---------------------------------------------------------------------------

def _axes_of(entry):
    if entry is None:
        return ()
    return entry if isinstance(entry, tuple) else (entry,)


def _spec_drops(spec, clamped, shape, mesh, where):
    """Diff one _valid_spec call: every axis the clamp dropped, with
    the reason it was dropped."""
    records = []
    clamped_entries = tuple(clamped) + (None,) * (
        len(tuple(spec)) - len(tuple(clamped)))
    for i, (orig, kept) in enumerate(zip(tuple(spec), clamped_entries)):
        kept_axes = set(_axes_of(kept))
        for axis in _axes_of(orig):
            if axis in kept_axes:
                continue
            reason = ('unknown-axis' if axis not in mesh.axis_names
                      else 'indivisible')
            records.append({
                'axis': axis, 'reason': reason, 'spec': str(spec),
                'dim': (shape[i] if i < len(shape) else None),
                'where': where,
            })
    return records


@contextlib.contextmanager
def spec_audit():
    """Record every PartitionSpec axis the distributed layer's
    clamp/filter helpers silently drop during the traced region.

    Yields the (live) record list; each record carries axis / reason
    ('unknown-axis' | 'indivisible') / spec / where.  Patched seams:
    `parallel._valid_spec` (sharding_constraint, shard_model,
    shard_tensor all route through it), `sharding.data_sharding` and
    `sharding.zero_spec` (their axis filters drop unknown names
    without ever reaching _valid_spec).
    """
    from paddle_tpu.distributed import parallel as par
    from paddle_tpu.distributed import sharding as shmod

    records = []
    orig_valid = par._valid_spec
    orig_ds = shmod.data_sharding
    orig_zs = shmod.zero_spec

    def valid_spec(spec, shape, mesh):
        out = orig_valid(spec, shape, mesh)
        if spec is not None:
            records.extend(
                _spec_drops(spec, out, shape, mesh, '_valid_spec'))
        return out

    def data_sharding(mesh, axes=('dp', 'fsdp')):
        for a in axes:
            if a not in mesh.axis_names:
                records.append({'axis': a, 'reason': 'unknown-axis',
                                'spec': f'data_sharding(axes={axes!r})',
                                'dim': None, 'where': 'data_sharding'})
        return orig_ds(mesh, axes)

    def zero_spec(shape, mesh, axes=None):
        for a in (axes or ()):
            if a not in mesh.axis_names:
                records.append({'axis': a, 'reason': 'unknown-axis',
                                'spec': f'zero_spec(axes={axes!r})',
                                'dim': None, 'where': 'zero_spec'})
        return orig_zs(shape, mesh, axes)

    par._valid_spec = valid_spec
    shmod.data_sharding = data_sharding
    shmod.zero_spec = zero_spec
    try:
        yield records
    finally:
        par._valid_spec = orig_valid
        shmod.data_sharding = orig_ds
        shmod.zero_spec = orig_zs


@contextlib.contextmanager
def host_transfer_audit():
    """Record `jax.device_get` calls that pull a SHARDED global to the
    host during the guarded region (SL004's implicit full gather).

    Only the canonical API is seamed — `np.asarray` routes that bypass
    device_get are tracelint TL002's (AST) territory.  Fully-replicated
    and single-device arrays record nothing: their transfer is a local
    D2H copy, not a gather.
    """
    import jax

    records = []
    orig = jax.device_get

    def device_get(x):
        def note(leaf):
            sharding = getattr(leaf, 'sharding', None)
            if (isinstance(leaf, jax.Array) and sharding is not None
                    and len(getattr(sharding, 'device_set', ())) > 1
                    and not sharding.is_fully_replicated):
                records.append({
                    'shape': tuple(leaf.shape), 'dtype': str(leaf.dtype),
                    'bytes': int(leaf.nbytes),
                    'devices': len(sharding.device_set),
                })
            return leaf

        jax.tree.map(note, x)
        return orig(x)

    jax.device_get = device_get
    try:
        yield records
    finally:
        jax.device_get = orig


# ---------------------------------------------------------------------------
# Collective census (post-SPMD HLO)
# ---------------------------------------------------------------------------

def _shape_bytes(shape_str):
    total = 0
    for m in _HLO_SHAPE_RE.finditer(shape_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _HLO_ITEMSIZE:
            continue
        n = 1
        for d in dims.split(','):
            if d:
                n *= int(d)
        total += n * _HLO_ITEMSIZE[dtype]
    return total


def collective_census(hlo_text):
    """{kind: {'count': n, 'bytes': b}} over the compiled module.

    Counts CALL SITES in the (single, SPMD) per-device program: a
    collective inside a while/scan body counts once, not per trip, and
    `bytes` is the per-device result payload of each site — the
    apples-to-apples number for a declared budget, documented as such
    in docs/shardlint.md.
    """
    census = {}
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_LINE_RE.search(line)
        if not m or '-done(' in line:
            continue
        kind = m.group(2)
        rec = census.setdefault(kind, {'count': 0, 'bytes': 0})
        rec['count'] += 1
        rec['bytes'] += _shape_bytes(m.group(1))
    return census


# ---------------------------------------------------------------------------
# shard_map normalisation (jaxpr level)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardMapInfo:
    """One shard_map equation, normalised for SL006."""

    mesh_axes: tuple             # every axis name of the shard_map mesh
    manual: frozenset            # manually-scheduled axes
    auto: frozenset              # GSPMD-managed axes
    data_axes: frozenset         # axes any in_spec splits over
    varying: frozenset           # data_axes + pvary/pcast + axis_index
    collectives: list            # [(primitive name, (axes...))]


def _collective_axes(eqn):
    axes = eqn.params.get('axes', None)
    if axes is None:
        axes = eqn.params.get('axis_name', ())
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def _normalize_shard_map(eqn):
    mesh = eqn.params['mesh']
    mesh_axes = tuple(mesh.axis_names)
    manual = frozenset(eqn.params['manual_axes'])
    auto = frozenset(mesh_axes) - manual
    data_axes = set()
    for spec in eqn.params['in_specs']:
        for entry in spec:
            data_axes.update(_axes_of(entry))
    varying = set(data_axes)
    collectives = []
    body = eqn.params['jaxpr']
    for sub in iter_eqns(body.jaxpr if hasattr(body, 'jaxpr') else body):
        name = sub.primitive.name
        if name in ('pvary', 'pcast', 'axis_index'):
            # rank-dependent (axis_index) or explicitly promoted
            # (pvary) values make the body vary over the axis even when
            # no input is split over it — the pipeline queue pattern
            varying.update(_collective_axes(sub))
        elif name in COLLECTIVE_PRIMITIVES:
            collectives.append((name, _collective_axes(sub)))
    return ShardMapInfo(
        mesh_axes=mesh_axes, manual=manual, auto=auto,
        data_axes=frozenset(data_axes), varying=frozenset(varying),
        collectives=collectives)


# ---------------------------------------------------------------------------
# Suite / Entry / context
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Suite:
    """What an Entry's `build()` returns: one jit-able workload plus
    the sharding contract it declares.

    `args` are (pytrees of) ShapeDtypeStructs; `donate` maps a FLAT
    input-leaf index to the FLAT output-leaf index it aliases (the
    whole top-level arg containing the input leaf is donated to jit).
    `specs` are extra declared PartitionSpecs SL001 validates against
    the mesh by name.  `host_probe` optionally runs a small EAGER
    workload under `host_transfer_audit` (SL004).  `compile=False`
    stops after the jaxpr — no census / sharding evidence (used by
    jaxpr-only fixtures; registry suites always compile).
    """

    fn: object
    args: tuple
    kwargs: dict = dataclasses.field(default_factory=dict)
    mesh: object = None
    in_shardings: object = None
    out_shardings: object = None
    donate: dict = dataclasses.field(default_factory=dict)
    specs: dict = dataclasses.field(default_factory=dict)
    host_probe: object = None
    compile: bool = True


@dataclasses.dataclass(frozen=True)
class Entry(_MosaicEntry):
    """One registered sharding suite (reuses mosaiclint's anchor
    resolution; `build()` returns a `Suite`).

    `budget` is the declared communication budget:
    {kind: count} or {kind: {'count': n, 'bytes': b}} over
    COLLECTIVE_KINDS; None opts the suite out of SL002 (a registered
    production suite should always declare one — {} means "no
    collectives allowed").  `replication_threshold` overrides SL003's
    byte threshold for this suite.
    """

    budget: object = None
    replication_threshold: int = REPLICATION_THRESHOLD_BYTES


@dataclasses.dataclass
class ShardContext:
    """What a ShardRule sees for one traced suite."""

    entry: Entry
    suite: Suite
    mesh: object
    n_devices: int
    shard_maps: list             # [ShardMapInfo]
    census: dict                 # {kind: {'count', 'bytes'}} or None
    inputs: list                 # [(label, aval, sharding-or-None)]
    outputs: list                # [(label, aval, sharding-or-None)]
    spec_records: list           # spec_audit records
    host_transfers: list         # host_transfer_audit records
    path: str
    line: int


class ShardRule:
    """Base class mirroring MosaicRule over a traced ShardContext."""

    id = 'SL000'
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
# Tracing
# ---------------------------------------------------------------------------

def _flat_shardings(tree):
    import jax

    if tree is None:
        return None
    return jax.tree.leaves(
        tree, is_leaf=lambda x: hasattr(x, 'is_fully_replicated'))


def trace_entry(entry, root=None):
    """ShardContext for one entry.  Any build/trace/compile failure
    propagates — lint_and_report turns it into an SL000 violation."""
    import jax

    path, line = entry.resolve_anchor(root=root)
    census = None
    in_shard_flat = out_shard_flat = None
    # the audit wraps build() too: specs are typically CONSTRUCTED
    # there (data_sharding/zero_spec calls), and a typo'd axis is
    # dropped at construction time, before anything traces
    with spec_audit() as spec_records:
        suite = entry.build()
        if not isinstance(suite, Suite):
            raise TypeError(
                f'{entry.name}: build() must return a '
                f'shard.engine.Suite, got {type(suite).__name__}')
        fn = suite.fn
        if suite.kwargs:
            inner = fn
            fn = lambda *a: inner(*a, **suite.kwargs)  # noqa: E731
        with _mesh_context(suite.mesh):
            closed = jax.make_jaxpr(fn)(*suite.args)
            if suite.compile:
                jit_kwargs = {}
                if suite.in_shardings is not None:
                    jit_kwargs['in_shardings'] = suite.in_shardings
                if suite.out_shardings is not None:
                    jit_kwargs['out_shardings'] = suite.out_shardings
                if suite.donate:
                    jit_kwargs['donate_argnums'] = _donated_argnums(suite)
                # tracelint: disable=TL001 - one-shot analysis compile:
                # the jit exists only to .lower().compile() this suite
                # once for its HLO/shardings; nothing ever executes it
                compiled = jax.jit(fn, **jit_kwargs).lower(
                    *suite.args).compile()
                census = collective_census(compiled.as_text())
                in_shard_flat = _flat_shardings(
                    compiled.input_shardings[0])
                out_shard_flat = _flat_shardings(
                    compiled.output_shardings)
            host_transfers = []
            if suite.host_probe is not None:
                with host_transfer_audit() as host_transfers:
                    suite.host_probe()

    in_avals = list(closed.in_avals)
    out_avals = list(closed.out_avals)
    inputs = _labelled(in_avals, in_shard_flat, 'arg')
    outputs = _labelled(out_avals, out_shard_flat, 'out')
    shard_maps = [
        _normalize_shard_map(eqn) for eqn in iter_eqns(closed.jaxpr)
        if eqn.primitive.name == 'shard_map']
    mesh = suite.mesh
    n_devices = mesh.devices.size if mesh is not None else 1
    return ShardContext(
        entry=entry, suite=suite, mesh=mesh, n_devices=n_devices,
        shard_maps=shard_maps, census=census, inputs=inputs,
        outputs=outputs, spec_records=spec_records,
        host_transfers=host_transfers, path=path, line=line)


def _donated_argnums(suite):
    """Top-level positional argnums covering the donated flat leaves."""
    import jax

    offsets = []
    total = 0
    for arg in suite.args:
        offsets.append(total)
        total += len(jax.tree.leaves(arg))
    argnums = set()
    for leaf_idx in suite.donate:
        pos = 0
        for argnum, off in enumerate(offsets):
            if leaf_idx >= off:
                pos = argnum
        argnums.add(pos)
    return tuple(sorted(argnums))


def _labelled(avals, shardings, prefix):
    out = []
    for i, aval in enumerate(avals):
        sharding = None
        if shardings is not None and i < len(shardings):
            sharding = shardings[i]
        out.append((f'{prefix}{i}', aval, sharding))
    return out


# ---------------------------------------------------------------------------
# Lint loop
# ---------------------------------------------------------------------------

def lint_and_report(entries, rules=None, root=None):
    """Run every rule over every entry, tracing+compiling each ONCE.

    Returns (violations, suppressed, comm): `suppressed` pairs each
    registry-suppressed Violation with its reason (empty reasons
    raise), and `comm` maps entry name -> collective census (None when
    the suite failed to trace) — the blob bench.py stamps as
    `shardlint_comm`.
    """
    if rules is None:
        from .rules import all_rules

        rules = all_rules()
    violations, suppressed, comm = [], [], {}
    for entry in entries:
        for rule_id, reason in entry.suppress.items():
            if not (isinstance(reason, str) and reason.strip()):
                raise ValueError(
                    f'{entry.name}: suppression of {rule_id} must carry '
                    f'a non-empty reason')
        try:
            ctx = trace_entry(entry, root=root)
        except Exception as e:  # noqa: BLE001 - any failure is a finding
            comm[entry.name] = None
            path, line = '<registry>', 1
            try:
                path, line = entry.resolve_anchor(root=root)
            except Exception:  # noqa: BLE001
                pass
            violations.append(Violation(
                path=path, line=line, col=0, rule='SL000',
                severity='error',
                message=f'[{entry.name}] suite failed to trace/compile: '
                        f'{type(e).__name__}: {e}'))
            continue
        comm[entry.name] = ctx.census
        for rule in rules:
            for v in rule.check(ctx):
                if v.rule in entry.suppress:
                    suppressed.append((v, entry.suppress[v.rule]))
                else:
                    violations.append(v)
    return sorted(violations), suppressed, comm


def lint_entries(entries, rules=None, root=None):
    """(violations, suppressed) — see lint_and_report."""
    violations, suppressed, _ = lint_and_report(entries, rules=rules,
                                                root=root)
    return violations, suppressed


def comm_report(entries, root=None):
    """{entry name: collective census} without running any rules."""
    return lint_and_report(entries, rules=[], root=root)[2]
