"""costs — ONE normalized reading of XLA's compile-time cost model.

Before this module, `compiled.cost_analysis()` was queried in three
independent places (`utils.flops`, `profiler.op_summary`,
`jit.compilation_report`), each re-discovering the same quirks: some
jax versions return a LIST of per-partition dicts instead of a dict,
the call can raise outright on exotic backends, keys are
space-separated strings ('bytes accessed'), and `memory_analysis` has
its own failure modes. `analyze()` handles all of it once and returns
one stable shape; the old call sites now delegate here, and
`aot.build` uses it to stamp per-geometry flops+bytes into the
artifact manifest — the static numbers the serving and train engines
turn into live `serve.mfu_est` / `train.mfu_est` / roofline gauges at
their existing window-commit syncs (host arithmetic on host-known wall
times: zero new device syncs, zero retraces on the hot path).

Everything here is compile-time/host-side; the only jax touches are
lazy (inside the helpers that take jitted functions or query devices),
so the module imports cleanly without a backend.
"""
from __future__ import annotations

import os

__all__ = ['analyze', 'analyze_jitted', 'intensity', 'geometry_cost',
           'measure_dispatch_costs', 'device_peak_flops',
           'PEAK_BF16_FLOPS']

# normalized field -> cost_analysis key
_COST_FIELDS = (('flops', 'flops'),
                ('bytes_accessed', 'bytes accessed'),
                ('transcendentals', 'transcendentals'))

# per-chip dense bf16 peak, from Google Cloud's per-generation TPU
# documentation — the repo's ONE peaks table, keyed by `device_kind`
# (longest-prefix matched so 'TPU v5 lite' cannot shadow 'TPU v5p' or
# vice versa)
PEAK_BF16_FLOPS = {
    'TPU v2': 45e12, 'TPU v3': 123e12, 'TPU v4': 275e12,
    'TPU v5 lite': 197e12, 'TPU v5e': 197e12, 'TPU v5': 459e12,
    'TPU v5p': 459e12, 'TPU v6 lite': 918e12, 'TPU v6e': 918e12,
}


def analyze(compiled):
    """Normalized cost view of one compiled executable:

        {'flops': float|None, 'bytes_accessed': float|None,
         'transcendentals': float|None,
         'memory': {'argument_bytes', 'output_bytes', 'temp_bytes'}}

    Accepts a `Compiled` OR a `Lowered` (compiled here; a compile
    failure degrades to all-None instead of raising). Handles the
    list-vs-dict return quirk, the bare-raise quirk, and missing keys
    — the one place those are allowed to exist."""
    out = {'flops': None, 'bytes_accessed': None, 'transcendentals': None,
           'memory': {}}
    if compiled is None:
        return out
    if hasattr(compiled, 'compile'):          # a Lowered: compile first
        try:
            compiled = compiled.compile()
        except Exception:  # noqa: BLE001 - degrade, never raise
            return out
    try:
        cost = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - exotic backends raise here
        cost = None
    if isinstance(cost, (list, tuple)):       # per-partition list quirk
        cost = cost[0] if cost else None
    if isinstance(cost, dict):
        for field, key in _COST_FIELDS:
            v = cost.get(key)
            if v is not None:
                try:
                    out[field] = float(v)
                except (TypeError, ValueError):
                    pass
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            out['memory'] = {
                'argument_bytes': int(mem.argument_size_in_bytes),
                'output_bytes': int(mem.output_size_in_bytes),
                'temp_bytes': int(mem.temp_size_in_bytes),
            }
    except Exception:  # noqa: BLE001 - memory analysis is best-effort
        pass
    return out


def analyze_jitted(fn, *args, **kwargs):
    """`analyze` of a jitted callable lowered over `args` (args may be
    ShapeDtypeStructs — nothing executes). Lowering re-traces, so keep
    this OFF serving hot paths (it bumps the engines' trace counters)."""
    return analyze(fn.lower(*args, **kwargs))


def intensity(cost):
    """Roofline operational intensity (flops / bytes accessed) of one
    `analyze()` result, or None when either half is unknown/zero."""
    f, b = cost.get('flops'), cost.get('bytes_accessed')
    if not f or not b:
        return None
    return f / b


def geometry_cost(engine, g, draft=None):
    """Static cost of ONE enumerated aot geometry: lower each of its
    dispatch specs (`engine._cost_specs` — the same MODULE-LEVEL jitted
    steps the live scheduler dispatches, with the live model riding as
    an argument, so the analyzed HLO is the served HLO) and sum
    `analyze()` over them. Under `aot.build` the persistent cache is
    already wired, so the `.compile()` inside is a disk read of the
    executable the build just persisted. Raises NotImplementedError for
    kinds without cost specs (speculative windows)."""
    total = {'flops': 0.0, 'bytes_accessed': 0.0, 'transcendentals': 0.0}
    seen = {k: False for k in total}
    n = 0
    for fn, args, kwargs in engine._cost_specs(g, draft=draft):
        c = analyze_jitted(fn, *args, **kwargs)
        n += 1
        for k in total:
            if c[k] is not None:
                total[k] += c[k]
                seen[k] = True
    out = {k: (total[k] if seen[k] else None) for k in total}
    out['specs'] = n
    return out


def measure_dispatch_costs(engine, geometries=None, draft=None):
    """Compute per-geometry costs for a LIVE engine and load them into
    its dispatch-cost table (`_note_geometry_cost`) — the no-artifact
    path `tools/telemetry_dump.py` uses; engines warmed from an
    `aot.EngineArtifact` get the same table from the manifest for free.
    Lowering re-traces, so call this off the serving hot path. Returns
    {geometry label: cost-or-error-string}."""
    from ..aot import geometry as _geometry

    if geometries is None:
        geometries = _geometry.for_engine(engine)
    report = {}
    for g in geometries:
        try:
            c = geometry_cost(engine, g, draft=draft)
        except NotImplementedError as e:
            report[g.label()] = f'skipped: {e}'
            continue
        except Exception as e:  # noqa: BLE001 - per-geometry, not fatal
            report[g.label()] = f'error: {type(e).__name__}: {e}'
            continue
        engine._note_geometry_cost(g, c)
        report[g.label()] = c
    return report


def device_peak_flops(device=None):
    """Peak dense flops/s the MFU denominator divides by:
    `PADDLE_TPU_PEAK_FLOPS` (explicit, any backend — what the bench
    gate pins) wins; else the bf16 table for known TPU kinds. A TPU
    whose kind is not in the table is an error — a peak assumed for an
    unknown chip makes every MFU derived from it a fabrication. Off the
    TPU there is no peak: None, and the engines skip the `*.mfu_est`
    gauge while still recording achieved flops/s."""
    env = os.environ.get('PADDLE_TPU_PEAK_FLOPS')
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    try:
        import jax

        d = device if device is not None else jax.devices()[0]
    except Exception:  # noqa: BLE001 - no backend: no peak
        return None
    kind = str(getattr(d, 'device_kind', ''))
    best = None
    for k, v in PEAK_BF16_FLOPS.items():
        if kind.casefold().startswith(k.casefold()):
            if best is None or len(k) > best[0]:
                best = (len(k), v)
    if best is None and getattr(d, 'platform', None) == 'tpu':
        raise ValueError(
            f'no peak flops known for TPU device kind {kind!r}: add it '
            f'(with its source) to observability.costs.PEAK_BF16_FLOPS')
    return best[1] if best else None
