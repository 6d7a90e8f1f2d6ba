"""What the drivers share: the run's environment, the lookup of a family's
and a reader's files, the profiler window and the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, 'benchmark')
FAMILIES = BENCH        # where `families/` and `reference/families/` stand


def load(kind, name):
    with open(os.path.join(BENCH, kind, f'{name}.json')) as f:
        return json.load(f)


@dataclasses.dataclass
class Env:
    """One run: where it started, what it runs on, what it may compare.
    `peak` is None only in the CPU rehearsal, which reports no device
    metric."""
    t_start: float
    seed: int
    seconds: float
    trace: bool
    device: object
    peak: object
    compiles: object
    per_layer: list                 # names of the cell's per-layer metrics
    trace_dir: str = os.path.join(ROOT, '.bench_trace')


@functools.lru_cache(maxsize=None)
def load_module(path):
    """A file of the benchmark found by a name in its data, loaded by path
    and once a process: a per-layer metric's reader, a family's two files.
    It stands in `sys.modules` under its path, so it can name itself."""
    name = f'benchmark:{path}'
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module
    spec.loader.exec_module(module)
    return module


def family(cfg):
    """What the configuration's `family` names (`benchmark/families`'s
    docstring has the contract): `families/<family>.py`, with the plain
    reference of `reference/families/<family>.py` as its `reference`. A
    configuration that names none, or one with no files, ends the run."""
    name = cfg.get('family')
    if name is None:
        raise SystemExit(f'benchmark: the configuration {cfg.get("name")!r} '
                         f'names no family')
    paths = [os.path.join(FAMILIES, *where, f'{name}.py')
             for where in (('families',), ('reference', 'families'))]
    missing = [p for p in paths if not os.path.exists(p)]
    if missing:
        raise SystemExit(f'benchmark: the family {name!r} of the '
                         f'configuration {cfg.get("name")!r} has no '
                         f'{" and no ".join(missing)}')
    module = load_module(paths[0])
    module.reference = load_module(paths[1])
    return module


@contextlib.contextmanager
def span(name):
    """A span of the harness's own in the profiler's trace."""
    import jax

    with jax.profiler.TraceAnnotation(name):
        yield


class Profile:
    """The profiler over one window, python tracing off. `window_s` is
    the host-clock length between start and stop."""

    def __init__(self, trace_dir):
        self.dir, self.window_s, self._t0 = trace_dir, None, None

    def start(self):
        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self._t0 = time.perf_counter()

    def stop(self):
        import jax

        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()

    def load(self):
        from benchmark.harness import trace_reduce

        trace = trace_reduce.load_xplane(self.dir)
        shutil.rmtree(self.dir, ignore_errors=True)
        return trace


def read_metrics(env, ctx):
    """Each of the cell's per-layer metrics through the reader its own
    file names; a reader that finds nothing to read returns None and the
    metric is left out of the line."""
    out = {}
    for name in env.per_layer:
        spec = load('metrics', name)
        reader = load_module(os.path.join(BENCH, 'metrics', 'readers',
                                          f'{spec["reader"]}.py'))
        value = reader.read(ctx, **spec.get('args', {}))
        if value is not None:
            out[name] = {'value': float(value), 'unit': spec['unit']}
    return out


def traced_line(env, chips, profile, peak_bytes, ctx):
    """What a `--trace 1` run reports in place of the end-to-end metrics:
    (per-layer metrics, device with busy_s and window_s, breakdown).
    `ctx` is what the readers read; the trace and the chip's peak join it
    here."""
    from benchmark.harness import model_flops, trace_reduce

    trace = profile.load()
    ctx = dict(ctx, trace=trace, window_s=profile.window_s, peak=env.peak,
               chips=chips, flops=model_flops.Work(family(ctx['cfg'])))
    return (read_metrics(env, ctx),
            device_line(env, chips, peak_bytes,
                        trace_reduce.busy_seconds(trace), profile.window_s),
            trace_reduce.breakdown(trace))


def memory_peak(devices):
    peaks = [(d.memory_stats() or {}).get('peak_bytes_in_use') for d in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


def free_device():
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


def device_line(env, n_chips, memory_peak_bytes, busy_s=None, window_s=None):
    d = {'platform': env.device.platform, 'kind': env.device.device_kind,
         'count': n_chips, 'memory_peak_bytes': memory_peak_bytes}
    if busy_s is not None:
        d.update(busy_s=busy_s, window_s=window_s)
    return d
