"""What the drivers share: the run's environment, the model made from the
seed, the profiler window and the result line."""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, 'benchmark')


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


def make_model(cfg, seed, max_positions):
    """The program's model class at the configuration's sizes, every leaf
    made on the device from the seed in one jitted call."""
    import jax

    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    from benchmark.harness import weights

    if cfg['hidden_act'] != 'silu' or cfg['sliding_window'] is not None:
        raise SystemExit('benchmark: models/llama.py runs silu and full '
                         'attention only')
    if cfg['head_dim'] * cfg['num_attention_heads'] != cfg['hidden_size']:
        raise SystemExit('benchmark: LlamaConfig derives head_dim from '
                         'hidden_size / heads')
    lc = LlamaConfig(
        vocab_size=cfg['vocab_size'], hidden_size=cfg['hidden_size'],
        intermediate_size=cfg['intermediate_size'],
        num_hidden_layers=cfg['num_hidden_layers'],
        num_attention_heads=cfg['num_attention_heads'],
        num_key_value_heads=cfg['num_key_value_heads'],
        max_position_embeddings=max_positions,
        rms_norm_eps=cfg['rms_norm_eps'], rope_theta=cfg['rope_theta'],
        tie_word_embeddings=cfg['tie_word_embeddings'],
        attention_bias=cfg['attention_bias'], dtype=cfg['torch_dtype'])
    struct = jax.eval_shape(lambda: LlamaForCausalLM(lc))
    model, shapes = weights.fill_model(struct, seed)
    expect = {(-1, n): s for n, (s, _) in weights.global_shapes(cfg).items()}
    for layer in range(cfg['num_hidden_layers']):
        expect.update({(layer, n): s for n, (s, _) in
                       weights.layer_shapes(cfg).items()})
    if shapes != expect:
        odd = set(shapes.items()) ^ set(expect.items())
        raise SystemExit(f'benchmark: the model\'s leaves are not the '
                         f'configuration\'s: {sorted(odd)[:6]}')
    return model


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
        path = os.path.join(BENCH, 'metrics', 'readers',
                            f'{spec["reader"]}.py')
        module_spec = importlib.util.spec_from_file_location(
            f'benchmark_reader_{spec["reader"]}', path)
        module = importlib.util.module_from_spec(module_spec)
        module_spec.loader.exec_module(module)
        value = module.read(ctx, **spec.get('args', {}))
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
               chips=chips, flops=model_flops)
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
