"""What jax says about the programs a run compiles and dispatches.

Copies of `chip_smoke.py`'s helpers (PR 21), kept with the yardstick so
that a later PR cannot change them: the compile counter that proves a
window compiled nothing, and the attribution of Mosaic custom calls to the
file under `ops/pallas/` that issued them, here with the instructions'
names so that the trace's device events can be matched to a kernel.
"""
from __future__ import annotations

import collections
import os
import re

GiB = 2 ** 30


class CompileLog:
    """Compile requests as jax reports them: served from the persistent
    cache, compiled, and the seconds spent."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        self.seconds = 0.0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        self.hits += event == '/jax/compilation_cache/cache_hits'
        self.misses += event == '/jax/compilation_cache/cache_misses'

    def _duration(self, event, seconds, **_):
        if event == '/jax/core/compile/backend_compile_duration':
            self.seconds += seconds

    @property
    def requests(self):
        return self.hits + self.misses

    def snapshot(self):
        return self.hits, self.misses, self.seconds


def _tables(hlo_text):
    tables, current = {}, None
    for line in hlo_text.splitlines():
        if line in ('FileNames', 'FileLocations', 'StackFrames'):
            current = tables.setdefault(line, {})
        elif not line.strip():
            current = None
        elif current is not None:
            key, _, rest = line.partition(' ')
            current[int(key)] = rest
    return tables


_CALL = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=[^\n]*?'
    r'custom_call_target="tpu_custom_call"[^\n]*?stack_frame_id=(\d+)',
    re.M)


def mosaic_kernels(hlo_text):
    """{pallas module: [instruction names]} of one compiled program, each
    Mosaic call attributed through the text's own stack-frame tables to
    the file under ops/pallas/ that issued it."""
    tables = _tables(hlo_text)
    found = collections.defaultdict(list)
    for m in _CALL.finditer(hlo_text):
        frame = tables['StackFrames'][int(m.group(2))]
        loc = tables['FileLocations'][
            int(re.search(r'file_location_id=(\d+)', frame).group(1))]
        path = tables['FileNames'][
            int(re.search(r'file_name_id=(\d+)', loc).group(1))].strip('"')
        found[os.path.splitext(os.path.basename(path))[0]].append(m.group(1))
    return dict(found)


def describe(label, fn, args, kwargs):
    """memory_analysis() and Mosaic calls of one dispatched program: the
    same jitted function on the same arguments, so the cache hands back
    the executable that ran."""
    compiled = fn.lower(*args, **kwargs).compile()
    mem = compiled.memory_analysis()
    text = compiled.as_text()
    name = re.search(r'HloModule\s+([\w.\-]+)', text)
    return {
        'label': label, 'module': name.group(1) if name else None,
        'needs_gib': (mem.argument_size_in_bytes + mem.output_size_in_bytes
                      - mem.alias_size_in_bytes
                      + mem.temp_size_in_bytes) / GiB,
        'arguments_gib': mem.argument_size_in_bytes / GiB,
        'temporaries_gib': mem.temp_size_in_bytes / GiB,
        'kernels': mosaic_kernels(text)}
