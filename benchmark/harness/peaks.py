"""Published peaks of one chip, keyed by jax's `device_kind`.

One table, a source per row, no environment override: a device that is
not in it is an error, never a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_bf16: float       # FLOP/s
    hbm_bytes_s: float      # B/s
    source: str


PEAKS = {
    'TPU v5 lite': Peak(197e12, 819e9,
                        'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                        'bf16, 819 GB/s HBM per chip'),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f'benchmark: no published peak for device_kind {device_kind!r}; '
            f'add a row with its source to benchmark/harness/peaks.py '
            f'(known: {sorted(PEAKS)})') from None
