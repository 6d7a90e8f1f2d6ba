"""Run every static analyzer family — tracelint + mosaiclint +
shardlint + hlolint — with one combined exit code.

    python tools/lint_all.py [--root DIR] [--format text|json]

Thin wrapper over the unified runner (`python -m paddle_tpu.analysis
--all`), kept for muscle memory and for the backend guard below: the
unified runner shares one JSON schema ({'schema', 'rc', 'families'})
and one combined rc across all four families:

    0  every family clean (modulo baselines/suppressions)
    1  any family found new error-severity violations
    2  no family violated but at least one could not run (no jax
       backend, registry failed to load, usage error)

mosaiclint traces the kernel registry, shardlint compiles the
distributed registry, and hlolint compiles the serving/AOT suite
registry, so a usable jax backend is required — pin
`JAX_PLATFORMS=cpu` so the analyzers never claim a chip another
process needs (the rc-2 guard below refuses cleanly when no backend initialises,
mirroring tools/mosaic_check.py).  Importable anywhere; only main()
touches the backend.
"""
from __future__ import annotations

import argparse
import os
import sys

# `python tools/lint_all.py` puts tools/ (not the repo root) on
# sys.path and paddle_tpu is not pip-installed on the dev boxes — make
# the repo importable no matter where the script is launched from
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _backend_ok():
    """True when jax can initialise SOME backend (shardlint/hlolint
    force the virtual-device flag themselves; this only guards total
    absence)."""
    try:
        from paddle_tpu.analysis.shard import ensure_virtual_devices

        # sets --xla_force_host_platform_device_count=8 before the
        # backend wakes up, then counts devices
        ensure_virtual_devices()
        return True
    except Exception:  # noqa: BLE001 - no backend at all
        return False


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog='lint_all',
        description='tracelint + mosaiclint + shardlint + hlolint, '
                    'combined rc (delegates to '
                    '`python -m paddle_tpu.analysis --all`)')
    ap.add_argument('--root', default=_ROOT,
                    help='project root (default: the repo this script '
                         'lives in)')
    ap.add_argument('--format', choices=('text', 'json'), default='text')
    args = ap.parse_args(argv)

    if not _backend_ok():
        print('lint_all: no jax backend reachable (mosaiclint/'
              'shardlint/hlolint trace with jax) — run with '
              'JAX_PLATFORMS=cpu', file=sys.stderr)
        return 2

    from paddle_tpu.analysis.__main__ import main as analysis_main

    return analysis_main(
        ['--all', '--root', args.root, '--format', args.format])


if __name__ == '__main__':
    sys.exit(main())
