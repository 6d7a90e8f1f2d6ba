"""Mosaic-legality check for the pallas kernels on the REAL chip.

Driven by the SHARED kernel registry
(`paddle_tpu.analysis.mosaic.registry`) — the same suites mosaiclint
lints statically in tier-1.  The flow:

  1. static pass first (abstract tracing, costs no chip time): every
     registered suite is linted with ML001–ML006;
  2. entries with live static violations are SKIPPED on chip — their
     verdict already says they will not lower, so on-chip minutes go
     only to statically-clean kernels;
  3. clean entries with an `onchip` runner compile + run real data
     against their XLA reference, printed as PASS/FAIL with the static
     verdict alongside so the two columns are directly comparable.

Run on a machine with a TPU (the only process using it):

    python tools/mosaic_check.py

Exits 0 all-clean, 1 on any on-chip failure or static violation, 2
when no TPU backend is reachable (importable anywhere; only main()
touches the backend).
"""
import os
import sys

# `python tools/mosaic_check.py` puts tools/ (not the repo root) on
# sys.path and paddle_tpu is not pip-installed on the dev boxes — make
# the repo importable no matter where the script is launched from
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

# what a kernel-vs-reference check can actually throw: numeric
# mismatches (AssertionError), Mosaic lowering refusals
# (NotImplementedError / TypeError / ValueError), XLA runtime failures
# (XlaRuntimeError subclasses RuntimeError), and a kernel module that
# does not exist on this build (ImportError / AttributeError). A bare
# `except Exception` also swallowed KeyboardInterrupt-adjacent bugs and
# typos in the checks themselves — this tuple does not.
KERNEL_CHECK_ERRORS = (AssertionError, NotImplementedError, TypeError,
                       ValueError, RuntimeError, ImportError,
                       AttributeError)


def static_verdicts(entries, root=None):
    """{entry name: (violations, suppressed)} from the static pass."""
    from paddle_tpu.analysis.mosaic import lint_entries

    verdicts = {}
    for entry in entries:
        vs, sup = lint_entries([entry], root=root)
        verdicts[entry.name] = (vs, sup)
    return verdicts


def _verdict_str(vs, sup):
    if vs:
        rules = sorted({v.rule for v in vs})
        errors = sum(1 for v in vs if v.severity == 'error')
        kind = (f'{errors} error(s)' if errors
                else f'{len(vs)} warning(s)')
        return f'static: {kind} [{", ".join(rules)}]'
    if sup:
        return f'static: clean ({len(sup)} suppressed)'
    return 'static: clean'


def main():
    import jax

    from paddle_tpu.analysis.mosaic.registry import all_entries

    # guard, not assert: `python -O` strips asserts, and an import of
    # this module (pytest collection, tracelint) must never touch the
    # backend at all — only main() does
    if jax.default_backend() != 'tpu':
        print(f'mosaic_check: needs the real chip '
              f'(backend={jax.default_backend()}); run it on a machine '
              f'with a TPU')
        return 2
    print(f'device: {jax.devices()[0].device_kind}')

    root = _ROOT
    entries = all_entries()
    print(f'static pass over {len(entries)} registered suite(s)...')
    verdicts = static_verdicts(entries, root=root)

    failures, skipped = [], []
    for entry in entries:
        vs, sup = verdicts[entry.name]
        verdict = _verdict_str(vs, sup)
        if any(v.severity == 'error' for v in vs):
            # statically illegal: the chip would only re-discover what
            # the lint already proved — spend zero on-chip time on it.
            # WARNINGS do not skip: they exist precisely to be
            # confirmed or cleared by this on-chip run.
            skipped.append(entry.name)
            print(f'SKIP {entry.name} [{verdict}]')
            for v in vs:
                print(f'     {v.render()}')
            continue
        if entry.onchip is None:
            print(f'---- {entry.name} [{verdict}] (no on-chip runner)')
            continue
        try:
            entry.onchip()
            print(f'PASS {entry.name} [{verdict}]')
        except KERNEL_CHECK_ERRORS as e:
            failures.append(entry.name)
            print(f'FAIL {entry.name} [{verdict}]: '
                  f'{type(e).__name__}: {e}')

    # -- TP decode via shard_map needs >1 device: skipped on one chip --

    if failures or skipped:
        print(f'\n{len(failures)} on-chip FAILURE(S): {failures}; '
              f'{len(skipped)} statically-dirty suite(s) skipped: '
              f'{skipped}')
        return 1
    print('\nall registered kernels Mosaic-legal: static pass clean, '
          'on-chip runners green')
    return 0


if __name__ == '__main__':
    sys.exit(main())
