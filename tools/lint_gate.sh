#!/usr/bin/env bash
# Static-analysis gate: every analyzer family — tracelint, mosaiclint,
# shardlint, hlolint, statelint — in one shot with baseline-diff
# semantics (fail ONLY on NEW violations; everything in the committed
# tools/*_baseline files is tolerated until ratcheted out).
#
# This is the shell entry point for CI and pre-push hooks; bench.py's
# per-family gates (_tracelint_gate .. gate_statelint) run the same
# unified runner in-process per family so each family's evidence lands
# in the bench detail blob separately.
#
#   tools/lint_gate.sh            # all five families, combined rc
#   tools/lint_gate.sh --format json
#
# rc 0: every family clean (modulo baselines/suppressions)
# rc 1: NEW error-severity violations somewhere — fix or re-baseline
# rc 2: a family could not run (no jax backend, registry import error)
#
# The analyzers need no chip and must not claim one: pin the CPU
# backend (statelint's live wire-schema engines included), and pre-set
# the virtual 8-device flag shardlint/hlolint need so the mesh suites
# compile even when something imported jax before the runner's own
# guard could.
set -u

ROOT="$(cd "$(dirname "$0")/.." && pwd)"

JAX_PLATFORMS=cpu \
XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}" \
exec python -m paddle_tpu.analysis --all --root "$ROOT" "$@"
