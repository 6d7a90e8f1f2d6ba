"""Pallas TPU kernels (SURVEY §2.12).

Each kernel ships a lax reference implementation and is verified
against it in tests (interpret mode on CPU) — and, because interpret
green does not imply Mosaic-legality, every kernel entry point in this
package MUST also be registered in `paddle_tpu.analysis.mosaic.registry`
with its bench-representative shape suites.  mosaiclint
(docs/mosaiclint.md) abstract-evals those suites in tier-1 and
enforces the TPU lowering rules (tile alignment, tail masking, VMEM
budget, ...); `tests/test_mosaiclint.py::TestMeta` fails if a module
here has no registry entry, so a new kernel cannot land unanalyzed.

The kernels: `flash_attention` (training and prefill attention, custom
VJP), `decode_attention` (a contiguous cache's single-token step),
`paged_attention` (block-table decode over page pools, and the
head-major form), `grouped_matmul` (a served rank's expert products: the
hit experts' weights once; its row tile follows the call's static row
count, the whole call up to 1024 rows; `distributed.moe`'s branch without
`expert_offset` keeps `lax.ragged_dot`), `quant_matmul` (weight-only
int8 / fp8 / int4), `rms_norm`, `softmax_xent`.
"""


def interpret_mode():
    """Shared dispatch predicate: pallas kernels run natively only on
    TPU backends; everywhere else (CPU tests) use interpret mode.

    mosaiclint's `force_tpu_variant()` patches this to False while
    TRACING (never lowering) so block-size policies take their TPU
    branch during static analysis — keep any new dispatch decisions
    routed through here for the same reason.
    """
    import jax

    return jax.default_backend() not in ('tpu',)
