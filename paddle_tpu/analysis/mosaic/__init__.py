"""mosaiclint — static Mosaic/TPU legality analysis for pallas kernels.

tracelint (the sibling package) proves the serving contract at the
SOURCE level; this package proves the compiler contract at the JAXPR
level.  Interpret-mode green does not imply Mosaic-legality — tile
alignment, i1 reshapes, unsupported primitives, and VMEM budgets only
bite when a real chip lowers the kernel.  mosaiclint abstract-evals
every registered kernel suite (`registry.py`) on CPU, inspects each
`pallas_call`'s GridMapping and body jaxpr, and enforces ML001–ML006
(`rules/`) — so tier-1 catches the refusals the rules know about with
no chip, and `tools/mosaic_check.py` spends on-chip minutes only on
statically-clean kernels.  The rules are not Mosaic: what they pass can
still be refused (`quant_matmul_int4`'s int8 shifts were), and
`tests/test_chip_compile.py`, which asks the chip's own compiler, is the
authority.

CLI: `python -m paddle_tpu.analysis --mosaic` or the `mosaiclint`
console script.  Same Violation/severity/baseline machinery as
tracelint (`tools/mosaiclint_baseline.json`); suppression lives in the
registry (jaxprs have no comment lines) and always carries a reason.
"""
from .engine import (
    Entry,
    KernelContext,
    MosaicRule,
    PallasCall,
    VMEM_BYTES_PER_CORE,
    VMEM_BYTES_PHYSICAL,
    extract_pallas_calls,
    force_tpu_variant,
    iter_eqns,
    lint_and_report,
    lint_entries,
    sublane_multiple,
    trace_entry,
    vmem_report,
)
from .registry import all_entries, entries_for
from .rules import all_rules, get_rule

__all__ = [
    'Entry', 'KernelContext', 'MosaicRule', 'PallasCall',
    'VMEM_BYTES_PER_CORE',
    'VMEM_BYTES_PHYSICAL',
    'extract_pallas_calls', 'force_tpu_variant', 'iter_eqns',
    'lint_and_report', 'lint_entries', 'sublane_multiple', 'trace_entry',
    'vmem_report',
    'all_entries', 'entries_for', 'all_rules', 'get_rule',
]
