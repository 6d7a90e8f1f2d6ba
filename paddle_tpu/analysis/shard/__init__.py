"""shardlint — static sharding & communication-budget analysis for the
distributed layer.

tracelint proves the serving contract at the SOURCE level, mosaiclint
the Mosaic kernel contract at the JAXPR level; this third family
proves the SHARDING contract at the level GSPMD decides it.  Every
registered suite (`registry.py`: mp_layers, data_sharding/ZeRO specs,
ring/Ulysses sequence parallel, MoE dispatch, the pipeline schedules,
the collective wrappers) is compiled over ShapeDtypeStructs under a
virtual 8-device mesh on CPU, and SL001–SL006 (`rules/`) check the
post-SPMD collective census against each suite's declared
communication budget, replication blowups, donation/sharding aliasing,
host gathers of sharded globals, axis-name typos that the clamping
helpers would silently replicate, and shard_map-body collectives over
axes the body cannot vary over — so an all-gather nobody asked for
fails tier-1 on CPU instead of burning a multichip run.

CLI: `python -m paddle_tpu.analysis --shard` or the `shardlint`
console script.  Same Violation/severity/baseline machinery as its
siblings (`tools/shardlint_baseline.json`); suppression lives in the
registry (compiled HLO has no comment lines) and always carries a
reason.
"""
from .engine import (
    COLLECTIVE_KINDS,
    COLLECTIVE_PRIMITIVES,
    DEFAULT_VIRTUAL_DEVICES,
    REPLICATION_THRESHOLD_BYTES,
    Entry,
    ShardContext,
    ShardMapInfo,
    ShardRule,
    Suite,
    collective_census,
    comm_report,
    ensure_virtual_devices,
    host_transfer_audit,
    lint_and_report,
    lint_entries,
    spec_audit,
    trace_entry,
    virtual_mesh,
)
from .registry import all_entries, entries_for
from .rules import all_rules, get_rule

__all__ = [
    'COLLECTIVE_KINDS', 'COLLECTIVE_PRIMITIVES',
    'DEFAULT_VIRTUAL_DEVICES', 'REPLICATION_THRESHOLD_BYTES',
    'Entry', 'ShardContext', 'ShardMapInfo', 'ShardRule', 'Suite',
    'collective_census', 'comm_report', 'ensure_virtual_devices',
    'host_transfer_audit', 'lint_and_report', 'lint_entries',
    'spec_audit', 'trace_entry', 'virtual_mesh',
    'all_entries', 'entries_for', 'all_rules', 'get_rule',
]
