"""The statelint registry: every stateful runtime class, every
mutable attribute, classified.

This file IS the engine-state coverage contract. ST001 forces every
`self.X = ...` site the AST scan finds into exactly one of four
classifications, and the classifications are PROVEN, not trusted:
`persisted` claims are diffed against the live wire dicts (ST002),
every live wire key must be claimed by something (ST003), and the
declared asymmetries/lock-free paths/suppressions all carry mandatory
reasons — rc 2 on an empty one, never a silent pass.

Adding an attribute to a registered class therefore FAILS the lint
until its author answers the question PR 8-16 kept re-answering by
hand in review: does this survive a snapshot/restore, a KV migration,
an AOT attach — and if not, why is losing it correct?

Wire names used in claims (extracted live by live.py):

  snapshot         ServingEngine.snapshot() top level
  snapshot_config  _snapshot_config() — the restore/import refusal set
  request          _request_record() — per-request record (snapshot
                   'requests'/'terminal' entries AND the blob 'request')
  blob             export_kv() migration blob top level
  aot_config       ServingEngine.aot_config() — artifact refusal set
  train_aot_config TrainEngine.aot_config()
  watchdog         Watchdog.snapshot_state()
  prefill_snapshot PrefillEngine.snapshot() (extends 'snapshot')
  pair_snapshot    DisaggPair.snapshot()
  fleet_snapshot   Fleet.snapshot() — per-replica engine snapshots
                   plus the fleet's routing table and sim clock
"""
from __future__ import annotations

from .engine import (ClassDecl, RoundTrip, derived, device, ephemeral,
                     persisted)

# Wire keys that are not backed by any single instance attribute —
# schema stamps, structural sections, derived scalars. ST003 treats
# these as documented; everything else on a live wire needs an
# attribute claim.
WIRE_STRUCTURAL = {
    'snapshot': {
        'schema': 'wire version stamp (inference._schema)',
        'config': 'the _snapshot_config() refusal set, nested',
    },
    'blob': {
        'schema': 'wire version stamp (inference._schema)',
        'kind': 'blob discriminator (inference._schema KV_BLOB_KIND)',
        'config': 'the _snapshot_config() refusal set, nested',
        'request': 'the full _request_record of the migrated stream',
        'kv_len': 'derived: context_len - 1 of the carried request',
        'layers': 'per-layer contiguous KV rows (the payload)',
        'draft_kv_len': 'derived: draft-pool coverage at export',
        'draft_layers': 'draft-pool KV rows when speculative',
        'trail': 'flight-recorder trail riding the migration',
    },
    'aot_config': {
        'engine': 'class tag, not instance state',
        'prefill_tokens': 'module constant (serving.PREFILL_TOKENS): '
                          'the admission batch\'s token budget, which '
                          'fixes the row count of every prefill program',
    },
    'train_aot_config': {
        'engine': 'class tag, not instance state',
    },
    'watchdog': {
        'schema': 'wire version stamp',
    },
    'pair_snapshot': {
        'schema': 'wire version stamp (inference._schema)',
    },
    'fleet_snapshot': {
        'schema': 'wire version stamp (fleet.FLEET_SNAPSHOT_SCHEMA)',
    },
}

# A wire that is a superset of another (subclass snapshot overrides):
# ST003 folds the base wire's claims in before hunting dead keys.
WIRE_EXTENDS = {
    'prefill_snapshot': 'snapshot',
}


_SERVING = ClassDecl(
    name='inference.serving.ServingEngine',
    path='paddle_tpu/inference/serving.py',
    cls='ServingEngine',
    owns_wires=('snapshot', 'snapshot_config', 'blob', 'aot_config'),
    roundtrips=(
        RoundTrip('snapshot', 'restore', 'snap', marker='schema'),
        RoundTrip('export_kv', 'import_kv', 'blob', marker='schema'),
        RoundTrip('_request_record', '_rebuild_request', 'r',
                  marker='rid'),
    ),
    roundtrip_ok={
        'block_size': 'informational: KV rows ship flat (contiguous '
                      'positions), so the importer scatters per its '
                      'OWN page geometry and never reads the '
                      "exporter's",
    },
    geometry_methods=('_geometry', '_sampling_key'),
    config_identity={
        # attr -> (wire, key) pairs its identity must ride. Evidence:
        # every self.X load inside _geometry()/_sampling_key() — the
        # tuples that key compiled executables — must appear here,
        # and every named key must exist on the live refusal wire.
        'max_slots': (('aot_config', 'max_slots'),),
        'allocator': (('aot_config', 'num_blocks'),),
        'block_size': (('aot_config', 'block_size'),),
        'max_blocks_per_seq': (('aot_config', 'max_context_len'),
                               ('aot_config', 'block_size')),
        'tp': (('aot_config', 'tp'),),
        'spec_window': (('aot_config', 'num_draft_tokens'),),
        'draft': (('aot_config', 'draft'),
                  ('aot_config', 'draft_struct')),
        'max_new_tokens': (('aot_config', 'max_new_tokens'),),
        'temperature': (('aot_config', 'temperature'),
                        ('snapshot_config', 'temperature')),
        'top_k': (('aot_config', 'top_k'),
                  ('snapshot_config', 'top_k')),
        'top_p': (('aot_config', 'top_p'),
                  ('snapshot_config', 'top_p')),
        'eos_token_id': (('aot_config', 'eos_token_id'),
                         ('snapshot_config', 'eos_token_id')),
    },
    attrs={
        # -- host-authoritative state the snapshot carries ------------
        '_live': persisted(('snapshot', 'requests')),
        'queue': persisted(
            ('snapshot', 'requests'),
            note='queued requests serialize into the same records as '
                 'running ones; restore() re-pushes'),
        '_terminal': persisted(('snapshot', 'terminal')),
        '_rid': persisted(('snapshot', 'next_rid')),
        'preemption_count': persisted(('snapshot', 'preemptions')),
        'counts': persisted(('snapshot', 'counts')),
        'prefix_counts': persisted(('snapshot', 'prefix_counts')),
        'spec_counts': persisted(('snapshot', 'spec_counts')),
        'migration_counts': persisted(('snapshot', 'migration_counts')),
        '_tokens_out': persisted(('snapshot', 'tokens_out')),
        '_serve_time': persisted(('snapshot', 'serve_time')),
        'draining': persisted(('snapshot', 'draining')),
        '_watchdog': persisted(
            ('snapshot', 'watchdog'),
            note='its own snapshot_state()/load_state() pair; see the '
                 'observability.watchdog.Watchdog declaration'),
        # -- constructor config whose IDENTITY rides the refusal sets -
        'model': derived(
            note='weights are the checkpoint/artifact problem; the '
                 'structure hash is what must match',
            claims=(('aot_config', 'model'),
                    ('aot_config', 'model_struct'),
                    ('aot_config', 'cache_dtype'),
                    ('snapshot_config', 'model'),
                    ('snapshot_config', 'model_struct'))),
        'draft': derived(
            note='speculative draft model; identity rides the refusal '
                 'set like the target model',
            claims=(('aot_config', 'draft'),
                    ('aot_config', 'draft_struct'))),
        'allocator': derived(
            note='page maps rebuild by re-placement; pool size is the '
                 'compilation-relevant part',
            claims=(('aot_config', 'num_blocks'),)),
        'temperature': persisted(('aot_config', 'temperature'),
                                 ('snapshot_config', 'temperature')),
        'top_k': persisted(('aot_config', 'top_k'),
                           ('snapshot_config', 'top_k')),
        'top_p': persisted(('aot_config', 'top_p'),
                           ('snapshot_config', 'top_p')),
        'eos_token_id': persisted(('aot_config', 'eos_token_id'),
                                  ('snapshot_config', 'eos_token_id')),
        'max_context_len': persisted(
            ('aot_config', 'max_context_len'),
            ('snapshot_config', 'max_context_len')),
        'max_new_tokens': persisted(('aot_config', 'max_new_tokens')),
        'max_slots': persisted(('aot_config', 'max_slots')),
        'block_size': persisted(('aot_config', 'block_size'),
                                ('blob', 'block_size')),
        'decode_window': persisted(('aot_config', 'decode_window')),
        'buckets': persisted(('aot_config', 'buckets')),
        'prefix_cache': persisted(('aot_config', 'prefix_cache')),
        'prefill_chunk': persisted(('aot_config', 'prefill_chunk')),
        'kv_cache_dtype': persisted(('aot_config', 'kv_cache_dtype'),
                                    ('blob', 'kv_cache_dtype')),
        'spec_window': persisted(('aot_config', 'num_draft_tokens')),
        'tp': persisted(('aot_config', 'tp')),
        # -- host bookkeeping restore() rebuilds ----------------------
        '_slot_req': derived(note='slot table; requests re-enter '
                                  'preempted and re-place'),
        '_slot_pages': derived(note='per-slot page lists; re-placement'),
        '_btab': derived(note='block tables; re-placement'),
        # a two-kind model's recycled kind of page (its description is
        # the model's, which rides the refusal set): twins of the above
        '_kinds': derived(note="the model's page_kinds(); rebuilt from "
                               'the model at construction'),
        '_ring': derived(note='index of the recycled kind in _kinds'),
        'win_allocator': derived(
            note='recycled-kind page maps rebuild by re-placement; its '
                 'pool size follows max_slots, decode_window and the '
                 "kind's window"),
        'win_pages_per_slot': derived(note='the bound a slot holds of '
                                           'the recycled kind'),
        '_slot_wpages': derived(note='per-slot recycled-kind page '
                                     'lists; re-placement'),
        '_wfirst': derived(note='first logical page a slot holds of '
                                'the recycled kind; re-placement'),
        '_wtab': derived(note='recycled-kind block tables; '
                              're-placement'),
        '_ctx': derived(note='per-slot context lengths; re-prefill'),
        '_dctx': derived(note='draft-pool context lengths; catch-up'),
        '_plen': derived(note='per-slot prompt lengths'),
        '_pfill': derived(note='chunked-prefill progress; restarts'),
        '_budget': derived(note='per-step admission budget'),
        '_temp': derived(note='per-slot sampling temperature staging'),
        '_topk': derived(note='per-slot top-k staging'),
        '_topp': derived(note='per-slot top-p staging'),
        '_seed': derived(note='per-slot sampling seed staging'),
        '_cow_pending': derived(note='copy-on-write staging; empty at '
                                     'any snapshot boundary'),
        '_cow_release': derived(note='CoW release staging'),
        '_paused_head': derived(note='head-of-line pause bookkeeping'),
        '_deadlines_live': derived(note='count recomputed as restore '
                                        're-registers deadlines'),
        '_admit_seq': derived(note='arrival stamps; queue.reset_seq '
                                   'continues past the snapshot'),
        'max_blocks_per_seq': derived(note='computed from '
                                           'max_context_len/block_size'),
        '_layer_windows': derived(note="read off the model's attention "
                                       'layers at construction'),
        # -- device-resident, re-derived by AOT attach / re-prefill ---
        '_pages': device(note='paged KV pool; re-prefill reconstructs'),
        '_dpages': device(note='draft KV pool'),
        '_last_logits': device(note='last decode logits; recomputed'),
        '_dlogits': device(note='draft logits'),
        '_dummy_slots': device(note='warmup dummy slot buffers'),
        '_draft_shapes': device(note='draft dispatch shape cache'),
        '_zero_ftok': device(note='zero forced-token buffer'),
        '_zero_forced': device(note='zero forced-count buffer'),
        '_rep': device(note='replicated sharding handle'),
        '_dev': device(note='device handle'),
        'mesh': device(note='device mesh; rebuilt at construction, '
                            'its degree rides aot_config tp'),
        # -- deliberately process-local ------------------------------
        'ops_server': ephemeral(
            'a bound socket cannot ride a snapshot; the standby opens '
            'its own ops endpoint (close() owns the shutdown)'),
        '_ts': ephemeral(
            'windowed perf timeseries; windows restart with the '
            'process, durable totals ride the snapshot counts'),
        '_mx': ephemeral('cached metric handles; re-created on use'),
        '_mgen': ephemeral('metrics-registry generation stamp'),
        '_last_occ': ephemeral('last occupancy gauge value'),
        '_dispatch_costs': ephemeral(
            'per-geometry dispatch cost cache for MFU; re-measured'),
        '_peak_flops': ephemeral('device peak-FLOPs estimate; '
                                 're-probed per process'),
        '_last_mfu': ephemeral('rolling MFU gauge'),
        '_collect_guard': ephemeral('re-entrancy guard flag'),
        'postmortem_dir': ephemeral('host path knob'),
        'last_postmortem': ephemeral('path of the last postmortem '
                                     'bundle written by THIS process'),
        '_postmortem_seq': ephemeral('postmortem filename counter'),
        'last_deliveries': ephemeral('(rid, first_index, n) of the step '
                                     'just ended; rewritten every step'),
        'max_queue': ephemeral('host admission knob; an operator sets '
                               'it per replica, not per snapshot'),
        'admit_watermark': ephemeral('host admission knob'),
        'shed_policy': ephemeral('host admission knob'),
        'max_terminal': ephemeral('host retention knob'),
        'phase_role': ephemeral(
            'constructor role config; a standby is built WITH its '
            'role — carrying it would let a snapshot silently flip '
            "an engine's role"),
        '_registry': ephemeral(
            'which MetricsRegistry the serve.*/pool.* series land in '
            '(a fleet replica gets a private one); scrape-time state, '
            'and the durable counters ride the snapshot counts wires'),
        '_jr': ephemeral(
            'which flight-recorder Journal request trails land in; '
            'the trails themselves ride the snapshot trails key'),
        '_rid_start': ephemeral(
            "the replica's rid-stride origin — construction config "
            "(the fleet rebuilds it from the fleet_snapshot replica "
            "index), used only by restore()'s fresh-engine check"),
    },
)


_PREFILL = ClassDecl(
    name='inference.disagg.PrefillEngine',
    path='paddle_tpu/inference/disagg.py',
    cls='PrefillEngine',
    inherit='inference.serving.ServingEngine',
    owns_wires=('prefill_snapshot',),
    # subclass-override style: snapshot() mutates super()'s dict
    roundtrips=(RoundTrip('snapshot', 'restore', 'snap', marker=None),),
    attrs={
        '_handoffs': persisted(
            ('prefill_snapshot', 'handoffs'),
            note='completed-but-unferried blobs — the ONLY record a '
                 'migrated request exists between sweep and ferry'),
        'handoff_sink': ephemeral(
            'host callback; re-bound at construction like the '
            "watchdog's breach hooks"),
    },
)


_PAIR = ClassDecl(
    name='inference.disagg.DisaggPair',
    path='paddle_tpu/inference/disagg.py',
    cls='DisaggPair',
    owns_wires=('pair_snapshot',),
    roundtrips=(RoundTrip('snapshot', 'restore', 'snap',
                          marker='schema'),),
    attrs={
        'prefill': persisted(
            ('pair_snapshot', 'prefill'),
            note='the prefill pool; its full snapshot nests here'),
        'decode': persisted(
            ('pair_snapshot', 'decode'),
            note='the decode pool; its full snapshot nests here'),
        '_pending': persisted(
            ('pair_snapshot', 'pending'),
            note='in-transit ferry blobs — neither pool knows them'),
        '_failed': persisted(
            ('pair_snapshot', 'failed'),
            note='permanent placement failures re-raised at result()'),
    },
)


_REQUEST = ClassDecl(
    name='inference.serving.Request',
    path='paddle_tpu/inference/serving.py',
    cls='Request',
    owns_wires=('request',),
    attrs={
        'rid': persisted(('request', 'rid')),
        'prompt': persisted(('request', 'prompt')),
        'generated': persisted(('request', 'generated')),
        'max_new_tokens': persisted(('request', 'max_new_tokens')),
        'priority': persisted(('request', 'priority')),
        'seq': persisted(('request', 'seq')),
        'state': persisted(('request', 'state')),
        'reason': persisted(('request', 'reason')),
        'error': persisted(
            ('request', 'error'),
            note='as repr() — exception objects do not cross a '
                 'process boundary'),
        'result': persisted(('request', 'result')),
        'deadline': persisted(
            ('request', 'deadline_left_s'),
            note='as REMAINING budget — absolute perf_counter stamps '
                 'are meaningless in another process; restore re-arms'),
        'temperature': persisted(('request', 'temperature')),
        'top_k': persisted(('request', 'top_k')),
        'top_p': persisted(('request', 'top_p')),
        'sample_seed': persisted(('request', 'sample_seed')),
        'spec_next': persisted(
            ('request', 'spec_next'),
            note="the verify step's pending choice; a restored "
                 'speculative stream resumes bit-equal'),
        'page_hashes': derived(note='recomputed from the prompt for '
                                    'prefix-cache placement'),
        'times': ephemeral(
            'absolute perf_counter marks; the durable event record is '
            'the journal trail, which rides the snapshot'),
        'enqueued_at': ephemeral(
            'absolute clock stamp; deadline re-arms from '
            'deadline_left_s instead'),
        'admit_seq': ephemeral(
            'admission stamp re-issued by the restoring engine'),
        'journal': ephemeral(
            "which flight recorder mark() writes to (the owning "
            "engine's private journal, or the process one); the "
            'events themselves ride the snapshot trails key'),
    },
)


_QUEUE = ClassDecl(
    name='inference.serving.RequestQueue',
    path='paddle_tpu/inference/serving.py',
    cls='RequestQueue',
    attrs={
        '_heap': derived(note='rebuilt by restore() re-pushing every '
                              'live request'),
        '_seq': derived(note='reset_seq() continues past the '
                             "snapshot's max request seq"),
        '_dead': derived(note='lazy-deletion tombstones; empty on a '
                              'fresh restore'),
    },
)


_ALLOCATOR = ClassDecl(
    name='inference.serving.BlockAllocator',
    path='paddle_tpu/inference/serving.py',
    cls='BlockAllocator',
    attrs={
        'num_blocks': derived(note='pool geometry; rides aot_config '
                                   'num_blocks via the owning engine'),
        'block_size': derived(note='rides aot_config block_size via '
                                   'the owning engine'),
        'bytes_per_page': derived(note='computed from geometry/dtype'),
        '_free': derived(note='free list; rebuilt by re-placement'),
        '_ref': derived(note='page refcounts; re-placement'),
        '_hash_of': derived(note='prefix-cache page hashes; '
                                 're-placement'),
        '_index': derived(note='prefix hash index; re-placement'),
        '_cached': derived(note='evictable cached-page set; '
                                're-placement'),
        'phase': ephemeral('scheduler-phase tag for allocation '
                           'accounting only'),
        'alloc_count': ephemeral('pool-lifetime stat; a restored '
                                 "standby's pool starts fresh"),
        'free_count': ephemeral('pool-lifetime stat'),
        'cow_count': ephemeral('pool-lifetime stat'),
        'high_water': ephemeral('pool-lifetime stat'),
        'prefix_evictions': ephemeral('pool-lifetime stat'),
        'prefix_shares': ephemeral('pool-lifetime stat'),
        'journal': ephemeral(
            'which flight recorder pool events land in (set by a '
            'private-registry engine); pool state itself is derived '
            'by re-placement'),
    },
)


_WATCHDOG = ClassDecl(
    name='observability.watchdog.Watchdog',
    path='paddle_tpu/observability/watchdog.py',
    cls='Watchdog',
    owns_wires=('watchdog',),
    roundtrips=(RoundTrip('snapshot_state', 'load_state', 'snap',
                          marker='schema'),),
    attrs={
        '_state': persisted(
            ('watchdog', 'rules'),
            note='per-rule breach state, matched BY NAME on load'),
        'windows_evaluated': persisted(('watchdog',
                                        'windows_evaluated')),
        'breaches_total': persisted(('watchdog', 'breaches_total')),
        'recoveries_total': persisted(('watchdog', 'recoveries_total')),
        'last_window_idx': persisted(
            ('watchdog', 'last_window_idx'),
            note="a restored standby's verdict() reports the "
                 "primary's last window instead of a fresh -1"),
        'rules': derived(note='constructor rule list; snapshot state '
                              'matches by name'),
        'on_breach': ephemeral('host callback hooks re-bound at '
                               'construction'),
        'on_recover': ephemeral('host callback hooks re-bound at '
                                'construction'),
        'postmortem_engine': ephemeral('host object reference'),
        'postmortem_min_interval_s': ephemeral('host knob'),
        '_last_postmortem_t': ephemeral('absolute clock stamp for '
                                        'postmortem rate-limiting'),
        'registry': ephemeral(
            'which MetricsRegistry the watchdog.* series land in (a '
            'private-registry replica scopes them); breach totals '
            'ride the watchdog wire'),
        'journal': ephemeral(
            'which Journal slo_breach/slo_recovered events land in; '
            'durable breach state rides the watchdog wire'),
    },
)


_SLORULE = ClassDecl(
    name='observability.watchdog.SLORule',
    path='paddle_tpu/observability/watchdog.py',
    cls='SLORule',
    attrs={
        'name': derived(note='parsed rule config; rebuilt from the '
                             'rule expression at construction'),
        'expr': derived(note='parsed rule config'),
        'op': derived(note='parsed rule config'),
        'threshold': derived(note='parsed rule config'),
        'for_windows': derived(note='parsed rule config'),
        'clear_windows': derived(note='parsed rule config'),
        'help': derived(note='parsed rule config'),
        '_a': derived(note='parsed expression operand'),
        '_b': derived(note='parsed expression operand'),
        '_fn': derived(note='compiled comparator'),
    },
)


_TIMESERIES = ClassDecl(
    name='observability.timeseries.WindowedTimeseries',
    path='paddle_tpu/observability/timeseries.py',
    cls='WindowedTimeseries',
    locks={
        # scrape thread reads while the commit path writes — the
        # PR-14 "dictionary changed size during iteration" class
        '_ring': '_lock', '_idx': '_lock', '_prev': '_lock',
        '_prev_t': '_lock', '_prev_gen': '_lock', '_edges': '_lock',
    },
    lock_free={
        '_cumulative': 'called only from _commit/_rebase, both '
                       'already under the lock',
        '_rebase': 'called only from _commit, under the lock',
    },
    attrs={
        'interval_s': ephemeral('observability window config'),
        'max_windows': ephemeral('observability window config'),
        'derive': ephemeral('derivation callables; host config'),
        'registry': ephemeral('host registry reference'),
        'journal': ephemeral('host journal reference (whose overflow '
                             'count rides the windows)'),
        '_lock': ephemeral('the lock object itself'),
        '_ring': ephemeral('perf windows restart with the process; '
                           'durable breach totals ride the watchdog '
                           'wire'),
        '_idx': ephemeral('window ring cursor'),
        '_prev': ephemeral('previous cumulative sample for deltas'),
        '_prev_t': ephemeral('previous sample clock stamp'),
        '_prev_gen': ephemeral('previous registry generation'),
        '_edges': ephemeral('histogram bucket edges cache'),
    },
)


_METRICS = ClassDecl(
    name='observability.metrics.MetricsRegistry',
    path='paddle_tpu/observability/metrics.py',
    cls='MetricsRegistry',
    locks={'_metrics': '_lock', 'generation': '_lock'},
    attrs={
        '_lock': ephemeral('the lock object itself'),
        '_metrics': ephemeral('scrape-time registry; the durable '
                              'counters ride the snapshot counts '
                              'wires instead'),
        'generation': ephemeral('registry mutation stamp for cache '
                                'invalidation'),
    },
)


_JOURNAL = ClassDecl(
    name='observability.journal.Journal',
    path='paddle_tpu/observability/journal.py',
    cls='Journal',
    lock_free={'*': 'single-writer: only the scheduler thread '
                    'records; readers copy under list()'},
    attrs={
        '_trails': persisted(
            ('snapshot', 'trails'),
            note="per-request flight-recorder trails ride the OWNING "
                 "engine's snapshot; restore() re-injects them"),
        '_events': ephemeral('ring of recent events for ops dumps; '
                             'the durable record is the trails'),
        '_seq': derived(note='bumped past injected trails on restore '
                             'so new events extend in order'),
        '_closed': ephemeral('process shutdown flag'),
        'dropped': ephemeral('ring overflow stat'),
        'max_events': ephemeral('ring size knob'),
        'max_trails': ephemeral('trail retention knob'),
        'trail_evictions': ephemeral('trail retention stat'),
    },
)


_FAULTRULE = ClassDecl(
    name='testing.faults.FaultRule',
    path='paddle_tpu/testing/faults.py',
    cls='FaultRule',
    attrs={
        'site': ephemeral('test-only fault harness config'),
        'exc': ephemeral('test-only fault harness config'),
        'p': ephemeral('test-only fault harness config'),
        'at': ephemeral('test-only fault harness config'),
        'after': ephemeral('test-only fault harness config'),
        'times': ephemeral('test-only fault harness config'),
        'when': ephemeral('test-only fault harness config'),
        'calls': ephemeral('test-only fault harness counter'),
        'fired': ephemeral('test-only fault harness counter'),
    },
)


_FAULTS = ClassDecl(
    name='testing.faults.FaultInjector',
    path='paddle_tpu/testing/faults.py',
    cls='FaultInjector',
    attrs={
        'rules': ephemeral('test-only fault harness; dies with the '
                           'process by design'),
        'calls': ephemeral('test-only fault harness counter'),
        'log': ephemeral('test-only fault harness log'),
        '_rng': ephemeral('test-only fault harness RNG'),
    },
)


_FLEET = ClassDecl(
    name='inference.fleet.Fleet',
    path='paddle_tpu/inference/fleet.py',
    cls='Fleet',
    owns_wires=('fleet_snapshot',),
    roundtrips=(RoundTrip('snapshot', 'restore', 'snap',
                          marker='schema'),),
    attrs={
        'replicas': persisted(
            ('fleet_snapshot', 'replicas'),
            note="every replica's full engine snapshot nests here, "
                 'keyed by name'),
        '_index': persisted(
            ('fleet_snapshot', 'replicas'),
            note="each replica's rid-stride index rides inside its "
                 'replicas entry; restore() rebuilds rid_start from '
                 'index * rid_stride'),
        '_next_index': persisted(('fleet_snapshot', 'next_index')),
        '_where': persisted(
            ('fleet_snapshot', 'where'),
            note='the rid -> replica routing table; without it a '
                 "restored fleet could not answer result(rid)"),
        'counts': persisted(('fleet_snapshot', 'counts')),
        'sim_time_s': persisted(
            ('fleet_snapshot', 'sim_time_s'),
            note='the autoscaling-simulation clock continues across a '
                 'fleet restore, like the engine serve_time'),
        'factory': ephemeral('host callable that builds replicas; '
                             're-bound at construction'),
        'router': ephemeral('pure placement policy object; stateless '
                            'between decisions'),
        'artifact': ephemeral('host path knob (the shared AOT '
                              'artifact dir replicas warm from)'),
        'rid_stride': ephemeral(
            'host knob; both sides of a fleet restore must agree — '
            'the wire carries each replica index, rid_start is '
            'index * stride'),
        'postmortem_dir': ephemeral('host path knob'),
        'name_prefix': ephemeral('host naming knob'),
        '_round': ephemeral('fleet step-round counter; durable sim '
                            'continuity rides sim_time_s'),
        '_submit_t': ephemeral(
            'sim-clock first-token staging for in-flight rids; a '
            'restored fleet re-measures TTFT from restore onward'),
        '_ttft': ephemeral('recorded sim TTFTs; reporting state, '
                           'bounded and re-accumulated per process'),
        'max_ttft_records': ephemeral('retention knob'),
        '_routed_by': ephemeral(
            'per-replica route census behind the route_share gauges; '
            'the durable total rides the fleet_snapshot counts'),
    },
)


_ROUTER = ClassDecl(
    name='inference.fleet.Router',
    path='paddle_tpu/inference/fleet.py',
    cls='Router',
    attrs={
        'max_pressure': ephemeral('pure routing-policy knob; no '
                                  'placement state survives a decision'),
    },
)


_SIGNALS = ClassDecl(
    name='inference.fleet.ReplicaSignals',
    path='paddle_tpu/inference/fleet.py',
    cls='ReplicaSignals',
    attrs={
        # a signals object is one point-in-time scrape — every field
        # is recomputed per routing decision, nothing survives
        'name': ephemeral('scrape identity'),
        'role': ephemeral('point-in-time scrape value'),
        'healthy': ephemeral('point-in-time scrape value'),
        'draining': ephemeral('point-in-time scrape value'),
        'breaching': ephemeral('point-in-time scrape value'),
        'queue_depth': ephemeral('point-in-time scrape value'),
        'in_flight': ephemeral('point-in-time scrape value'),
        'pool_pressure': ephemeral('point-in-time scrape value'),
        'tok_s': ephemeral('point-in-time scrape value'),
        'err_rate': ephemeral('point-in-time scrape value'),
    },
)


_TRAIN = ClassDecl(
    name='training.engine.TrainEngine',
    path='paddle_tpu/training/engine.py',
    cls='TrainEngine',
    owns_wires=('train_aot_config',),
    attrs={
        'model': derived(
            note='weight values are the checkpoint problem; structure '
                 'is the refusal contract',
            claims=(('train_aot_config', 'model'),
                    ('train_aot_config', 'model_struct'))),
        'optimizer': derived(
            note='optimizer identity + lr mode are '
                 'compilation-relevant',
            claims=(('train_aot_config', 'optimizer'),
                    ('train_aot_config', 'lr_mode'))),
        'loss_fn': derived(
            note='traced into the fused step',
            claims=(('train_aot_config', 'loss_fn'),)),
        'loss_mode': persisted(('train_aot_config', 'loss_mode')),
        'accum_steps': persisted(('train_aot_config', 'accum_steps')),
        '_scaler_cfg': persisted(('train_aot_config', 'scaler_cfg')),
        'mesh': derived(
            note='device mesh rebuilt at construction; its geometry '
                 'is the refusal contract',
            claims=(('train_aot_config', 'mesh'),)),
        'scaler': derived(note='rebuilt from _scaler_cfg'),
        '_lr_kw': derived(note='derived from the optimizer config'),
        'opt_state': ephemeral(
            "optimizer moments are the training loop CHECKPOINT's "
            "payload, not the serving/AOT wires' — torn off and "
            'saved alongside params'),
        'scaler_state': ephemeral(
            'loss-scale state rides the checkpoint with opt_state'),
        '_host_step': ephemeral('step counter; rides the training '
                                'loop checkpoint, not these wires'),
        'metrics': ephemeral('host metric callables'),
        'log_window': ephemeral('host logging knob'),
        '_engine_id': ephemeral('process-local id for trace labels'),
        '_pending': ephemeral('in-flight dispatch bookkeeping drained '
                              'at the step boundary'),
        '_eval_pending': ephemeral('in-flight eval bookkeeping'),
        '_last_loss': ephemeral('last step loss gauge'),
        '_last_vals': ephemeral('last metric values gauge'),
        '_last_scale_seen': ephemeral('last loss-scale gauge'),
        '_last_mfu': ephemeral('rolling MFU gauge'),
        '_dispatch_costs': ephemeral('per-geometry dispatch cost '
                                     'cache; re-measured'),
        '_peak_flops': ephemeral('device peak-FLOPs estimate; '
                                 're-probed per process'),
        '_traces_mark': ephemeral('compile-trace cursor'),
        '_window_bytes': ephemeral('perf window accumulator'),
        '_window_flops': ephemeral('perf window accumulator'),
        '_window_miss': ephemeral('perf window accumulator'),
        '_window_t0': ephemeral('perf window clock stamp'),
        '_window_tokens': ephemeral('perf window accumulator'),
    },
)


DECLS = (
    _SERVING, _PREFILL, _PAIR, _REQUEST, _QUEUE, _ALLOCATOR,
    _WATCHDOG, _SLORULE, _TIMESERIES, _METRICS, _JOURNAL,
    _FAULTRULE, _FAULTS, _FLEET, _ROUTER, _SIGNALS, _TRAIN,
)


def entries_for(paths=None, root=None):
    """The declarations to lint — all of DECLS, or only those whose
    source file matches one of `paths` (repo-relative prefixes, like
    the other families' path filters)."""
    if not paths:
        return list(DECLS)
    norm = [p.rstrip('/') for p in paths]
    out = []
    for decl in DECLS:
        if any(decl.path == p or decl.path.startswith(p + '/')
               for p in norm):
            out.append(decl)
    return out
