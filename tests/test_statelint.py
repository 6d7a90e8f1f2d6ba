"""statelint (paddle_tpu.analysis.state) tier-1 tests.

Every rule ST001-ST006 gets at least one negative case (a tiny
synthetic class + declaration that must trigger it) and one clean
case; plus the AST scanners (attribute inventory, lock-context
mutation scan, round-trip key extraction) as units, registry
validation (reasonless ephemeral/suppression -> ValueError -> rc 2),
the ST000 live-failure contract (AST rules still run), the census
detail blob bench.py stamps, and — the acceptance items — BOTH
injected-regression flip tests proving the unified runner goes
rc 0 -> 1 when (a) a mutable attribute loses its classification and
(b) the snapshot wire drops a persisted key.

Unit tests inject canned wire schemas (the real key lists, captured
from a live CPU run) so nothing here builds engines; the one true
live-extraction sweep is `slow`-marked — the bench gate
(gate_statelint) and tools/lint_gate.sh pin that end to end.
"""
import dataclasses
import json
import os
import textwrap

import pytest

from paddle_tpu.analysis.state import (Attr, ClassDecl, RoundTrip,
                                       derived, device, ephemeral,
                                       lint_and_report, lint_entries,
                                       persisted, roundtrip_io,
                                       scan_attrs, scan_loads,
                                       scan_mutations)
from paddle_tpu.analysis.state.registry import (DECLS, WIRE_EXTENDS,
                                                WIRE_STRUCTURAL,
                                                entries_for)
from paddle_tpu.analysis.state.rules import all_rules, get_rule

pytestmark = pytest.mark.tier1

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# The real wire key lists, captured from live_schemas() on a CPU run
# (tiny-llama geometry). Tests inject these so the unit layer never
# builds engines; test_exit_zero_with_canned_wires proves the REAL
# registry is clean against them, and the slow live sweep + the bench
# gate prove the canned copy has not drifted from the implementation.
WIRES = {
    'aot_config': [
        'block_size', 'buckets', 'cache_dtype', 'decode_window',
        'draft', 'draft_struct', 'engine', 'eos_token_id',
        'kv_cache_dtype', 'max_context_len', 'max_new_tokens',
        'max_slots', 'model', 'model_struct', 'num_blocks',
        'num_draft_tokens', 'prefill_chunk', 'prefill_tokens',
        'prefix_cache', 'temperature', 'top_k', 'top_p', 'tp'],
    'blob': [
        'block_size', 'config', 'draft_kv_len', 'draft_layers', 'kind',
        'kv_cache_dtype', 'kv_len', 'layers', 'request', 'schema',
        'trail'],
    'fleet_snapshot': ['counts', 'next_index', 'replicas', 'schema',
                       'sim_time_s', 'where'],
    'pair_snapshot': ['decode', 'failed', 'pending', 'prefill',
                      'schema'],
    'prefill_snapshot': [
        'config', 'counts', 'draining', 'handoffs', 'migration_counts',
        'next_rid', 'preemptions', 'prefix_counts', 'requests',
        'schema', 'serve_time', 'spec_counts', 'terminal', 'tokens_out',
        'trails', 'watchdog'],
    'request': [
        'deadline_left_s', 'error', 'generated', 'max_new_tokens',
        'priority', 'prompt', 'reason', 'result', 'rid', 'sample_seed',
        'seq', 'spec_next', 'state', 'temperature', 'top_k', 'top_p'],
    'snapshot': [
        'config', 'counts', 'draining', 'migration_counts', 'next_rid',
        'preemptions', 'prefix_counts', 'requests', 'schema',
        'serve_time', 'spec_counts', 'terminal', 'tokens_out', 'trails',
        'watchdog'],
    'snapshot_config': [
        'eos_token_id', 'max_context_len', 'model', 'model_struct',
        'temperature', 'top_k', 'top_p'],
    'train_aot_config': [
        'accum_steps', 'engine', 'loss_fn', 'loss_mode', 'lr_mode',
        'mesh', 'model', 'model_struct', 'optimizer', 'scaler_cfg'],
    'watchdog': [
        'breaches_total', 'last_window_idx', 'recoveries_total',
        'rules', 'schema', 'windows_evaluated'],
}


def fixture_root(tmp_path, source):
    (tmp_path / 'fixture.py').write_text(textwrap.dedent(source))
    return str(tmp_path)


def decl_of(attrs, **kw):
    kw.setdefault('name', 'fix.Fx')
    kw.setdefault('path', 'fixture.py')
    kw.setdefault('cls', 'Fx')
    return ClassDecl(attrs=attrs, **kw)


def lint_fixture(tmp_path, source, decls, rules=None, schemas=None):
    if not isinstance(decls, (list, tuple)):
        decls = [decls]
    return lint_and_report(decls, rules=rules,
                           root=fixture_root(tmp_path, source),
                           schemas=schemas if schemas is not None
                           else {})


def hits(tmp_path, source, decls, rule, schemas=None):
    vs, _, _ = lint_fixture(tmp_path, source, decls,
                            rules=[get_rule(rule)], schemas=schemas)
    return vs


def parse_class(tmp_path, source, cls='Fx'):
    import ast

    tree = ast.parse(textwrap.dedent(source))
    for node in ast.walk(tree):
        if isinstance(node, ast.ClassDef) and node.name == cls:
            return node
    raise AssertionError(f'no class {cls} in fixture')


# ---------------------------------------------------------------------------
# AST scanners
# ---------------------------------------------------------------------------

class TestScanAttrs:
    def test_every_assignment_form_is_inventoried(self, tmp_path):
        node = parse_class(tmp_path, """
            class Fx:
                def __init__(self):
                    self.a = 0
                    self.b, self.c = 1, 2
                    self.d: int = 3
                def step(self):
                    self.a += 1
                    for self.e in range(3):
                        pass
                    with open('/dev/null') as self.f:
                        pass
            """)
        attrs = scan_attrs(node)
        assert set(attrs) == {'a', 'b', 'c', 'd', 'e', 'f'}
        # first-assignment site is (line, col, method), sorted
        line, _col, method = attrs['a'][0]
        assert method == '__init__'
        assert any(m == 'step' for _, _, m in attrs['a'])

    def test_nested_function_attributed_to_enclosing_method(
            self, tmp_path):
        node = parse_class(tmp_path, """
            class Fx:
                def submit(self):
                    def on_done():
                        self.finished = True
                    return on_done
            """)
        attrs = scan_attrs(node)
        assert set(attrs) == {'finished'}
        assert attrs['finished'][0][2] == 'submit'

    def test_loads_are_not_assignments(self, tmp_path):
        node = parse_class(tmp_path, """
            class Fx:
                def get(self):
                    return self.a + self.b
            """)
        assert scan_attrs(node) == {}

    def test_scan_loads_reads_geometry_methods_only(self, tmp_path):
        node = parse_class(tmp_path, """
            class Fx:
                def _geometry(self):
                    return (self.max_slots, self.block_size)
                def other(self):
                    return self.unrelated
            """)
        assert scan_loads(node, ('_geometry',)) == {'max_slots',
                                                    'block_size'}


class TestScanMutations:
    SRC = """
        class Fx:
            def __init__(self):
                self.table = {}
            def locked(self):
                with self.lock:
                    self.table['k'] = 1
                    self.table.update({})
            def unlocked(self):
                self.table['k'] = 2
                self.table.pop('k')
                del self.table['k']
                self.table = {}
        """

    def test_lock_context_tracked_lexically(self, tmp_path):
        node = parse_class(tmp_path, self.SRC)
        sites = scan_mutations(node, {'table'})
        by_method = {}
        for attr, _line, method, held in sites:
            assert attr == 'table'
            by_method.setdefault(method, []).append(held)
        # __init__ rebind is still a site (the RULE exempts __init__)
        assert '__init__' in by_method
        assert all(held == frozenset({'lock'})
                   for held in by_method['locked'])
        assert len(by_method['locked']) == 2   # subscript + .update()
        assert all(held == frozenset() for held in by_method['unlocked'])
        assert len(by_method['unlocked']) == 4  # store/pop/del/rebind


class TestRoundtripIO:
    def test_marker_selects_the_wire_dict(self, tmp_path):
        node = parse_class(tmp_path, """
            class Fx:
                def snapshot(self):
                    junk = {'k': 1, 'v': 2}
                    return {'schema': 1, 'counts': self.c}
                def restore(self, snap):
                    self.c = snap['counts']
                    self.opt = snap.get('opt', None)
            """)
        io = roundtrip_io(node, RoundTrip('snapshot', 'restore', 'snap',
                                          'schema'))
        writes, required, optional = io
        assert writes == {'schema', 'counts'}       # junk dict skipped
        assert required == {'counts'}
        assert optional == {'opt'}

    def test_marker_none_collects_subscript_stores(self, tmp_path):
        node = parse_class(tmp_path, """
            class Fx:
                def snapshot(self):
                    snap = {}
                    snap['handoffs'] = list(self.h)
                    return snap
                def restore(self, snap):
                    self.h = snap.get('handoffs', [])
            """)
        writes, required, optional = roundtrip_io(
            node, RoundTrip('snapshot', 'restore', 'snap'))
        assert 'handoffs' in writes
        assert optional == {'handoffs'} and required == set()

    def test_missing_method_returns_none(self, tmp_path):
        node = parse_class(tmp_path, """
            class Fx:
                def snapshot(self):
                    return {'schema': 1}
            """)
        assert roundtrip_io(node, RoundTrip('snapshot', 'gone',
                                            'snap', 'schema')) is None


# ---------------------------------------------------------------------------
# ST001 — unclassified attribute (the ratchet)
# ---------------------------------------------------------------------------

class TestST001:
    SRC = """
        class Fx:
            def __init__(self):
                self.known = 0
            def step(self):
                self.new_counter = 1
        """

    def test_unclassified_attr_is_an_error(self, tmp_path):
        decl = decl_of({'known': ephemeral('test fixture')})
        vs = hits(tmp_path, self.SRC, decl, 'ST001')
        assert len(vs) == 1
        v = vs[0]
        assert v.severity == 'error'
        assert 'new_counter' in v.message and 'step()' in v.message

    def test_fully_classified_is_clean(self, tmp_path):
        decl = decl_of({'known': ephemeral('test fixture'),
                        'new_counter': derived('rebuilt in step')})
        assert hits(tmp_path, self.SRC, decl, 'ST001') == []

    def test_stale_declaration_warns(self, tmp_path):
        decl = decl_of({'known': ephemeral('test fixture'),
                        'new_counter': derived('x'),
                        'ghost': ephemeral('no longer assigned')})
        vs = hits(tmp_path, self.SRC, decl, 'ST001')
        assert [v.severity for v in vs] == ['warning']
        assert 'ghost' in vs[0].message

    def test_inherited_classification_covers_subclass(self, tmp_path):
        src = """
            class Base:
                def __init__(self):
                    self.shared = 0
            class Fx(Base):
                def step(self):
                    self.shared += 1
            """
        base = decl_of({'shared': derived('base bookkeeping')},
                       name='fix.Base', cls='Base')
        sub = decl_of({}, name='fix.Fx', inherit='fix.Base')
        vs, _, _ = lint_fixture(tmp_path, src, [base, sub],
                                rules=[get_rule('ST001')])
        assert vs == []


# ---------------------------------------------------------------------------
# ST002 — persisted claim absent from the live wire
# ---------------------------------------------------------------------------

class TestST002:
    SRC = """
        class Fx:
            def __init__(self):
                self.counts = {}
        """

    def test_claim_on_live_key_is_clean(self, tmp_path):
        decl = decl_of({'counts': persisted(('snapshot', 'counts'))})
        assert hits(tmp_path, self.SRC, decl, 'ST002',
                    schemas=WIRES) == []

    def test_missing_key_is_an_error(self, tmp_path):
        decl = decl_of({'counts': persisted(('snapshot', 'countz'))})
        vs = hits(tmp_path, self.SRC, decl, 'ST002', schemas=WIRES)
        assert len(vs) == 1 and vs[0].severity == 'error'
        assert "snapshot['countz']" in vs[0].message

    def test_unknown_wire_is_an_error(self, tmp_path):
        decl = decl_of({'counts': persisted(('no_such_wire', 'k'))})
        vs = hits(tmp_path, self.SRC, decl, 'ST002', schemas=WIRES)
        assert len(vs) == 1 and 'unknown wire' in vs[0].message


# ---------------------------------------------------------------------------
# ST003 — live wire key nobody claims
# ---------------------------------------------------------------------------

class TestST003:
    SRC = """
        class Fx:
            def __init__(self):
                self.a = 0
        """

    def test_unclaimed_key_warns_on_the_owner(self, tmp_path):
        decl = decl_of({'a': persisted(('w', 'a'))}, owns_wires=('w',))
        vs = hits(tmp_path, self.SRC, decl, 'ST003',
                  schemas={'w': ['a', 'dead_field']})
        assert len(vs) == 1 and vs[0].severity == 'warning'
        assert "'dead_field'" in vs[0].message

    def test_fully_claimed_wire_is_clean(self, tmp_path):
        decl = decl_of({'a': persisted(('w', 'a'))}, owns_wires=('w',))
        assert hits(tmp_path, self.SRC, decl, 'ST003',
                    schemas={'w': ['a']}) == []

    def test_non_owner_stays_silent(self, tmp_path):
        decl = decl_of({'a': persisted(('w', 'a'))})  # no owns_wires
        assert hits(tmp_path, self.SRC, decl, 'ST003',
                    schemas={'w': ['a', 'dead_field']}) == []

    def test_missing_owned_wire_is_an_error(self, tmp_path):
        decl = decl_of({'a': persisted(('w', 'a'))},
                       owns_wires=('w', 'gone'))
        vs = hits(tmp_path, self.SRC, decl, 'ST003',
                  schemas={'w': ['a']})
        assert len(vs) == 1 and vs[0].severity == 'error'
        assert "'gone'" in vs[0].message

    def test_wire_extends_folds_base_claims(self):
        # the real registry case: prefill_snapshot is a superset of
        # snapshot, and its live dict carries every base key — claims
        # made under 'snapshot' must count for it
        assert WIRE_EXTENDS.get('prefill_snapshot') == 'snapshot'
        base_only = set(WIRES['snapshot']) - {'schema', 'config'}
        assert base_only < set(WIRES['prefill_snapshot'])


# ---------------------------------------------------------------------------
# ST004 — writer/reader asymmetry
# ---------------------------------------------------------------------------

class TestST004:
    def _decl(self, **kw):
        return decl_of({'c': persisted(('w', 'counts'))},
                       roundtrips=(RoundTrip('snapshot', 'restore',
                                             'snap', 'schema'),), **kw)

    def test_symmetric_pair_is_clean(self, tmp_path):
        src = """
            class Fx:
                def snapshot(self):
                    return {'schema': 1, 'counts': self.c}
                def restore(self, snap):
                    self.c = snap['counts']
                    assert snap.get('schema', 1) == 1
            """
        assert hits(tmp_path, src, self._decl(), 'ST004') == []

    def test_required_read_never_written_is_an_error(self, tmp_path):
        src = """
            class Fx:
                def snapshot(self):
                    return {'schema': 1, 'counts': self.c}
                def restore(self, snap):
                    self.c = snap['counts']
                    self.t = snap['terminal']
                    assert snap.get('schema', 1) == 1
            """
        vs = hits(tmp_path, src, self._decl(), 'ST004')
        assert len(vs) == 1
        assert 'REQUIRES' in vs[0].message
        assert "'terminal'" in vs[0].message

    def test_written_never_read_is_an_error(self, tmp_path):
        src = """
            class Fx:
                def snapshot(self):
                    return {'schema': 1, 'counts': self.c, 'extra': 0}
                def restore(self, snap):
                    self.c = snap['counts']
            """
        vs = hits(tmp_path, src, self._decl(), 'ST004')
        # 'schema' is read by neither — two dead keys ('schema','extra')
        dead = {m for v in vs for m in ("'schema'", "'extra'")
                if m in v.message}
        assert dead == {"'schema'", "'extra'"}
        assert all(v.severity == 'error' for v in vs)

    def test_roundtrip_ok_declares_the_asymmetry(self, tmp_path):
        src = """
            class Fx:
                def snapshot(self):
                    return {'schema': 1, 'counts': self.c, 'extra': 0}
                def restore(self, snap):
                    self.c = snap['counts']
                    assert snap.get('schema', 1) == 1
            """
        decl = self._decl(roundtrip_ok={
            'extra': 'informational only, reader ignores by design'})
        assert hits(tmp_path, src, decl, 'ST004') == []

    def test_optional_read_of_missing_key_is_legal(self, tmp_path):
        # back-compat: reading an OLDER snapshot's missing key via
        # .get() is exactly what schema evolution looks like
        src = """
            class Fx:
                def snapshot(self):
                    return {'schema': 1, 'counts': self.c}
                def restore(self, snap):
                    self.c = snap['counts']
                    self.new = snap.get('added_in_v2', None)
                    assert snap.get('schema', 1) == 1
            """
        assert hits(tmp_path, src, self._decl(), 'ST004') == []

    def test_missing_method_is_an_error(self, tmp_path):
        src = """
            class Fx:
                def snapshot(self):
                    return {'schema': 1, 'counts': self.c}
            """
        vs = hits(tmp_path, src, self._decl(), 'ST004')
        assert len(vs) == 1 and 'not found' in vs[0].message

    def test_moved_marker_is_an_error(self, tmp_path):
        src = """
            class Fx:
                def snapshot(self):
                    return {'version': 1, 'counts': self.c}
                def restore(self, snap):
                    self.c = snap['counts']
            """
        vs = hits(tmp_path, src, self._decl(), 'ST004')
        assert len(vs) == 1 and 'no writer keys' in vs[0].message


# ---------------------------------------------------------------------------
# ST005 — config identity vs the refusal sets
# ---------------------------------------------------------------------------

class TestST005:
    SRC = """
        class Fx:
            def __init__(self, tp):
                self.tp = tp
                self.block_size = 8
            def _geometry(self):
                return (self.tp, self.block_size)
        """

    def _decl(self, config_identity):
        return decl_of({'tp': derived('ctor arg'),
                        'block_size': derived('ctor arg')},
                       geometry_methods=('_geometry',),
                       config_identity=config_identity)

    def test_mapped_identity_is_clean(self, tmp_path):
        decl = self._decl({'tp': (('aot_config', 'tp'),),
                           'block_size': (('aot_config',
                                           'block_size'),)})
        assert hits(tmp_path, self.SRC, decl, 'ST005',
                    schemas=WIRES) == []

    def test_unmapped_geometry_load_is_an_error(self, tmp_path):
        decl = self._decl({'tp': (('aot_config', 'tp'),)})
        vs = hits(tmp_path, self.SRC, decl, 'ST005', schemas=WIRES)
        assert len(vs) == 1 and vs[0].severity == 'error'
        assert 'block_size' in vs[0].message
        assert 'config_identity' in vs[0].message

    def test_identity_key_missing_from_refusal_set_is_an_error(
            self, tmp_path):
        decl = self._decl({'tp': (('aot_config', 'tp'),),
                           'block_size': (('aot_config',
                                           'block_size_v2'),)})
        vs = hits(tmp_path, self.SRC, decl, 'ST005', schemas=WIRES)
        assert len(vs) == 1
        assert 'ATTACHES' in vs[0].message

    def test_no_geometry_methods_means_no_st005(self, tmp_path):
        decl = decl_of({'tp': derived('x'), 'block_size': derived('x')})
        assert hits(tmp_path, self.SRC, decl, 'ST005',
                    schemas=WIRES) == []


# ---------------------------------------------------------------------------
# ST006 — unlocked mutation of a thread-shared structure
# ---------------------------------------------------------------------------

class TestST006:
    SRC = """
        class Fx:
            def __init__(self):
                self.table = {}
            def commit(self, k):
                with self._lock:
                    self.table[k] = 1
            def scrape_race(self, k):
                self.table.pop(k, None)
            def _evict(self, k):
                del self.table[k]
        """

    def _decl(self, **kw):
        return decl_of({'table': derived('rebuilt on restore')},
                       locks={'table': '_lock'}, **kw)

    def test_unlocked_mutation_is_an_error(self, tmp_path):
        vs = hits(tmp_path, self.SRC, self._decl(), 'ST006')
        assert {v.severity for v in vs} == {'error'}
        msgs = ' '.join(v.message for v in vs)
        assert 'scrape_race()' in msgs and '_evict()' in msgs
        assert 'commit()' not in msgs        # locked site is clean
        assert '__init__' not in msgs        # ctor is exempt

    def test_lock_free_method_exemption_needs_its_reason(self, tmp_path):
        decl = self._decl(lock_free={
            '_evict': 'only called from commit(), under the lock',
            'scrape_race': 'single-writer: scheduler thread only'})
        assert hits(tmp_path, self.SRC, decl, 'ST006') == []

    def test_star_lock_free_exempts_every_method(self, tmp_path):
        decl = self._decl(lock_free={'*': 'single-threaded test class'})
        assert hits(tmp_path, self.SRC, decl, 'ST006') == []


# ---------------------------------------------------------------------------
# Registry validation, suppression, ST000, census
# ---------------------------------------------------------------------------

class TestEngine:
    def test_reasonless_ephemeral_is_a_value_error(self):
        with pytest.raises(ValueError, match='non-empty'):
            lint_entries([decl_of({'x': Attr('ephemeral')})],
                         rules=[], schemas={})

    def test_persisted_without_claims_is_a_value_error(self):
        with pytest.raises(ValueError, match='claim'):
            lint_entries([decl_of({'x': Attr('persisted')})],
                         rules=[], schemas={})

    def test_unknown_kind_is_a_value_error(self):
        with pytest.raises(ValueError, match='unknown kind'):
            lint_entries([decl_of({'x': Attr('immortal')})],
                         rules=[], schemas={})

    def test_reasonless_suppression_is_a_value_error(self):
        with pytest.raises(ValueError, match='reason'):
            lint_entries([decl_of({}, suppress={'ST001': ''})],
                         rules=[], schemas={})

    def test_unknown_inherit_is_a_value_error(self):
        with pytest.raises(ValueError, match='not a declared class'):
            lint_entries([decl_of({}, inherit='fix.Missing')],
                         rules=[], schemas={})

    def test_suppression_with_reason_silences_and_is_reported(
            self, tmp_path):
        decl = decl_of({}, suppress={
            'ST001': 'fixture: intentionally unclassified'})
        vs, suppressed, _ = lint_fixture(
            tmp_path, TestST001.SRC, decl, rules=[get_rule('ST001')])
        assert vs == []
        assert len(suppressed) == 2          # known + new_counter
        for v, reason in suppressed:
            assert v.rule == 'ST001'
            assert 'intentionally unclassified' in reason

    def test_live_failure_is_st000_not_a_silent_pass(
            self, tmp_path, monkeypatch):
        import paddle_tpu.analysis.state.live as live

        def boom():
            raise RuntimeError('no backend in test')

        monkeypatch.setattr(live, 'live_schemas', boom)
        decl = decl_of({'known': ephemeral('test fixture')})
        vs, _, detail = lint_and_report(
            [decl], root=fixture_root(tmp_path, TestST001.SRC))
        by_rule = {}
        for v in vs:
            by_rule.setdefault(v.rule, []).append(v)
        st0 = by_rule['ST000']
        assert len(st0) == 1 and st0[0].severity == 'error'
        assert 'no backend in test' in st0[0].message
        assert st0[0].path == 'paddle_tpu/analysis/state/registry.py'
        # the pure-AST ratchet still ran despite the live failure
        assert any('new_counter' in v.message
                   for v in by_rule.get('ST001', []))
        assert detail['live'] is False and detail['wires'] is None

    def test_broken_declaration_is_st000_on_its_own_file(self, tmp_path):
        decl = decl_of({}, cls='NoSuchClass')
        vs, _, detail = lint_fixture(tmp_path, TestST001.SRC, decl)
        assert [v.rule for v in vs] == ['ST000']
        assert 'NoSuchClass' in vs[0].message
        assert vs[0].path == 'fixture.py'
        assert detail['classes']['fix.Fx'] is None

    def test_census_detail_counts_kinds(self, tmp_path):
        src = """
            class Fx:
                def __init__(self):
                    self.a = 0
                    self.b = 1
                    self.c = 2
                    self.d = 3
            """
        decl = decl_of({'a': persisted(('w', 'a')),
                        'b': derived('rebuilt'),
                        'c': ephemeral('perf window')})
        _, _, detail = lint_fixture(tmp_path, src, decl,
                                    schemas={'w': ['a']})
        census = detail['classes']['fix.Fx']
        assert census == {'attrs': 4, 'unclassified': 1, 'persisted': 1,
                          'derived-rebuilt': 1, 'device-rederived': 0,
                          'ephemeral': 1}
        assert detail['live'] is True
        assert detail['wires'] == {'w': 1}


# ---------------------------------------------------------------------------
# Registry shape meta-tests
# ---------------------------------------------------------------------------

class TestRegistryMeta:
    def test_every_declared_source_file_exists(self):
        for decl in DECLS:
            absolute, _ = decl.resolve(root=REPO)
            assert os.path.exists(absolute), decl.name

    def test_decl_names_are_unique_and_sorted_wires_owned_once(self):
        names = [d.name for d in DECLS]
        assert len(names) == len(set(names))
        owners = [w for d in DECLS for w in d.owns_wires]
        assert len(owners) == len(set(owners)), 'one owner per wire'

    def test_path_filter_selects_serving_classes(self):
        entries = entries_for(['paddle_tpu/inference/serving.py'],
                              root=REPO)
        assert entries and all(
            d.path == 'paddle_tpu/inference/serving.py'
            for d in entries)
        assert any(d.cls == 'ServingEngine' for d in entries)

    def test_structural_keys_cover_schema_stamps(self):
        # every wire with a 'schema' version stamp declares it
        # structurally — a version field is not attribute-backed
        for wire in ('snapshot', 'blob', 'watchdog', 'pair_snapshot'):
            assert 'schema' in WIRE_STRUCTURAL[wire]

    def test_registry_is_clean_against_canned_wires(self):
        """The fast whole-registry meta-test: every DECL lints clean
        against the captured wire schemas at the committed ZERO
        baseline (the live sweep below proves the capture is
        current)."""
        vs, suppressed, detail = lint_and_report(DECLS, root=REPO,
                                                 schemas=WIRES)
        assert vs == [], '\n'.join(v.render() for v in vs)
        for v, reason in suppressed:
            assert reason.strip(), v.render()
        assert all(c and c['unclassified'] == 0
                   for c in detail['classes'].values())

    def test_baseline_file_is_committed_and_empty(self):
        path = os.path.join(REPO, 'tools', 'statelint_baseline.json')
        with open(path) as f:
            data = json.load(f)
        assert data['counts'] == {}          # zero tolerated debt

    @pytest.mark.slow
    def test_registry_is_clean_against_live_wires(self):
        """The acceptance sweep: real engines, real wire dicts, zero
        violations (slow: builds tiny CPU serving/disagg/train
        engines)."""
        vs, _, detail = lint_and_report(DECLS, root=REPO)
        assert vs == [], '\n'.join(v.render() for v in vs)
        assert detail['live'] is True
        # and the canned copy the fast tests use has not drifted
        from paddle_tpu.analysis.state.live import live_schemas

        assert {w: sorted(k) for w, k in live_schemas().items()} \
            == {w: sorted(k) for w, k in WIRES.items()}


# ---------------------------------------------------------------------------
# CLI + the injected-regression flip tests
# ---------------------------------------------------------------------------

def run_state_cli(monkeypatch, extra=None, wires=WIRES, decls=None):
    """Run `python -m paddle_tpu.analysis --state` in-process against
    canned wires (and optionally a substituted registry)."""
    import paddle_tpu.analysis.state.live as live
    import paddle_tpu.analysis.state.registry as registry
    from paddle_tpu.analysis.__main__ import main

    monkeypatch.setattr(live, 'live_schemas', lambda: wires)
    if decls is not None:
        monkeypatch.setattr(registry, 'entries_for',
                            lambda paths=None, root=None: list(decls))
    return main(['--state', '--root', REPO, '--no-baseline',
                 '--format', 'json'] + (extra or []))


class TestCLI:
    def test_state_main_list_rules(self, capsys):
        from paddle_tpu.analysis.__main__ import state_main

        assert state_main(['--list-rules']) == 0
        out = capsys.readouterr().out
        for rid in ('ST001', 'ST002', 'ST003', 'ST004', 'ST005',
                    'ST006'):
            assert rid in out

    def test_family_flags_mutually_exclusive(self, capsys):
        from paddle_tpu.analysis.__main__ import main

        assert main(['--state', '--hlo', '--root', REPO]) == 2
        assert 'mutually exclusive' in capsys.readouterr().err

    def test_exit_two_on_unknown_rule(self):
        from paddle_tpu.analysis.__main__ import main

        assert main(['--state', '--root', REPO,
                     '--select', 'ST999']) == 2

    def test_exit_zero_with_canned_wires(self, monkeypatch, capsys):
        """rc 0 on the real repo: the healthy half of both flips."""
        assert run_state_cli(monkeypatch) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload['violations'] == []
        assert payload['state']['live'] is True
        assert payload['state']['wires']['snapshot'] == len(
            WIRES['snapshot'])

    def test_flip_unclassified_attribute(self, monkeypatch, capsys):
        """Injected regression A: a mutable attribute LOSES its
        classification (what adding `self._new = 0` to the engine
        without a registry entry looks like) — rc flips 0 -> 1."""
        decls = [dataclasses.replace(
            d, attrs={a: v for a, v in d.attrs.items()
                      if a != 'draining'})
            if d.cls == 'ServingEngine' else d for d in DECLS]
        assert any(d.cls == 'ServingEngine'
                   and 'draining' not in d.attrs for d in decls)
        assert run_state_cli(monkeypatch, decls=decls) == 1
        payload = json.loads(capsys.readouterr().out)
        assert any(v['rule'] == 'ST001'
                   and 'draining' in v['message']
                   for v in payload['violations'])

    def test_flip_dropped_snapshot_key(self, monkeypatch, capsys):
        """Injected regression B: the live snapshot wire DROPS a
        persisted key (what deleting the counts line from snapshot()
        looks like) — rc flips 0 -> 1."""
        wires = {w: [k for k in keys if not (w == 'snapshot'
                                             and k == 'counts')]
                 for w, keys in WIRES.items()}
        assert run_state_cli(monkeypatch, wires=wires) == 1
        payload = json.loads(capsys.readouterr().out)
        assert any(v['rule'] == 'ST002' and 'counts' in v['message']
                   for v in payload['violations'])

    def test_baseline_round_trip(self, monkeypatch, tmp_path, capsys):
        """--write-baseline captures current violations; a rerun
        against that baseline is rc 0 with them counted as
        baselined."""
        decls = [dataclasses.replace(
            d, attrs={a: v for a, v in d.attrs.items()
                      if a != 'draining'})
            if d.cls == 'ServingEngine' else d for d in DECLS]
        baseline = str(tmp_path / 'bl.json')
        import paddle_tpu.analysis.state.live as live
        import paddle_tpu.analysis.state.registry as registry
        from paddle_tpu.analysis.__main__ import main

        monkeypatch.setattr(live, 'live_schemas', lambda: WIRES)
        monkeypatch.setattr(registry, 'entries_for',
                            lambda paths=None, root=None: list(decls))
        assert main(['--state', '--root', REPO, '--baseline', baseline,
                     '--write-baseline']) == 0
        capsys.readouterr()
        assert main(['--state', '--root', REPO, '--baseline', baseline,
                     '--format', 'json']) == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload['violations'] == []
        assert payload['baselined'] >= 1

    @pytest.mark.slow
    def test_exit_zero_on_repo_live(self):
        """The acceptance run: a real `--state` CLI pass with live
        engine extraction is green at the committed zero baseline
        (slow: builds engines)."""
        from paddle_tpu.analysis.__main__ import main

        assert main(['--state', '--root', REPO]) == 0
