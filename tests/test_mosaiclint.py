"""mosaiclint (paddle_tpu.analysis.mosaic) tier-1 tests.

Every rule ML001–ML006 gets at least one positive (a small pallas
fixture kernel that must trigger it) and one negative (a near-identical
legal kernel that must not); plus the jaxpr extraction contract (grads
surface the custom-VJP backward kernels), registry suppression with
mandatory reasons, the baseline round-trip through tracelint's shared
machinery, the CLI exit-code contract, and the meta-test: every
registered pallas kernel suite is statically Mosaic-legal (or carries a
reasoned suppression) — the analyzer runs clean over the very kernels
whose lowering it polices.

All fixtures trace abstractly (ShapeDtypeStruct + make_jaxpr): nothing
executes, no backend is touched, everything runs on CPU.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.analysis import (filter_new, load_baseline, write_baseline)
from paddle_tpu.analysis.mosaic import (Entry, KernelContext,
                                        VMEM_BYTES_PER_CORE,
                                        VMEM_BYTES_PHYSICAL, all_entries,
                                        all_rules, extract_pallas_calls,
                                        lint_entries, sublane_multiple,
                                        trace_entry, vmem_report)

pytestmark = pytest.mark.tier1

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SDS = jax.ShapeDtypeStruct

# any real module:attr works as a fixture anchor; violations just need
# a path to point at
ANCHOR = 'paddle_tpu.ops.pallas:interpret_mode'


def lint_fn(fn, *args, rules=None):
    calls = extract_pallas_calls(fn, args)
    ctx = KernelContext(
        entry=Entry('fixture/kernel', ANCHOR, lambda: None),
        calls=calls, path='fixture.py', line=1)
    out = []
    for rule in (rules or all_rules()):
        out.extend(rule.check(ctx))
    return out


def codes(fn, *args):
    return {v.rule for v in lint_fn(fn, *args)}


def _copy_kernel(x_ref, o_ref):
    o_ref[:] = x_ref[:]


def _simple_call(kernel, in_shape, block, out_shape=None, out_block=None,
                 grid=(1,), dtype=jnp.float32, scratch=None):
    def fn(x):
        return pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec(block, lambda *i: (0,) * len(block))],
            out_specs=pl.BlockSpec(out_block or block,
                                   lambda *i: (0,) * len(out_block or block)),
            out_shape=SDS(out_shape or in_shape, dtype),
            scratch_shapes=scratch or [],
            interpret=True)(x)

    return fn, SDS(in_shape, dtype)


# ---------------------------------------------------------------------------
# ML001 — tile alignment
# ---------------------------------------------------------------------------

class TestML001:
    def test_positive_minor_dim_not_128(self):
        def fn(x):
            return pl.pallas_call(
                _copy_kernel, grid=(2, 2),
                in_specs=[pl.BlockSpec((64, 100), lambda i, j: (i, j))],
                out_specs=pl.BlockSpec((64, 100), lambda i, j: (i, j)),
                out_shape=SDS((128, 200), jnp.float32),
                interpret=True)(x)

        assert 'ML001' in codes(fn, SDS((128, 200), jnp.float32))

    def test_positive_sublane_not_multiple(self):
        # bf16 wants sublane x16: a partial 8-row block is illegal
        def fn(x):
            return pl.pallas_call(
                _copy_kernel, grid=(2,),
                in_specs=[pl.BlockSpec((8, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((8, 128), lambda i: (i, 0)),
                out_shape=SDS((16, 128), jnp.bfloat16),
                interpret=True)(x)

        assert 'ML001' in codes(fn, SDS((16, 128), jnp.bfloat16))

    def test_negative_full_dim_and_multiples(self):
        # minor = full array dim (100) and sublane = full dim: legal
        fn, x = _simple_call(_copy_kernel, (64, 100), (64, 100))
        assert 'ML001' not in codes(fn, x)

    def test_negative_sublane_one(self):
        # (1, bq) segment-id-style blocks: a single sublane row is legal
        def fn(x):
            return pl.pallas_call(
                _copy_kernel, grid=(2,),
                in_specs=[pl.BlockSpec((1, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((1, 128), lambda i: (i, 0)),
                out_shape=SDS((2, 128), jnp.int32),
                interpret=True)(x)

        assert 'ML001' not in codes(fn, SDS((2, 128), jnp.int32))

    def test_sublane_table(self):
        assert sublane_multiple(jnp.dtype(jnp.float32)) == 8
        assert sublane_multiple(jnp.dtype(jnp.bfloat16)) == 16
        assert sublane_multiple(jnp.dtype(jnp.int8)) == 32
        assert sublane_multiple(jnp.dtype(jnp.float8_e4m3fn)) == 32


# ---------------------------------------------------------------------------
# ML002 — grid divisibility / tail masking
# ---------------------------------------------------------------------------

def _tail_call(kernel):
    def fn(x):
        return pl.pallas_call(
            kernel, grid=(2,),
            in_specs=[pl.BlockSpec((64, 128), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((64, 128), lambda i: (i, 0)),
            out_shape=SDS((100, 128), jnp.float32),
            interpret=True)(x)

    return fn, SDS((100, 128), jnp.float32)


class TestML002:
    def test_positive_unmasked_tail(self):
        fn, x = _tail_call(_copy_kernel)
        assert 'ML002' in codes(fn, x)

    def test_negative_masked_tail(self):
        def kernel(x_ref, o_ref):
            i = pl.program_id(0)
            rows = i * 64 + jax.lax.broadcasted_iota(
                jnp.int32, (64, 128), 0)
            o_ref[:] = jnp.where(rows < 100, x_ref[:], 0.0)

        fn, x = _tail_call(kernel)
        assert 'ML002' not in codes(fn, x)

    def test_negative_dividing_blocks(self):
        def fn(x):
            return pl.pallas_call(
                _copy_kernel, grid=(2,),
                in_specs=[pl.BlockSpec((64, 128), lambda i: (i, 0))],
                out_specs=pl.BlockSpec((64, 128), lambda i: (i, 0)),
                out_shape=SDS((128, 128), jnp.float32),
                interpret=True)(x)

        assert 'ML002' not in codes(fn, SDS((128, 128), jnp.float32))


# ---------------------------------------------------------------------------
# ML003 — illegal dtypes / i1 reshape
# ---------------------------------------------------------------------------

class TestML003:
    def test_positive_float64_operand(self):
        jax.config.update('jax_enable_x64', True)
        try:
            fn, x = _simple_call(_copy_kernel, (8, 128), (8, 128),
                                 dtype=jnp.float64)
            assert 'ML003' in codes(fn, x)
        finally:
            jax.config.update('jax_enable_x64', False)

    def test_positive_bool_reshape(self):
        def kernel(x_ref, o_ref):
            m = x_ref[:] > 0                     # (64, 256) i1
            m2 = m.reshape(128, 128)             # illegal i1 re-tile
            o_ref[:] = jnp.where(m2, 1.0, 0.0)

        def fn(x):
            return pl.pallas_call(
                kernel, grid=(1,),
                in_specs=[pl.BlockSpec((64, 256), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0)),
                out_shape=SDS((128, 128), jnp.float32),
                interpret=True)(x)

        vs = lint_fn(fn, SDS((64, 256), jnp.float32))
        assert any(v.rule == 'ML003' and 'i1' in v.message for v in vs)

    def test_warning_lane_changing_reshape(self):
        def kernel(x_ref, o_ref):
            o_ref[:] = x_ref[:].reshape(128, 128)

        def fn(x):
            return pl.pallas_call(
                kernel, grid=(1,),
                in_specs=[pl.BlockSpec((64, 256), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((128, 128), lambda i: (0, 0)),
                out_shape=SDS((128, 128), jnp.float32),
                interpret=True)(x)

        vs = [v for v in lint_fn(fn, SDS((64, 256), jnp.float32))
              if v.rule == 'ML003']
        assert vs and all(v.severity == 'warning' for v in vs)

    def test_negative_major_collapse_reshape(self):
        # (8, 4, 128) -> (32, 128): lane preserved — the decode-kernel
        # collapse, legal
        def kernel(x_ref, o_ref):
            o_ref[:] = x_ref[:].reshape(32, 128)

        def fn(x):
            return pl.pallas_call(
                kernel, grid=(1,),
                in_specs=[pl.BlockSpec((8, 4, 128),
                                       lambda i: (0, 0, 0))],
                out_specs=pl.BlockSpec((32, 128), lambda i: (0, 0)),
                out_shape=SDS((32, 128), jnp.float32),
                interpret=True)(x)

        assert 'ML003' not in codes(fn, SDS((8, 4, 128), jnp.float32))


# ---------------------------------------------------------------------------
# ML004 — unaligned dynamic slices
# ---------------------------------------------------------------------------

def _ds_call(kernel):
    def fn(x):
        return pl.pallas_call(
            kernel, grid=(2,),
            in_specs=[pl.BlockSpec((128, 128), lambda i: (0, 0))],
            out_specs=pl.BlockSpec((64, 128), lambda i: (i, 0)),
            out_shape=SDS((128, 128), jnp.float32),
            interpret=True)(x)

    return fn, SDS((128, 128), jnp.float32)


class TestML004:
    def test_positive_unprovable_traced_start(self):
        def kernel(x_ref, o_ref):
            i = pl.program_id(0)
            o_ref[:] = x_ref[pl.ds(i * 37, 64), :]

        fn, x = _ds_call(kernel)
        assert 'ML004' in codes(fn, x)

    def test_positive_misaligned_constant_start(self):
        def kernel(x_ref, o_ref):
            o_ref[:] = x_ref[pl.ds(3, 64), :]

        fn, x = _ds_call(kernel)
        assert 'ML004' in codes(fn, x)

    def test_negative_provable_start(self):
        # i * 64: a multiple of the f32 sublane count (8) by construction
        def kernel(x_ref, o_ref):
            i = pl.program_id(0)
            o_ref[:] = x_ref[pl.ds(i * 64, 64), :]

        fn, x = _ds_call(kernel)
        assert 'ML004' not in codes(fn, x)

    def test_negative_integer_index(self):
        # m[:, 0]-style scalar extracts are not slices
        def kernel(x_ref, o_ref):
            o_ref[:] = x_ref[:] * x_ref[0, 0]

        fn, x = _simple_call(kernel, (64, 128), (64, 128))
        assert 'ML004' not in codes(fn, x)


# ---------------------------------------------------------------------------
# ML005 — unsupported primitives
# ---------------------------------------------------------------------------

class TestML005:
    def test_positive_sort(self):
        def kernel(x_ref, o_ref):
            o_ref[:] = jnp.sort(x_ref[:], axis=-1)

        fn, x = _simple_call(kernel, (64, 128), (64, 128))
        assert 'ML005' in codes(fn, x)

    def test_positive_gather_from_fancy_indexing(self):
        def kernel(x_ref, o_ref):
            idx = jnp.argmax(x_ref[:], axis=-1)
            o_ref[:] = x_ref[:] + jnp.take_along_axis(
                x_ref[:], idx[:, None], axis=-1)

        fn, x = _simple_call(kernel, (64, 128), (64, 128))
        assert 'ML005' in codes(fn, x)

    def test_negative_online_softmax_body(self):
        def kernel(x_ref, o_ref):
            x = x_ref[:].astype(jnp.float32)
            m = jnp.max(x, axis=-1, keepdims=True)
            o_ref[:] = (jnp.exp(x - m)
                        / jnp.sum(jnp.exp(x - m), -1, keepdims=True))

        fn, x = _simple_call(kernel, (64, 128), (64, 128))
        assert 'ML005' not in codes(fn, x)


# ---------------------------------------------------------------------------
# ML006 — VMEM budget
# ---------------------------------------------------------------------------

class TestML006:
    def test_positive_over_budget(self):
        # 2 x (4096x1024 f32 in + out) = 64 MB of double-buffered blocks
        fn, x = _simple_call(_copy_kernel, (4096, 1024), (4096, 1024))
        vs = [v for v in lint_fn(fn, x) if v.rule == 'ML006']
        assert vs and vs[0].severity == 'error'

    def test_warning_near_budget(self):
        # 2x(3.1 MB in + 3.1 MB out) + 3.1 MB scratch = 15.7 MB:
        # inside the 75% warning band, under the 16 MB cap
        def kernel(x_ref, o_ref, acc):
            acc[:] = x_ref[:]
            o_ref[:] = acc[:]

        fn, x = _simple_call(kernel, (768, 1024), (768, 1024),
                             scratch=[pltpu.VMEM((768, 1024),
                                                 jnp.float32)])
        vs = [v for v in lint_fn(fn, x) if v.rule == 'ML006']
        assert vs and vs[0].severity == 'warning'

    def test_negative_small_blocks(self):
        fn, x = _simple_call(_copy_kernel, (256, 1024), (256, 1024))
        assert 'ML006' not in codes(fn, x)

    def test_estimates_match_report(self):
        report = vmem_report(all_entries(), root=REPO)
        assert set(report) == {e.name for e in all_entries()}
        for name, est in report.items():
            # a grouped-matmul call states its own limit, and ML006 (the
            # meta-test below) holds it to that
            cap = (VMEM_BYTES_PHYSICAL if name.startswith('grouped_matmul/')
                   else VMEM_BYTES_PER_CORE)
            assert 0 < est <= cap, (name, est)

    @pytest.mark.parametrize('rows,limit,passes', [
        # 2 x (4096 x 1024 f32 in + out) = 64 MB
        (4096, 96 * 1024 * 1024, True),     # its own limit, which a core has
        (4096, None, False),                # the default: ~16 MB
        # 2 x 2 x 36 MB = 144 MB
        (9216, 1024 * 1024 * 1024, False),  # no core has what it states
    ])
    def test_a_call_is_held_to_the_limit_it_states(self, rows, limit,
                                                   passes):
        def fn(x):
            return pl.pallas_call(
                _copy_kernel, grid=(1,),
                in_specs=[pl.BlockSpec((rows, 1024), lambda i: (0, 0))],
                out_specs=pl.BlockSpec((rows, 1024), lambda i: (0, 0)),
                out_shape=SDS((rows, 1024), jnp.float32),
                compiler_params=pltpu.CompilerParams(vmem_limit_bytes=limit),
                interpret=True)(x)

        assert ('ML006' not in codes(fn, SDS((rows, 1024), jnp.float32))
                ) == passes


# ---------------------------------------------------------------------------
# extraction: grads surface the custom-VJP backward kernels
# ---------------------------------------------------------------------------

class TestExtraction:
    def test_flash_grad_traces_three_kernels(self):
        entry = next(e for e in all_entries()
                     if e.name == 'flash_attention/causal_fwd_bwd')
        ctx = trace_entry(entry, root=REPO)
        names = sorted(c.name for c in ctx.calls)
        # the `name=` each pallas_call gives its kernel
        assert names == ['flash_attention_dkv', 'flash_attention_dq',
                         'flash_attention_fwd']

    def test_scratch_and_scalar_prefetch_extracted(self):
        entry = next(e for e in all_entries()
                     if e.name == 'decode_attention/bf16_start')
        ctx = trace_entry(entry, root=REPO)
        (call,) = ctx.calls
        assert call.num_scalar_prefetch == 2
        assert len(call.scratch) == 3           # acc, m, l
        assert call.vmem_estimate() > 0

    def test_anchor_resolves_into_kernel_file(self):
        entry = all_entries()[0]
        path, line = entry.resolve_anchor(root=REPO)
        assert path == 'paddle_tpu/ops/pallas/flash_attention.py'
        assert line > 1


# ---------------------------------------------------------------------------
# suppression + baseline round-trip
# ---------------------------------------------------------------------------

def _bad_entry(suppress=None):
    def build():
        fn, x = _tail_call(_copy_kernel)
        return fn, (x,), {}

    return Entry('fixture/unmasked_tail', ANCHOR, build,
                 suppress=suppress or {})


class TestSuppression:
    def test_registry_suppression_silences_with_reason(self):
        vs, sup = lint_entries(
            [_bad_entry({'ML002': 'fixture: tail is write-only'})],
            root=REPO)
        assert [v for v in vs if v.rule == 'ML002'] == []
        assert sup and sup[0][1] == 'fixture: tail is write-only'

    def test_unsuppressed_rule_still_fires(self):
        vs, _ = lint_entries([_bad_entry()], root=REPO)
        assert any(v.rule == 'ML002' for v in vs)

    def test_empty_reason_rejected(self):
        with pytest.raises(ValueError, match='reason'):
            lint_entries([_bad_entry({'ML002': '  '})], root=REPO)

    def test_trace_failure_is_ml000(self):
        def build():
            raise RuntimeError('suite exploded')

        vs, _ = lint_entries(
            [Entry('fixture/broken', ANCHOR, build)], root=REPO)
        assert [v.rule for v in vs] == ['ML000']
        assert 'suite exploded' in vs[0].message


class TestBaseline:
    def test_round_trip(self, tmp_path):
        vs, _ = lint_entries([_bad_entry()], root=REPO)
        assert vs
        bpath = tmp_path / 'baseline.json'
        write_baseline(vs, str(bpath))
        baseline = load_baseline(str(bpath))
        assert filter_new(vs, baseline) == []
        doubled = vs + [v for v in vs]
        assert len(filter_new(doubled, baseline)) == len(vs)

    def test_baseline_file_is_committed_and_empty(self):
        path = os.path.join(REPO, 'tools', 'mosaiclint_baseline.json')
        with open(path) as f:
            data = json.load(f)
        assert data['counts'] == {}          # zero tolerated debt


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestCLI:
    def test_exit_zero_on_repo(self):
        env = dict(os.environ, JAX_PLATFORMS='cpu')
        proc = subprocess.run(
            [sys.executable, '-m', 'paddle_tpu.analysis', '--mosaic',
             '--root', REPO, '--format', 'json'],
            capture_output=True, text=True, cwd=REPO, env=env,
            timeout=240)
        assert proc.returncode == 0, proc.stdout + proc.stderr
        payload = json.loads(proc.stdout)
        assert payload['new'] == 0
        assert payload['suppressed'] >= 1       # rms ragged-rows entry
        assert payload['vmem']                  # stamped for bench.py

    def test_exit_two_on_unknown_rule(self):
        from paddle_tpu.analysis.__main__ import main

        assert main(['--mosaic', '--root', REPO,
                     '--select', 'ML999']) == 2

    def test_exit_two_on_unregistered_path(self):
        from paddle_tpu.analysis.__main__ import main

        assert main(['--mosaic', '--root', REPO,
                     'paddle_tpu/vision']) == 2

    def test_path_filter_selects_kernel_file(self):
        from paddle_tpu.analysis.mosaic.registry import entries_for

        entries = entries_for(['paddle_tpu/ops/pallas/rms_norm.py'],
                              root=REPO)
        assert {e.name for e in entries} == {'rms_norm/fwd_bwd',
                                             'rms_norm/ragged_rows'}

    def test_list_rules_names_all_six(self, capsys):
        from paddle_tpu.analysis.__main__ import main

        assert main(['--mosaic', '--list-rules']) == 0
        out = capsys.readouterr().out
        for rid in ('ML001', 'ML002', 'ML003', 'ML004', 'ML005',
                    'ML006'):
            assert rid in out

    def test_mosaic_main_entry_point(self):
        from paddle_tpu.analysis.__main__ import mosaic_main

        assert mosaic_main(['--list-rules']) == 0

    def test_warning_only_exits_zero(self, capsys):
        """Warnings are advisory: they print but never flip the exit
        code — only error-severity violations gate CI."""
        import argparse
        import dataclasses

        from paddle_tpu.analysis import Violation
        from paddle_tpu.analysis.__main__ import _finish

        args = argparse.Namespace(mosaic=True, write_baseline=False,
                                  no_baseline=True, format='text')
        warn = Violation(path='x.py', line=1, col=0, rule='ML006',
                         severity='warning', message='near budget')
        assert _finish(args, [warn], '/nonexistent') == 0
        err = dataclasses.replace(warn, severity='error')
        assert _finish(args, [err], '/nonexistent') == 1
        capsys.readouterr()

    def test_reasonless_suppression_is_usage_error(self, monkeypatch,
                                                   capsys):
        """A registry misconfiguration must exit 2 (usage), never 1 —
        bench would otherwise report it as kernel violations."""
        from paddle_tpu.analysis import mosaic
        from paddle_tpu.analysis.__main__ import main

        monkeypatch.setattr(mosaic.registry, 'entries_for',
                            lambda paths=None, root=None:
                            [_bad_entry({'ML002': ''})])
        assert main(['--mosaic', '--root', REPO]) == 2
        assert 'reason' in capsys.readouterr().err


# ---------------------------------------------------------------------------
# meta: the shipped kernels are statically Mosaic-legal
# ---------------------------------------------------------------------------

class TestMeta:
    def test_all_registered_kernels_statically_legal(self):
        """Every kernel suite in the registry lints clean (modulo the
        reasoned suppressions carried in the registry itself)."""
        vs, sup = lint_entries(all_entries(), root=REPO)
        assert vs == [], '\n'.join(v.render() for v in vs)
        for v, reason in sup:
            assert reason.strip(), v.render()

    def test_every_pallas_module_is_registered(self):
        """A kernel file with no registry entry is a coverage hole —
        mosaiclint can only prove what it traces."""
        pallas_dir = os.path.join(REPO, 'paddle_tpu', 'ops', 'pallas')
        modules = {f[:-3] for f in os.listdir(pallas_dir)
                   if f.endswith('.py') and f != '__init__.py'}
        anchored = {e.anchor.split(':')[0].rsplit('.', 1)[-1]
                    for e in all_entries()}
        assert modules <= anchored, modules - anchored

    def test_rule_ids_and_severities(self):
        rules = all_rules()
        assert [r.id for r in rules] == [f'ML00{i}' for i in
                                         range(1, 7)]
        for r in rules:
            assert r.severity in ('error', 'warning')
            assert r.description
