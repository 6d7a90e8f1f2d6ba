"""paddle_tpu.aot — AOT engine artifacts: warmup, export, zero-compile
cold start.

Covers the tentpole contracts (ISSUE 7 / ROADMAP item 4):
  - CompileCache keys are tuples of primitives with a stable string
    form that round-trips (`key_str`/`key_from_str`) — no object ids,
    no callables;
  - GeometrySet enumeration EXACTLY matches the keys a live engine
    populates while serving the declared workload (no missing, no
    extra) — for the serving scheduler, the decode engine, and the
    train engine;
  - warm attach: a warmed engine's first request is zero traces and
    zero registry misses; TrainEngine warmup leaves the live params
    bit-identical;
  - the manifest refuses to attach across fingerprint or engine-config
    mismatches, loudly;
  - the full artifact round-trips through a FRESH subprocess: load,
    warm, first request with zero compiles (the bench gate_cold_start
    contract in miniature);
  - sysconfig.enable_persistent_compilation_cache takes an explicit
    directory and surfaces it in telemetry.
"""
import json
import os
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import aot
from paddle_tpu import observability as obs
from paddle_tpu import sysconfig
from paddle_tpu.inference.engine import (
    COMPILE_CACHE,
    DecodeEngine,
    key_from_str,
    key_str,
    total_traces,
)
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
from paddle_tpu.optimizer import AdamW
from paddle_tpu.training.engine import (
    TRAIN_COMPILE_CACHE,
    TrainEngine,
)
from paddle_tpu.training.engine import total_traces as train_traces

pytestmark = pytest.mark.tier1

jnp = jax.numpy


def tiny_model(**kw):
    cfg = dict(vocab_size=64, hidden_size=32, layers=1, heads=2,
               kv_heads=2, intermediate_size=64)
    cfg.update(kw)
    pt.seed(0)
    return LlamaForCausalLM(llama_tiny(**cfg))


def serving_engine(model=None, **kw):
    cfg = dict(max_slots=2, block_size=4, max_context_len=8,
               max_new_tokens=3, decode_window=2, buckets=(4, 8))
    cfg.update(kw)
    return ServingEngine(model if model is not None else tiny_model(),
                         **cfg)


def _reset_persistent_cache():
    """Unwire the process-global persistent cache so later tests don't
    keep persisting executables into a vanished tmp dir."""
    sysconfig.restore_persistent_compilation_cache(None)


# ---------------------------------------------------------------------------
# Satellite: serializable CompileCache keys
# ---------------------------------------------------------------------------

def _assert_primitives(x):
    if isinstance(x, tuple):
        for v in x:
            _assert_primitives(v)
        return
    assert x is None or isinstance(x, (str, int, float, bool)), (
        f'non-primitive key component {x!r} ({type(x).__name__})')


class TestKeys:
    def test_roundtrip_and_primitives_decode(self):
        eng = DecodeEngine(tiny_model(), max_new_tokens=4, buckets=(4, 8))
        k = eng.registry_key_generate(1, 3)
        _assert_primitives(k)
        assert key_from_str(key_str(k)) == k

    def test_roundtrip_and_primitives_serving(self):
        srv = serving_engine()
        for tag in (('serve_step', 2, 4), ('serve_window', 2),
                    ('serve_prefill', 8)):
            k = srv.registry_key(*tag)
            _assert_primitives(k)
            assert key_from_str(key_str(k)) == k

    def test_roundtrip_and_primitives_train(self):
        eng = TrainEngine(tiny_model(), AdamW(learning_rate=1e-3))
        k = eng.registry_key((4, 9), 'int32')
        _assert_primitives(k)
        assert key_from_str(key_str(k)) == k

    def test_live_noted_keys_are_serializable(self):
        """The keys the live engines actually note round-trip too (the
        registry's own contents, not just the helper methods)."""
        eng = DecodeEngine(tiny_model(), max_new_tokens=2, buckets=(4,))
        eng.generate(jnp.zeros((1, 3), jnp.int32))
        for k in COMPILE_CACHE.keys():
            _assert_primitives(k)
            assert key_from_str(key_str(k)) == k

    def test_model_tag_not_object_id(self):
        eng = DecodeEngine(tiny_model(), max_new_tokens=4)
        k = eng.registry_key_generate(1, 3)
        assert k[0] == ('paddle_tpu.models.llama.LlamaForCausalLM')
        # the model id is the monotonic engine counter, not id(model)
        assert k[1] < 10_000_000


# ---------------------------------------------------------------------------
# Geometry enumeration == live engine keys (no missing, no extra)
# ---------------------------------------------------------------------------

class TestEnumeration:
    def test_serving_enumeration_matches_live(self):
        srv = serving_engine()
        gs = aot.for_serving_engine(srv)
        want = set(gs.registry_keys(srv))
        before = set(COMPILE_CACHE.keys())
        # workload engineered to hit EVERY dispatch kind the config
        # implies: same-step admissions in both bucket orders (the
        # second group takes the standalone prefill), plus a pure
        # decode window step
        srv.submit(np.arange(1, 4), 3)          # len 3  -> bucket 4
        srv.submit(np.arange(1, 6), 3)          # len 5  -> bucket 8
        srv.step()                              # serve_step(4) + prefill(8)
        srv.run()                               # serve_window drains
        srv.submit(np.arange(1, 6), 3)          # bucket 8 placed first
        srv.submit(np.arange(1, 4), 3)          # bucket 4 second
        srv.step()                              # serve_step(8) + prefill(4)
        srv.run()
        got = set(COMPILE_CACHE.keys()) - before
        assert got == want, (
            f'missing={sorted(want - got)} extra={sorted(got - want)}')

    def test_decode_enumeration_matches_live(self):
        eng = DecodeEngine(tiny_model(), max_new_tokens=4, buckets=(4, 8))
        a = aot.for_decode_engine(eng, prompt_lens=(3, 4), batch_sizes=(1,))
        b = aot.for_decode_engine(eng, prompt_lens=(7,), batch_sizes=(2,))
        gs = aot.GeometrySet(list(a) + list(b))
        want = set(gs.registry_keys(eng))
        before = set(COMPILE_CACHE.keys())
        eng.generate(jnp.zeros((1, 3), jnp.int32))   # padded, bucket 4
        eng.generate(jnp.zeros((1, 4), jnp.int32))   # exact,  bucket 4
        eng.generate(jnp.zeros((2, 7), jnp.int32))   # padded, bucket 8
        got = set(COMPILE_CACHE.keys()) - before
        assert got == want, (
            f'missing={sorted(want - got)} extra={sorted(got - want)}')

    def test_train_enumeration_matches_live(self):
        eng = TrainEngine(tiny_model(), AdamW(learning_rate=1e-3),
                          log_window=100)
        gs = aot.for_train_engine(eng, (2, 5))
        (want,) = gs.registry_keys(eng)
        eng.step((jnp.zeros((2, 5), jnp.int32),))
        assert want in TRAIN_COMPILE_CACHE._keys

    def test_spec_enumeration_honors_budget_override(self):
        eng = DecodeEngine(tiny_model(), max_new_tokens=8)
        gs = aot.for_decode_engine(eng, prompt_lens=(5,), batch_sizes=(),
                                   max_new_tokens=[3],
                                   spec_draft_tokens=(2,))
        (g,) = gs
        assert g.params['max_new_tokens'] == 3
        # and the key matches what the overridden live call notes
        assert gs.registry_keys(eng) == [
            eng.registry_key_speculative(1, 5, 3, 2)]

    def test_train_loss_fn_identity_distinguishes_lambdas(self):
        model = tiny_model()
        a = TrainEngine(model, AdamW(learning_rate=1e-3),
                        loss_fn=lambda p, y: (p.mean() - y.mean()) ** 2)
        b = TrainEngine(model, AdamW(learning_rate=1e-3),
                        loss_fn=lambda p, y: abs(p.mean() - y.mean()))
        assert a.aot_config()['loss_fn'] != b.aot_config()['loss_fn']
        assert aot.config_hash(a.aot_config()) != aot.config_hash(
            b.aot_config())

    def test_geometry_manifest_roundtrip(self):
        srv = serving_engine()
        gs = aot.for_serving_engine(srv)
        back = aot.GeometrySet.from_manifest(
            json.loads(json.dumps(gs.to_manifest())))
        assert list(back) == list(gs)
        assert back.registry_keys(srv) == gs.registry_keys(srv)


# ---------------------------------------------------------------------------
# Warm attach (in-process)
# ---------------------------------------------------------------------------

class TestWarmup:
    def test_decode_warmup_zero_traces_and_misses(self):
        # a distinctive shape so other tests cannot have pre-warmed the
        # module-level jit cache for these avals
        eng = DecodeEngine(tiny_model(hidden_size=48, intermediate_size=80),
                           max_new_tokens=5, buckets=(4, 8))
        gs = aot.for_decode_engine(eng, prompt_lens=(3,), batch_sizes=(1,))
        rep = eng.warmup(geometries=gs)
        assert rep['geometries'] == 1 and rep['traces'] > 0
        t0, m0 = total_traces(), COMPILE_CACHE.misses
        out = eng.generate(jnp.zeros((1, 2), jnp.int32))  # same bucket
        assert out.shape == (1, 7)
        assert total_traces() - t0 == 0
        assert COMPILE_CACHE.misses - m0 == 0

    def test_serving_warmup_zero_traces_and_misses(self):
        srv = serving_engine(tiny_model(hidden_size=48,
                                        intermediate_size=80))
        srv.warmup(geometries=aot.for_serving_engine(srv))
        t0, m0 = total_traces(), COMPILE_CACHE.misses
        rid = srv.submit(np.arange(1, 4), 3)
        srv.run()
        assert srv.result(rid) is not None
        assert total_traces() - t0 == 0
        assert COMPILE_CACHE.misses - m0 == 0

    def test_serving_warmup_refuses_in_flight(self):
        """The dummy warm batch is only inert when every slot is empty:
        warming mid-traffic would silently corrupt live streams, so it
        must refuse instead."""
        srv = serving_engine(max_new_tokens=6)
        srv.submit(np.arange(1, 3), 6)
        srv.step()                       # admitted, not finished
        assert srv.in_flight() == 1
        with pytest.raises(RuntimeError, match='in flight'):
            srv.warmup(geometries=aot.for_serving_engine(srv))
        srv.run()                        # drained: warmup is legal again
        srv.warmup(geometries=aot.GeometrySet(
            [aot.Geometry('serve_window', window=2)]))

    def test_serving_warmup_then_parity(self):
        """Warming with dummy all-frozen batches must not corrupt the
        scheduler: post-warmup outputs equal a cold engine's."""
        m = tiny_model()
        cold = serving_engine(m)
        prompt = np.arange(1, 4)
        want = cold.serve([prompt], 3)[0]
        warm = serving_engine(m)
        warm.warmup(geometries=aot.for_serving_engine(warm))
        got = warm.serve([prompt], 3)[0]
        np.testing.assert_array_equal(got, want)

    def test_train_warmup_preserves_params_zero_traces(self):
        eng = TrainEngine(tiny_model(hidden_size=48, intermediate_size=80),
                          AdamW(learning_rate=1e-3), log_window=100)
        before = [np.asarray(p) for p in eng.model.parameters()]
        rep = eng.warmup(geometries=aot.for_train_engine(eng, (2, 5)))
        assert rep['traces'] > 0
        after = [np.asarray(p) for p in eng.model.parameters()]
        for b, a in zip(before, after):
            np.testing.assert_array_equal(b, a)
        t0, m0 = train_traces(), TRAIN_COMPILE_CACHE.misses
        eng.step((jnp.zeros((2, 5), jnp.int32),))
        assert train_traces() - t0 == 0
        assert TRAIN_COMPILE_CACHE.misses - m0 == 0

    def test_warmup_needs_artifact_or_geometries(self):
        eng = DecodeEngine(tiny_model(), max_new_tokens=2)
        with pytest.raises(ValueError, match='artifact'):
            eng.warmup()

    def test_speculative_warmup_zero_traces(self):
        target = tiny_model(hidden_size=48, intermediate_size=80)
        draft = tiny_model(hidden_size=48, intermediate_size=80)
        eng = DecodeEngine(target, max_new_tokens=4)
        gs = aot.for_decode_engine(eng, prompt_lens=(3,), batch_sizes=(),
                                   spec_draft_tokens=(2,))
        assert [g.kind for g in gs] == ['decode_spec']
        # the draft model is part of the traced computation: warmup
        # without it must fail loudly, not warm the wrong thing
        with pytest.raises(ValueError, match='draft'):
            eng.warmup(geometries=gs)
        eng.warmup(geometries=gs, draft=draft)
        t0, m0 = total_traces(), COMPILE_CACHE.misses
        out = eng.generate_speculative(
            draft, jnp.zeros((1, 3), jnp.int32), num_draft_tokens=2)
        assert out.shape[1] == 3 + 4
        assert total_traces() - t0 == 0
        assert COMPILE_CACHE.misses - m0 == 0


# ---------------------------------------------------------------------------
# The artifact: build, manifest, attach checks, subprocess round-trip
# ---------------------------------------------------------------------------

@pytest.fixture(scope='module')
def built(tmp_path_factory):
    """One shared artifact build (compiling is the expensive part):
    the tiny serving config at module scope."""
    path = str(tmp_path_factory.mktemp('aot') / 'artifact')
    srv = serving_engine()
    art = aot.build(srv, path)
    _reset_persistent_cache()
    return {'path': path, 'engine': srv, 'artifact': art}


class TestArtifact:
    def test_manifest_contents(self, built):
        m = built['artifact'].manifest
        assert m['version'] == 1
        assert m['config_hash'] == aot.config_hash(
            built['engine'].aot_config())
        for field in ('jax', 'jaxlib', 'backend', 'device_kind'):
            assert m['fingerprint'][field] == aot.fingerprint()[field]
        # every geometry carries its registry key in stable string
        # form, with the per-process model-id component normalized
        for g in m['geometries']:
            k = key_from_str(g['key'])
            _assert_primitives(k)
            assert k[1] == -1
        assert m['build']['n_geometries'] == len(m['geometries']) == 5
        assert os.path.isdir(built['artifact'].cache_dir)
        assert os.listdir(built['artifact'].cache_dir), (
            'no executables were persisted into the artifact cache')

    def test_load_missing_manifest(self, tmp_path):
        with pytest.raises(FileNotFoundError, match='manifest'):
            aot.EngineArtifact.load(str(tmp_path))

    def test_empty_geometries_refused(self, tmp_path):
        srv = built_engine = serving_engine()
        with pytest.raises(ValueError, match='empty'):
            aot.build(built_engine, str(tmp_path / 'x'),
                      geometries=aot.GeometrySet([]))
        del srv

    def test_fingerprint_mismatch_refuses(self, built, tmp_path):
        tampered = str(tmp_path / 'tampered')
        shutil.copytree(built['path'], tampered)
        mpath = os.path.join(tampered, aot.MANIFEST_NAME)
        with open(mpath) as f:
            m = json.load(f)
        m['fingerprint']['jaxlib'] = '0.0.1-other'
        with open(mpath, 'w') as f:
            json.dump(m, f)
        srv = serving_engine()
        with pytest.raises(aot.ArtifactMismatch,
                           match='jaxlib.*0.0.1-other'):
            srv.warmup(artifact=tampered)
        _reset_persistent_cache()

    def test_config_mismatch_refuses(self, built):
        other = serving_engine(decode_window=3)   # differs from built
        with pytest.raises(aot.ArtifactMismatch, match='decode_window'):
            other.warmup(artifact=built['path'])
        _reset_persistent_cache()

    def test_model_size_mismatch_refuses(self, built):
        """Same model CLASS, different parameter shapes: every cache
        lookup would miss, so the attach must refuse (model_struct is
        part of the config hash)."""
        other = serving_engine(tiny_model(hidden_size=64,
                                          intermediate_size=128))
        with pytest.raises(aot.ArtifactMismatch, match='model_struct'):
            other.warmup(artifact=built['path'])
        _reset_persistent_cache()

    def test_build_restores_prior_cache_wiring(self, built, tmp_path):
        """The artifact redirection is scoped to the build: the
        previously wired dir (or unwired state) comes back, so a
        still-serving builder cannot leak later compiles into the
        artifact."""
        assert sysconfig.persistent_compilation_cache_dir() is None
        srv = serving_engine()
        aot.build(srv, str(tmp_path / 'scoped'),
                  geometries=aot.GeometrySet(
                      [aot.Geometry('serve_window', window=2)]))
        assert sysconfig.persistent_compilation_cache_dir() is None
        prior = sysconfig.enable_persistent_compilation_cache(
            str(tmp_path / 'prior'))
        try:
            srv2 = serving_engine()
            aot.build(srv2, str(tmp_path / 'scoped2'),
                      geometries=aot.GeometrySet(
                          [aot.Geometry('serve_window', window=2)]))
            assert sysconfig.persistent_compilation_cache_dir() == prior
        finally:
            _reset_persistent_cache()

    def test_warm_attach_from_path(self, built):
        srv = serving_engine()
        rep = srv.warmup(artifact=built['path'])
        assert rep['geometries'] == 5
        assert rep['persistent_cache_dir'] == built['artifact'].cache_dir
        # the redirection is scoped: after attach, the process is back
        # to its previous (unwired) state — later compiles must not
        # write into the artifact mount
        assert sysconfig.persistent_compilation_cache_dir() is None
        t0, m0 = total_traces(), COMPILE_CACHE.misses
        rid = srv.submit(np.arange(1, 4), 3)
        srv.run()
        assert srv.result(rid) is not None
        assert total_traces() - t0 == 0
        assert COMPILE_CACHE.misses - m0 == 0
        _reset_persistent_cache()

    def test_subprocess_cold_start_zero_compiles(self, built):
        """THE tentpole proof: a fresh process loads the artifact,
        warm-attaches, and serves its first request with zero traces
        and zero registry misses — the executables come off disk."""
        src = r'''
import json, os
import numpy as np
import paddle_tpu as pt
from paddle_tpu import aot
from paddle_tpu.inference.engine import COMPILE_CACHE, total_traces
from paddle_tpu.inference.serving import ServingEngine
from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

pt.seed(0)
model = LlamaForCausalLM(llama_tiny(vocab_size=64, hidden_size=32,
                                    layers=1, heads=2, kv_heads=2,
                                    intermediate_size=64))
srv = ServingEngine(model, max_slots=2, block_size=4, max_context_len=8,
                    max_new_tokens=3, decode_window=2, buckets=(4, 8))
rep = srv.warmup(artifact=os.environ['AOT_TEST_DIR'])
t0, m0 = total_traces(), COMPILE_CACHE.misses
rid = srv.submit(np.arange(1, 4), 3)
srv.run()
ok = srv.result(rid) is not None
print(json.dumps({'traces': total_traces() - t0,
                  'misses': COMPILE_CACHE.misses - m0,
                  'served': bool(ok),
                  'warm_geometries': rep['geometries']}))
'''
        env = dict(os.environ, JAX_PLATFORMS='cpu',
                   AOT_TEST_DIR=built['path'])
        proc = subprocess.run(
            [sys.executable, '-c', src], capture_output=True, text=True,
            timeout=420, env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        assert proc.returncode == 0, proc.stderr[-2000:]
        payload = json.loads(proc.stdout.strip().splitlines()[-1])
        assert payload['served'] is True
        assert payload['warm_geometries'] == 5
        assert payload['traces'] == 0, payload
        assert payload['misses'] == 0, payload


class TestStableHLO:
    def test_decode_export_roundtrips(self, tmp_path):
        from jax import export as jax_export

        eng = DecodeEngine(tiny_model(), max_new_tokens=2, buckets=(4,))
        art = aot.build(eng, str(tmp_path / 'a'),
                        geometries=aot.for_decode_engine(
                            eng, prompt_lens=(3,), batch_sizes=(1,)),
                        export_stablehlo=True)
        (g,) = art.manifest['geometries']
        assert g['stablehlo'] == ['decode-b1-m2-p3-prefill.stablehlo',
                                  'decode-b1-m2-p3-decode.stablehlo']
        for fname in g['stablehlo']:
            p = os.path.join(art.stablehlo_dir, fname)
            with open(p, 'rb') as f:
                exported = jax_export.deserialize(bytearray(f.read()))
            assert exported.mlir_module_serialized
        _reset_persistent_cache()


# ---------------------------------------------------------------------------
# Satellite: sysconfig explicit cache dir + telemetry
# ---------------------------------------------------------------------------

class TestSysconfig:
    def test_explicit_dir_and_telemetry(self, tmp_path):
        obs.REGISTRY.reset()
        obs.TRACER.clear()
        want = str(tmp_path / 'cache_here')
        try:
            got = sysconfig.enable_persistent_compilation_cache(want)
            assert got == os.path.abspath(want)
            assert os.path.isdir(got)
            assert sysconfig.persistent_compilation_cache_dir() == got
            assert jax.config.jax_compilation_cache_dir == got
            # the PR-6 telemetry surfaces the wired dir
            g = obs.REGISTRY.get('compile.persistent_cache_enabled')
            assert g is not None and g.value == 1.0
            events = [e for e in obs.TRACER.to_chrome_trace()
                      if e.get('name') == 'compile.persistent_cache_dir']
            assert events and events[0]['args']['path'] == got
            # an explicit dir REPLACES a previously wired one
            want2 = str(tmp_path / 'cache_two')
            assert sysconfig.enable_persistent_compilation_cache(
                want2) == os.path.abspath(want2)
        finally:
            _reset_persistent_cache()

    @pytest.mark.parametrize('from_env', [True, False])
    def test_default_dir_rule(self, from_env, tmp_path, monkeypatch):
        """No explicit path: JAX_COMPILATION_CACHE_DIR when set, else
        <checkout>/.jax_cache — and the same directory on every call
        (a name that moves never hits)."""
        checkout = os.path.dirname(os.path.dirname(
            os.path.abspath(sysconfig.__file__)))
        if from_env:
            want = str(tmp_path / 'placed_from_outside')
            monkeypatch.setenv('JAX_COMPILATION_CACHE_DIR', want)
        else:
            want = os.path.join(checkout, '.jax_cache')
            monkeypatch.delenv('JAX_COMPILATION_CACHE_DIR', raising=False)
        try:
            first = sysconfig.enable_persistent_compilation_cache()
            second = sysconfig.enable_persistent_compilation_cache()
            assert first == second == want
            assert jax.config.jax_compilation_cache_dir == want
            assert os.path.isdir(want)
        finally:
            _reset_persistent_cache()

    def test_env_switch_is_on_off_only(self, monkeypatch):
        """PADDLE_TPU_PERSISTENT_CACHE no longer names a directory: a
        value that is not boolean-ish leaves the cache off."""
        monkeypatch.setenv('PADDLE_TPU_PERSISTENT_CACHE', '/some/dir')
        try:
            DecodeEngine(tiny_model(), max_new_tokens=2)
            assert sysconfig.persistent_compilation_cache_dir() is None
        finally:
            _reset_persistent_cache()
