"""Device scopes: every instruction of a compiled serve program carries the
model part it was traced under (`observability.tracing.DEVICE_SCOPES`, a
`jax.named_scope` a part), and the benchmark's reading of the compiled text
(`benchmark/harness/scopes.py`) gives each instruction its part:

  - the two vocabularies are one, and the package opens no other scope;
  - the reading's rules on small texts written by hand: the op_name's
    segments, a fusion named by what it computes, XLA's own instructions
    by their users, variants that disagree;
  - the tiny llama, afmoe and mimo_v2 engines' three serve programs,
    compiled on the CPU: every dot, convolution, sort, scatter, gather and
    custom call (alone or inside a fusion) maps to a part of the
    vocabulary, the router's product to `expert_share/route` and the
    grouped expert products to `expert_share/matmul`.
"""
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.harness import common, scopes  # noqa: E402
from paddle_tpu.aot import geometry  # noqa: E402
from paddle_tpu.inference.serving import ServingEngine  # noqa: E402
from paddle_tpu.observability.tracing import DEVICE_SCOPES  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import test_afmoe  # noqa: E402  (the families' tiny configurations)
import test_mimo_v2  # noqa: E402

LLAMA = {
    'name': 'tiny-llama', 'family': 'llama', 'hidden_size': 64,
    'intermediate_size': 128, 'num_attention_heads': 4,
    'num_key_value_heads': 2, 'head_dim': 16, 'num_hidden_layers': 2,
    'vocab_size': 256, 'rope_theta': 1e6, 'rms_norm_eps': 1e-5,
    'tie_word_embeddings': False, 'attention_bias': False,
    'sliding_window': None, 'hidden_act': 'silu', 'torch_dtype': 'float32'}
FAMILIES = {'llama': LLAMA, 'afmoe': test_afmoe.CFG,
            'mimo_v2': test_mimo_v2.CFG}
PROGRAMS = ('jit__serve_step', 'jit__serve_window', 'jit__paged_prefill')
KEY_OPS = ('dot', 'convolution', 'sort', 'scatter', 'gather', 'custom-call')


def test_the_benchmark_reads_the_programs_vocabulary():
    assert scopes.VOCABULARY == DEVICE_SCOPES


def test_the_package_opens_no_other_scope():
    """Every `jax.named_scope` the package opens is a vocabulary path, or
    an expert stage's last segment opened inside `ExpertShare.SCOPE`."""
    stages = {p.split('/', 1)[1] for p in DEVICE_SCOPES
              if p.startswith('expert_share/')}
    found = set()
    for root, _dirs, files in os.walk(os.path.join(ROOT, 'paddle_tpu')):
        for name in files:
            if name.endswith('.py'):
                with open(os.path.join(root, name)) as f:
                    found |= set(re.findall(r"named_scope\('([^']+)'\)",
                                            f.read()))
    assert found and found <= set(DEVICE_SCOPES) | stages


@pytest.mark.parametrize('op_name, path', [
    ('jit(_serve_window)/while/body/closed_call/attn/qkv/dot_general',
     'attn/qkv'),
    ('jit(f)/while/body/closed_call/expert_share/route/jit(matmul)/'
     'dot_general', 'expert_share/route'),
    ('jit(train)/transpose(jvp(head))/dot_general', 'head'),
    ('jit(f)/jvp(attn/core)/vmap()/mul', 'attn/core'),
    ('jit(f)/attn/cache/reshape;jit(f)/attn/qkv/reshape', 'attn/cache'),
    ('jit(f)/jit(floor_divide)/rem;jit(f)/mlp/mul', 'mlp'),
    ('jit(_serve_window)/while/cond/lt', 'other'),
    (None, 'other'),
])
def test_a_path_is_the_vocabularys_segments_in_order(op_name, path):
    assert scopes.path_of(op_name) == path


HAND = '''HloModule jit_f, entry_computation_layout={(f32[8,8]{1,0})->f32[8,8]{1,0}}

%fused_a (p0: f32[8,8], p1: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %p1 = f32[8,8]{1,0} parameter(1)
  %dot.1 = f32[8,8]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/mlp/dot_general"}
  ROOT %add.1 = f32[8,8]{1,0} add(%dot.1, %p0), metadata={op_name="jit(f)/add"}
}

%fused_b (p0.1: f32[8,8]) -> f32[8,8] {
  %p0.1 = f32[8,8]{1,0} parameter(0)
  ROOT %mul.1 = f32[8,8]{1,0} multiply(%p0.1, %p0.1), metadata={op_name="jit(f)/norm/mul"}
}

ENTRY %main (x: f32[8,8]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %copy.1 = f32[8,8]{1,0:T(8,128)} copy(%x)
  %fusion.1 = f32[8,8]{1,0} fusion(%copy.1, %x), kind=kOutput, calls=%fused_a, metadata={op_name="jit(f)/add"}
  %fusion.2 = f32[8,8]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_b, metadata={op_name="jit(f)/norm/mul"}
  %sort.1 = f32[8,8]{1,0} sort(%fusion.2), dimensions={1}, metadata={op_name="jit(f)/expert_share/dispatch/sort"}
  ROOT %copy.2 = f32[8,8]{1,0} copy(%sort.1)
}
'''


def test_a_fusion_is_named_by_what_it_computes():
    """The residual add a product is fused with does not name the fusion:
    the product does; a fusion with no product is its root's; XLA's copy
    of the argument goes with its user, the last copy with what fed it."""
    module, got = scopes.program_scopes(HAND)
    assert module == 'jit_f'
    assert got['fusion.1'] == 'mlp'
    assert got['fusion.2'] == 'norm'
    assert got['sort.1'] == 'expert_share/dispatch'
    assert got['copy.1'] == 'mlp'
    assert got['copy.2'] == 'expert_share/dispatch'
    assert 'dot.1' not in got and 'mul.1' not in got    # fused: no events


def test_variants_that_disagree_on_a_name_are_ambiguous():
    merged = scopes.merge([{'a': 'mlp', 'b': 'head'},
                           {'a': 'mlp', 'b': 'sample', 'c': 'norm'}])
    assert merged == {'a': 'mlp', 'b': scopes.AMBIGUOUS, 'c': 'norm'}


def test_each_execution_reads_the_variant_it_ran():
    """Two buckets of one program name `fusion.2` for other parts; each has
    an instruction of its own (`dot.5`, `dot.6`), which tells which of them
    an execution ran. An execution that ran none of them is `ambiguous`
    where the two disagree; an event outside every execution is `other`."""
    from benchmark.harness import trace_reduce

    small = {'fusion.2': 'head', 'dot.5': 'mlp', 'copy.1': 'attn/cache'}
    large = {'fusion.2': 'mlp', 'dot.6': 'mlp', 'copy.1': 'attn/cache'}
    rows = [('fusion.2', 'fusion', 0.0, 1.0, 'jit_step'),
            ('dot.6', 'dot', 1.0, 1.0, 'jit_step'),
            ('fusion.2', 'fusion', 3.0, 1.0, 'jit_step'),
            ('dot.5', 'dot', 4.0, 1.0, 'jit_step'),
            ('fusion.2', 'fusion', 6.0, 0.5, 'jit_step'),
            ('copy.1', 'copy', 6.5, 0.5, 'jit_step'),
            ('fusion.2', 'fusion', 8.0, 0.5, '')]
    trace = trace_reduce.Trace(
        ops={'tpu': rows}, host=[],
        programs={'tpu': [('jit_step', 0.0, 2.0), ('jit_step', 3.0, 2.0),
                          ('jit_step', 6.0, 1.0)]})
    got = scopes.row_paths(trace, {'jit_step': [small, large]})
    assert got == {'tpu': ['mlp', 'mlp', 'head', 'mlp', scopes.AMBIGUOUS,
                           'attn/cache', scopes.OTHER]}


@pytest.fixture(scope='module')
def compiled():
    """{family: {module: compiled text}} of the three serve programs."""
    out = {}
    for family, cfg in FAMILIES.items():
        engine = ServingEngine(
            common.family(cfg).make_model(cfg, 11, 64), max_slots=2,
            block_size=4, max_context_len=64, decode_window=4,
            max_new_tokens=8, buckets=(32,))
        texts = {}
        for g in geometry.for_serving_engine(engine, prompt_lens=[32]):
            (fn, args, statics), = engine._cost_specs(g)
            text = fn.lower(*args, **statics).compile().as_text()
            texts[scopes.parse(text)[0]] = text
        out[family] = texts
    return out


def _frames(text):
    """{stack_frame_id: the function it lies in} from the text's tables."""
    tables, current = {}, None
    for line in text.splitlines():
        if line in ('FunctionNames', 'FileLocations', 'StackFrames',
                    'FileNames'):
            current = tables.setdefault(line, {})
        elif not line.strip():
            current = None
        elif current is not None:
            key, _, rest = line.partition(' ')
            current[int(key)] = rest
    out = {}
    for fid, frame in tables.get('StackFrames', {}).items():
        loc = tables['FileLocations'][int(re.search(
            r'file_location_id=(\d+)', frame).group(1))]
        out[fid] = tables['FunctionNames'][int(re.search(
            r'function_name_id=(\d+)', loc).group(1))].strip('"')
    return out


def _top_level(text):
    """[(instruction, its line, [lines of what it fuses])] over the
    computations the device runs as events."""
    _, computations = scopes.parse(text)
    lines = {}
    for line in text.splitlines():
        m = re.match(r'^\s+(?:ROOT\s+)?%([\w.\-]+)\s*=', line)
        if m:
            lines[m.group(1)] = line
    fused = {c for body in computations.values() for i in body
             for c in i.calls}

    def inside(name, seen):
        out = []
        for i in computations.get(name, ()):
            out.append(i)
            for c in i.calls:
                if c not in seen:
                    seen.add(c)
                    out += inside(c, seen)
        return out

    return [(i, lines[i.name],
             inside(i.calls[0], {i.calls[0]}) if i.calls else [])
            for name, body in computations.items() if name not in fused
            for i in body]


@pytest.mark.parametrize('program', PROGRAMS)
@pytest.mark.parametrize('family', sorted(FAMILIES))
def test_every_op_of_a_serve_program_has_its_part(compiled, family,
                                                  program):
    text = compiled[family][program]
    _, paths = scopes.program_scopes(text)
    checked, unscoped = 0, []
    for i, _line, body in _top_level(text):
        if i.opcode in KEY_OPS or any(b.opcode in KEY_OPS for b in body):
            checked += 1
            if paths[i.name] not in DEVICE_SCOPES:
                unscoped.append((i.name, i.opcode, paths[i.name],
                                 i.op_name))
    assert checked > 10
    assert unscoped == []


@pytest.mark.parametrize('family', ['afmoe', 'mimo_v2'])
def test_the_router_and_the_grouped_products_are_their_stages(compiled,
                                                             family):
    """The router's product is the one dot at `highest` precision; the
    grouped products are what `grouped_expert_mlp`'s `product` issued
    (`lax.ragged_dot` on the CPU), found by the text's stack frames."""
    for program in ('jit__serve_step', 'jit__serve_window'):
        text = compiled[family][program]
        _, paths = scopes.program_scopes(text)
        frames = _frames(text)
        router, grouped = [], []
        for i, line, _body in _top_level(text):
            if i.opcode == 'dot' and 'operand_precision={highest' in line:
                router.append(paths[i.name])
            frame = re.search(r'stack_frame_id=(\d+)', line)
            if (i.opcode in KEY_OPS + ('fusion',) and frame
                    and frames[int(frame.group(1))]
                    == 'grouped_expert_mlp.<locals>.product'):
                grouped.append(paths[i.name])
        assert router and set(router) == {'expert_share/route'}
        assert grouped and set(grouped) == {'expert_share/matmul'}
