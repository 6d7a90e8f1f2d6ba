"""CPU rehearsal of chip_smoke.py: its phases at a tiny size, through the
same functions the chip run calls. On the CPU the kernels take their lax
references, so no Mosaic call is found here; `main()` — the only place that
prints the `"ok": true` line — must refuse to run at all."""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    width=dict(vocab_size=256, hidden_size=64, intermediate_size=128,
               num_attention_heads=4, num_key_value_heads=4,
               dtype='float32'),
    serve_layers=2, slots=8, context=64,
    prompt_lens=(40, 33, 36, 20, 12, 5), reference=(0, 3), new_tokens=12,
    tolerance=1e-3, train_layers=2, train_batch=2, train_seq=32,
    train_steps=3)


@pytest.fixture(autouse=True)
def _no_persistent_cache():
    """The phases compile sharded, donated programs; a persistent cache
    left wired by an earlier test file in the same worker makes XLA:CPU
    serialize them, which has crashed the worker. main() is what switches
    the cache on, and main() never gets that far here."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update('jax_enable_compilation_cache', False)
    compilation_cache.reset_cache()
    yield
    jax.config.update('jax_enable_compilation_cache', was)
    compilation_cache.reset_cache()


def test_main_refuses_off_the_chip(capsys):
    from paddle_tpu import sysconfig

    wired = sysconfig.persistent_compilation_cache_dir()
    with pytest.raises(SystemExit) as exit_:
        chip_smoke.main([])
    assert exit_.value.code not in (0, None)
    assert 'needs a TPU' in str(exit_.value.code)
    assert '"ok"' not in capsys.readouterr().out
    # and it left the process as it found it: no cache wired on a refusal
    assert sysconfig.persistent_compilation_cache_dir() == wired


def test_serve_phase(capsys):
    assert chip_smoke.serve_phase(TINY, jax.devices()[0]) == {}
    out = capsys.readouterr().out
    assert out.count('finished with 12 new tokens') == len(TINY.prompt_lens)
    assert out.count('generate() gives the served tokens') == 2
    assert '"ok"' not in out


def test_train_phase(capsys):
    assert chip_smoke.train_phase(TINY, jax.devices()[0]) == {}
    assert 'loss fell on the repeated batch' in capsys.readouterr().out


def test_a_failed_check_ends_the_run():
    with pytest.raises(SystemExit, match='CHECK FAILED: the sky is green'):
        chip_smoke.check(False, 'the sky is green')


@pytest.fixture
def restore_global_mesh():
    from paddle_tpu.distributed import mesh as mesh_mod

    before = mesh_mod.get_mesh()
    yield
    mesh_mod.set_mesh(before)


def test_four_chip_phases_on_four_virtual_devices(capsys,
                                                  restore_global_mesh):
    chip_smoke.tp_serve_phase(TINY)
    chip_smoke.sharded_train_phase(TINY, jax.devices()[:4])
    out = capsys.readouterr().out
    assert out.count('sits in quarters on four devices') == 3
    assert out.count('the one-chip engine gives the served tokens') == 2
    assert 'agree with one chip' in out


def test_mosaic_kernels_reads_the_stack_frame_tables():
    """Each Mosaic custom call is attributed to the pallas file whose
    frame issued it; other custom calls are not counted."""
    text = '''HloModule jit_step

FileNames
1 "/x/paddle_tpu/models/llama.py"
2 "/x/paddle_tpu/ops/pallas/rms_norm.py"
3 "/x/paddle_tpu/ops/pallas/paged_attention.py"

FunctionNames
1 "forward"
2 "_run_fwd"

FileLocations
1 {file_name_id=1 function_name_id=1 line=5 end_line=5 column=1 end_column=2}
2 {file_name_id=2 function_name_id=2 line=56 end_line=72 column=11 end_column=12}
3 {file_name_id=3 function_name_id=2 line=120 end_line=130 column=11 end_column=12}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}
3 {file_location_id=3 parent_frame_id=1}

ENTRY %main {
  %a = f32[8] custom-call(%p), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/pallas_call" stack_frame_id=2}, backend_config={}
  %b = f32[8] custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/pallas_call" stack_frame_id=3}
  %c = f32[8] custom-call(%b), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/pallas_call" stack_frame_id=2}
  %d = f32[8] custom-call(%c), custom_call_target="Sharding", metadata={op_name="x" stack_frame_id=1}
}
'''
    assert chip_smoke.mosaic_kernels(text) == {'rms_norm': 2,
                                               'paged_attention': 1}
    assert chip_smoke.mosaic_kernels('HloModule empty\n') == {}
