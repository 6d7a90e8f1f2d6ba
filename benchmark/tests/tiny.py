"""Tiny sizes for the CPU rehearsal: the same files' keys, toy numbers."""
import time

TINY_SERVE_CFG = {
    'name': 'tiny', 'family': 'llama', 'hidden_size': 64, 'intermediate_size': 128, 'num_attention_heads': 4,
    'num_key_value_heads': 2, 'head_dim': 16, 'num_hidden_layers': 2,
    'vocab_size': 512, 'rope_theta': 1e6, 'rms_norm_eps': 1e-5,
    'tie_word_embeddings': False, 'attention_bias': False,
    'sliding_window': None, 'hidden_act': 'silu', 'torch_dtype': 'bfloat16'}
TINY_TRAIN_CFG = dict(TINY_SERVE_CFG, tie_word_embeddings=True,
                      attention_bias=True, rms_norm_eps=1e-6)

OPEN = {'loop': 'open', 'rate_rps': 6.0, 'lead_in_s': 1, 'drain_limit_s': 30,
        'prompt': {'dist': 'lognormal', 'median': 24, 'sigma': 0.5,
                   'min': 8, 'max': 60},
        'output': {'dist': 'lognormal', 'median': 10, 'sigma': 0.4,
                   'min': 4, 'max': 20},
        'buckets': [16, 32, 64]}
CLOSED = {'loop': 'closed', 'clients': 4, 'pool': 16, 'lead_in_s': 1,
          'drain_limit_s': 30,
          'prompt': {'dist': 'lognormal', 'median': 40, 'sigma': 0.3,
                     'min': 33, 'max': 64},
          'output': {'dist': 'uniform', 'min': 4, 'max': 9},
          'buckets': [64]}
TRAIN = {'loop': 'train', 'batch': 2, 'seq': 32}

SERVE_CELL = {'driver': 'serve', 'chips': 1, 'trace_seconds': 2,
              'geometry': {'max_slots': 2, 'block_size': 16,
                           'max_context_len': 128, 'decode_window': 4,
                           'max_new_tokens': 20},
              'check_requests': 3, 'control': 'int8', 'limits': {'served_logit_gap': 0.25},
              'end_to_end': ['ttft_p95_s', 'itl_p95_ms', 'setup_s']}
TRAIN_CELL = {'driver': 'train', 'chips': 1, 'trace_seconds': 2,
              'optimizer': {'learning_rate': 1e-3, 'weight_decay': 0.01},
              'warm_steps': 1, 'sync_every': 4, 'control': 'fp8',
              'limits': {'loss_gap': 0.02, 'grad_norm_gap': 0.1,
                         'change_norm_gap': 0.1, 'grad_proj_gap': 0.05},
              'end_to_end': ['train_tok_s', 'setup_s']}


def env(seed=3, seconds=2.0, trace=False):
    import jax

    from benchmark.harness import common, programs

    return common.Env(t_start=time.perf_counter(), seed=seed,
                      seconds=seconds, trace=trace, device=jax.devices()[0],
                      peak=None, compiles=_compiles(programs), per_layer=[])


_LOG = []


def _compiles(programs):
    if not _LOG:                    # listeners cannot be unregistered
        _LOG.append(programs.CompileLog())
    return _LOG[0]
