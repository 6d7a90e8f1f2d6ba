"""Build an AOT EngineArtifact for a named bench config and print its
manifest.

The artifact flow bench.py's `gate_cold_start` proves in miniature,
as an operator tool: pick one of the bench-shaped engine configs,
enumerate its GeometrySet, compile every geometry with the persistent
executable cache wired into --out, and write the manifest — so a later
process (a fresh serving replica, or the warm half of the cold-start
gate) can `engine.warmup(artifact=OUT)` and serve its first request
with zero compiles.

    python tools/warmup_cli.py --config serving-gate --out /tmp/aot [--cpu]
    python tools/warmup_cli.py --list

Configs mirror the bench gate workloads (tiny Llama shapes that run
anywhere); `--export-stablehlo` additionally serializes each geometry
through jax.export into OUT/stablehlo/.

Importable anywhere (pytest collection, tracelint) without touching a
backend — only main() initialises jax, with the same rc-2 guard
discipline as tools/telemetry_dump.py: when NO jax backend can be
initialised at all, exit 2 with a message instead of a traceback.
"""
import argparse
import json
import os
import sys

# `python tools/warmup_cli.py` puts tools/ (not the repo root) on
# sys.path and paddle_tpu is not pip-installed on the dev boxes — make
# the repo importable no matter where the script is launched from
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def _tiny_model(**kw):
    import paddle_tpu as pt
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    pt.seed(0)
    return LlamaForCausalLM(llama_tiny(**kw))


def build_serving_gate(out, export_stablehlo):
    """The bench serving-gate engine (tiny Llama, 4 slots, paged pool):
    full-coverage enumeration over its admissible context lengths."""
    from paddle_tpu import aot
    from paddle_tpu.inference.serving import ServingEngine

    model = _tiny_model(vocab_size=96, hidden_size=64, layers=2)
    srv = ServingEngine(model, max_slots=4, block_size=8,
                        max_context_len=32, max_new_tokens=16,
                        decode_window=8)
    return aot.build(srv, out, export_stablehlo=export_stablehlo)


def build_decode_gate(out, export_stablehlo):
    """The bench decode-engine config: batch-1 generate over the gate's
    prompt bucket."""
    from paddle_tpu import aot
    from paddle_tpu.inference.engine import DecodeEngine

    model = _tiny_model(vocab_size=96, hidden_size=64, layers=2)
    eng = DecodeEngine(model, max_new_tokens=32)
    return aot.build(eng, out, export_stablehlo=export_stablehlo,
                     prompt_lens=(13,), batch_sizes=(1,))


def build_train_gate(out, export_stablehlo):
    """The bench train-gate engine (tiny Llama + AdamW, fused step at
    the gate's global batch shape)."""
    from paddle_tpu import aot
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.training.engine import TrainEngine

    model = _tiny_model(vocab_size=64, hidden_size=32, layers=1, heads=2,
                        kv_heads=2, intermediate_size=64)
    eng = TrainEngine(model, AdamW(learning_rate=1e-3), log_window=100)
    return aot.build(eng, out, export_stablehlo=export_stablehlo,
                     batch_shape=(8, 17))


CONFIGS = {
    'serving-gate': build_serving_gate,
    'decode-gate': build_decode_gate,
    'train-gate': build_train_gate,
}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--config', default='serving-gate',
                    choices=sorted(CONFIGS),
                    help='named bench config to build (default '
                         'serving-gate)')
    ap.add_argument('--out', default='./aot_artifact',
                    help='artifact directory (created if missing)')
    ap.add_argument('--list', action='store_true',
                    help='list configs and exit')
    ap.add_argument('--cpu', action='store_true',
                    help='pin JAX_PLATFORMS=cpu')
    ap.add_argument('--export-stablehlo', action='store_true',
                    help='also serialize each geometry via jax.export')
    args = ap.parse_args(argv)

    if args.list:
        for name, fn in sorted(CONFIGS.items()):
            print(f'{name:14s} {fn.__doc__.splitlines()[0]}')
        return 0

    if args.cpu:
        os.environ['JAX_PLATFORMS'] = 'cpu'

    # backend guard, telemetry_dump-style: a guard rather than an
    # assert (python -O strips asserts), and rc 2 distinguishes "no
    # backend" from a real build failure for the calling automation
    try:
        import jax

        backend = jax.default_backend()
    except Exception as e:  # noqa: BLE001 - any backend-init failure
        print(f'warmup_cli: no usable jax backend ({e}); '
              f'retry with --cpu')
        return 2

    art = CONFIGS[args.config](args.out, args.export_stablehlo)
    m = art.manifest

    print(json.dumps(m, indent=2))
    print(f'# backend      {backend}')
    print(f'# config_hash  {m["config_hash"][:16]}')
    print(f'# geometries   {m["build"]["n_geometries"]} '
          f'({m["build"]["traces"]} traces, '
          f'{m["build"]["seconds"]}s)')
    print(f'# wrote        {os.path.join(art.path, "manifest.json")}')
    print(f'# attach with  engine.warmup(artifact={art.path!r})')
    return 0


if __name__ == '__main__':
    sys.exit(main())
