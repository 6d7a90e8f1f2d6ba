"""Run the replica-fleet autoscaling simulation and report its gates.

Drives the same workload `gate_fleet_sim` (bench.py) pins, standalone
and tunable: a tiny-llama fleet behind the load-aware Router, fed a
seeded Poisson arrival stream on the fleet's SIMULATED deployment
clock (replicas are parallel hosts — sim time advances by the max
per-replica wall per round, see docs/serving.md#fleet):

    steady phase     n=1, low arrival rate;
    traffic spike    arrival rate x ~6, absorbed by `scale_to(n)` —
                     every new replica warm-attaches to ONE shared AOT
                     artifact, so elasticity is zero-compile;
    rolling restart  one replica replaced mid-spike (replacement spun
                     FIRST — capacity never dips);
    replica kill     one replica's step() killed via the
                     `replica_step` fault seam — its requests
                     resurrect on a standby from the auto-dumped
                     postmortem bundle;
    drain            run the flood dry.

Printed report: per-replica route shares, sim-clock TTFT percentiles
(p50/p95/p99) for the steady and spike phases, the 1-vs-n sim
throughput ratio, and the lifecycle counters (routed / migrations /
resurrections / restarts). Every stream is checked bit-equal against
a plain single engine.

Exit code contract (calling automation keys off it):
    0 — simulation ran and every fleet gate held (parity, zero
        retraces/compile-misses after the first replica warmed, zero
        leaked pages, throughput ratio >= 2 at n=4, spike p99 TTFT
        within budget, migrations > 0, one resurrection);
    1 — simulation ran but a gate failed (the report says which);
    2 — no usable jax backend (nothing ran; retry with --cpu).

Importable anywhere (pytest collection, tracelint) without touching a
backend — only main() initialises jax, same rc-2 guard discipline as
tools/telemetry_dump.py.

    python tools/fleet_sim.py --cpu [--replicas 4] [--requests 48]
"""
import argparse
import json
import os
import sys
import tempfile

# `python tools/fleet_sim.py` puts tools/ (not the repo root) on
# sys.path and paddle_tpu is not pip-installed on the dev boxes
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

SPIKE_TTFT_FACTOR = 4.0    # bench._FLEET_SPIKE_TTFT_FACTOR


def run_sim(n_replicas=4, n_requests=48, seed=0, work=None,
            spike_factor_budget=SPIKE_TTFT_FACTOR):
    """Run the full autoscaling simulation; returns the report dict
    (gates + counters + percentiles). jax must already be up."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu import aot
    from paddle_tpu.inference.engine import total_traces
    from paddle_tpu.inference.fleet import Fleet
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny
    from paddle_tpu.observability import REGISTRY
    from paddle_tpu.testing.faults import FaultInjector

    pt.seed(0)
    model = LlamaForCausalLM(llama_tiny(vocab_size=96, hidden_size=64,
                                        layers=2))
    kw = dict(max_slots=4, num_blocks=64, block_size=8,
              max_context_len=64, max_new_tokens=12, decode_window=4)

    def factory(**fkw):
        return ServingEngine(model, **kw, **fkw)

    work = work or tempfile.mkdtemp(prefix='paddle_tpu_fleet_sim_')
    art = os.path.join(work, 'artifact')
    builder = ServingEngine(model, **kw)
    aot.build(builder, art)
    builder.close()

    rng = np.random.default_rng(seed)
    n_cal = max(8, n_requests // 4)
    n_scale = n_cal * n_replicas
    n_steady = max(8, n_requests // 4)
    n_spike = n_requests - n_steady if n_requests > n_steady else 8
    total = n_cal + n_scale + n_steady + n_spike
    prompts = [rng.integers(3, 96, (int(rng.integers(4, 12)),)).astype(
        np.int32) for _ in range(total)]
    mnts = [int(rng.integers(6, 13)) for _ in range(total)]

    ref = ServingEngine(model, **kw)
    expect = []
    for p, m in zip(prompts, mnts):
        r = ref.submit(p, max_new_tokens=m)
        while ref.in_flight() or len(ref.queue):
            ref.step()
        expect.append(np.asarray(ref.result(r)))
    ref.close()

    fleet = Fleet(factory, artifact=art,
                  postmortem_dir=os.path.join(work, 'pm'))
    fleet.scale_to(1)
    mark = total_traces()
    cm = REGISTRY.get('compile.cache_misses')
    cm0 = cm.value if cm is not None else 0
    state = {'cursor': 0, 'parity': True}

    def run_batch(n):
        t0, rids = fleet.sim_time_s, []
        lo = state['cursor']
        for i in range(lo, lo + n):
            rids.append(fleet.submit(prompts[i], max_new_tokens=mnts[i]))
        fleet.run(max_steps=4000)
        toks = 0
        for i, r in zip(range(lo, lo + n), rids):
            out = np.asarray(fleet.result(r))
            toks += len(out) - len(prompts[i])
            state['parity'] &= bool(np.array_equal(out, expect[i]))
        state['cursor'] += n
        return toks, fleet.sim_time_s - t0

    toks1, dt1 = run_batch(n_cal)
    tok_s_single = toks1 / max(dt1, 1e-9)
    fleet.scale_to(n_replicas)
    toksn, dtn = run_batch(n_scale)
    tok_s_fleet = toksn / max(dtn, 1e-9)
    scale_ratio = tok_s_fleet / max(tok_s_single, 1e-9)

    # the Poisson flood: steady at n=1, spike + scale-up under load,
    # one rolling restart and one replica kill mid-spike, then drain
    fleet.scale_to(1)
    steady_draw = rng.poisson(0.45, 4000).tolist()
    spike_draw = rng.poisson(3.0, 4000).tolist()
    steady_rids, spike_rids = [], []
    flood = {'submitted': 0}

    def arrive(n, bucket, limit):
        for _ in range(n):
            if flood['submitted'] >= limit:
                return
            i = state['cursor']
            bucket.append((i, fleet.submit(prompts[i],
                                           max_new_tokens=mnts[i])))
            state['cursor'] += 1
            flood['submitted'] += 1

    rnd = 0
    while flood['submitted'] < n_steady and rnd < 4000:
        arrive(steady_draw[rnd], steady_rids, n_steady)
        fleet.step()
        rnd += 1
    fleet.scale_to(n_replicas)         # scale up UNDER the steady tail
    restarted = killed = False
    rnd = 0
    limit = n_steady + n_spike
    while (flood['submitted'] < limit or fleet.in_flight()
           or fleet.queue_depth()) and rnd < 4000:
        arrive(spike_draw[rnd], spike_rids, limit)
        if not restarted and flood['submitted'] >= n_steady + 4:
            fleet.restart(next(iter(fleet.replicas)))
            restarted = True
        if not killed and flood['submitted'] >= n_steady + n_spike // 2:
            victim = next(iter(fleet.replicas))
            with FaultInjector(seed=0) as inj:
                inj.script('replica_step',
                           when=lambda c: c['replica'] == victim)
                fleet.step()
            killed = True
        else:
            fleet.step()
        rnd += 1

    for i, r in steady_rids + spike_rids:
        state['parity'] &= bool(np.array_equal(
            np.asarray(fleet.result(r)), expect[i]))

    def pctiles(pairs):
        vals = sorted(fleet._ttft[r] for _, r in pairs
                      if r in fleet._ttft)
        if not vals:
            return {f'p{p}': None for p in (50, 95, 99)}
        out = {}
        for p in (50, 95, 99):
            k = min(len(vals) - 1,
                    max(0, int(round(p / 100 * len(vals) + 0.5)) - 1))
            out[f'p{p}'] = round(vals[k] * 1e3, 3)
        return out

    steady_ttft = pctiles(steady_rids)
    spike_ttft = pctiles(spike_rids)
    spike_fac = (spike_ttft['p99'] / max(steady_ttft['p99'], 1e-9)
                 if steady_ttft['p99'] and spike_ttft['p99'] else None)
    cm = REGISTRY.get('compile.cache_misses')
    report = {
        'replicas': n_replicas,
        'routed': fleet.counts['routed'],
        'route_shares': {k: round(v, 4)
                         for k, v in fleet.route_shares().items()},
        'ttft_sim_ms_steady': steady_ttft,
        'ttft_sim_ms_spike': spike_ttft,
        'tok_s_single_sim': round(tok_s_single, 2),
        'tok_s_fleet_sim': round(tok_s_fleet, 2),
        'migrations': fleet.counts['migrations'],
        'resurrections': fleet.counts['resurrections'],
        'restarts': fleet.counts['restarts'],
        'sim_time_s': round(fleet.sim_time_s, 4),
        'rounds': fleet._round,
        'gates': {
            'parity': bool(state['parity']),
            'zero_retraces': total_traces() - mark == 0,
            'zero_cache_misses':
                (cm.value if cm is not None else 0) - cm0 == 0,
            'zero_leaked_pages': sum(
                e.allocator.in_use()
                for e in fleet.replicas.values()) == 0,
            'scale_ratio_ge_2': bool(scale_ratio >= 2.0),
            'scale_ratio': round(scale_ratio, 4),
            'spike_ttft_within_budget': bool(
                spike_fac is not None
                and spike_fac <= spike_factor_budget),
            'spike_ttft_factor': (round(spike_fac, 4)
                                  if spike_fac is not None else None),
            'migrated': fleet.counts['migrations'] > 0,
            'resurrected': fleet.counts['resurrections'] == 1,
        },
    }
    fleet.close()
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--replicas', type=int, default=4,
                    help='fleet size at the spike (default 4)')
    ap.add_argument('--requests', type=int, default=48,
                    help='flood size: steady + spike arrivals '
                         '(default 48)')
    ap.add_argument('--seed', type=int, default=0,
                    help='workload + arrival-stream seed (default 0)')
    ap.add_argument('--json', action='store_true',
                    help='print the raw report dict as JSON only')
    ap.add_argument('--cpu', action='store_true',
                    help='pin JAX_PLATFORMS=cpu')
    args = ap.parse_args(argv)

    if args.cpu:
        os.environ['JAX_PLATFORMS'] = 'cpu'
    try:
        import jax

        jax.default_backend()
    except Exception as e:  # noqa: BLE001 - any backend-init failure
        print(f'fleet_sim: no usable jax backend ({e}); '
              f'retry with --cpu')
        return 2

    report = run_sim(n_replicas=args.replicas, n_requests=args.requests,
                     seed=args.seed)
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        g = report['gates']
        print(f"fleet_sim: {report['replicas']} replicas, "
              f"{report['routed']} routed over {report['rounds']} "
              f"rounds ({report['sim_time_s']}s sim)")
        print(f"  sim tok/s: {report['tok_s_single_sim']} at 1 -> "
              f"{report['tok_s_fleet_sim']} at {report['replicas']} "
              f"(ratio {g['scale_ratio']})")
        print('  route shares:')
        for name, share in sorted(report['route_shares'].items()):
            print(f'    {name:<12} {share:6.1%}')
        for phase in ('steady', 'spike'):
            t = report[f'ttft_sim_ms_{phase}']
            print(f"  TTFT sim ms ({phase:>6}): p50={t['p50']} "
                  f"p95={t['p95']} p99={t['p99']}")
        print(f"  spike p99 factor: {g['spike_ttft_factor']} "
              f"(budget {SPIKE_TTFT_FACTOR})")
        print(f"  lifecycle: {report['migrations']} migration(s), "
              f"{report['resurrections']} resurrection(s), "
              f"{report['restarts']} restart(s)")
        for k, v in g.items():
            if isinstance(v, bool):
                print(f"  gate {k:<24} {'PASS' if v else 'FAIL'}")
    failed = [k for k, v in report['gates'].items()
              if isinstance(v, bool) and not v]
    if failed:
        print(f'fleet_sim: GATE FAILURE: {", ".join(failed)}')
        return 1
    return 0


if __name__ == '__main__':
    sys.exit(main())
