"""Run the bench serving workload and dump its telemetry artifacts.

Drives the same tiny continuous-batching workload the bench serving
gate uses (Poisson-ish mixed-length requests through a ServingEngine)
with telemetry on, then writes two artifacts into --out:

    telemetry.json   — the full MetricsRegistry snapshot (counters,
                       gauges, histogram percentiles: ttft/itl/queue
                       wait, pool bytes, compile events, ...)
    host_trace.json  — the host-span tracer's Chrome trace_event array
                       (scheduler steps, admissions, preemptions,
                       compile spans) — open in Perfetto or
                       chrome://tracing, optionally alongside a
                       jax.profiler device trace (docs/observability.md
                       shows the overlay recipe)
    telemetry.prom   — Prometheus text exposition of the same registry
                       (what the /metrics ops endpoint serves)
    journal.jsonl    — the flight-recorder event journal (scheduler
                       decisions, allocator ops, compile events, one
                       line per event; `trail(rid)` material)
    timeseries.json  — the windowed-timeseries ring (per-window counter
                       deltas/rates, gauge values, rolling histogram
                       percentiles — the live view /statusz serves)
    postmortem/      — a full postmortem bundle of the run (what the
                       crash path would auto-dump; validate/pretty-
                       print with tools/postmortem.py)

The run also measures the engine's per-geometry dispatch costs
(observability.costs), prints the resulting live cost gauges
(serve.mfu_est / model_flops_per_s / roofline_intensity), and runs
the workload under the default SLO watchdog: the verdict and every
rule's state are printed, and the engine's ops endpoint is scraped
once (/healthz + /metrics) to prove the served verdict matches the
in-process one.

Exit code contract (calling automation keys off it):
    0 — artifacts written, watchdog verdict healthy;
    1 — artifacts written, but an SLO rule is in ACTIVE breach at the
        end of the run (the printed rule states say which);
    2 — no usable jax backend (nothing ran; retry with --cpu).

Importable anywhere (pytest collection, tracelint) without touching a
backend — only main() initialises jax, and the same rc-2 guard
discipline as tools/mosaic_check.py applies: when NO jax backend can
be initialised at all, exit 2 with a message instead of a traceback.
The workload itself is CPU-runnable, so off-TPU boxes get real
artifacts (pass --cpu to pin there explicitly, so the run never claims
a chip).

    python tools/telemetry_dump.py --out /tmp/telemetry [--cpu]
"""
import argparse
import json
import os
import sys

# `python tools/telemetry_dump.py` puts tools/ (not the repo root) on
# sys.path and paddle_tpu is not pip-installed on the dev boxes — make
# the repo importable no matter where the script is launched from
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)


def run_workload(n_requests=16, decode_window=8, seed=0, tp=1):
    """The gate-shaped serving workload: mixed budgets, every 4th
    request long, priority-0 FIFO arrivals — now with the prefix
    cache and chunked prefill ON and every second request sharing a
    16-token system prefix, so the dump exercises (and the artifacts
    carry) the `serve.prefix_*` / `serve.chunk*` / `pool.prefix_*`
    series alongside the classic lifecycle metrics. Returns the
    engine (its run has fed the process-global registry and
    tracer)."""
    import numpy as np

    import paddle_tpu as pt
    from paddle_tpu.inference.serving import ServingEngine
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    pt.seed(0)
    model = LlamaForCausalLM(llama_tiny(vocab_size=96, hidden_size=64,
                                        layers=2))
    rng = np.random.default_rng(seed)
    sys_prefix = rng.integers(3, 96, (16,))
    prompts = [np.concatenate([sys_prefix, rng.integers(3, 96, (5,))])
               if i % 2 else rng.integers(3, 96, (6,))
               for i in range(n_requests)]
    mnts = [16 if i % 4 == 0 else 6 for i in range(n_requests)]
    # tp > 1 exercises the TP-sharded path (page pools head-sharded
    # over the serving mesh, fused dispatches through the megatron
    # layout) — the dumped telemetry/journal then carries the sharded
    # engine's gauges; kv_heads=2 in the tiny model, so tp=2 is the
    # largest degree that still head-shards
    # watchdog=True arms the default serving SLO ruleset over a
    # private windowed ring (50ms windows so even this tiny workload
    # commits several) — the dump's verdict/ruleset printout and the
    # timeseries.json artifact both come from it
    # draft=model is self-speculation (accept rate 1.0 for greedy
    # rows): the dump exercises the speculative window path and the
    # serve.spec_* counters without needing a second checkpoint
    srv = ServingEngine(model, max_slots=4, block_size=8,
                        max_context_len=48, max_new_tokens=16,
                        decode_window=decode_window,
                        prefix_cache=True, prefill_chunk=16,
                        draft=model, num_draft_tokens=3,
                        watchdog=True, ts_interval_s=0.05,
                        **({'tp': int(tp)} if tp and int(tp) > 1 else {}))
    rids = [srv.submit(p, m) for p, m in zip(prompts, mnts)]
    srv.run()
    for r in rids:
        srv.result(r)
    return srv


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument('--out', default='./telemetry_out',
                    help='output directory (created if missing)')
    ap.add_argument('--requests', type=int, default=16,
                    help='workload size (default 16)')
    ap.add_argument('--cpu', action='store_true',
                    help='pin JAX_PLATFORMS=cpu')
    ap.add_argument('--tp', type=int, default=1,
                    help='tensor-parallel degree for the ServingEngine '
                         '(>1 runs the TP-sharded serving path; with '
                         '--cpu the virtual-device flag is forced '
                         'automatically)')
    args = ap.parse_args(argv)

    if args.cpu:
        os.environ['JAX_PLATFORMS'] = 'cpu'
    if args.tp and args.tp > 1:
        # must land BEFORE jax initialises a backend, like the
        # shardlint recipe (serving_mesh would force it too, but only
        # if nothing woke the backend first — do it here, determinate)
        from paddle_tpu.distributed.mesh import force_virtual_devices

        force_virtual_devices(args.tp)

    # backend guard, mosaic_check-style: a guard rather than an assert
    # (python -O strips asserts), and rc 2 distinguishes "no backend"
    # from a real workload failure for the calling automation
    try:
        import jax

        backend = jax.default_backend()
    except Exception as e:  # noqa: BLE001 - any backend-init failure
        print(f'telemetry_dump: no usable jax backend ({e}); '
              f'retry with --cpu')
        return 2

    from paddle_tpu import observability as obs
    from paddle_tpu.observability import costs as obs_costs
    from paddle_tpu.observability import journal as obs_journal
    from paddle_tpu.observability import postmortem as obs_pm

    obs.set_enabled(True)
    obs.REGISTRY.reset()
    obs.TRACER.clear()
    obs_journal.JOURNAL.clear()

    srv = run_workload(n_requests=args.requests, tp=args.tp)

    # cost observatory: measure this engine's per-geometry static
    # flops/bytes (one lower+compile each — off the serving path, so
    # the retraces it counts are analysis, not regressions), then one
    # more tiny pass so the window commits stamp the live mfu/roofline
    # gauges from them
    import numpy as np

    cost_report = obs_costs.measure_dispatch_costs(srv)
    # budgets spanning several windows: a first-time-compiled dispatch
    # is excluded from the mfu gauges (its wall is compile, not model
    # execution — the ITL rule), so the pass must outlive the warmup
    srv.serve([np.arange(3, 9) for _ in range(6)], 16)

    os.makedirs(args.out, exist_ok=True)
    tpath = os.path.join(args.out, 'telemetry.json')
    with open(tpath, 'w') as f:
        json.dump({'backend': backend,
                   'engine_stats': srv.stats(),
                   'dispatch_costs': {str(k): v for k, v in
                                      srv._dispatch_costs.items()},
                   'metrics': obs.REGISTRY.snapshot()}, f, indent=2,
                  default=str)
    hpath = obs.TRACER.export(os.path.join(args.out, 'host_trace.json'))
    ppath = os.path.join(args.out, 'telemetry.prom')
    with open(ppath, 'w') as f:
        f.write(obs.REGISTRY.to_prometheus())
    jpath = obs_journal.save(os.path.join(args.out, 'journal.jsonl'))
    # close the tail window so the run's last partial interval is in
    # the ring — and run the watchdog over it, so a breach that
    # manifests only in the final <interval slice still flips the
    # verdict (the rc-1 contract below) — then dump the windowed view
    w = srv._ts.commit()
    if w is not None:
        srv._watchdog.evaluate(w, srv._ts)
    spath = os.path.join(args.out, 'timeseries.json')
    with open(spath, 'w') as f:
        f.write(srv._ts.to_json(indent=2))
    bdir = os.path.join(args.out, 'postmortem')
    obs_pm.dump_bundle(bdir, engine=srv,
                       reason='telemetry_dump reference bundle')

    snap = obs.REGISTRY.snapshot()
    R = obs.REGISTRY

    print(f'backend          {backend}')
    if srv.tp > 1:
        k0 = srv._pages[0].kp
        print(f'tp degree        {srv.tp} (pool sharding '
              f'{k0.sharding.spec}, {len(k0.addressable_shards)} '
              f'shard(s))')
    print(f'ttft_ms p50/p99  {R.percentile("serve.ttft_ms", 50)} / '
          f'{R.percentile("serve.ttft_ms", 99)}')
    print(f'itl_ms p99       {R.percentile("serve.itl_ms", 99)}')
    print(f'queue_wait p99   {R.percentile("serve.queue_wait_ms", 99)}')
    print(f'tokens           '
          f'{snap.get("serve.tokens", {}).get("value")}')
    pfx = srv.stats()['prefix']
    print(f'prefix hits      {pfx["hits"]} ({pfx["misses"]} miss, '
          f'{pfx["hit_tokens"]} tokens reused)')
    print(f'prefix pool      {pfx["cached_pages"]} cached / '
          f'{pfx["shared_pages"]} shared / {pfx["cow_pages"]} cow page(s)')
    print(f'chunk steps      {pfx["chunk_steps"]} '
          f'({pfx["chunked_admissions"]} chunked admission(s))')
    spc = srv.stats()['spec']
    ar = spc['accept_rate']
    print(f'spec windows     {spc["windows"]} '
          f'({spc["accepted"]}/{spc["proposed"]} draft tokens accepted'
          f'{"" if ar is None else f", rate {ar:.3f}"})')
    print(f'spec_accept_rate '
          f'{snap.get("serve.spec_accept_rate", {}).get("value")}')
    print(f'compile events   '
          f'{snap.get("compile.traces", {}).get("value")}')
    print(f'host spans       {len(obs.TRACER)}')
    # the cost observatory gauges (mfu_est needs a known peak: set
    # PADDLE_TPU_PEAK_FLOPS explicitly on CPU boxes; TPU kinds resolve
    # from the built-in table)
    n_costed = sum(1 for v in cost_report.values()
                   if isinstance(v, dict))
    print(f'geometry costs   {n_costed}/{len(cost_report)} measured')
    print(f'mfu_est          '
          f'{snap.get("serve.mfu_est", {}).get("value")}')
    print(f'model flops/s    '
          f'{snap.get("serve.model_flops_per_s", {}).get("value")}')
    print(f'roofline f/B     '
          f'{snap.get("serve.roofline_intensity", {}).get("value")}')
    print(f'journal events   {len(obs_journal.JOURNAL)} '
          f'({len(obs_journal.JOURNAL.trails())} trails, '
          f'{obs_journal.JOURNAL.dropped} dropped)')
    print(f'windows          {len(srv._ts)} committed '
          f'(interval {srv._ts.interval_s}s)')
    print(f'serve.tok_s      '
          f'{snap.get("serve.tok_s", {}).get("value")}')

    # the statelint coverage census (pure-AST: rules=[] skips the live
    # wire build) — how much engine state exists and how it is
    # classified; `statelint` proves the claims, this line surfaces
    # the coverage shape next to the telemetry it protects
    from paddle_tpu.analysis.state import DECLS, lint_and_report
    _, _, st_census = lint_and_report(DECLS, rules=[], root=_ROOT,
                                      schemas={})
    classes = [c for c in st_census['classes'].values() if c]
    print(f'statelint census {len(classes)} classes, '
          f'{sum(c["attrs"] for c in classes)} mutable attrs '
          f'({sum(c["persisted"] for c in classes)} persisted / '
          f'{sum(c["derived-rebuilt"] for c in classes)} rebuilt / '
          f'{sum(c["device-rederived"] for c in classes)} device / '
          f'{sum(c["ephemeral"] for c in classes)} ephemeral, '
          f'{sum(c["unclassified"] for c in classes)} unclassified)')

    # the SLO watchdog verdict + per-rule states, and one scrape of
    # the live ops endpoint to prove the SERVED verdict matches
    verdict = srv._watchdog.verdict()
    print(f'watchdog         '
          f'{"HEALTHY" if verdict["healthy"] else "BREACH"} '
          f'({verdict["windows_evaluated"]} windows evaluated, '
          f'{verdict["breaches_total"]} breach(es), '
          f'{verdict["recoveries_total"]} recovery(ies))')
    for name, st in sorted(srv._watchdog.state().items()):
        print(f'  rule {name:<18} {st["state"]:<7} '
              f'last={st["last"]} value={st["last_value"]} '
              f'({st["expr"]} {st["op"]} {st["threshold"]})')
    try:
        import urllib.request

        from paddle_tpu.observability.httpd import start_ops_server

        ops = start_ops_server(srv)
        try:
            code = urllib.request.urlopen(
                ops.url('/healthz'), timeout=5).status
        except urllib.error.HTTPError as e:  # 503 on breach IS the answer
            code = e.code
        prom = urllib.request.urlopen(
            ops.url('/metrics'), timeout=5).read().decode()
        print(f'ops endpoint     /healthz {code}, /metrics '
              f'{len(prom.splitlines())} lines (port {ops.port})')
        ops.close()
    except Exception as e:  # noqa: BLE001 - the scrape is a demo, not a gate
        print(f'ops endpoint     scrape failed: {e!r}')

    print(f'wrote {tpath}')
    print(f'wrote {hpath}')
    print(f'wrote {ppath}')
    print(f'wrote {jpath}')
    print(f'wrote {spath}')
    print(f'wrote {bdir}/ (postmortem bundle)')
    # rc contract: 1 = artifacts written but an SLO rule is in active
    # breach (0 healthy, 2 no backend — see module docstring)
    return 0 if verdict['healthy'] else 1


if __name__ == '__main__':
    sys.exit(main())
