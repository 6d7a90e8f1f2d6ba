"""Multi-host / multi-process bring-up (ref:
python/paddle/distributed/launch/main.py — the
`python -m paddle.distributed.launch` elastic launcher: process
spawning, per-rank logs, env wiring, fail-fast monitoring).

On TPU pods there is no mother process spawning ranks: each host runs
the same script and `jax.distributed.initialize()` wires the cluster
from the TPU metadata. This module is that entry point, PLUS the
reference launcher's local-process mode for CPU/GPU clusters and
multi-process testing:

    # TPU pod host (auto-detected cluster):
    python -m paddle_tpu.distributed.launch train.py --args...

    # spawn N local processes wired through a localhost coordinator
    # (reference: --nproc_per_node), per-rank logs under --log_dir.
    # CPU/GPU hosts only: on a host with TPU chips this is refused,
    # since every rank would claim every chip (one process drives all
    # local chips there):
    python -m paddle_tpu.distributed.launch --nproc_per_node 4 \\
        --log_dir ./logs train.py --args...

Child processes receive the coordinator address / world size / rank in
`PADDLE_TPU_COORDINATOR` / `PADDLE_TPU_NUM_PROCESSES` /
`PADDLE_TPU_PROCESS_ID` (plus the reference-compatible
`PADDLE_TRAINER_ID` / `PADDLE_TRAINERS_NUM`), which
`init_on_cluster()` picks up automatically. If any rank dies, the
launcher terminates the rest (the reference's fail-fast elastic
default) and returns that rank's exit code.
"""
from __future__ import annotations

import os
import runpy
import signal
import socket
import subprocess
import sys
import time


def _env_int(name):
    v = os.environ.get(name)
    return int(v) if v not in (None, '') else None


def init_on_cluster(coordinator_address=None, num_processes=None,
                    process_id=None, local_device_ids=None):
    """ref capability: launch's rank bring-up. On TPU hosts all args are
    auto-detected; explicit args (or the PADDLE_TPU_* env vars a parent
    launcher sets) wire CPU/GPU clusters."""
    import jax

    # env fills in ONLY missing args — explicit args always win
    if coordinator_address is None:
        coordinator_address = os.environ.get('PADDLE_TPU_COORDINATOR')
    if coordinator_address is not None:
        if num_processes is None:
            num_processes = _env_int('PADDLE_TPU_NUM_PROCESSES')
        if process_id is None:
            process_id = _env_int('PADDLE_TPU_PROCESS_ID')
    kwargs = {}
    if coordinator_address is not None:
        kwargs.update(coordinator_address=coordinator_address,
                      num_processes=num_processes, process_id=process_id)
    if local_device_ids is not None:
        kwargs.update(local_device_ids=local_device_ids)
    try:
        jax.distributed.initialize(**kwargs)
    except RuntimeError as e:
        # idempotent bring-up: the launcher auto-init may have run
        # already (children are spawned through the launcher itself)
        if 'already initialized' not in str(e).lower():
            raise
    return {
        'rank': jax.process_index(),
        'world_size': jax.process_count(),
        'local_devices': len(jax.local_devices()),
        'global_devices': jax.device_count(),
    }


def _free_port():
    with socket.socket() as s:
        s.bind(('127.0.0.1', 0))
        return s.getsockname()[1]


def _refuse_shared_tpu(nprocs, child_env):
    """A chip belongs to one process, and every rank spawned here would
    claim ALL of this host's chips: the first wins, the rest fail or hang
    in backend init. One process drives every local chip (`jax.devices()`
    under one mesh), so on a TPU host several local ranks are refused
    unless their environment pins them to another platform."""
    platforms = child_env.get('JAX_PLATFORMS', '')
    if nprocs < 2 or (platforms and 'tpu' not in platforms.split(',')):
        return
    # the PCI scan jax itself uses to decide whether this is a TPU host;
    # it initialises no backend
    from jax._src import hardware_utils

    chips, _ = hardware_utils.num_available_tpu_chips_and_device_id()
    if chips:
        raise RuntimeError(
            f'launch: refusing to spawn {nprocs} local processes on a host '
            f'with {chips} TPU chip(s): each would claim every chip and '
            f'all but one would fail or hang. Run ONE process (it sees '
            f'all local chips), or set JAX_PLATFORMS=cpu for CPU ranks.')


def launch_local(script, script_args=(), nprocs=1, log_dir=None, env=None,
                 poll_s=0.2, timeout_s=None, with_info=False):
    """Spawn `nprocs` local ranks of `script` wired through a localhost
    coordinator (ref: launch/main.py local mode + its per-rank
    workerlog.N files and fail-fast watch loop).

    Returns the list of per-rank exit codes. If any rank exits non-zero,
    the remaining ranks are terminated (SIGTERM, then SIGKILL after a
    grace period) — surviving stragglers of a dead collective would hang
    forever on the next barrier.

    with_info=True returns (codes, launcher_terminated) where
    launcher_terminated is the set of rank indices THIS launcher tore
    down (fail-fast or timeout) — their exit codes (-SIGTERM, or
    -SIGKILL for a straggler that ignored SIGTERM) are collateral, not
    the root failure, and must not masquerade as it.
    """
    _refuse_shared_tpu(nprocs, {**os.environ, **(env or {})})
    port = _free_port()
    procs = []
    logs = []
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
    # children must be able to import this package regardless of cwd
    pkg_parent = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        for rank in range(nprocs):
            child_env = dict(os.environ)
            child_env.update(env or {})
            child_env['PYTHONPATH'] = os.pathsep.join(
                [pkg_parent] + ([child_env['PYTHONPATH']]
                                if child_env.get('PYTHONPATH') else []))
            child_env.update({
                'PADDLE_TPU_COORDINATOR': f'127.0.0.1:{port}',
                'PADDLE_TPU_NUM_PROCESSES': str(nprocs),
                'PADDLE_TPU_PROCESS_ID': str(rank),
                # reference-compatible names (fleet scripts read these)
                'PADDLE_TRAINER_ID': str(rank),
                'PADDLE_TRAINERS_NUM': str(nprocs),
            })
            if log_dir:
                f = open(os.path.join(log_dir, f'workerlog.{rank}'), 'wb')
                logs.append(f)
                out = err = f
            else:
                out = err = None
            # spawn THROUGH the launcher's single-process path so each
            # rank auto-runs init_on_cluster (picking up the env above)
            # before the script — same contract as the TPU-pod path
            procs.append(subprocess.Popen(
                [sys.executable, '-m', 'paddle_tpu.distributed.launch',
                 script, *script_args], env=child_env,
                stdout=out, stderr=err))
    except BaseException:
        # a failed spawn (ENOMEM, bad interpreter) must not strand the
        # ranks already running on a barrier that can never complete
        for pr in procs:
            pr.terminate()
        for f in logs:
            f.close()
        raise

    codes = [None] * nprocs
    launcher_terminated = set()
    t0 = time.time()
    try:
        while any(c is None for c in codes):
            for i, p in enumerate(procs):
                if codes[i] is None:
                    codes[i] = p.poll()
            failed = [i for i, c in enumerate(codes) if c not in (None, 0)]
            timed_out = timeout_s is not None and time.time() - t0 > timeout_s
            if failed or timed_out:
                for i, p in enumerate(procs):
                    if codes[i] is None:
                        launcher_terminated.add(i)
                        p.terminate()
                grace = time.time() + 10
                for i, p in enumerate(procs):
                    if codes[i] is None:
                        try:
                            codes[i] = p.wait(max(0.1, grace - time.time()))
                        except subprocess.TimeoutExpired:
                            p.send_signal(signal.SIGKILL)
                            codes[i] = p.wait()
                if timed_out and not failed:
                    raise TimeoutError(
                        f'launch_local: ranks still alive after '
                        f'{timeout_s}s; terminated (codes={codes})')
                break
            time.sleep(poll_s)
    finally:
        for f in logs:
            f.close()
    if with_info:
        return codes, launcher_terminated
    return codes


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    nprocs = 1
    log_dir = None
    # reference-style flags before the script path
    def usage():
        print('usage: python -m paddle_tpu.distributed.launch '
              '[--nproc_per_node N] [--log_dir DIR] SCRIPT [args...]',
              file=sys.stderr)

    while argv and argv[0].startswith('--'):
        flag = argv.pop(0)
        name, eq, inline = flag.lstrip('-').partition('=')

        def value():
            if eq:
                return inline
            if not argv:
                raise IndexError
            return argv.pop(0)

        try:
            if name in ('nproc_per_node', 'nprocs'):
                nprocs = int(value())
            elif name == 'log_dir':
                log_dir = value()
            elif name == 'help':
                print(__doc__)
                return 0
            else:
                print(f'launch: unknown flag {flag}', file=sys.stderr)
                return 2
        except (IndexError, ValueError):
            print(f'launch: flag {flag} needs a value', file=sys.stderr)
            usage()
            return 2
    if not argv:
        print('usage: python -m paddle_tpu.distributed.launch '
              '[--nproc_per_node N] [--log_dir DIR] SCRIPT [args...]')
        return 1
    script, *rest = argv
    if nprocs > 1:
        codes, terminated = launch_local(script, rest, nprocs=nprocs,
                                         log_dir=log_dir, with_info=True)
        if any(c != 0 for c in codes):
            print(f'launch: ranks failed with codes {codes}',
                  file=sys.stderr)
            return _pick_exit_code(codes, terminated)
        return 0
    # single process: initialize the cluster unless the script opts out
    if os.environ.get('PADDLE_TPU_NO_AUTO_INIT') != '1':
        try:
            init_on_cluster()
        except Exception as e:
            if os.environ.get('PADDLE_TPU_COORDINATOR'):
                # a child rank of an explicit cluster: running the
                # script standalone as rank 0 would silently compute on
                # 1/N of the data (and deadlock its peers) — fail loudly
                # so the launcher's fail-fast tears the job down
                print(f'launch: cluster init failed for rank '
                      f'{os.environ.get("PADDLE_TPU_PROCESS_ID", "?")} '
                      f'({e})', file=sys.stderr)
                return 1
            # single-host dev boxes: no coordinator requested, plain run
            print(f'launch: single-process mode ({e})', file=sys.stderr)
    sys.argv = [script] + rest
    runpy.run_path(script, run_name='__main__')
    return 0


def _pick_exit_code(codes, launcher_terminated):
    """The exit code the launcher should surface: prefer a rank that
    exited ON ITS OWN with a non-zero code (the root failure) over
    ranks the launcher itself tore down — a straggler that ignored
    SIGTERM gets SIGKILLed (-9), and that collateral -9 must not
    masquerade as an OOM kill. Falls back to any non-zero code (e.g.
    every rank was terminated by a timeout)."""
    self_exited = [c for i, c in enumerate(codes)
                   if c not in (None, 0) and i not in launcher_terminated]
    if self_exited:
        return self_exited[0]
    bad = [c for c in codes if c not in (None, 0)]
    return bad[0] if bad else 1


if __name__ == '__main__':
    raise SystemExit(main())
