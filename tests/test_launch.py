"""Launcher process management (ref:
python/paddle/distributed/launch/main.py — spawn, per-rank logs, env
wiring, fail-fast). Exercises the real subprocess machinery on this
host; the jax.distributed cross-process bring-up itself is covered by
the 2-proc CPU collective test (heavy)."""
import os
import subprocess
import sys
import textwrap
import time

import pytest

from paddle_tpu.distributed.launch import launch_local, main

# plain (non-jax) worker scripts must not pay — or hang on — the jax
# cluster auto-init the launcher child path runs by default
_NO_INIT = {'PADDLE_TPU_NO_AUTO_INIT': '1'}


def _write(tmp_path, name, body):
    p = tmp_path / name
    p.write_text(textwrap.dedent(body))
    return str(p)


class TestSharedTPURefusal:
    """Several local ranks on a TPU host would each claim every chip."""

    @pytest.fixture
    def four_chips(self, monkeypatch):
        from jax._src import hardware_utils

        monkeypatch.setattr(
            hardware_utils, 'num_available_tpu_chips_and_device_id',
            lambda: (4, hardware_utils.TpuVersion.v5e))

    @pytest.mark.parametrize('platforms', [None, 'tpu', 'tpu,cpu'])
    def test_refuses_ranks_that_would_share_chips(self, four_chips,
                                                  tmp_path, monkeypatch,
                                                  platforms):
        if platforms is None:
            monkeypatch.delenv('JAX_PLATFORMS', raising=False)
        else:
            monkeypatch.setenv('JAX_PLATFORMS', platforms)
        script = _write(tmp_path, 'w.py', 'print("never runs")')
        with pytest.raises(RuntimeError, match='4 TPU chip'):
            launch_local(script, nprocs=2, env=_NO_INIT)

    def test_cpu_pinned_ranks_and_single_rank_run(self, four_chips,
                                                  tmp_path):
        script = _write(tmp_path, 'w.py', 'print("ok")')
        assert launch_local(script, nprocs=2, timeout_s=120,
                            env={**_NO_INIT, 'JAX_PLATFORMS': 'cpu'}
                            ) == [0, 0]
        assert launch_local(script, nprocs=1, timeout_s=120,
                            env={**_NO_INIT, 'JAX_PLATFORMS': ''}) == [0]


class TestLaunchLocal:
    def test_env_wiring_and_logs(self, tmp_path):
        script = _write(tmp_path, 'worker.py', """
            import os
            print('rank', os.environ['PADDLE_TPU_PROCESS_ID'],
                  'of', os.environ['PADDLE_TPU_NUM_PROCESSES'],
                  'trainer', os.environ['PADDLE_TRAINER_ID'],
                  'coord', os.environ['PADDLE_TPU_COORDINATOR'])
        """)
        log_dir = str(tmp_path / 'logs')
        codes = launch_local(script, nprocs=3, log_dir=log_dir,
                             timeout_s=60, env=_NO_INIT)
        assert codes == [0, 0, 0]
        logs = sorted(os.listdir(log_dir))
        assert logs == ['workerlog.0', 'workerlog.1', 'workerlog.2']
        for r in range(3):
            text = (tmp_path / 'logs' / f'workerlog.{r}').read_text()
            assert f'rank {r} of 3' in text
            assert f'trainer {r}' in text
        # all ranks got the SAME coordinator address
        coords = {(tmp_path / 'logs' / f'workerlog.{r}').read_text()
                  .split('coord ')[1].strip() for r in range(3)}
        assert len(coords) == 1

    def test_fail_fast_terminates_peers(self, tmp_path):
        script = _write(tmp_path, 'worker.py', """
            import os, sys, time
            if os.environ['PADDLE_TPU_PROCESS_ID'] == '1':
                sys.exit(7)      # rank 1 dies immediately
            time.sleep(600)      # peers would hang forever
        """)
        t0 = time.time()
        codes = launch_local(script, nprocs=3, timeout_s=120, env=_NO_INIT)
        assert time.time() - t0 < 60, 'fail-fast did not trigger'
        assert codes[1] == 7
        assert codes[0] != 0 and codes[2] != 0   # terminated, not success

    def test_timeout_kills_stragglers(self, tmp_path):
        script = _write(tmp_path, 'worker.py', 'import time; time.sleep(600)')
        with pytest.raises(TimeoutError):
            launch_local(script, nprocs=2, timeout_s=3, env=_NO_INIT)

    def test_main_cli_multi_process(self, tmp_path, monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_NO_AUTO_INIT', '1')
        script = _write(tmp_path, 'ok.py', """
            import os
            assert os.environ['PADDLE_TRAINERS_NUM'] == '2'
        """)
        assert main(['--nproc_per_node', '2', script]) == 0

    def test_main_cli_propagates_failure(self, tmp_path, monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_NO_AUTO_INIT', '1')
        script = _write(tmp_path, 'bad.py', 'import sys; sys.exit(3)')
        assert main(['--nprocs', '2', script]) == 3

    def test_main_usage_and_unknown_flag(self):
        assert main([]) == 1
        assert main(['--bogus', 'x']) == 2
        assert main(['--nproc_per_node']) == 2      # missing value

    def test_main_cli_eq_form(self, tmp_path, monkeypatch):
        monkeypatch.setenv('PADDLE_TPU_NO_AUTO_INIT', '1')
        script = _write(tmp_path, 'ok.py', """
            import os
            assert os.environ['PADDLE_TRAINERS_NUM'] == '2'
        """)
        assert main(['--nproc_per_node=2', script]) == 0


@pytest.mark.heavy
class TestCrossProcessCollective:
    def test_two_process_cpu_psum(self, tmp_path):
        """The real thing: two ranks wired by the launcher run
        jax.distributed + a cross-process psum (the DCN-layer
        equivalent of the reference's NCCL all-reduce bring-up)."""
        script = _write(tmp_path, 'psum.py', """
            import os
            os.environ['JAX_PLATFORMS'] = 'cpu'
            import jax
            jax.config.update('jax_platforms', 'cpu')
            from paddle_tpu.distributed.launch import init_on_cluster
            info = init_on_cluster()
            assert info['world_size'] == 2, info
            import numpy as np
            import jax.numpy as jnp
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            mesh = Mesh(jax.devices(), ('dp',))
            sharding = NamedSharding(mesh, P('dp'))
            # multi-controller: each process contributes its LOCAL shard
            # of the (2,)-global array
            x = jax.make_array_from_process_local_data(
                sharding, np.asarray([float(info['rank'] + 1)]), (2,))
            y = jax.jit(jnp.sum,
                        out_shardings=NamedSharding(mesh, P()))(x)
            # ranks contribute 1.0 and 2.0 -> 3.0 everywhere (the sum is
            # a cross-process all-reduce under GSPMD)
            assert float(y) == 3.0, y
            print('psum ok rank', info['rank'])
        """)
        import paddle_tpu

        repo_root = os.path.dirname(os.path.dirname(
            os.path.abspath(paddle_tpu.__file__)))
        # APPEND to PYTHONPATH: `python script.py` puts the script dir,
        # not the cwd, on sys.path — and a preset path must survive
        pypath = os.pathsep.join(
            [repo_root] + ([os.environ['PYTHONPATH']]
                           if os.environ.get('PYTHONPATH') else []))
        log_dir = str(tmp_path / 'logs')
        codes = launch_local(script, nprocs=2, log_dir=log_dir,
                             timeout_s=300,
                             env={'XLA_FLAGS': '', 'JAX_PLATFORMS': 'cpu',
                                  'PYTHONPATH': pypath,
                                  # the script must force the cpu
                                  # platform BEFORE any jax backend use,
                                  # so it drives init_on_cluster itself
                                  'PADDLE_TPU_NO_AUTO_INIT': '1'})
        logs = ''.join((tmp_path / 'logs' / f'workerlog.{r}').read_text()
                       for r in range(2))
        assert codes == [0, 0], f'codes={codes}\n{logs}'
        assert 'psum ok rank 0' in logs and 'psum ok rank 1' in logs
