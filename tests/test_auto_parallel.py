"""Auto-parallel semantic API + process-group compat on the virtual
8-device CPU mesh (ref: python/paddle/distributed/auto_parallel/api.py,
communication/*)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as pt
import paddle_tpu.distributed as dist


@pytest.fixture()
def pmesh():
    n = len(jax.devices())
    return dist.ProcessMesh(np.arange(n).reshape(2, n // 2), ['x', 'y'])


def test_process_mesh_basics(pmesh):
    assert pmesh.shape == [2, len(jax.devices()) // 2]
    assert pmesh.dim_names == ['x', 'y']
    assert pmesh.get_dim_size('x') == 2
    assert pmesh.process_ids == list(range(len(jax.devices())))
    assert pmesh == dist.ProcessMesh(
        np.arange(len(jax.devices())).reshape(2, -1), ['x', 'y'])


def test_placements_spec_roundtrip(pmesh):
    placements = [dist.Shard(0), dist.Replicate()]
    spec = dist.placements_to_spec(placements, pmesh, 2)
    assert spec == P('x')
    back = dist.spec_to_placements(spec, pmesh, 2)
    assert back[0] == dist.Shard(0) and back[1].is_replicated()
    # both mesh dims shard the same tensor dim
    spec2 = dist.placements_to_spec([dist.Shard(1), dist.Shard(1)], pmesh, 2)
    assert spec2 == P(None, ('x', 'y'))


def test_shard_tensor_and_reshard(pmesh):
    x = np.arange(32, dtype=np.float32).reshape(8, 4)
    d = dist.shard_tensor(x, pmesh, [dist.Shard(0), dist.Replicate()])
    assert d.sharding.spec == P('x')
    np.testing.assert_array_equal(np.asarray(d), x)
    r = dist.reshard(d, pmesh, [dist.Replicate(), dist.Shard(1)])
    assert r.sharding.spec == P(None, 'y')
    np.testing.assert_array_equal(np.asarray(r), x)
    u = dist.unshard_dtensor(r)
    assert u.sharding.spec == P()
    f = dist.dtensor_from_fn(jnp.ones, pmesh,
                             [dist.Shard(0), dist.Replicate()], (8, 4))
    assert f.sharding.spec == P('x')


def test_shard_layer_and_optimizer(pmesh):
    layer = pt.nn.Linear(8, 8)
    placed = dist.shard_layer(layer, pmesh)
    out = placed(jnp.ones((4, 8)))
    assert out.shape == (4, 8)

    opt = pt.optimizer.AdamW(learning_rate=1e-3)
    opt = dist.shard_optimizer(opt, dist.ShardingStage1('x', pmesh))
    state = opt.init(placed)
    m_leaves = jax.tree.leaves(state['slots'])
    sharded = [l for l in m_leaves
               if l.ndim and l.shape[0] % 2 == 0
               and l.sharding.spec == P('x')]
    assert sharded, 'optimizer slots should be sharded over x'
    assert dist.shard_scaler(opt) is opt


def test_dist_model_to_static(pmesh):
    model = pt.nn.Linear(4, 2)
    opt = pt.optimizer.SGD(learning_rate=0.1)
    loss_fn = lambda out, y: jnp.mean((out - y) ** 2)
    dm = dist.to_static(model, None, loss_fn, opt)
    x = jnp.ones((8, 4))
    y = jnp.zeros((8, 2))
    l0 = float(dm(x, y))
    for _ in range(5):
        l1 = float(dm(x, y))
    assert l1 < l0
    dm.eval()
    le = float(dm(x, y))
    assert np.isfinite(le)
    assert isinstance(dm.state_dict(), dict)


def test_group_management():
    g = dist.new_group(axis='dp')
    assert dist.get_group(g.id) is g
    assert g.nranks >= 1
    assert dist.is_initialized() in (True, False)
    assert dist.is_available()
    assert dist.get_backend() == 'XLA'
    env = dist.ParallelEnv()
    assert env.world_size >= 1 and env.device_type in ('cpu', 'tpu')
    assert dist.ParallelMode.TENSOR_PARALLEL == 1
    dist.destroy_process_group(g)
    assert dist.get_group(g.id) is None


def test_object_collectives_and_wait():
    objs = []
    dist.all_gather_object(objs, {'a': 1})
    assert len(objs) == dist.get_world_size() and objs[0] == {'a': 1}
    lst = [1, 2]
    assert dist.broadcast_object_list(lst) is lst
    out = []
    dist.scatter_object_list(out, [10, 20, 30])
    assert out[0] in (10, 20, 30)
    v = dist.wait(jnp.ones(3) * 2)
    np.testing.assert_array_equal(np.asarray(v), [2, 2, 2])
    t = dist.isend(jnp.ones(()), dst=0)
    assert t.is_completed()
    dist.gloo_init_parallel_env(0, 1, 'x')
    dist.gloo_barrier()
    dist.gloo_release()
    dist.spawn(lambda: 42) == 42


def test_alltoall_under_shard_map():
    from functools import partial

    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ('ep',))
    x = jnp.arange(32.0).reshape(16, 2)

    @partial(shard_map, mesh=mesh, in_specs=P('ep'), out_specs=P('ep'),
             check_rep=False)
    def f(block):
        return dist.alltoall_single(block, group='ep')

    out = np.asarray(f(x))
    # tiled all_to_all transposes the (rank, chunk) grid of row blocks
    want = np.asarray(x).reshape(4, 4, 2).transpose(1, 0, 2).reshape(16, 2)
    np.testing.assert_array_equal(out, want)
    with pytest.raises(NotImplementedError):
        dist.alltoall_single(x, in_split_sizes=[1, 2, 3, 10])


def test_shard_layer_respects_user_shard_fn(pmesh):
    """A shard_fn's placements must survive (no replication clobber)."""
    placed_specs = {}

    def shard_fn(name, layer, mesh):
        if hasattr(layer, 'weight') and layer.weight is not None \
                and getattr(layer.weight, 'ndim', 0) == 2:
            layer.weight = dist.shard_tensor(
                layer.weight, mesh, [dist.Replicate(), dist.Shard(1)])
            placed_specs[name] = layer.weight.sharding.spec

    layer = pt.nn.Linear(8, 8)
    out = dist.shard_layer(layer, pmesh, shard_fn=shard_fn)
    assert placed_specs, 'shard_fn ran'
    # Shard(1) on mesh dim 1 ('y') -> tensor dim 1 split over 'y'
    assert out.weight.sharding.spec == P(None, 'y'), \
        'user placement was clobbered'


def test_send_recv_default_rides_pp_axis():
    from functools import partial

    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ('pp',))
    x = jnp.arange(4.0)

    @partial(shard_map, mesh=mesh, in_specs=P('pp'), out_specs=P('pp'),
             check_rep=False)
    def ring(v):
        return dist.send(v, dst=1)      # group=None -> 'pp' axis

    out = np.asarray(ring(x))
    assert not np.array_equal(out, np.asarray(x)), \
        'default send must actually shift over pp'
