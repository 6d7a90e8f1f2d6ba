"""Hand-written TPU kernels (pallas) with lax references.

Dispatch policy: pallas kernels on TPU backends, pure-lax reference
implementations elsewhere (CPU tests) — same math, verified against each
other in tests/test_pallas.py. The choice is made from the backend, never
from a failure: a kernel that raises on the TPU raises to the caller.
"""
from __future__ import annotations

import jax


def _on_tpu():
    # TPU only: pallas-in-interpret on other accelerators is orders of
    # magnitude slower than the lax fallback
    return jax.default_backend() == 'tpu'


def use_pallas():
    """True when pallas fast paths should dispatch (TPU + flag on)."""
    from ..framework.flags import get_flags

    return _on_tpu() and get_flags(['FLAGS_use_pallas_kernels'])[
        'FLAGS_use_pallas_kernels']


# mesh axes a kernel's batch dim may stay split over
DATA_AXES = ('dp', 'fsdp')


def mesh_kernel(kernel, args, specs, out=0):
    """Call a pallas kernel on array `args` under the active device mesh.

    Mosaic kernels cannot be partitioned by GSPMD (jax refuses to lower
    one inside a multi-device jit: "wrap the call in a shard_map"), so
    under a mesh of several devices the call runs per shard: `specs` names,
    per argument, the dims that may stay split (batch over the data axes,
    heads over 'tp' — each clamped to what the mesh has and the shape
    divides), GSPMD reshards the operands to match, and the result carries
    the clamped spec of argument `out`. With no mesh, on one device, or in
    a shard_map body that already holds mesh axes manually (ring/Ulysses
    attention, the pipeline schedules) it is a plain call.
    """
    from ..distributed.mesh import get_mesh

    mesh = get_mesh()
    if (mesh is None or mesh.size == 1
            or jax.sharding.get_abstract_mesh().manual_axes):
        return kernel(*args)
    from ..distributed._spmd import shard_map
    from ..distributed.parallel import _valid_spec

    in_specs = tuple(_valid_spec(s, a.shape, mesh)
                     for s, a in zip(specs, args))
    return shard_map(kernel, mesh=mesh, in_specs=in_specs,
                     out_specs=in_specs[out], check_vma=False)(*args)


def head_axis(q_heads, kv_heads):
    """The mesh axis attention heads may stay split over: 'tp' when the
    active mesh's tp degree divides BOTH head counts (a GQA group must not
    straddle shards), else None (heads whole on every shard)."""
    from ..distributed.mesh import get_mesh

    mesh = get_mesh()
    tp = mesh.shape.get('tp', 1) if mesh is not None else 1
    return 'tp' if q_heads % tp == 0 and kv_heads % tp == 0 else None


def _rows_spec(ndim):
    """Spec of a (batch, [seq,] ..., features) operand whose rows are
    independent: batch over the data axes, seq over 'sp', features whole."""
    from jax.sharding import PartitionSpec as P

    return P(*((DATA_AXES, 'sp') + (None,) * ndim)[:ndim - 1], None)


def rms_norm(x, weight=None, epsilon=1e-6):
    """Fused RMSNorm; pallas kernel on TPU (ops/pallas/rms_norm.py)."""
    if use_pallas() and x.shape[-1] % 128 == 0 and x.dtype != jax.numpy.float64:
        from jax.sharding import PartitionSpec as P

        from .pallas.rms_norm import rms_norm as _k

        weights = () if weight is None else (weight,)
        return mesh_kernel(
            lambda x_, *w: _k(x_, w[0] if w else None, epsilon),
            (x,) + weights, (_rows_spec(x.ndim),) + (P(),) * len(weights))
    from ..nn.functional.norm import rms_norm as _ref

    return _ref(x, weight, epsilon)


def softmax_cross_entropy(logits, labels):
    """Fused softmax-xent; pallas on TPU (ops/pallas/softmax_xent.py),
    lax reference elsewhere. Per-example nll, fp32."""
    import jax.numpy as jnp

    # any vocab size: the kernel masks the padded tail block (the guard
    # only excludes degenerate tiny vocabs where tiling can't help)
    if use_pallas() and logits.shape[-1] >= 128:
        from jax.sharding import PartitionSpec as P

        from .pallas.softmax_xent import softmax_cross_entropy_with_logits

        # the kernel reduces over whole vocab rows: a vocab-parallel
        # (tp-sharded) logits row is gathered first
        rows = _rows_spec(logits.ndim)
        return mesh_kernel(softmax_cross_entropy_with_logits,
                           (logits, labels), (rows, P(*rows[:-1])), out=1)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def grouped_expert_mlp(x, w_gate, w_up, w_down, group_sizes, act):
    """The three grouped products of ONE RANK'S SHARE of an expert layer
    (`distributed.moe.ExpertShare`), over rows sorted by held expert:
    act(x @ w_gate[e]) * (x @ w_up[e]), rounded to x's dtype, then
    @ w_down[e]; float32 accumulation, (rows, H) float32. Rows behind the
    held groups hold whatever the products left there. Pallas on TPU
    (ops/pallas/grouped_matmul.py: the weights of the experts HIT, once
    each; whole on every shard of a mesh), `lax.ragged_dot` elsewhere."""
    import jax.numpy as jnp

    if use_pallas():
        from jax.sharding import PartitionSpec as P

        from .pallas.grouped_matmul import grouped_gated, grouped_matmul

        def kernel(x_, gate_, up_, down_, sizes_):
            return grouped_matmul(
                grouped_gated(x_, gate_, up_, sizes_, act), down_, sizes_)

        return mesh_kernel(kernel, (x, w_gate, w_up, w_down, group_sizes),
                           (P(),) * 5)

    def product(rows, w):
        return jax.lax.ragged_dot(rows, w, group_sizes,
                                  preferred_element_type=jnp.float32)

    h = act(product(x, w_gate)) * product(x, w_up)
    return product(h.astype(x.dtype), w_down)
