"""Mixture-of-Experts with expert parallelism.

ref: python/paddle/incubate/distributed/models/moe (MoELayer, gate/
top-k dispatch, NCCL all-to-all) — Paddle routes token tensors between
expert ranks with `global_scatter`/`global_gather`.

TPU-native: gating + capacity-bucketed dispatch is dense einsum algebra
(one-hot combine/dispatch masks — the classic GShard formulation, which
IS what XLA wants: static shapes, MXU-friendly), and the rank-to-rank
exchange is `lax.all_to_all` over the 'ep' mesh axis when run under
shard_map — or plain GSPMD sharding of the expert axis under pjit
(experts sharded over 'ep'; XLA inserts the all-to-all pair itself).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..nn import functional as F
from ..nn import initializer as I
from ..nn.layer.base import Layer, Parameter


def _topk_gates(logits, k: int):
    """Shared gating math for the dense and ragged dispatch paths:
    softmax probs, top-k choice, per-token gate normalisation, and the
    Switch/GShard load-balance aux loss E·sum(frac_tokens·frac_probs)."""
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, k)           # (T, k)
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True),
                                        1e-9)
    me = probs.mean(axis=0)                                   # (E,)
    ce = jax.nn.one_hot(expert_idx[:, 0], E).mean(axis=0)
    aux_loss = E * jnp.sum(me * ce)
    return probs, gate_vals, expert_idx, aux_loss


def sigmoid_topk_gates(logits, bias, k: int, route_norm=True,
                       route_scale=1.0):
    """Sigmoid routing with a selection-only bias (the aux-loss-free
    balancing of DeepSeek-V3 and AFMoE): scores s = sigmoid(logits) in
    float32; the k experts are CHOSEN by s + bias and WEIGHED by s alone,
    normalised over the chosen when `route_norm`, times `route_scale`.
    logits (T, E), bias (E,). Returns (gate_vals (T, k) f32, expert_idx
    (T, k))."""
    s = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, expert_idx = jax.lax.top_k(s + bias.astype(jnp.float32), k)
    gate_vals = jnp.take_along_axis(s, expert_idx, axis=-1)
    if route_norm:
        gate_vals = gate_vals / (gate_vals.sum(-1, keepdims=True) + 1e-20)
    return gate_vals * route_scale, expert_idx


def limit_by_capacity(topk_idx, num_expert, capacity):
    """ref: incubate/.../moe/utils.py::limit_by_capacity — keep at most
    ``capacity`` (token-order) routings per expert; dropped entries
    become -1."""
    flat = topk_idx.reshape(-1).astype(jnp.int32)
    oh = jax.nn.one_hot(flat, num_expert, dtype=jnp.int32)
    pos = jnp.cumsum(oh, axis=0) - oh
    slot = (pos * oh).sum(-1)
    keep = slot < capacity
    return jnp.where(keep, flat, -1).reshape(topk_idx.shape)


def top_k_gating(logits, k: int, capacity: int, jitter_key=None):
    """GShard-style top-k gating with capacity.

    logits: (tokens, E). Returns (dispatch (T, E, C) bool-ish float,
    combine (T, E, C) float, aux_loss scalar).
    """
    T, E = logits.shape
    probs, gate_vals, expert_idx, aux_loss = _topk_gates(logits, k)

    # position of each (token, choice) within its expert's capacity buffer
    dispatch = jnp.zeros((T, E, capacity), jnp.float32)
    combine = jnp.zeros((T, E, capacity), jnp.float32)
    fill = jnp.zeros((E,), jnp.int32)
    for choice in range(k):
        e = expert_idx[:, choice]                             # (T,)
        onehot_e = jax.nn.one_hot(e, E, dtype=jnp.int32)      # (T, E)
        # slot index = tokens already routed to e before me (this choice pass)
        pos_in_e = jnp.cumsum(onehot_e, axis=0) - onehot_e    # (T, E)
        slot = (pos_in_e * onehot_e).sum(-1) + fill[e]        # (T,)
        keep = slot < capacity
        slot_oh = jax.nn.one_hot(slot, capacity) * keep[:, None]
        upd = onehot_e[:, :, None] * slot_oh[:, None, :]      # (T, E, C)
        dispatch = dispatch + upd
        combine = combine + upd * (gate_vals[:, choice] * keep)[:, None, None]
        fill = fill + onehot_e.sum(0)
    return dispatch, combine, aux_loss


def ragged_expert_apply(tokens, expert_idx, gate_vals, w_gate, w_up, w_down,
                        num_experts, act=F.silu, expert_offset=None):
    """Dropless expert compute: sort tokens by expert, run grouped GEMMs.

    ref: the reference's large-E MoE path (incubate/.../moe global_scatter
    to per-expert buffers). TPU-native: a stable sort by expert id turns
    the (token, choice) pairs into contiguous per-expert groups, and
    `jax.lax.ragged_dot` runs every expert's GEMM in one MXU call —
    O(T·k·H) memory instead of the GShard einsum's O(T·E·C), the right
    shape for E >= ~16 (DeepSeek-style).

    tokens (T, H); expert_idx/gate_vals (T, k). Returns (T, H).

    With `expert_offset` the weights are ONE RANK'S SHARE of a wider
    router: experts [expert_offset, expert_offset + num_experts) are
    held here, `expert_idx` still counts over the router's whole width,
    and the pairs routed elsewhere are dropped before the sort (they
    take a last, empty-weighted group and add nothing). The products
    then accumulate and combine in float32; the result is this rank's
    part of the sum, in float32.
    """
    T, H = tokens.shape
    k = expert_idx.shape[1]
    with jax.named_scope('dispatch'):
        flat_e = expert_idx.reshape(-1).astype(jnp.int32)     # (T·k,)
        flat_g = gate_vals.reshape(-1)
        held = None
        if expert_offset is not None:
            flat_e = flat_e - expert_offset
            held = (flat_e >= 0) & (flat_e < num_experts)
            flat_e = jnp.where(held, flat_e, num_experts)     # sorts last
        order = jnp.argsort(flat_e, stable=True)
        tok_ids = order // k                                  # source token
        x = jnp.take(tokens, tok_ids, axis=0)                 # (T·k, H)
        group_sizes = jnp.bincount(
            flat_e, length=num_experts + (held is not None))[:num_experts]
        group_sizes = group_sizes.astype(jnp.int32)
        w_gate = _dense_expert(w_gate, x.dtype)
        w_up = _dense_expert(w_up, x.dtype)
        w_down = _dense_expert(w_down, x.dtype)
    with jax.named_scope('matmul'):
        if held is None:
            h = act(jax.lax.ragged_dot(x, w_gate, group_sizes))
            h = h * jax.lax.ragged_dot(x, w_up, group_sizes)
            y = jax.lax.ragged_dot(h.astype(x.dtype), w_down, group_sizes)
        else:
            # a served rank's share has 1-8 rows an expert and its cost is
            # the weights read: on TPU a kernel that reads the experts HIT
            # (ops/pallas/grouped_matmul.py). The branch above is trained
            # and differentiated, and stays XLA's op
            from ..ops import grouped_expert_mlp

            y = grouped_expert_mlp(x, w_gate, w_up, w_down, group_sizes, act)
    with jax.named_scope('combine'):
        y = y * jnp.take(flat_g, order)[:, None].astype(y.dtype)  # (T·k,H)
        if held is not None:
            # rows past the held groups are whatever the grouped product
            # left there: they are dropped, not weighed
            y = jnp.where(jnp.take(held, order)[:, None], y, 0.0)
        return jnp.zeros((T, H), y.dtype).at[tok_ids].add(y)


# ---------------------------------------------------------------------------
# Gate variants (ref: incubate/distributed/models/moe/gate/{base,naive,
# switch,gshard}_gate.py — fastmoe lineage)
# ---------------------------------------------------------------------------

class BaseGate(Layer):
    """ref: gate/base_gate.py — scoring module contract: forward(inp) ->
    (topk_val, topk_idx); the load-balance loss is stashed on the gate."""

    # routing scores must stay full precision: int8 noise flips top-k
    # expert selection (quantization.quantize_matmul_weights honours this)
    no_quantize = True

    def __init__(self, num_expert, world_size=1):
        super().__init__()
        self.num_expert = num_expert
        self.world_size = world_size
        self.tot_expert = num_expert * world_size
        self.loss = jnp.zeros(())

    def set_loss(self, loss):
        object.__setattr__(self, 'loss', loss)

    def get_loss(self, clear=True):
        return self.loss


class NaiveGate(BaseGate):
    """ref: gate/naive_gate.py — plain linear scores, top-k, no balance
    loss."""

    def __init__(self, d_model, num_expert, world_size=1, topk=2):
        super().__init__(num_expert, world_size)
        from ..nn import Linear
        self.gate = Linear(d_model, self.tot_expert)
        self.top_k = topk

    def forward(self, inp, return_all_scores=False):
        gate = self.gate(inp)
        val, idx = jax.lax.top_k(gate, self.top_k)
        if return_all_scores:
            return val, idx, gate
        return val, idx


class SwitchGate(NaiveGate):
    """ref: gate/switch_gate.py — top-1 routing with train-time jitter
    noise and the Switch load-balance loss."""

    def __init__(self, d_model, num_expert, world_size=1, topk=1,
                 switch_eps=0.1, capacity=(1.2, 2.4)):
        if topk != 1:
            raise ValueError('topk should be 1 in switch')
        super().__init__(d_model, num_expert, world_size, topk=1)
        self.switch_eps = switch_eps
        self.capacity = capacity

    def forward(self, inp, jitter_key=None):
        import math

        score = self.gate(inp)
        if self.training:
            if jitter_key is None:
                from ..framework import random as random_mod
                jitter_key = random_mod.split_key()
            noise = jax.random.uniform(jitter_key, score.shape,
                                       dtype=score.dtype)
            score = score + noise * 2 * self.switch_eps + 1.0 - self.switch_eps
        probs = jax.nn.softmax(score.astype(jnp.float32), axis=-1)
        top1_val, top1_idx = jax.lax.top_k(probs, 1)
        # Switch balance loss: E * sum(frac_tokens_e * frac_prob_e)
        E = self.tot_expert
        ce = jax.nn.one_hot(top1_idx[:, 0], E).mean(axis=0)
        me = probs.mean(axis=0)
        self.set_loss(E * jnp.sum(ce * me))
        # capacity pruning (ref switch_gate.py -> limit_by_capacity):
        # per-expert budget from the train/eval capacity factor; dropped
        # routings come back as -1
        cap_rate = self.capacity[0 if self.training else 1]
        cap = max(1, math.ceil(cap_rate * inp.shape[0] / self.tot_expert))
        top1_idx = limit_by_capacity(top1_idx, self.tot_expert, cap)
        return top1_val.astype(inp.dtype), top1_idx


class GShardGate(NaiveGate):
    """ref: gate/gshard_gate.py — top-2 routing + GShard balance loss."""

    def __init__(self, d_model, num_expert, world_size=1, topk=2,
                 capacity=(1.2, 2.4), random_routing=True):
        if topk != 2:
            raise ValueError('topk should be 2 in gshard')
        super().__init__(d_model, num_expert, world_size)
        self.top_k = 2
        self.capacity = capacity
        self.random_routing = random_routing

    def forward(self, inp, rng_key=None):
        import math

        val, idx, score = super().forward(inp, return_all_scores=True)
        E = self.tot_expert
        ce = jax.nn.one_hot(idx.reshape(-1), E).sum(axis=0) / score.shape[0]
        me = jax.nn.softmax(score.astype(jnp.float32), axis=-1).mean(axis=0)
        self.set_loss(jnp.mean(ce * me) * (self.num_expert ** 2))
        # capacity pruning (ref gshard_gate.py -> limit_by_capacity)
        cap_rate = self.capacity[0 if self.training else 1]
        cap = max(1, math.ceil(cap_rate * inp.shape[0] / self.tot_expert))
        idx = limit_by_capacity(idx, self.tot_expert, cap)
        if self.random_routing:
            # ref gshard_gate.py: keep the 2nd choice with probability
            # proportional to its (doubled) gate value
            if rng_key is None:
                from ..framework import random as random_mod
                rng_key = random_mod.split_key()
            gate2 = jax.nn.softmax(score.astype(jnp.float32), axis=-1)
            gate2 = jnp.take_along_axis(gate2, idx[:, 1:2].clip(0), axis=-1)
            keep2 = (jax.random.uniform(rng_key, (score.shape[0], 1))
                     < 2.0 * gate2)
            idx = jnp.concatenate(
                [idx[:, :1], jnp.where(keep2, idx[:, 1:2], -1)], axis=-1)
        return val, idx


def _expert_einsum(eq, x, w):
    """Expert einsum that serves int8-quantized weights: a
    QuantizedExpertWeight feeds its codes into the dot (int8 HBM
    stream) and scales the output; dense arrays take the plain path."""
    from ..nn.quant import QuantizedExpertWeight

    if isinstance(w, QuantizedExpertWeight):
        return w.einsum(eq, x)
    return jnp.einsum(eq, x, w)


def _dense_expert(w, dtype):
    """ragged_dot needs dense operands: dequantize quantized experts
    (documented cost — see quantization.quantize_matmul_weights)."""
    from ..nn.quant import QuantizedExpertWeight

    if isinstance(w, QuantizedExpertWeight):
        return w.dequantize(dtype)
    return w


class ExpertMLP(Layer):
    """E experts' weights batched on a leading axis sharded over 'ep' —
    one einsum runs every expert (GSPMD splits it across ranks)."""

    def __init__(self, num_experts, hidden, intermediate, activation=F.silu):
        super().__init__()
        init = I.Normal(0.0, 0.02)
        self.w_up = Parameter(init((num_experts, hidden, intermediate), 'float32'),
                              spec=P('ep', None, 'tp'))
        self.w_gate = Parameter(init((num_experts, hidden, intermediate), 'float32'),
                                spec=P('ep', None, 'tp'))
        self.w_down = Parameter(init((num_experts, intermediate, hidden), 'float32'),
                                spec=P('ep', 'tp', None))
        self.act = activation

    def forward(self, x):
        """x: (E, C, H) expert-major buckets."""
        h = self.act(_expert_einsum('ech,ehm->ecm', x, self.w_gate))
        h = h * _expert_einsum('ech,ehm->ecm', x, self.w_up)
        return _expert_einsum('ecm,emh->ech', h, self.w_down)


class MoELayer(Layer):
    """ref: incubate.distributed.models.moe.MoELayer.

    Dense GShard dispatch: out = combine · expert(dispatchᵀ · x).
    Shared experts (DeepSeek-style) run on every token additively.
    """

    # the router weight: keep full precision under weight-only PTQ
    no_quantize = ('gate',)

    def __init__(self, hidden, intermediate, num_experts=8, top_k=2,
                 capacity_factor=1.25, num_shared_experts=0, gate_init=None,
                 return_aux=False, dispatch_mode='auto'):
        super().__init__()
        if dispatch_mode not in ('auto', 'dense', 'ragged'):
            raise ValueError(
                f"dispatch_mode must be 'auto'|'dense'|'ragged', "
                f'got {dispatch_mode}')
        self.num_experts = num_experts
        self.top_k = top_k
        self.capacity_factor = capacity_factor
        # 'dense' = GShard (T, E, C) einsum dispatch: best for small E,
        # and the form GSPMD turns into the ep all-to-all. 'ragged' =
        # DROPLESS sort + lax.ragged_dot grouped GEMM: O(T·k) memory,
        # the right shape for E >= ~16 — note it ignores capacity_factor
        # (no token dropping, DeepSeek-style). 'auto' preserves the
        # historical dense numerics but nudges large-E users once.
        if dispatch_mode == 'auto':
            if num_experts >= 16:
                import warnings

                warnings.warn(
                    f'MoELayer(num_experts={num_experts}) defaults to the '
                    f'dense GShard dispatch, whose (tokens, E, C) tensors '
                    f"are O(T²); pass dispatch_mode='ragged' for the "
                    f'dropless grouped-GEMM path at this expert count.',
                    stacklevel=3)
            dispatch_mode = 'dense'
        self.dispatch_mode = dispatch_mode
        init = gate_init or I.Normal(0.0, 0.02)
        self.gate = Parameter(init((hidden, num_experts), 'float32'))
        self.experts = ExpertMLP(num_experts, hidden, intermediate)
        self.num_shared = num_shared_experts
        self.shared = (
            None if num_shared_experts == 0
            else ExpertMLP(num_shared_experts, hidden,
                           intermediate)
        )
        self.return_aux = return_aux
        self.aux_loss = jnp.zeros(())   # registered buffer: last aux loss

    def forward(self, x, dropless=False):
        """x: (B, S, H) → (B, S, H), or (out, aux_loss) if return_aux.

        ``dropless=True`` routes through the ragged (no-capacity) path
        regardless of dispatch_mode — KV-cached decode passes it, since
        capacity computed from a single-token call (T = B) drops every
        routing collision and silently degrades generation.

        `self.aux_loss` is also updated in place; being a registered
        buffer it follows the framework's state-in/state-out rule — under
        jit it carries out only if the (traced) model is returned from
        the jitted fn, like BatchNorm stats. Use `return_aux=True` (or
        read `m.aux_loss` on the traced model inside the step) when
        adding it to the training loss."""
        B, S, H = x.shape
        tokens = x.reshape(B * S, H)
        T = B * S
        logits = tokens @ self.gate
        if dropless or self.dispatch_mode == 'ragged':
            _, gate_vals, expert_idx, aux = _topk_gates(logits, self.top_k)
            out = ragged_expert_apply(
                tokens.astype(x.dtype), expert_idx, gate_vals,
                self.experts.w_gate, self.experts.w_up, self.experts.w_down,
                self.num_experts, act=self.experts.act)
            out = out.reshape(B, S, H).astype(x.dtype)
        else:
            capacity = int(
                self.capacity_factor * self.top_k * T / self.num_experts)
            capacity = max(capacity, 1)
            dispatch, combine, aux = top_k_gating(logits, self.top_k,
                                                  capacity)
            # (T,E,C)·(T,H) → (E,C,H): under GSPMD with 'ep'-sharded
            # experts this einsum IS the all-to-all dispatch
            expert_in = jnp.einsum('tec,th->ech', dispatch,
                                   tokens.astype(jnp.float32))
            expert_out = self.experts(expert_in.astype(x.dtype))
            out = jnp.einsum('tec,ech->th', combine,
                             expert_out.astype(jnp.float32))
            out = out.reshape(B, S, H).astype(x.dtype)
        if self.shared is not None:
            shared_in = jnp.broadcast_to(
                tokens[None], (self.num_shared, T, H)).astype(x.dtype)
            shared_out = self.shared(shared_in).sum(axis=0)
            out = out + shared_out.reshape(B, S, H)
        # state-in/state-out: only stash aux on a layer whose own leaves
        # are part of the active trace. When a CONCRETE model runs under
        # an inner trace (e.g. generate()'s lax.scan closes over self),
        # writing the traced aux would leak a tracer into the instance
        # and poison every later flatten/jit with UnexpectedTracerError.
        # NOTE: in that skipped case `self.aux_loss` retains its value
        # from the last eager call (stale) — read the aux via
        # `return_aux=True` inside jitted code, never off the instance.
        stash_ok = not (isinstance(aux, jax.core.Tracer)
                        and not isinstance(self.aux_loss, jax.core.Tracer))
        if stash_ok:
            object.__setattr__(self, 'aux_loss', aux)
        if self.return_aux:
            return out, aux
        return out


# ---------------------------------------------------------------------------
# One rank's share of an expert layer (expert-parallel serving)
# ---------------------------------------------------------------------------

ROUTING_FIELDS = ('picks_total', 'picks_local', 'experts_hit', 'load_max',
                  'load_mean', 'layer_steps', 'weight_visits')
_COUNTING = []          # the open `routing_counts()` collectors, innermost last


class RoutingCounts:
    """What the expert layers traced inside one `routing_counts()` block
    routed, summed over those layers in ROUTING_FIELDS' order (float32):
    (token, choice) pairs in all, those that chose an expert held here,
    held experts with at least one pick, the fullest held expert's picks,
    the mean over the held, the layers counted, and the (expert, row tile)
    visits the grouped-matmul kernel makes for those picks, each one read
    of an expert's matrices (`ops.pallas.grouped_matmul.visits`: equal to
    the experts hit where a call's rows are one tile, as a decode step's
    are). Only `rows` count."""

    def __init__(self, rows):
        self.rows = rows
        self.layers = []

    def total(self):
        """(len(ROUTING_FIELDS),) float32, or None where no expert layer
        ran inside the block."""
        return sum(self.layers[1:], self.layers[0]) if self.layers else None


@contextlib.contextmanager
def routing_counts(rows=None):
    """Collect the routing counts of every `ExpertShare` traced inside
    the block. `rows` (bool, broadcastable to the layer's (B, S)) says
    which tokens count (a serving batch carries frozen and empty rows).
    The counts are values of the trace the block runs in: read
    `.total()` inside that trace."""
    counts = RoutingCounts(rows)
    _COUNTING.append(counts)
    try:
        yield counts
    finally:
        _COUNTING.remove(counts)


class ExpertShare(Layer):
    """One rank's share of a sparse expert layer, as an expert-parallel
    deployment holds it: the router over all `num_experts`, the
    `experts_held` experts from `expert_offset` on, and the shared
    expert whole. Routing is sigmoid top-k with a selection-only bias
    (`sigmoid_topk_gates`) over the router's whole width; the output is
    shared(x) plus the chosen experts' parts THAT ARE HELD HERE, each
    with its weight normalised over all the chosen. The other ranks'
    parts and the exchange that would add them are not here. With
    `experts_held == num_experts` it is the whole layer.

    x (B, S, H) -> (B, S, H). Expert compute is dropless
    (`ragged_expert_apply`); the router's product runs in float32."""

    no_quantize = ('router', 'expert_bias')
    # the jax.named_scope its ops run under, one entry of
    # `observability.tracing.DEVICE_SCOPES`: its stages (route, dispatch,
    # matmul, combine, shared) open theirs inside it
    SCOPE = 'expert_share'

    def __init__(self, hidden, intermediate, num_experts, top_k,
                 experts_held=None, expert_offset=0, shared_intermediate=0,
                 route_norm=True, route_scale=1.0, dtype='float32',
                 activation=F.silu):
        super().__init__()
        held = num_experts if experts_held is None else int(experts_held)
        if not 0 <= expert_offset <= num_experts - held:
            raise ValueError(
                f'experts [{expert_offset}, {expert_offset + held}) are not '
                f'among the router\'s {num_experts}')
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.experts_held, self.expert_offset = held, int(expert_offset)
        self.route_norm, self.route_scale = bool(route_norm), float(route_scale)
        self.act = activation
        init = I.Normal(0.0, 0.02)
        self.router = Parameter(init((hidden, num_experts), 'float32'))
        self.expert_bias = Parameter(jnp.zeros((num_experts,), jnp.float32))
        self.w_gate = Parameter(init((held, hidden, intermediate), dtype))
        self.w_up = Parameter(init((held, hidden, intermediate), dtype))
        self.w_down = Parameter(init((held, intermediate, hidden), dtype))
        if shared_intermediate:
            self.shared_gate = Parameter(
                init((hidden, shared_intermediate), dtype))
            self.shared_up = Parameter(
                init((hidden, shared_intermediate), dtype))
            self.shared_down = Parameter(
                init((shared_intermediate, hidden), dtype))
        else:
            self.shared_gate = self.shared_up = self.shared_down = None

    def route(self, tokens):
        """(gate_vals (T, k) f32, expert_idx (T, k)) over all experts."""
        with jax.named_scope('route'):
            logits = jnp.matmul(tokens.astype(jnp.float32), self.router,
                                precision=jax.lax.Precision.HIGHEST)
            return sigmoid_topk_gates(logits, self.expert_bias, self.top_k,
                                      self.route_norm, self.route_scale)

    def _count(self, expert_idx, shape):
        from ..ops.pallas.grouped_matmul import visits

        with jax.named_scope('route'):
            rows = _COUNTING[-1].rows
            rows = (jnp.ones(shape, bool) if rows is None
                    else jnp.broadcast_to(rows, shape)).reshape(-1, 1)
            local = expert_idx - self.expert_offset
            held = rows & (local >= 0) & (local < self.experts_held)
            load = jnp.zeros((self.experts_held,), jnp.float32).at[
                jnp.where(held, local, self.experts_held).reshape(-1)].add(
                    1.0, mode='drop')
            local_picks = load.sum()
            _COUNTING[-1].layers.append(jnp.stack([
                rows.sum().astype(jnp.float32) * self.top_k, local_picks,
                (load > 0).sum().astype(jnp.float32), load.max(),
                local_picks / self.experts_held,
                rows.any().astype(jnp.float32),
                visits(load.astype(jnp.int32), expert_idx.size).sum().astype(
                    jnp.float32)]))

    def forward(self, x):
        B, S, H = x.shape
        with jax.named_scope(self.SCOPE):
            tokens = x.reshape(B * S, H)
            gate_vals, expert_idx = self.route(tokens)
            if _COUNTING:
                self._count(expert_idx, (B, S))
            out = ragged_expert_apply(
                tokens, expert_idx, gate_vals, self.w_gate, self.w_up,
                self.w_down, self.experts_held, act=self.act,
                expert_offset=self.expert_offset)
            if self.shared_gate is not None:
                with jax.named_scope('shared'):
                    hid = (self.act(tokens @ self.shared_gate)
                           * (tokens @ self.shared_up))
                    out = out + (hid @ self.shared_down).astype(out.dtype)
            return out.reshape(B, S, H).astype(x.dtype)
