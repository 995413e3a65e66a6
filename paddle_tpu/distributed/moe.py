"""Mixture-of-Experts with expert parallelism.

Parity: the reference MoELayer + gates + global_scatter/global_gather
all-to-all dispatch (/root/reference/python/paddle/incubate/distributed/
models/moe/moe_layer.py:263, gate/*.py, paddle/fluid/operators/collective/
global_*). TPU-native: GShard-style einsum dispatch/combine over a
[E(xperts), C(apacity), D] buffer whose expert dim is sharded over the 'ep'
mesh axis — GSPMD lowers the dispatch einsums to the all-to-all the reference
hand-writes. Gates: naive(top-1)/switch(top-1 + load-balance loss)/
gshard(top-2 + aux loss).

:class:`SparseMoELayer` is the no-drop form that serving uses: top-k of all
experts per token, no capacity, rows sorted by expert and multiplied by the
experts this chip holds through ``kernels.moe_grouped_matmul`` (on a TPU
one Mosaic call for the experts' whole gated MLP).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import nn
from ..core.dispatch import apply
from ..nn import initializer as I
from .mp_layers import mark_sharding

__all__ = ["MoELayer", "SparseMoELayer", "top2_gating", "top1_gating"]


def _one_hot(x, n):
    return jax.nn.one_hot(x, n, dtype=jnp.float32)


def top1_gating(logits, capacity, noisy=False, key=None):
    """Switch-style top-1 routing. logits [N, E] -> dispatch [N, E, C],
    combine [N, E, C], aux loss."""
    N, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)  # [N]
    expert_mask = _one_hot(expert_idx, E)  # [N, E]
    # load-balance loss (Switch Transformer eq. 4)
    density = jnp.mean(expert_mask, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * E
    # position of each token within its expert
    pos = jnp.cumsum(expert_mask, axis=0) * expert_mask - 1.0  # [N, E]
    pos_in_expert = jnp.sum(pos * expert_mask, axis=-1)  # [N]
    keep = pos_in_expert < capacity
    gate = jnp.sum(probs * expert_mask, axis=-1) * keep
    # [N,E,1] * [N,1,C] -> [N,E,C]
    slot = _one_hot(pos_in_expert.astype(jnp.int32), capacity)[:, None, :]
    dispatch = expert_mask[..., None] * slot * keep[:, None, None]
    combine = gate[:, None, None] * dispatch
    return dispatch, combine, aux


def top2_gating(logits, capacity):
    """GShard top-2 routing."""
    N, E = logits.shape
    probs = jax.nn.softmax(logits, axis=-1)
    idx1 = jnp.argmax(probs, axis=-1)
    mask1 = _one_hot(idx1, E)
    probs_wo1 = probs * (1 - mask1)
    idx2 = jnp.argmax(probs_wo1, axis=-1)
    mask2 = _one_hot(idx2, E)

    density = jnp.mean(mask1, axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * E

    pos1 = jnp.cumsum(mask1, axis=0) * mask1 - 1.0
    pos_in1 = jnp.sum(pos1 * mask1, axis=-1)
    # second choice queues after all first choices
    pos2 = (jnp.cumsum(mask2, axis=0) + jnp.sum(mask1, axis=0, keepdims=True)) * mask2 - 1.0
    pos_in2 = jnp.sum(pos2 * mask2, axis=-1)

    keep1 = pos_in1 < capacity
    keep2 = pos_in2 < capacity
    g1 = jnp.sum(probs * mask1, axis=-1) * keep1
    g2 = jnp.sum(probs * mask2, axis=-1) * keep2
    denom = jnp.maximum(g1 + g2, 1e-9)
    g1, g2 = g1 / denom, g2 / denom

    slot1 = _one_hot(pos_in1.astype(jnp.int32), capacity)[:, None, :]
    slot2 = _one_hot(pos_in2.astype(jnp.int32), capacity)[:, None, :]
    d1 = mask1[..., None] * slot1 * keep1[:, None, None]
    d2 = mask2[..., None] * slot2 * keep2[:, None, None]
    dispatch = (d1 + d2).astype(jnp.float32)
    combine = g1[:, None, None] * d1 + g2[:, None, None] * d2
    return dispatch, combine, aux


class MoELayer(nn.Layer):
    """Experts = per-expert FFNs stored stacked [E, ...] sharded over 'ep'."""

    def __init__(self, d_model, d_hidden, num_experts, gate="gshard", top_k=None,
                 capacity_factor=1.25, activation=None, mp_group=None, name=None):
        super().__init__()
        self.num_experts = num_experts
        self.capacity_factor = capacity_factor
        self.gate_type = gate if isinstance(gate, str) else "gshard"
        self.top_k = top_k or (2 if self.gate_type == "gshard" else 1)
        self.gate_weight = self.create_parameter(
            [d_model, num_experts], default_initializer=I.XavierUniform())
        self.w1 = self.create_parameter(
            [num_experts, d_model, d_hidden], default_initializer=I.XavierUniform())
        self.b1 = self.create_parameter([num_experts, 1, d_hidden], is_bias=True)
        self.w2 = self.create_parameter(
            [num_experts, d_hidden, d_model], default_initializer=I.XavierUniform())
        self.b2 = self.create_parameter([num_experts, 1, d_model], is_bias=True)
        for p in (self.w1, self.b1, self.w2, self.b2):
            p.sharding_spec = P("ep", *([None] * (p.ndim - 1)))
        self.aux_loss = None

    def forward(self, x):
        orig_shape = x.shape
        d = orig_shape[-1]
        N = int(np.prod(orig_shape[:-1]))
        E = self.num_experts
        capacity = max(int(self.capacity_factor * self.top_k * N / E), 4)
        gate_type = self.gate_type

        def body(xv, gw, w1, b1, w2, b2):
            xf = xv.reshape(N, d)
            logits = xf @ gw
            if gate_type in ("gshard", "top2"):
                dispatch, combine, aux = top2_gating(logits, capacity)
            else:
                dispatch, combine, aux = top1_gating(logits, capacity)
            # [N,E,C] x [N,D] -> [E,C,D]; GSPMD turns this into the EP all-to-all
            expert_in = jnp.einsum("nec,nd->ecd", dispatch, xf)
            h = jnp.einsum("ecd,edh->ech", expert_in, w1) + b1
            h = jax.nn.gelu(h)
            expert_out = jnp.einsum("ech,ehd->ecd", h, w2) + b2
            out = jnp.einsum("nec,ecd->nd", combine, expert_out)
            return out.reshape(orig_shape), aux

        out, aux = apply(body, x, self.gate_weight, self.w1, self.b1,
                         self.w2, self.b2, op_name="moe")
        out = mark_sharding(out, *([None] * out.ndim))
        self.aux_loss = aux
        return out


class SparseMoELayer(nn.Layer):
    """Sparse experts without dropped rows: every token goes to its
    ``top_k`` experts, whatever their load.

    The router scores all ``num_experts`` (sigmoid), keeps the ``top_k``
    largest, and weighs expert ``e``'s output by ``routed_scaling * s_e /
    sum(kept s)``. Experts are gated MLPs without bias,
    ``down(silu(gate(x)) * up(x))``, stored stacked: ``gate_up_proj
    [held, d_model, 2 * d_expert]`` and ``down_proj [held, d_expert,
    d_model]``.

    ``experts_held = (first, count)`` says which experts live here (all by
    default). The layer routes over all of them and computes the part of the
    result that its own experts give: the (token, expert) rows of the held
    experts are sorted by expert, passed group by group through their
    experts' gated MLP (``kernels.moe_grouped_matmul.moe_gated_mlp_impl``:
    on a TPU one Mosaic call, which reads no weight of an expert without
    rows; :attr:`fused` says whether this layer takes it) and added back
    into their tokens by weight; rows of experts held elsewhere add
    nothing. With every expert held that is the whole layer.
    What the shares of several chips add up to is the sum of their results;
    the exchange that would form it is not this layer's.

    ``shared_expert`` is an optional layer ``[N, d_model] -> [N, d_model]``
    that every token passes through; its output is added once, so of the
    shares of one layer exactly one is given it.

    ``forward(x, row_mask=None)`` returns ``(y, load)``: ``load`` is int32
    ``[count]``, the rows each held expert received (of the rows
    ``row_mask`` keeps, where given: a serving step's padding rows are
    computed like any other but are nobody's load).
    """

    def __init__(self, d_model, d_expert, num_experts, top_k, *,
                 experts_held=None, shared_expert=None, routed_scaling=1.0):
        super().__init__()
        first, count = experts_held or (0, num_experts)
        if not 0 <= first <= first + count <= num_experts:
            raise ValueError(f"experts_held {(first, count)} is not a range "
                             f"of {num_experts} experts")
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.experts_held = (int(first), int(count))
        self.routed_scaling = float(routed_scaling)
        self.router = nn.Linear(d_model, num_experts, bias_attr=False)
        self.gate_up_proj = self.create_parameter(
            [count, d_model, 2 * d_expert],
            default_initializer=I.XavierUniform())
        self.down_proj = self.create_parameter(
            [count, d_expert, d_model], default_initializer=I.XavierUniform())
        if shared_expert is not None:
            self.shared_expert = shared_expert

    def _gated_mlp(self):
        from ..kernels.moe_grouped_matmul import moe_gated_mlp_impl

        _, d_expert, d_model = self.down_proj.shape
        return moe_gated_mlp_impl(d_model, d_expert)

    @property
    def fused(self) -> bool:
        """Whether the routed experts' gated MLP runs as the one kernel
        call (on a TPU, for widths that tile) or as the plain form."""
        from ..kernels.moe_grouped_matmul import moe_gated_mlp_ref

        return self._gated_mlp() is not moe_gated_mlp_ref

    def forward(self, x, row_mask=None):
        k, scaling = self.top_k, self.routed_scaling
        first, count = self.experts_held
        gated_mlp = self._gated_mlp()

        def body(xv, wr, wgu, wd, mask=None):
            d = xv.shape[-1]
            xf = xv.reshape(-1, d)
            n = xf.shape[0]
            s = jax.nn.sigmoid(jnp.dot(xf, wr,
                                       preferred_element_type=jnp.float32))
            top_s, top_e = jax.lax.top_k(s, k)                   # [n, k]
            w = scaling * top_s / jnp.sum(top_s, axis=-1, keepdims=True)
            # experts held elsewhere sort last and belong to no group
            local = top_e.astype(jnp.int32) - first
            local = jnp.where((local >= 0) & (local < count), local, count)
            flat = local.reshape(-1)
            order = jnp.argsort(flat, stable=True)
            sizes = jnp.bincount(flat, length=count + 1)[:count]
            rows = xf[order // k]                                # [n k, d]
            y = gated_mlp(rows, wgu, wd, sizes)
            y = y[jnp.argsort(order)].reshape(n, k, d)           # by token
            out = jnp.sum(w[..., None] * y.astype(jnp.float32), axis=1)
            if mask is None:
                load = sizes
            else:
                kept = jnp.where(mask.reshape(-1, 1), local, count)
                load = jnp.bincount(kept.reshape(-1), length=count + 1)[:count]
            return (out.astype(xv.dtype).reshape(xv.shape),
                    load.astype(jnp.int32))

        args = (x, self.router.weight, self.gate_up_proj, self.down_proj)
        if row_mask is not None:
            args += (row_mask,)
        out, load = apply(body, *args, op_name="sparse_moe")
        if hasattr(self, "shared_expert"):
            out = out + self.shared_expert(x)
        return out, load
