"""Sequence / context parallelism: ring attention over the 'sep' mesh axis.

BEYOND-reference capability (SURVEY §5.7: the reference has no ring
attention / Ulysses / context parallelism — sequences scale only via
TP+recompute). Design per the ring-attention recipe: Q/K/V sharded on the
sequence dim; each ring step computes blockwise attention against the
resident KV shard, then rotates KV one hop over ICI with ``ppermute``;
partial results merge with the flash-attention online-softmax rule, so the
full S×S score matrix never exists on any chip AND sequence memory scales
1/sep_degree.

Also provides the Ulysses-style all-to-all head-scatter
(``ulysses_attention``): resharding [B, S/p, H, D] -> [B, S, H/p, D] with two
all_to_alls around any single-device attention kernel.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map as _shard_map

__all__ = ["ring_attention", "ulysses_attention"]

NEG_INF = -1e30


def _block_flash(q, k, v, sm_scale, causal):
    """Per-ring-block flash attention: the Pallas kernel (jnp mirror under
    the CPU interpreter) over [B,S,H,D], returning the normalized partial
    and its logsumexp — the pair the online-softmax merge needs. The lse
    cotangent from the merge flows back through the kernel's custom_vjp."""
    from ..kernels.flash_attention import _flash_core

    B, Sq, H, D = q.shape
    Sk = k.shape[1]

    def to_bhsd(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, x.shape[1], D)

    out, lse = _flash_core(to_bhsd(q), to_bhsd(k), to_bhsd(v), None, None,
                           None, None, causal, sm_scale, 0.0, H)
    out = out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    return out, lse.reshape(B, H, Sq, 1)


def _merge_partials(o1, lse1, o2, lse2):
    """Online-softmax merge of two normalized partials ([B,S,H,D], [B,H,S,1])."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    denom = jnp.maximum(w1 + w2, 1e-30)
    sw = lambda w: jnp.moveaxis(w, 1, 2)  # [B,S,H,1] for the [B,S,H,D] layout
    out = (o1 * sw(w1) + o2 * sw(w2)) / sw(denom)
    return out.astype(o1.dtype), m + jnp.log(denom)


def ring_attention(q, k, v, mesh=None, axis="sep", causal=True, scale=None):
    """q,k,v: [B, S, H, D] GLOBAL arrays sharded over `axis` on dim 1.
    Returns attention output with the same sharding. Must run inside jit
    (GSPMD context); eager single-device falls back to plain attention."""
    from ..nn.functional.attention import sdpa_ref

    if mesh is None:
        from .mesh import current_mesh

        mesh = current_mesh()
    if mesh is None or dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1) == 1:
        return sdpa_ref(q, k, v, is_causal=causal, scale=scale)

    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    sm_scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])

    def local(q, k, v):
        my = jax.lax.axis_index(axis)
        B, Sl, H, D = q.shape
        perm = [(i, (i + 1) % n) for i in range(n)]

        # carries must be typed varying-over-axis from tick 0 (check_vma)
        pv = lambda a: jax.lax.pcast(a, (axis,), to="varying")
        lse0 = pv(jnp.full((B, H, Sl, 1), NEG_INF, jnp.float32))
        out0 = pv(jnp.zeros((B, Sl, H, D), jnp.float32))

        def step(carry, r):
            out, lse, kr, vr = carry
            # kv block currently resident came from rank (my - r) mod n
            src = (my - r) % n
            if causal:
                # src < my: full flash block; src == my: causal-diagonal flash
                # block; src > my: skip. lax.switch runs exactly ONE branch —
                # the Pallas kernel is dispatched once per ring tick.
                def full(_):
                    o, s = _block_flash(q, kr, vr, sm_scale, False)
                    return o.astype(jnp.float32), s

                def diag(_):
                    o, s = _block_flash(q, kr, vr, sm_scale, True)
                    return o.astype(jnp.float32), s

                def skip(_):
                    # fresh constants must be typed varying like the flash
                    # branches' outputs (check_vma)
                    return (pv(jnp.zeros((B, Sl, H, D), jnp.float32)),
                            pv(jnp.full((B, H, Sl, 1), NEG_INF, jnp.float32)))

                idx = jnp.where(src < my, 0, jnp.where(src == my, 1, 2))
                o_b, lse_b = jax.lax.switch(idx, (full, diag, skip), None)
            else:
                o_b, lse_b = _block_flash(q, kr, vr, sm_scale, False)
            out, lse = _merge_partials(out, lse, o_b.astype(out.dtype), lse_b)
            kr = jax.lax.ppermute(kr, axis, perm)
            vr = jax.lax.ppermute(vr, axis, perm)
            return (out, lse, kr, vr), None

        (out, lse, _, _), _ = jax.lax.scan(
            step, (out0, lse0, k, v), jnp.arange(n))
        return out.astype(q.dtype)

    spec = P(None, axis, None, None)
    return _shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={axis}, check_vma=True,
    )(q, k, v)


def ulysses_attention(q, k, v, mesh=None, axis="sep", causal=True, scale=None,
                      attn_fn=None):
    """Ulysses SP: all-to-all scatter heads / gather sequence, run full-seq
    attention per head group, then reverse. Requires H % sep == 0."""
    from ..kernels import attention_impl

    if mesh is None:
        from .mesh import current_mesh

        mesh = current_mesh()
    # default = the platform attention policy: the Pallas flash kernel on
    # chip, einsum composition on CPU meshes
    attn = attn_fn or (lambda a, b, c: attention_impl()(
        a, b, c, is_causal=causal, scale=scale))
    if mesh is None or dict(zip(mesh.axis_names, mesh.devices.shape)).get(axis, 1) == 1:
        return attn(q, k, v)

    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]

    def local(q, k, v):
        # local [B, S/n, H, D] -> exchange to [B, S, H/n, D]
        def seq_to_head(x):
            B, Sl, H, D = x.shape
            xs = x.reshape(B, Sl, n, H // n, D)
            xs = jnp.moveaxis(xs, 2, 0)  # [n, B, Sl, H/n, D]
            xs = jax.lax.all_to_all(xs, axis, 0, 0, tiled=False)
            return jnp.moveaxis(xs, 0, 1).reshape(x.shape[0], Sl * n, H // n, D)

        def head_to_seq(x, H):
            B, S, Hl, D = x.shape
            xs = x.reshape(B, n, S // n, Hl, D)
            xs = jnp.moveaxis(xs, 1, 0)
            xs = jax.lax.all_to_all(xs, axis, 0, 0, tiled=False)
            # index 0 = source rank = owner of head group -> heads ordered
            # (rank, local_head) to restore the global head order
            xs = jnp.moveaxis(xs, 0, 2)  # [B, S/n, n, Hl, D]
            return xs.reshape(B, S // n, n * Hl, D)

        H = q.shape[2]
        qf, kf, vf = seq_to_head(q), seq_to_head(k), seq_to_head(v)
        out = attn(qf, kf, vf)
        return head_to_seq(out, H)

    spec = P(None, axis, None, None)
    return _shard_map(
        local, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        axis_names={axis}, check_vma=True,
    )(q, k, v)
