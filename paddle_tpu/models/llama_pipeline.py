"""Full 3-D hybrid (dp × mp × pp, + ZeRO 'sharding') Llama training step.

This is the TPU-native composition the reference reaches via
PipelineParallel(TensorParallel(model)) + HybridParallelOptimizer
(/root/reference/python/paddle/distributed/fleet/meta_parallel/ — SURVEY
§3.5): ONE jitted SPMD function where
- embed / final-norm / lm-head params carry mp/ZeRO shardings,
- the L homogeneous decoder blocks are STACKED [S, L/S, ...] with the leading
  stage dim sharded over 'pp',
- micro-batches stream through ``spmd_pipeline`` (ppermute hand-off),
- the batch dim is sharded over ('dp','sharding'),
and GSPMD + the latency-hiding scheduler produce the overlapped collectives
the reference implements as comm-stream machinery.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..core.tensor import Tensor
from ..distributed.engine import _divisible_dim
from ..distributed.pipeline import spmd_pipeline
from ..nn.layer import functional_call, functional_state
from .llama import LlamaConfig, LlamaDecoderLayer, _rope_tables

__all__ = ["LlamaPipelineTrainer"]


class LlamaPipelineTrainer:
    """Builds and owns the hybrid train step + sharded state."""

    def __init__(self, config: LlamaConfig, mesh, optimizer, n_micro=None,
                 zero_stage=2, compute_dtype="auto", seed=0,
                 pp_schedule="1f1b", vpp=2, offload=False):
        from .. import nn
        from ..distributed.mp_layers import ColumnParallelLinear, VocabParallelEmbedding
        from ..framework import random as frandom

        self.config = config
        self.mesh = mesh
        self.optimizer = optimizer
        if compute_dtype == "auto":
            # bf16 on TPU; f32 on the CPU test mesh (XLA:CPU crashes on
            # bf16 collective-permute — "Invalid binary instruction opcode")
            plat = mesh.devices.flat[0].platform
            compute_dtype = jnp.bfloat16 if plat == "tpu" else jnp.float32
        self.compute_dtype = compute_dtype
        # install the mesh globally so mark_sharding constraints resolve
        from ..distributed.mesh import HybridCommunicateGroup, set_hybrid_communicate_group

        set_hybrid_communicate_group(HybridCommunicateGroup(None, mesh))
        shape = dict(zip(mesh.axis_names, mesh.devices.shape))
        self.n_stages = shape.get("pp", 1)
        self.zdeg = shape.get("sharding", 1)
        self.zero_stage = zero_stage
        # "1f1b" (reference pipeline_parallel.py:372, the default schedule
        # there too), "fthenb" (GPipe fill-drain, autodiff backward), or
        # "interleaved" (virtual stages: vpp non-adjacent chunks per device,
        # reference PipelineParallelWithInterleave:807)
        self.pp_schedule = pp_schedule
        self.vpp = vpp if pp_schedule == "interleaved" else 1
        # host-offload tier (reference GroupShardedOptimizerStage2(offload=
        # True)): master params + Adam moments live in HOST memory, the
        # device holds only working params and computes grads; the update
        # runs on the CPU backend. Buys ~8 bytes/param of HBM (moments) at
        # the cost of a grads-down + params-up host transfer per step.
        self.offload = offload
        self.n_micro = n_micro or max(2 * self.n_stages, 2)
        assert config.num_hidden_layers % (self.n_stages * self.vpp) == 0, \
            "layers must divide evenly over pipeline stages (x vpp chunks)"

        frandom.seed(seed)
        # template block: ONE set of python layers reused functionally per block
        self.block = LlamaDecoderLayer(config)
        self.embed = VocabParallelEmbedding(config.vocab_size, config.hidden_size)
        self.norm = nn.RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.head = ColumnParallelLinear(config.hidden_size, config.vocab_size,
                                         has_bias=False, gather_output=True)
        cos, sin = _rope_tables(config.head_dim, config.max_position_embeddings,
                                config.rope_theta)
        self.rope = (cos, sin)
        self._state = None
        self._step_fn = None
        self._step_count = 0

    # ------------------------------------------------------------------
    def _block_param_specs(self):
        """Template block specs, prefixed with the [S, L/S] stack dims."""
        specs = {}
        for n, p in self.block.named_parameters():
            base = tuple(p.sharding_spec) if p.sharding_spec is not None else ()
            base = base + (None,) * (p.ndim - len(base))
            specs[n] = P("pp", None, *base)
        return specs

    def _edge_specs(self, named_params):
        """embed/norm/head: annotated mp specs + ZeRO-3 extension."""
        specs = {}
        for n, p in named_params.items():
            base = tuple(p.sharding_spec) if p.sharding_spec is not None else ()
            base = base + (None,) * (p.ndim - len(base))
            if self.zero_stage >= 3 and self.zdeg > 1 and "sharding" not in base:
                dim = _divisible_dim(tuple(p.shape), P(*base), self.zdeg)
                if dim is not None:
                    lst = list(base)
                    lst[dim] = "sharding"
                    base = tuple(lst)
            specs[n] = P(*base)
        return specs

    def _init_state(self):
        c = self.config
        S, Lps = self.n_stages, c.num_hidden_layers // self.n_stages
        tmpl_params, _ = functional_state(self.block)

        # build L independent block inits by re-randomizing the template
        blocks = []
        for _ in range(c.num_hidden_layers):
            fresh = LlamaDecoderLayer(c)
            p, _ = functional_state(fresh)
            blocks.append(p)
        stacked = {
            k: jnp.stack([b[k] for b in blocks], axis=0).reshape(
                (S, Lps) + blocks[0][k].shape)
            for k in tmpl_params
        }
        edge_named = {}
        for prefix, layer in (("embed", self.embed), ("norm", self.norm), ("head", self.head)):
            for n, p in layer.named_parameters():
                edge_named[f"{prefix}.{n}"] = p

        bspecs = self._block_param_specs()
        especs = self._edge_specs(edge_named)

        params = {}
        for k, v in stacked.items():
            params[f"blocks.{k}"] = jax.device_put(v, NamedSharding(self.mesh, bspecs[k]))
        for n, p in edge_named.items():
            params[n] = jax.device_put(p._value, NamedSharding(self.mesh, especs[n]))
        # from here on the layers are structure for functional_call only:
        # their own weights are never read again. At 7B widths that is a
        # 0.8 GB template block, and on a mesh 1 GB of embedding and head
        # left whole on device 0 beside its shards
        for layer in (self.block, self.embed, self.norm, self.head):
            for p in layer.parameters():
                p._value = jax.ShapeDtypeStruct(p._value.shape, p._value.dtype)

        self._pspecs = {**{f"blocks.{k}": v for k, v in bspecs.items()}, **especs}
        if self.offload:
            cpu = jax.devices("cpu")[0]
            with jax.default_device(cpu):
                host_state = self.optimizer.init_state_tree(
                    {n: np.zeros(v.shape, np.float32)
                     for n, v in params.items()})
            self._host_opt = jax.tree_util.tree_map(np.asarray, host_state)
            self._host_master = {n: np.asarray(jax.device_get(v), np.float32)
                                 for n, v in params.items()}
            self._state = (params, None)
            return
        opt_state = self.optimizer.init_state_tree(params)
        self._ospecs = {
            n: {k: (self._pspecs[n] if np.ndim(v) else P()) for k, v in st.items()}
            for n, st in opt_state.items()
        }
        opt_state = {
            n: {k: jax.device_put(v, NamedSharding(self.mesh, self._ospecs[n][k]))
                for k, v in st.items()}
            for n, st in opt_state.items()
        }
        self._state = (params, opt_state)

    # ------------------------------------------------------------------
    def _build_step(self):
        c = self.config
        S = self.n_stages
        M = self.n_micro
        cdt = self.compute_dtype
        block, embed, norm, head = self.block, self.embed, self.norm, self.head
        cos, sin = self.rope
        opt = self.optimizer
        mesh = self.mesh

        cos_arr, sin_arr = jnp.asarray(cos), jnp.asarray(sin)

        def block_apply(bp, h):
            out, _ = functional_call(block, bp, {}, h, cos_arr, sin_arr)
            return out

        # remat each block: backward replays the block forward instead of
        # keeping S^2 attention residuals per layer (reference recompute role).
        # Policy: keep matmul outputs (cheap HBM, expensive to recompute on
        # MXU); everything elementwise is recomputed.
        import os

        remat_policy = os.environ.get("PADDLE_TPU_REMAT_POLICY", "dots")
        if remat_policy == "off":
            # no rematerialization: all residuals saved (HBM permitting)
            block_apply_ck = block_apply
        else:
            policy = None
            if remat_policy == "dots":
                policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            block_apply_ck = jax.checkpoint(block_apply, policy=policy)

        def stage_fn(stage_params, h):
            # stage_params leaves [L/S, ...]; scan the blocks of this stage
            def body(hh, layer_params):
                return block_apply_ck(layer_params, hh), None

            h, _ = jax.lax.scan(body, h, stage_params)
            return h

        def loss_fn(params, x, y):
            bparams = {k[len("blocks."):]: v for k, v in params.items()
                       if k.startswith("blocks.")}
            eparams = {k[len("embed."):]: v for k, v in params.items()
                       if k.startswith("embed.")}
            nparams = {k[len("norm."):]: v for k, v in params.items()
                       if k.startswith("norm.")}
            hparams = {k[len("head."):]: v for k, v in params.items()
                       if k.startswith("head.")}
            if cdt is not None:
                bparams = jax.tree_util.tree_map(
                    lambda a: a.astype(cdt) if jnp.issubdtype(a.dtype, jnp.floating) else a,
                    bparams)
                eparams = jax.tree_util.tree_map(
                    lambda a: a.astype(cdt) if jnp.issubdtype(a.dtype, jnp.floating) else a,
                    eparams)
                hparams = jax.tree_util.tree_map(
                    lambda a: a.astype(cdt) if jnp.issubdtype(a.dtype, jnp.floating) else a,
                    hparams)

            h, _ = functional_call(embed, eparams, {}, x)
            h = h.astype(cdt) if cdt is not None else h
            B, Sq, H = h.shape
            mb = B // M
            h_micro = h.reshape(M, mb, Sq, H)
            # keep the per-microbatch batch dim sharded over the data axes
            h_micro = jax.lax.with_sharding_constraint(
                h_micro, NamedSharding(mesh, P(None, ("dp", "sharding"), None, None)))

            def head_loss(norm_p, head_p, hh, yy):
                """norm (f32) + lm head (compute dtype) + CE, mean per token.

                CE picks the label logit with a one-hot contraction, not a
                gather: gathers are slow on TPU and XLA's SPMD partitioner
                cannot partition them inside the partial-manual pp region
                (PartitionGather check-fails)."""
                h32 = hh.astype(jnp.float32)
                hn, _ = functional_call(norm, norm_p, {}, h32)
                logits, _ = functional_call(
                    head, head_p, {}, hn.astype(cdt) if cdt is not None else hn)
                logits = logits.astype(jnp.float32)
                logp = jax.nn.log_softmax(logits, axis=-1)
                onehot = jax.nn.one_hot(yy.astype(jnp.int32), logits.shape[-1],
                                        dtype=logp.dtype)
                return -jnp.mean(jnp.sum(logp * onehot, axis=-1))

            if S > 1 and self.pp_schedule == "1f1b":
                from ..distributed.pipeline import make_pipeline_1f1b_loss

                def mb_loss(ep, hh, yy):
                    return head_loss(ep["norm"], ep["head"], hh, yy)

                ploss = make_pipeline_1f1b_loss(stage_fn, mb_loss, mesh, S)
                y_micro = y.reshape(M, mb, Sq)
                return ploss(bparams, {"norm": nparams, "head": hparams},
                             h_micro, y_micro)

            if S > 1 and self.pp_schedule == "interleaved":
                from ..distributed.pipeline import (
                    interleave_stage_params, spmd_pipeline_interleaved)

                vpp = self.vpp

                def to_chunks(a):
                    # [S, L/S, ...] -> [L, ...] -> [S*vpp, L/(S*vpp), ...]
                    L_total = a.shape[0] * a.shape[1]
                    lpc = L_total // (S * vpp)
                    return a.reshape((L_total,) + a.shape[2:]) \
                        .reshape((S * vpp, lpc) + a.shape[2:])

                chunked = jax.tree_util.tree_map(to_chunks, bparams)
                inter = interleave_stage_params(chunked, S)
                h_micro = spmd_pipeline_interleaved(
                    stage_fn, inter, h_micro, mesh, S, vpp)
            elif S > 1:
                h_micro = spmd_pipeline(stage_fn, bparams, h_micro, mesh, S)
            else:
                squeezed = jax.tree_util.tree_map(lambda a: a.reshape((-1,) + a.shape[2:]), bparams)
                h_micro = jax.vmap(lambda hm: stage_fn(squeezed, hm))(h_micro)

            h = h_micro.reshape(B, Sq, H)
            return head_loss(nparams, hparams, h, y)

        def train_step(params, opt_state, lr, x, y):
            loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
            new_params, new_opt = opt.apply_gradients(params, grads, opt_state, lr)
            return loss, new_params, new_opt

        if self.offload:
            pshard = {n: NamedSharding(mesh, s) for n, s in self._pspecs.items()}

            def grad_step(params, x, y):
                return jax.value_and_grad(loss_fn)(params, x, y)

            return jax.jit(grad_step, in_shardings=(pshard, None, None),
                           out_shardings=(None, pshard))

        pshard = {n: NamedSharding(mesh, s) for n, s in self._pspecs.items()}
        oshard = {n: {k: NamedSharding(mesh, s) for k, s in st.items()}
                  for n, st in self._ospecs.items()}
        return jax.jit(
            train_step,
            in_shardings=(pshard, oshard, None, None, None),
            out_shardings=(None, pshard, oshard),
            donate_argnums=(0, 1),
        )

    # ------------------------------------------------------------------
    def step(self, x, y):
        if self._state is None:
            self._init_state()
        if self._step_fn is None:
            self._step_fn = self._build_step()
        params, opt_state = self._state
        data_sharding = NamedSharding(self.mesh, P(("dp", "sharding"), None))

        def _put(a):
            # device-resident arrays reshard in place; never bounce via host
            if isinstance(a, jax.Array):
                return jax.device_put(a, data_sharding)
            return jax.device_put(np.asarray(a), data_sharding)

        x = _put(x)
        y = _put(y)
        lr = jnp.asarray(self.optimizer.get_lr(), jnp.float32)
        if self.offload:
            loss, grads = self._step_fn(params, x, y)
            grads_np = jax.tree_util.tree_map(np.asarray,
                                              jax.device_get(grads))
            del grads
            cpu = jax.devices("cpu")[0]
            with jax.default_device(cpu):  # update math on the CPU backend
                new_master, new_opt = self.optimizer.apply_gradients(
                    self._host_master, grads_np, self._host_opt,
                    float(self.optimizer.get_lr()))
            self._host_master = jax.tree_util.tree_map(np.asarray, new_master)
            self._host_opt = jax.tree_util.tree_map(np.asarray, new_opt)
            # release the old device params BEFORE uploading: double
            # residency would cost the ~4 bytes/param the offload tier is
            # buying back on HBM-limited configs
            self._state = None
            del params
            new_params = {n: jax.device_put(
                self._host_master[n],
                NamedSharding(self.mesh, self._pspecs[n]))
                for n in self._host_master}
            self._state = (new_params, None)
            self._step_count += 1
            return loss
        loss, params, opt_state = self._step_fn(params, opt_state, lr, x, y)
        self._state = (params, opt_state)
        self._step_count += 1
        return loss

    def compile(self, x, y):
        """Trace+compile without executing (AOT) — used by dryrun."""
        if self._state is None:
            self._init_state()
        if self._step_fn is None:
            self._step_fn = self._build_step()
        return self._step_fn

    def num_params(self):
        if self._state is None:
            self._init_state()
        return sum(int(np.prod(v.shape)) for v in self._state[0].values())
