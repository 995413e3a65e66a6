"""Model benchmark harness — BASELINE.md configs beyond the headline Llama.

The reference's model-level perf gate shells out to an external benchmark
repo (tools/ci_model_benchmark.sh); here each config builds the in-repo
model, jits one full train step through functional_call, and reports
steady-state throughput on the available accelerator. One JSON line per
config (the op-level analogue is tools/op_bench.py).

Usage:
    python tools/model_bench.py [--configs resnet50,ernie,conformer_ctc]
                                [--steps 10] [--smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _train_step_fn(net, loss_fn, opt_update):
    """(params, buffers, opt_state, *batch) -> (loss, params, buffers, opt)"""
    import jax

    from paddle_tpu.nn import functional_call

    def step(params, buffers, opt_state, rng, *batch):
        def lossf(p):
            out, new_buf = functional_call(net, p, buffers, batch[0],
                                           rng=rng, training=True)
            return loss_fn(out, *batch[1:]), new_buf

        (loss, new_buf), grads = jax.value_and_grad(lossf, has_aux=True)(params)
        new_params, new_opt = opt_update(params, grads, opt_state)
        return loss, new_params, new_buf, new_opt

    return step


def _adamw(lr=1e-3):
    """The REAL optimizer's pure functional path (optimizer.py
    apply_gradients) so the benchmark measures the train step users run."""
    from paddle_tpu.optimizer import AdamW

    opt = AdamW(learning_rate=lr)

    def update(params, grads, state):
        return opt.apply_gradients(params, grads, state)

    return opt.init_state_tree, update


def _bench_config(name, build, steps):
    """build() -> (net, loss_fn, batch tuple, unit, samples_per_batch)."""
    import jax

    import paddle_tpu as paddle
    from paddle_tpu.nn import functional_state

    paddle.seed(0)
    net, loss_fn, batches, unit, n_samples = build()
    params, buffers = functional_state(net)
    init, update = _adamw()
    opt_state = init(params)
    # distinct per-step batches, and every window ends in a host readback
    # of a value that depends on every step
    step = jax.jit(_train_step_fn(net, loss_fn, update))
    rng = jax.random.PRNGKey(0)

    loss, params, buffers, opt_state = step(params, buffers, opt_state, rng,
                                            *batches[0])
    float(np.asarray(loss))  # compile + warmup (true completion sync)

    def window(n):
        nonlocal params, buffers, opt_state, loss
        t0 = time.perf_counter()
        tot = None
        for i in range(n):
            loss, params, buffers, opt_state = step(
                params, buffers, opt_state, rng, *batches[i % len(batches)])
            tot = loss if tot is None else tot + loss
        float(np.asarray(tot))
        return (time.perf_counter() - t0) / n

    # best-of-3 windows; all three are recorded
    dts = [window(steps) for _ in range(3)]
    dt = min(dts)
    return {
        "metric": name,
        "value": round(n_samples / dt, 2),
        "unit": unit,
        "extra": {"step_ms": round(dt * 1000, 2),
                  "window_ms": [round(d * 1000, 2) for d in dts],
                  "loss": float(np.asarray(loss)),
                  "platform": jax.devices()[0].platform},
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", default="resnet50,ernie,conformer_ctc")
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()

    import jax

    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F

    on_tpu = jax.devices()[0].platform == "tpu" and not args.smoke
    rng = np.random.RandomState(0)

    def build_resnet50():
        from paddle_tpu.vision.models import resnet18, resnet50

        if on_tpu:
            net, bs, hw = resnet50(), 64, 224
        else:
            net, bs, hw = resnet18(num_classes=10), 2, 32
        batches = [
            (paddle.to_tensor(rng.rand(bs, 3, hw, hw).astype(np.float32))._value,
             paddle.to_tensor(rng.randint(0, 10, (bs,)).astype(np.int64))._value)
            for _ in range(4)]

        def lossf(out, yv):
            import jax.numpy as jnp
            import jax as _j

            return -jnp.mean(jnp.take_along_axis(
                _j.nn.log_softmax(out, -1), yv[:, None], axis=1))

        return net, lossf, batches, "imgs/s/chip", bs

    def build_ernie():
        from paddle_tpu.models import ErnieForMaskedLM, ernie_base, ernie_tiny

        if on_tpu:
            cfg = ernie_base()
            cfg.hidden_dropout_prob = 0.0
            cfg.attention_probs_dropout_prob = 0.0
            bs, seq = 16, 512
        else:
            cfg, bs, seq = ernie_tiny(), 2, 64
        net = ErnieForMaskedLM(cfg)
        batches = [
            (paddle.to_tensor(rng.randint(0, cfg.vocab_size, (bs, seq)).astype(np.int64))._value,
             paddle.to_tensor(rng.randint(0, cfg.vocab_size, (bs, seq)).astype(np.int64))._value)
            for _ in range(4)]

        def lossf(out, yv):
            import jax.numpy as jnp
            import jax as _j

            logits = out[0] if isinstance(out, (tuple, list)) else out
            lp = _j.nn.log_softmax(logits, -1)
            return -jnp.mean(jnp.take_along_axis(lp, yv[..., None], axis=-1))

        return net, lossf, batches, "tokens/s/chip", bs * seq

    def build_conformer_ctc():
        from paddle_tpu.models import ConformerForCTC, conformer_tiny
        from paddle_tpu.models.conformer import ConformerConfig

        if on_tpu:
            cfg = ConformerConfig(dropout=0.0)
            bs, T = 16, 1600  # ~16s of 10ms frames
        else:
            cfg, bs, T = conformer_tiny(), 2, 64
        net = ConformerForCTC(cfg)
        U = 48 if on_tpu else 6
        Tp = T // cfg.subsample
        il = paddle.to_tensor(np.full(bs, Tp, np.int64))
        ul = paddle.to_tensor(np.full(bs, U, np.int64))
        batches = [
            (paddle.to_tensor(rng.rand(bs, T, cfg.input_dim).astype(np.float32))._value,
             paddle.to_tensor(rng.randint(1, cfg.vocab_size, (bs, U)).astype(np.int64))._value,
             il._value, ul._value)
            for _ in range(4)]

        def lossf(out, lblv, ilv, ulv):
            from paddle_tpu.core.autograd import no_grad, pure_mode
            from paddle_tpu.core.tensor import Tensor

            with pure_mode(), no_grad():
                return F.ctc_loss(Tensor._wrap(out), Tensor._wrap(lblv),
                                  Tensor._wrap(ilv), Tensor._wrap(ulv),
                                  reduction="mean")._value

        return net, lossf, batches, "utterances/s/chip", bs

    builders = {"resnet50": build_resnet50, "ernie": build_ernie,
                "conformer_ctc": build_conformer_ctc}
    steps = 3 if args.smoke else args.steps
    rc = 0
    for name in args.configs.split(","):
        try:
            print(json.dumps(_bench_config(name, builders[name.strip()], steps)))
        except Exception as e:
            print(json.dumps({"metric": name, "error": repr(e)[:300]}))
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
