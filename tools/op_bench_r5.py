"""Round-5 op-bench loop (VERDICT r4 next #5): measure the Llama/Conformer
profile's hot non-matmul ops — fused RMSNorm(+residual), RoPE application,
and 32k-vocab softmax cross-entropy — XLA composition vs Pallas kernel,
on chip, and record the keep/drop DECISION per candidate.

Measurement discipline (tools/ctc_bench.py): one jit per timed loop, a
lax.scan over steps with per-step distinct inputs, host readback closing
the window.

Usage: python tools/op_bench_r5.py [--json OPBENCH_r05.json]
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, ".")

STEPS = 30


def _timed(step_fn, init, *consts):
    """consts are passed as jit ARGUMENTS (device buffers) — closure capture
    would bake them into the compiled program as constants."""

    @jax.jit
    def run(init, *consts):
        def body(c, i):
            return step_fn(c, i, *consts), ()

        c, _ = jax.lax.scan(body, init, jnp.arange(STEPS))
        return jax.tree_util.tree_reduce(
            lambda a, x: a + jnp.sum(x.astype(jnp.float32)), c, 0.0)

    float(run(init, *consts))  # compile + warm
    t0 = time.perf_counter()
    val = float(run(init, *consts))
    return (time.perf_counter() - t0) / STEPS, val


def bench_rmsnorm(B=8, S=2048, H=4096, dtype=jnp.bfloat16):
    from paddle_tpu.kernels.rmsnorm import rmsnorm_residual_pallas

    rng = np.random.RandomState(0)
    x0 = jnp.asarray(rng.randn(B * S, H), dtype)
    r0 = jnp.asarray(rng.randn(B * S, H), dtype)
    w = jnp.asarray(rng.randn(H), jnp.float32)
    g = jnp.asarray(rng.randn(B * S, H), dtype)

    def xla_impl(x, r):
        s = (x + r).astype(jnp.float32)
        out = s * jax.lax.rsqrt(jnp.mean(s * s, -1, keepdims=True) + 1e-6)
        return (out * w).astype(x.dtype), s.astype(x.dtype)

    def mk(fn):
        def step(x, i, r, gg):
            xi = x + (i * 1e-6).astype(x.dtype)

            def loss(xx):
                o, ssum = fn(xx, r)
                return jnp.vdot(o.astype(jnp.float32), gg.astype(jnp.float32))

            return xi + jax.grad(loss)(xi) * 1e-6

        return step

    tp, _ = _timed(mk(lambda x, r: rmsnorm_residual_pallas(x, r, w)), x0, r0, g)
    tx, _ = _timed(mk(xla_impl), x0, r0, g)
    return {"op": "rmsnorm_residual_fwd_bwd", "shape": f"[{B * S},{H}]",
            "pallas_ms": tp * 1e3, "xla_ms": tx * 1e3, "speedup": tx / tp}


def bench_softmax_ce(N=4096, V=32000):
    from paddle_tpu.kernels.softmax_ce import softmax_ce_pallas

    rng = np.random.RandomState(0)
    x0 = jnp.asarray(rng.randn(N, V), jnp.float32)
    lab = jnp.asarray(rng.randint(0, V, N), jnp.int32)

    def xla_impl(x, labels):
        ls = jax.nn.log_softmax(x, axis=-1)
        return -jnp.take_along_axis(ls, labels[:, None], axis=-1)[:, 0]

    def mk(fn):
        def step(x, i, labels):
            xi = x + (i * 1e-6).astype(x.dtype)

            def loss(xx):
                return jnp.sum(fn(xx, labels))

            return xi + jax.grad(loss)(xi) * 1e-6

        return step

    tp, _ = _timed(mk(softmax_ce_pallas), x0, lab)
    tx, _ = _timed(mk(xla_impl), x0, lab)
    return {"op": "softmax_ce_32k_fwd_bwd", "shape": f"[{N},{V}]",
            "pallas_ms": tp * 1e3, "xla_ms": tx * 1e3, "speedup": tx / tp}


def bench_rope(B=8, S=2048, H=32, D=128):
    """RoPE application: measured XLA-only — the composition is a pure
    elementwise mul/add over [B,S,H,D] that XLA fuses into the neighboring
    matmul epilogue; a standalone kernel would ADD an HBM round trip. The
    recorded decision is 'do not build' with the bandwidth arithmetic."""
    rng = np.random.RandomState(0)
    q0 = jnp.asarray(rng.randn(B, S, H, D), jnp.bfloat16)
    pos = np.arange(S)
    inv = 1.0 / (10000.0 ** (np.arange(0, D, 2) / D))
    ang = np.einsum("s,d->sd", pos, inv)
    cos = jnp.asarray(np.cos(ang), jnp.float32)
    sin = jnp.asarray(np.sin(ang), jnp.float32)

    def rope(q):
        q1, q2 = q[..., ::2].astype(jnp.float32), q[..., 1::2].astype(jnp.float32)
        c = cos[None, :, None, :]
        s = sin[None, :, None, :]
        out = jnp.stack([q1 * c - q2 * s, q1 * s + q2 * c], axis=-1)
        return out.reshape(q.shape).astype(q.dtype)

    def step(q, i):
        qi = q + (i * 1e-6).astype(q.dtype)
        return rope(qi) * (1.0 - 1e-6) + qi * 1e-6

    t, _ = _timed(step, q0)  # cos/sin tables are small; closure is fine
    bytes_moved = 2 * q0.size * 2  # read+write bf16
    return {"op": "rope_fwd", "shape": f"[{B},{S},{H},{D}]",
            "xla_ms": t * 1e3,
            "achieved_GBps": bytes_moved / t / 1e9,
            "decision": ("not built: elementwise map fused by XLA into the "
                         "neighboring matmul epilogue; a standalone kernel "
                         "adds an HBM round trip")}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    results = []
    for fn in (bench_rmsnorm, bench_softmax_ce, bench_rope):
        r = fn()
        results.append(r)
        print(json.dumps(r))
    for r in results:
        if "speedup" in r and "decision" not in r:
            r["decision"] = ("keep: measured win" if r["speedup"] > 1.05 else
                             "kernel stays OPT-IN: XLA matches/beats it "
                             "on chip (policy default keeps XLA)")
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"device": str(jax.devices()[0]), "steps": STEPS,
                       "results": results}, f, indent=1)
        print(f"# wrote {args.json}", file=sys.stderr)


if __name__ == "__main__":
    main()
