"""On-chip attention bench: Pallas flash (masked / varlen / dropout / plain)
vs the XLA einsum composition.

Measurement discipline (see tools/ctc_bench.py): the whole timed loop is ONE
jit — a lax.scan over fwd+bwd steps with per-step distinct inputs — and the
window closes with a host readback of a scalar depending on every step.

Usage: python tools/attn_bench.py [--json OUT.json]
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

from paddle_tpu.kernels.flash_attention import (  # noqa: E402
    flash_attention_pallas, flash_attn_varlen_pallas)
from paddle_tpu.nn.functional.attention import sdpa_ref  # noqa: E402

STEPS = 20


def _timed(step_fn, init, steps=STEPS):
    """step_fn(carry, i) -> carry; returns (seconds_per_step, readback)."""

    @jax.jit
    def run(init):
        def body(c, i):
            return step_fn(c, i), ()

        c, _ = jax.lax.scan(body, init, jnp.arange(steps))
        return jax.tree_util.tree_reduce(
            lambda a, x: a + jnp.sum(x.astype(jnp.float32)), c, 0.0)

    r = run(init)
    float(r)  # compile + warm
    t0 = time.perf_counter()
    r = run(init)
    val = float(r)  # host readback closes the window
    dt = (time.perf_counter() - t0) / steps
    return dt, val


def bench_masked(S, B=4, H=8, D=128, dtype=jnp.bfloat16):
    rng = np.random.RandomState(0)
    q0 = jnp.asarray(rng.randn(B, S, H, D), dtype)
    k0 = jnp.asarray(rng.randn(B, S, H, D), dtype)
    v0 = jnp.asarray(rng.randn(B, S, H, D), dtype)
    g = jnp.asarray(rng.randn(B, S, H, D), dtype)
    lens = jnp.asarray(rng.randint(S // 2, S, size=B), jnp.int32)
    amask = (jnp.arange(S)[None, :] < lens[:, None])[:, None, None, :]

    def mk(attn):
        def step(q, i):
            # fold the step index in so every step sees distinct inputs
            qi = q + (i * 1e-6).astype(q.dtype)

            def loss(qq):
                return jnp.vdot(attn(qq, k0, v0).astype(jnp.float32),
                                g.astype(jnp.float32))

            return qi + jax.grad(loss)(qi) * 1e-6

        return step

    flash = mk(lambda q, k, v: flash_attention_pallas(
        q, k, v, attn_mask=amask, is_causal=True))
    ein = mk(lambda q, k, v: sdpa_ref(q, k, v, attn_mask=amask, is_causal=True))
    tf, _ = _timed(flash, q0)
    te, _ = _timed(ein, q0)
    return {"case": f"masked_causal_S{S}", "flash_ms": tf * 1e3,
            "einsum_ms": te * 1e3, "speedup": te / tf}


def bench_plain(S, B=4, H=8, D=128, dtype=jnp.bfloat16):
    rng = np.random.RandomState(0)
    q0 = jnp.asarray(rng.randn(B, S, H, D), dtype)
    k0 = jnp.asarray(rng.randn(B, S, H, D), dtype)
    v0 = jnp.asarray(rng.randn(B, S, H, D), dtype)
    g = jnp.asarray(rng.randn(B, S, H, D), dtype)

    def mk(attn):
        def step(q, i):
            qi = q + (i * 1e-6).astype(q.dtype)

            def loss(qq):
                return jnp.vdot(attn(qq, k0, v0).astype(jnp.float32),
                                g.astype(jnp.float32))

            return qi + jax.grad(loss)(qi) * 1e-6

        return step

    flash = mk(lambda q, k, v: flash_attention_pallas(q, k, v, is_causal=True))
    ein = mk(lambda q, k, v: sdpa_ref(q, k, v, is_causal=True))
    tf, _ = _timed(flash, q0)
    te, _ = _timed(ein, q0)
    return {"case": f"plain_causal_S{S}", "flash_ms": tf * 1e3,
            "einsum_ms": te * 1e3, "speedup": te / tf}


def bench_varlen(total, nseq, H=8, D=128, dtype=jnp.bfloat16):
    """Packed varlen vs running the padded einsum over the packed layout with
    an equivalent block-diagonal mask (what a user without varlen would do)."""
    rng = np.random.RandomState(0)
    cuts = np.sort(rng.choice(np.arange(1, total), nseq - 1, replace=False))
    cu = jnp.asarray(np.concatenate([[0], cuts, [total]]), jnp.int32)
    q0 = jnp.asarray(rng.randn(total, H, D), dtype)
    k0 = jnp.asarray(rng.randn(total, H, D), dtype)
    v0 = jnp.asarray(rng.randn(total, H, D), dtype)
    g = jnp.asarray(rng.randn(total, H, D), dtype)

    seg = jnp.searchsorted(cu, jnp.arange(total), side="right")
    block_mask = (seg[:, None] == seg[None, :])[None, None]  # [1,1,T,T]

    def step_flash(q, i):
        qi = q + (i * 1e-6).astype(q.dtype)

        def loss(qq):
            return jnp.vdot(
                flash_attn_varlen_pallas(qq, k0, v0, cu, cu, causal=True)
                .astype(jnp.float32), g.astype(jnp.float32))

        return qi + jax.grad(loss)(qi) * 1e-6

    def step_ein(q, i):
        qi = q + (i * 1e-6).astype(q.dtype)

        def loss(qq):
            return jnp.vdot(
                sdpa_ref(qq[None], k0[None], v0[None], attn_mask=block_mask,
                         is_causal=True)[0].astype(jnp.float32),
                g.astype(jnp.float32))

        return qi + jax.grad(loss)(qi) * 1e-6

    tf, _ = _timed(step_flash, q0)
    te, _ = _timed(step_ein, q0)
    return {"case": f"varlen_T{total}_n{nseq}", "flash_ms": tf * 1e3,
            "einsum_ms": te * 1e3, "speedup": te / tf}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None)
    args = ap.parse_args()
    results = []
    for fn in (lambda: bench_plain(2048), lambda: bench_plain(4096),
               lambda: bench_masked(2048), lambda: bench_masked(4096),
               lambda: bench_varlen(4096, 8), lambda: bench_varlen(8192, 16)):
        r = fn()
        results.append(r)
        print(json.dumps(r))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"device": str(jax.devices()[0]), "steps": STEPS,
                       "results": results}, f, indent=1)


if __name__ == "__main__":
    main()
