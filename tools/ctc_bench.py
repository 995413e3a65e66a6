"""CTC kernel benchmark: Pallas T-tiled lattice vs the lax.scan lattice.

The timed region is ONE dispatch (an in-jit lax.scan over grad steps), so
host dispatch overhead stays out of the comparison. Not measured on this
code: run it on the chip and read its output.

Sequences that fit the VMEM budget run as a SINGLE tile — zero padding;
longer ones are time-tiled in even splits (kernels/ctc.py)."""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np, jax, jax.numpy as jnp
import paddle_tpu as paddle
from paddle_tpu.kernels import set_use_pallas
from paddle_tpu.kernels.ctc import ctc_loss_pallas
from paddle_tpu.nn import functional as F

rng = np.random.RandomState(0)
REPS = 8

def bench(T, B, C, L):
    lp = jax.nn.log_softmax(jnp.asarray(rng.randn(T, B, C), jnp.float32), axis=-1)
    lbl = jnp.asarray(rng.randint(1, C, (B, L)).astype(np.int64))
    il = jnp.asarray(np.full((B,), T, np.int64))
    ll = jnp.asarray(np.full((B,), L, np.int64))

    def loop(fn):
        @jax.jit
        def run(a):
            def body(carry, i):
                g = jax.grad(fn)(a + i.astype(jnp.float32) * 1e-6)
                return carry + jnp.sum(g), 0
            tot, _ = jax.lax.scan(body, jnp.float32(0), jnp.arange(REPS))
            return tot
        return run

    pal_fn = lambda a: jnp.sum(ctc_loss_pallas(a, lbl, il, ll, 0))
    set_use_pallas(False)
    try:
        scan_fn = lambda a: F.ctc_loss(
            paddle.to_tensor(a), paddle.to_tensor(lbl), paddle.to_tensor(il),
            paddle.to_tensor(ll), reduction="sum")._value
        scan_run = loop(scan_fn)
        jax.block_until_ready(scan_run(lp))
    finally:
        set_use_pallas(None)
    pal_run = loop(pal_fn)
    jax.block_until_ready(pal_run(lp))

    def timed(run, n=3):
        best = 1e9
        for _ in range(n):
            t0 = time.monotonic()
            float(np.asarray(run(lp)))
            best = min(best, (time.monotonic() - t0) / REPS)
        return best

    t_p, t_s = timed(pal_run), timed(scan_run)
    print(f"T={T} B={B} C={C} L={L}: pallas {t_p*1e3:.1f} ms  scan {t_s*1e3:.1f} ms  speedup {t_s/t_p:.2f}x")

bench(256, 32, 1024, 48)
bench(2048, 16, 1024, 48)
bench(4096, 8, 512, 96)
