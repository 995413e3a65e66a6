"""Train driver: the program's pipeline trainer's ``step`` on a one-mesh job,
as ``chip_smoke.train_leg`` builds it, with steps enqueued back to back for
the window on batches staged on the device before it and taken in turn
(``stage_batches``).
Which trainer, and how its state lays the weights' leaves out, is the
configuration's architecture's to say (``ctx.arch``: ``build_trainer``,
``shapes``, ``trainer_layout``, ``trainer_leaf_norms``).

Set-up builds ONE trainer, puts the benchmark's weights (from the seed) into
its state, drives it through its first steps on batches whose rows all
differ, reads what ``correct`` compares (each step's loss; the first
gradient's norm per leaf, from AdamW's first moment after one step; the norm
of each leaf's change after the steps), and hands that same trainer to the
window. After the window: peak memory read, the trainer freed, and the plain
reference follows the same steps from the same seed.
"""
from __future__ import annotations

import collections
import gc
import math
import os
import time

import numpy as np

from benchmark.lib import harness, traffic as traffic_mod
from benchmark.lib import weights as weights_mod


def build_trainer(ctx):
    import jax

    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.optimizer import AdamW

    arch = ctx.arch
    cfg, tr, oc = ctx.cfg, ctx.cfg["trainer"], ctx.cfg["optimizer"]
    if tr.get("remat_policy"):
        os.environ["PADDLE_TPU_REMAT_POLICY"] = tr["remat_policy"]
    mesh = build_mesh(degrees=tr["mesh"])
    opt = AdamW(learning_rate=oc["learning_rate"], beta1=oc["beta1"],
                beta2=oc["beta2"], epsilon=oc["epsilon"],
                weight_decay=oc["weight_decay"])
    trainer = arch.build_trainer(cfg, mesh, opt)
    trainer._init_state()
    params, opt_state = trainer._state
    shardings = {n: v.sharding for n, v in params.items()}
    shapes = {n: v.shape for n, v in params.items()}
    for v in params.values():
        v.delete()
    leaves = arch.shapes(cfg)

    def build(key):
        return arch.trainer_layout(
            weights_mod.build_flat(key, leaves, cfg["dtype"]), cfg)

    new = jax.jit(build, out_shardings=shardings)(
        weights_mod.seed_key(ctx.seed))
    if {n: v.shape for n, v in new.items()} != shapes:
        raise ValueError("the trainer's state has other leaves or shapes "
                         "than the benchmark's weights")
    trainer._state = (new, opt_state)
    return trainer, mesh, build


def stage_batches(ctx, mesh):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(mesh, P(("dp", "sharding"), None))
    host = traffic_mod.train_batches(ctx.traffic, ctx.cfg["vocab_size"],
                                     ctx.seed)
    dev = [(jax.device_put(x.astype(np.int64), sharding),
            jax.device_put(y.astype(np.int64), sharding)) for x, y in host]
    return host, dev


def first_steps(ctx, trainer, bufs, build):
    """Drive the trainer through the check's steps; return its readings."""
    import jax
    import jax.numpy as jnp

    n_steps = int(ctx.traffic["check"]["steps"])
    beta1 = ctx.cfg["optimizer"]["beta1"]
    losses, grad_norms = [], None
    for i in range(n_steps):
        loss = jax.block_until_ready(trainer.step(*bufs[i % len(bufs)]))
        losses.append(float(np.asarray(loss)))
        if i == 0:
            m1 = {n: st["moment1"] for n, st in trainer._state[1].items()}
            grad_norms = {n: float(v) / (1.0 - beta1) for n, v in
                          jax.jit(ctx.arch.trainer_leaf_norms)(m1).items()}

    def change(params, key):
        init = build(key)
        return ctx.arch.trainer_leaf_norms(
            {n: params[n].astype(jnp.float32) - init[n].astype(jnp.float32)
             for n in params})

    upd = jax.jit(change)(trainer._state[0], weights_mod.seed_key(ctx.seed))
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": {n: float(v) for n, v in upd.items()}}


def reference_steps(ctx, host_batches, linear=None, keep_rows=None):
    """The plain reference through the same steps from the same seed.
    ``linear`` swaps the matmul (the int8 control); ``keep_rows`` keeps only
    the first rows of each batch, the mean taken over them (the half-batch
    fault).

    To fit the chip in float32: rows go through in blocks inside one scan
    (its backward pass accumulates the gradient in place), and AdamW's
    moments wait on the host between steps, a leaf at a time."""
    import jax
    import jax.numpy as jnp

    ref, cfg, oc = ctx.reference, ctx.cfg, ctx.cfg["optimizer"]
    linear = linear or ref.f32_linear
    chk = ctx.traffic["check"]
    rows = int(chk.get("rows", 1))
    n_steps = int(chk["steps"])
    leaves = ctx.arch.shapes(cfg)
    w = weights_mod.make_weights(leaves, ctx.seed, "float32")
    moments = {}                             # leaf -> (m, v) as numpy

    def total(w, xs, ys):                    # xs, ys: [blocks, rows, seq]
        def body(acc, xy):
            return acc + ref.loss_sum(cfg, w, xy[0], xy[1], linear), None

        return jax.lax.scan(body, jnp.float32(0.0), (xs, ys))[0]

    grad_fn = jax.jit(jax.value_and_grad(total))
    norms = jax.jit(lambda t: {n: jnp.sqrt(jnp.sum(jnp.square(a)))
                               for n, a in t.items()})

    def update(p, g, m, v, t, scale):
        return ref.adamw_step(
            p, g * scale, m, v, t, oc["learning_rate"], oc["beta1"],
            oc["beta2"], oc["epsilon"], oc["weight_decay"])

    update = jax.jit(update, donate_argnums=(0, 2, 3), static_argnums=(4,))

    losses, grad_norms = [], None
    for t in range(n_steps):
        x, y = host_batches[t % len(host_batches)]
        if keep_rows is not None:
            x, y = x[:keep_rows], y[:keep_rows]
        shape = (x.shape[0] // rows, rows, x.shape[1])
        total_loss, g = grad_fn(w, jnp.asarray(x.reshape(shape)),
                                jnp.asarray(y.reshape(shape)))
        scale = 1.0 / x.size
        losses.append(float(total_loss) * scale)
        if t == 0:
            grad_norms = {n: float(a) * scale for n, a in norms(g).items()}
        for n in list(w):
            if n in moments:
                m, v = (jnp.asarray(a) for a in moments.pop(n))
            else:
                m, v = jnp.zeros_like(w[n]), jnp.zeros_like(w[n])
            w[n], m, v = update(w[n], g.pop(n), m, v, t + 1, scale)
            if t + 1 < n_steps:
                moments[n] = (np.asarray(m), np.asarray(v))
            del m, v
    key = weights_mod.seed_key(ctx.seed)
    upd = {}
    for n in w:
        leaf_change = jax.jit(lambda a, key, n=n: jnp.sqrt(jnp.sum(jnp.square(
            a - weights_mod.build_flat(key, leaves, "float32", (n,))[n]))))
        upd[n] = float(leaf_change(w[n], key))
    return {"losses": losses, "grad_norms": grad_norms, "update_norms": upd}


def compare(got, ref):
    """The three numbers ``correct`` compares: the worst step's relative loss
    gap, and by the worst leaf the gap between norms (not the norm of the
    difference) of the first gradient and of the parameters' change, each
    against the reference's norm of that leaf or of the median leaf,
    whichever is larger. Leaves whose reference gradient is under a
    thousandth of the median leaf's move by round-off alone under Adam and
    are left out of the change."""
    loss_gap = max(abs(a - b) / abs(b)
                   for a, b in zip(got["losses"], ref["losses"]))
    if not all(math.isfinite(x) for x in got["losses"]):
        loss_gap = float("inf")
    g_med = float(np.median(list(ref["grad_norms"].values())))
    u_med = float(np.median(list(ref["update_norms"].values())))

    def worst(key, med, leaves):
        gaps = {n: abs(got[key][n] - ref[key][n]) / max(ref[key][n], med)
                for n in leaves}
        n = max(gaps, key=gaps.get)
        return gaps[n], n

    leaves = list(ref["grad_norms"])
    moving = [n for n in leaves if ref["grad_norms"][n] >= 1e-3 * g_med]
    g_gap, g_leaf = worst("grad_norms", g_med, leaves)
    u_gap, u_leaf = worst("update_norms", u_med, moving)
    return {"loss_gap_max": loss_gap, "grad_norm_gap_max": g_gap,
            "update_norm_gap_max": u_gap,
            "worst_leaves": {"grad": g_leaf, "update": u_leaf}}


def run(ctx):
    import jax

    from benchmark.drivers_common import Tracing

    job = ctx.traffic
    trainer, mesh, build = build_trainer(ctx)
    host_batches, bufs = stage_batches(ctx, mesh)
    ctx.log("trainer built with the benchmark's weights; batches staged")
    got = first_steps(ctx, trainer, bufs, build)
    ctx.log(f"first steps: losses {got['losses']}")
    tokens_per_step = int(job["batch"]) * int(job["seq"])
    n_check = int(job["check"]["steps"])

    tracer = Tracing(ctx) if ctx.trace else None
    seconds = ctx.seconds
    if tracer is not None:
        seconds = min(seconds, float(job.get("trace_seconds", 6)))
        tracer.start()
    compiles0 = ctx.compiles.n
    t_open = time.monotonic()
    setup_s = t_open - ctx.t0
    ctx.log(f"window opens after {setup_s:.1f} s of set-up")
    inflight = collections.deque()
    steps = 0
    loss = None
    done_at, longest_s = t_open, 0.0   # a stall shows as one long interval
    while time.monotonic() - t_open < seconds:
        loss = trainer.step(*bufs[(n_check + steps) % len(bufs)])
        steps += 1
        inflight.append(loss)
        if len(inflight) > 2:          # run two steps ahead of the device
            jax.block_until_ready(inflight.popleft())
            now = time.monotonic()
            done_at, longest_s = now, max(longest_s, now - done_at)
    last = float(np.asarray(jax.block_until_ready(loss)))
    t_close = time.monotonic()
    compiles_in_window = ctx.compiles.n - compiles0
    trace = tracer.stop() if tracer is not None else None
    mem_peak = harness.memory_peak_bytes()
    window_s = t_close - t_open
    ctx.log(f"window: {steps} steps in {window_s:.3f} s, the longest between "
            f"two results {longest_s * 1e3:.0f} ms, last loss {last}, "
            f"compilations in the window {compiles_in_window}")

    # free the program's state before the reference takes the chip
    trainer._state = None
    trainer._step_fn = None
    del trainer, bufs, inflight, loss
    gc.collect()

    t = time.monotonic()
    ref = reference_steps(ctx, host_batches)
    numbers = compare(got, ref)
    ctx.log(f"reference in {time.monotonic() - t:.1f} s: losses "
            f"{ref['losses']}; compared {numbers}")
    limits = job["check"]["limits"]
    checks = {k: harness.check(numbers[k], limits[k]) for k in limits
              if k in numbers}
    checks["last_loss_finite"] = harness.check(
        int(math.isfinite(last)), 1, "eq")
    checks["compiles_in_window"] = harness.check(compiles_in_window, 0, "eq")
    out = {"e2e": {"train_tokens_per_s_per_chip":
                   steps * tokens_per_step / window_s / ctx.cell["chips"],
                   "setup_s": setup_s},
           "attempted": steps, "failed": 0 if math.isfinite(last) else 1,
           "checks": checks, "trace": trace, "memory_peak_bytes": mem_peak,
           "facts": {"train_tokens": steps * tokens_per_step,
                     "window_s": window_s, "driver": "train"}}
    if ctx.control:
        ctl = compare(reference_steps(ctx, host_batches,
                                      linear=ctx.reference.int8_linear), ref)
        half = compare(reference_steps(
            ctx, host_batches, keep_rows=max(int(job["batch"]) // 2, 1)), ref)
        out["control"] = {"int8": ctl, "half_batch": half}
        ctx.log(f"control int8 {ctl}; fault half batch {half}")
    return out
