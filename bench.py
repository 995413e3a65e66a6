"""Benchmark harness — prints ONE JSON line.

Measures decoder-LM training throughput (tokens/sec/chip) and MFU on a TPU
via the trainer's own FLOP counters and ``paddle_tpu.profiler.mfu`` (parity:
/root/reference/python/paddle/profiler/timer.py:349 ips; BASELINE.md north
star: >=45% MFU at the 7B DP+TP recipe).

One process, one configuration, a TPU or an error. The configuration is the
per-chip slice of Llama-2-7B under the DP+TP recipe — true 7B layer shapes
(hidden 4096, 32 heads, intermediate 11008, vocab 32000, seq 2048); layer
count set to what one v5e chip's HBM holds with f32 weights + Adam moments
(2 layers + embed/head = 667M params), batch 4, no rematerialization
(``$PADDLE_TPU_REMAT_POLICY`` overrides, as it does for the trainer).

A chip belongs to one process at a time, so nothing here starts a child that
needs the device. Every window ends in ``block_until_ready``; the headline is
the best window, and every window's batch cost is in the JSON.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time


def _timed_steps(trainer, bufs, n):
    """Run n steps over the staged batch rotation and wait for the last.
    Returns (elapsed_seconds, last_loss_float)."""
    import jax

    t0 = time.monotonic()
    loss = None
    for i in range(n):
        bx, by = bufs[i % len(bufs)]
        loss = trainer.step(bx, by)
    loss = jax.block_until_ready(loss)
    return time.monotonic() - t0, float(loss)


def _make_bufs(mesh, cfg, batch, seq, n_bufs=4, seed=1):
    """Distinct device-staged batches: fresh data per step without paying
    host->device transfers inside the window (a real input pipeline
    prefetches the same way; one fixed batch would memorize)."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    data_sharding = NamedSharding(mesh, P(("dp", "sharding"), None))
    rng = np.random.RandomState(seed)
    bufs = []
    for _ in range(n_bufs):
        bx = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
        by = rng.randint(0, cfg.vocab_size, (batch, seq)).astype(np.int64)
        bufs.append((jax.device_put(bx, data_sharding),
                     jax.device_put(by, data_sharding)))
    return bufs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=10,
                    help="steps per measurement window")
    ap.add_argument("--windows", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=2048)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--chaos", action="store_true",
                    help="opt-in: run the serving chaos sweep "
                         "(tools/chaos_run.py fault-plan battery, on CPU) "
                         "instead of the training bench")
    ap.add_argument("--metrics-out", default=None,
                    help="write the telemetry registry JSON snapshot here")
    args, chaos_argv = ap.parse_known_args()

    def _write_metrics():
        if args.metrics_out:
            from paddle_tpu import telemetry
            telemetry.registry().snapshot_json(args.metrics_out)
            print(f"# metrics snapshot -> {args.metrics_out}",
                  file=sys.stderr)

    if args.chaos:
        from tools.chaos_run import main as chaos_main
        rc = chaos_main(chaos_argv)
        _write_metrics()
        return rc
    if chaos_argv:
        ap.error(f"unrecognized arguments: {' '.join(chaos_argv)}")

    import jax

    from paddle_tpu import profiler as prof
    from paddle_tpu.distributed.mesh import build_mesh
    from paddle_tpu.models import LlamaConfig
    from paddle_tpu.models.llama_pipeline import LlamaPipelineTrainer
    from paddle_tpu.optimizer import AdamW
    from paddle_tpu.telemetry import perf as _perf
    from paddle_tpu.utils import compile_cache

    device = jax.devices()[0]
    if jax.default_backend() != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; the default backend here is "
            f"{jax.default_backend()!r} ({device.device_kind}). CPU runs "
            f"belong to the tests, never to this metric.")
    compile_cache.enable()

    # Llama-2-7B per-chip slice: exact 7B matmul shapes, HBM-limited depth
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=4096, intermediate_size=11008,
        num_hidden_layers=args.layers, num_attention_heads=32,
        num_key_value_heads=32, max_position_embeddings=args.seq)
    remat = os.environ.setdefault("PADDLE_TPU_REMAT_POLICY", "off")
    mesh = build_mesh(degrees={"dp": 1})
    trainer = LlamaPipelineTrainer(cfg, mesh, AdamW(learning_rate=1e-4),
                                   n_micro=1, zero_stage=1)
    bufs = _make_bufs(mesh, cfg, args.batch, args.seq)
    compile_s, _ = _timed_steps(trainer, bufs, 1)   # state set-up + compile
    _timed_steps(trainer, bufs, 2)                  # warm
    costs = []
    loss = None
    for _ in range(args.windows):
        dt, loss = _timed_steps(trainer, bufs, args.steps)
        costs.append(dt / args.steps)
    print(f"# windows remat={remat} batch={args.batch}: "
          f"{[round(c, 5) for c in costs]}", file=sys.stderr)

    best_cost = min(costs)
    med_cost = statistics.median(costs)
    tok_per_sec = args.batch * args.seq / best_cost
    # headline MFU counts true matmul FLOPs (input-embedding gather
    # excluded); the raw 6N convention is reported alongside for
    # cross-paper comparability
    mfu = prof.mfu(tok_per_sec, trainer.matmul_flops_per_token(args.seq),
                   device.device_kind)
    mfu_6n = prof.mfu(tok_per_sec, trainer.flops_per_token(args.seq),
                      device.device_kind)
    # north star: >=45% MFU (BASELINE.md config #4)
    result = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tok_per_sec, 2),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.45, 4),
        # provenance stamp: tools/perf_gate.py refuses to compare results
        # across platforms/configs instead of silently passing
        "__meta__": _perf.run_meta(),
        "extra": {
            "mfu": round(mfu, 4),
            "mfu_6n_convention": round(mfu_6n, 4),
            "platform": device.platform,
            "device_kind": device.device_kind,
            "device_count": len(jax.devices()),
            "params": trainer.num_params(),
            "layers": cfg.num_hidden_layers,
            "remat": remat,
            "batch": args.batch,
            "seq": args.seq,
            "compile_s": round(compile_s, 1),
            "windows": args.windows,
            "steps_per_window": args.steps,
            "window_batch_costs": [round(c, 5) for c in costs],
            "batch_cost_best": round(best_cost, 5),
            "batch_cost_median": round(med_cost, 5),
            # a transient stall shows as a window much slower than the best;
            # persistent slowness shows as ALL windows slow
            "transient_variance_flag": (med_cost - best_cost) / best_cost > 0.15,
            "fresh_batches": len(bufs),
            "loss": loss,
            "config_note": (
                f"7B layer shapes at HBM-limited depth (hidden "
                f"{cfg.hidden_size}, heads {cfg.num_attention_heads}, inter "
                f"{cfg.intermediate_size}, vocab {cfg.vocab_size}); headline "
                "= best window; headline mfu excludes the input-embedding "
                "gather; see the window fields for the full record"),
        },
    }
    print(json.dumps(result))
    _write_metrics()
    return 0


if __name__ == "__main__":
    sys.exit(main())
