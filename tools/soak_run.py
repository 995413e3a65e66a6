"""Long-run soak driver: hours of trace-driven traffic against a real
fleet under a rolling chaos plan, with pass criteria asserted
continuously.

Where ``chaos_run`` proves one failure mode per scenario, this driver
loops a seeded workload epoch after epoch against a ProcReplica fleet +
gateway while the chaos plan *rotates* — fault-plan degradation, replica
SIGKILL, drain/restart churn, explicit journal compaction — and after
every epoch re-asserts the soak invariants (zero lost accepted
requests, leak sentinel quiet, journal segment/byte/retention bounds,
per-tenant SLO goodput floor). One violated epoch fails the run and
names the epoch + chaos action that broke it.

Usage:

    python tools/soak_run.py --minutes 120 --replicas 3 --fleet proc
    python tools/soak_run.py --epochs 4 --preset tenant-mix --json -
    python tools/soak_run.py --spec my_workload.json --goodput-floor 0.7

The harness itself lives in ``paddle_tpu/serving/soak.py`` (the tier-1
smoke and ``chaos_run --suite soak`` drive the same code);
docs/WORKLOADS.md "Soak pass criteria" documents the contract.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from paddle_tpu.serving.soak import SoakConfig, run_soak          # noqa: E402
from paddle_tpu.serving.workload import generate, load_spec       # noqa: E402


# the rotating chaos catalog; ``kill`` is dropped on 1-replica fleets
# (killing the only replica makes accepted-request loss likely by
# construction, which is a capacity fact, not a robustness bug)
ROLLING_PLANS = [
    {"kind": "plan",
     "plan": "gateway.journal.append:delay=0.01%0.2"},
    {"kind": "kill"},
    {"kind": "plan", "plan": "serving.decode:delay=0.005%0.1"},
    {"kind": "churn"},
    {"kind": "compact"},
    {"kind": "plan", "plan": "router.probe:delay=0.05%0.2"},
]


def build_config(args) -> SoakConfig:
    spec = load_spec(args.spec)
    if args.seed is not None:
        spec.seed = args.seed
    workdir = args.workdir or tempfile.mkdtemp(prefix="soak-")
    max_len = args.prompt_max + args.output_max
    spec.prompt_len["max"] = min(
        int(spec.prompt_len.get("max", args.prompt_max)), args.prompt_max)
    spec.output_len["max"] = min(
        int(spec.output_len.get("max", args.output_max)), args.output_max)
    spec.vocab = args.vocab
    # liveness SLO: the soak's goodput floor asks "did requests finish",
    # not "was TTFT competitive" — a shared-core proc fleet mid-SIGKILL
    # legitimately runs seconds of TTFT
    spec.slo = {"ttft_s": args.slo_ttft_s, "tpot_s": args.slo_tpot_s}
    # one warmup prompt per power-of-two prefill bucket, so compile time
    # stays out of the replay epochs
    warm, p = [], args.block_size
    while p < args.prompt_max:
        warm.append(p)
        p *= 2
    warm.append(args.prompt_max)
    fleet_spec = {
        "seed": 0,
        "llama_tiny": {"vocab": args.vocab, "hidden": args.hidden,
                       "layers": args.layers, "heads": 4, "kv_heads": 2,
                       "inter": 2 * args.hidden, "seq": 2 * max_len},
        "engine": {"block_size": args.block_size,
                   "max_slots": args.slots, "max_model_len": max_len},
        "warmup": warm,
        "stats_interval_s": 0.05,
    }
    chaos = [a for a in ROLLING_PLANS
             if not (a["kind"] in ("kill", "churn")
                     and args.replicas < 2)]
    epochs = args.epochs
    if epochs is None:
        # size the epoch count off the workload's own replay duration
        wall = max(0.5, generate(spec).duration_s * args.time_scale)
        epochs = max(3, int(args.minutes * 60.0 / wall))
    return SoakConfig(
        spec=spec, fleet_spec=fleet_spec, workdir=workdir,
        epochs=epochs, replicas=args.replicas, fleet=args.fleet,
        time_scale=args.time_scale, epoch_wait_s=args.epoch_wait_s,
        chaos=chaos,
        journal={"segment_max_records": args.segment_max_records,
                 "compact_segments": args.compact_segments,
                 "retain_terminal": args.retain_terminal},
        goodput_floor=args.goodput_floor,
        kill_allowed=args.replicas >= 2,
        autoscale=args.autoscale)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--minutes", type=float, default=5.0,
                    help="target soak length (ignored with --epochs)")
    ap.add_argument("--epochs", type=int, default=None,
                    help="explicit epoch count instead of --minutes")
    ap.add_argument("--spec", default="burst",
                    help="workload preset name or spec JSON path")
    ap.add_argument("--seed", type=int, default=None,
                    help="override the spec's seed")
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--fleet", choices=("local", "proc"), default="proc")
    ap.add_argument("--time-scale", type=float, default=1.0)
    ap.add_argument("--epoch-wait-s", type=float, default=120.0)
    ap.add_argument("--goodput-floor", type=float, default=0.5)
    ap.add_argument("--slo-ttft-s", type=float, default=10.0,
                    help="liveness TTFT SLO the goodput floor is judged "
                         "against")
    ap.add_argument("--slo-tpot-s", type=float, default=2.0)
    ap.add_argument("--autoscale", action="store_true")
    ap.add_argument("--workdir", default=None)
    # model/engine sizing (tiny by default: the soak proves invariants,
    # not model quality)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--prompt-max", type=int, default=32)
    ap.add_argument("--output-max", type=int, default=16)
    # journal bounds under test (small: compaction must cycle on soak
    # timescales)
    ap.add_argument("--segment-max-records", type=int, default=64)
    ap.add_argument("--compact-segments", type=int, default=3)
    ap.add_argument("--retain-terminal", type=int, default=128)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the full report JSON ('-' = stdout)")
    args = ap.parse_args(argv)

    cfg = build_config(args)
    print(f"soak: {cfg.epochs} epochs x {cfg.spec.requests} requests, "
          f"{cfg.replicas} {cfg.fleet} replica(s), rolling plan: "
          f"{[a['kind'] for a in cfg.chaos]}")
    report = run_soak(cfg)
    if args.json:
        blob = json.dumps(report, indent=2, default=str)
        if args.json == "-":
            print(blob)
        else:
            with open(args.json, "w", encoding="utf-8") as f:
                f.write(blob)
    for row in report["epochs"]:
        w = row["workload"]
        print(f"  epoch {row['epoch']:>3} chaos={row['chaos']['kind']:<8}"
              f" outcomes={w['outcomes']} lost={row['lost']}"
              f" segs={row['journal']['segments']}"
              f" viol={row['violations'] or 'none'}")
    print(f"compaction cycles observed: "
          f"{report['compaction_cycles_observed']}")
    if report["passed"]:
        print(f"SOAK PASS ({report['wall_s']:.1f}s, "
              f"{len(report['epochs'])} epochs, zero lost accepted)")
        return 0
    print("SOAK FAIL:")
    for v in report["violations"]:
        print(f"  {v}")
    return 1


if __name__ == "__main__":
    sys.exit(main())
