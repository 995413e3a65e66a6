"""Capacity planner: how many replicas for X QPS at a TTFT/TPOT SLO.

Answers the fleet-sizing question from first principles plus one
measurement, and can validate its own answer against the serving
harness (the acceptance contract: prediction within 25% of the
harness-measured requirement).

The model (docs/WORKLOADS.md "Capacity planner math"):

1. **Throughput floor** — offered token demand is ``qps x E[output
   tokens]`` (means taken from the generated workload itself, so
   truncation and heavy tails are priced in). A replica delivers
   ``T_rep`` tokens/s — measured by a short closed-loop calibration run
   at full batch — derated by ``--headroom``.
   ``N_tput = ceil(demand / (T_rep * headroom))``.
2. **TPOT feasibility** — if calibrated TPOT exceeds the TPOT SLO at
   full batch, a replica must run smaller batches; ``T_rep`` is scaled
   by ``slo_tpot / tpot`` (decode on this engine is throughput-bound,
   so tokens/s gives back roughly what batch gives up).
3. **Latency (queueing)** — replicas are servers in an M/M/c queue
   with per-replica service rate ``mu = T_rep / E[out]`` requests/s;
   Erlang-C gives the expected queue wait ``Wq`` and ``N_latency`` is
   the smallest c with ``ttft_base + Wq <= slo_ttft``.
4. **Admission capacity** — a replica admits at most ``max_slots +
   max_queue`` requests at once; past that the engine sheds. The
   spec's *peak concurrency* (max overlap of the generated arrival
   schedule with calibrated service times — an M/G/infinity estimate)
   divided by per-replica admission capacity bounds the burst-
   absorbing fleet size. This is the binding constraint for bursty
   traffic on hosts where throughput is shared (replicas add queue
   slots and failure domains, not FLOPs).

``N = max`` of the four.

Usage:

    python tools/capacity_plan.py --spec burst --slo-ttft-ms 4000
    python tools/capacity_plan.py --spec steady --qps 12 --validate

``--validate`` runs the harness at N = 1..``--max-replicas`` open-loop
and reports the measured minimum fleet meeting the SLO (zero lost,
zero shed, goodput >= ``--meet-goodput``) next to the prediction, exit
1 if they disagree by more than 25%.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from paddle_tpu.serving.workload import (        # noqa: E402
    ClosedLoopRunner, OpenLoopRunner, generate, load_spec, summarize)


# ---------------------------------------------------------------------------
# the model

def erlang_c(c: int, a: float) -> float:
    """P(wait) for an M/M/c queue at offered load ``a = lambda/mu``."""
    if a >= c:
        return 1.0
    s = sum(a ** k / math.factorial(k) for k in range(c))
    top = a ** c / math.factorial(c) * (c / (c - a))
    return top / (s + top)


def queue_wait_s(c: int, lam: float, mu: float) -> float:
    """Expected M/M/c queue wait (Erlang-C) in seconds."""
    a = lam / mu
    if a >= c:
        return float("inf")
    return erlang_c(c, a) / (c * mu - lam)


def peak_concurrency(workload, service_s: float) -> int:
    """Max overlap of the arrival schedule given a fixed service time —
    the M/G/infinity in-system peak the admission bound divides."""
    events = []
    for r in workload:
        events.append((r.at_s, 1))
        events.append((r.at_s + service_s, -1))
    events.sort()
    cur = peak = 0
    for _, d in events:
        cur += d
        peak = max(peak, cur)
    return peak


def plan(*, qps: float, mean_out: float, slo_ttft_s: float | None,
         slo_tpot_s: float | None, tok_per_sec: float,
         ttft_base_s: float = 0.0, tpot_s: float | None = None,
         admission_per_replica: int | None = None,
         peak_conc: int | None = None,
         headroom: float = 0.75, max_replicas: int = 64) -> dict:
    """The pure sizing math; every input is a measured or derived
    scalar so tests can drive it deterministically."""
    notes = []
    t_rep = float(tok_per_sec)
    if (slo_tpot_s is not None and tpot_s is not None
            and tpot_s > slo_tpot_s):
        t_rep *= slo_tpot_s / tpot_s
        notes.append(
            f"TPOT {tpot_s:.4f}s exceeds SLO {slo_tpot_s:.4f}s at full "
            f"batch: derated T_rep to {t_rep:.1f} tok/s")
    demand_tok_s = qps * mean_out
    n_tput = max(1, math.ceil(demand_tok_s / (t_rep * headroom)))

    mu = t_rep / mean_out            # requests/s one replica drains
    n_lat = n_tput
    if slo_ttft_s is not None:
        budget = slo_ttft_s - ttft_base_s
        while n_lat < max_replicas:
            if budget > 0 and \
                    queue_wait_s(n_lat, qps, mu) <= budget:
                break
            n_lat += 1

    n_adm = 1
    if admission_per_replica and peak_conc:
        n_adm = max(1, math.ceil(peak_conc / admission_per_replica))

    n = max(n_tput, n_lat, n_adm)
    # ties label as the throughput floor; a constraint only "binds"
    # when it pushes the answer above the others
    binding = "throughput"
    if n_lat == n and n_lat > n_tput:
        binding = "latency"
    if n_adm == n and n_adm > max(n_tput, n_lat):
        binding = "admission"
    return {
        "replicas": n,
        "binding_constraint": binding,
        "n_throughput": n_tput,
        "n_latency": n_lat,
        "n_admission": n_adm,
        "demand_tok_per_sec": demand_tok_s,
        "t_rep_tok_per_sec": t_rep,
        "service_rate_req_per_sec": mu,
        "peak_concurrency": peak_conc,
        "admission_per_replica": admission_per_replica,
        "headroom": headroom,
        "notes": notes,
    }


# ---------------------------------------------------------------------------
# harness: calibration + validation fleets

def _engine_kw(args, max_len, slo):
    # prefix_cache off: capacity answers are conservative prefix-miss
    # numbers, and cached-prefix prefill variants would otherwise keep
    # compiling new traces mid-replay (compile time is not capacity)
    kw = dict(block_size=args.block_size, max_slots=args.slots,
              max_model_len=max_len, max_queue=args.max_queue,
              slo_window_s=8.0, prefix_cache=False)
    if slo.get("ttft_s") is not None:
        kw["slo_ttft_s"] = slo["ttft_s"]
    if slo.get("tpot_s") is not None:
        kw["slo_tpot_s"] = slo["tpot_s"]
    return kw


def _build_fleet(args, n, max_len, slo):
    import paddle_tpu
    from paddle_tpu.models import LlamaForCausalLM, llama_tiny
    from paddle_tpu.serving import FleetRouter, LLMEngine, LocalReplica

    def build_model():
        paddle_tpu.seed(0)
        cfg = llama_tiny(vocab=args.vocab, hidden=args.hidden,
                         layers=args.layers, heads=4, kv_heads=2,
                         inter=2 * args.hidden, seq=2 * max_len)
        return LlamaForCausalLM(cfg)

    def factory():
        return LLMEngine(build_model(), **_engine_kw(args, max_len, slo))

    # prefill traces are bucketed to power-of-two block counts, so one
    # warmup prompt per bucket keeps compile time out of the replay
    warm, p = [], args.block_size
    while p < max_len:
        warm.append(p)
        p *= 2
    reps = [LocalReplica(f"c{i}", factory, stats_interval_s=0.05,
                         warmup=warm or [1])
            for i in range(n)]
    return FleetRouter(reps, probe_interval_s=0.1, probe_timeout_s=30.0,
                       affinity_block_size=args.block_size,
                       ).start(wait_healthy_s=600)


def _router_submit(router):
    from paddle_tpu.serving import SamplingParams

    def submit(wreq):
        sp = SamplingParams(max_new_tokens=wreq.max_new_tokens,
                            temperature=0.0)
        rr = router.submit(list(wreq.prompt), sp, tenant=wreq.tenant)

        def finish():
            done = rr.wait(timeout=300)
            if rr.state == "finished":
                return {"outcome": "ok", "ttft": rr.ttft,
                        "tokens": len(rr.tokens)}
            if not done:
                return {"outcome": "lost", "tokens": len(rr.tokens),
                        "error": "no terminal state"}
            return {"outcome": "failed", "ttft": rr.ttft,
                    "tokens": len(rr.tokens), "error": rr.error}
        return finish

    return submit


def _wait_fleet_healthy(router, timeout_s: float = 20.0):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        reps = router.stats()["replicas"].values()
        bad = [v for v in reps
               if v.get("slo") and not v["slo"].get("empty")
               and not v["slo"]["healthy"]]
        if not bad:
            return
        time.sleep(0.25)


def calibrate(args, spec, slo) -> dict:
    """Closed-loop at full batch on one replica: steady per-replica
    tokens/s, base TTFT, and TPOT — the measured inputs to plan()."""
    cal = generate(spec, max_model_len=args.prompt_max + args.output_max)
    # no SLO on the calibration fleet: the point is raw service rate,
    # and an SLO-unhealthy replica would shed the measurement itself
    router = _build_fleet(args, 1, args.prompt_max + args.output_max, {})
    try:
        # pass 1 warms the remaining compile caches; pass 2 is measured
        ClosedLoopRunner(cal, _router_submit(router),
                         concurrency=args.slots, think_time_s=0.0,
                         max_wait_s=300).run()
        t0 = time.perf_counter()
        results = ClosedLoopRunner(
            cal, _router_submit(router), concurrency=args.slots,
            think_time_s=0.0, max_wait_s=300).run()
        wall = time.perf_counter() - t0
    finally:
        router.close()
    ok = [r for r in results if r.outcome == "ok"]
    if not ok:
        raise SystemExit("calibration run produced no completions")
    tokens = sum(r.tokens for r in ok)
    ttfts = sorted(r.ttft_s for r in ok if r.ttft_s is not None)
    tpots = [(r.latency_s - r.ttft_s) / (r.tokens - 1)
             for r in ok
             if r.tokens > 1 and r.ttft_s is not None
             and r.latency_s is not None]
    return {
        "tok_per_sec": tokens / wall,
        "ttft_base_s": ttfts[len(ttfts) // 2] if ttfts else 0.0,
        "tpot_s": (sum(tpots) / len(tpots)) if tpots else None,
        "requests": len(ok),
        "wall_s": wall,
    }


def measure_requirement(args, spec, slo, time_scale) -> tuple:
    """Harness ground truth: smallest fleet (1..--max-replicas) whose
    open-loop replay meets the SLO — zero lost, zero shed, goodput >=
    --meet-goodput. Returns (n or None, per-N rows)."""
    wl = generate(spec, max_model_len=args.prompt_max + args.output_max)
    rows = []
    found = None
    for n in range(1, args.max_replicas + 1):
        router = _build_fleet(args, n,
                              args.prompt_max + args.output_max, slo)
        try:
            # warm pass compiles the remaining traces, then wait out the
            # SLO window so its compile-inflated TTFTs age out of the
            # health verdict before the measured replay starts
            ClosedLoopRunner(wl, _router_submit(router),
                             concurrency=args.slots, think_time_s=0.0,
                             max_wait_s=300).run()
            _wait_fleet_healthy(router, timeout_s=20.0)
            results = OpenLoopRunner(
                wl, _router_submit(router), time_scale=time_scale,
                max_wait_s=300).run()
        finally:
            router.close()
        s = summarize(results, slo=spec.slo)
        # failed counts against capacity too: an engine-level QueueFull
        # reject comes back as outcome "failed", not "shed"
        meets = (s["lost"] == 0
                 and s["outcomes"].get("shed", 0) == 0
                 and s["outcomes"].get("failed", 0) == 0
                 and (s["goodput_ratio"] or 0.0) >= args.meet_goodput)
        rows.append({"replicas": n, "meets": meets,
                     "outcomes": s["outcomes"],
                     "goodput_ratio": s["goodput_ratio"],
                     "ttft_p99_s": s["ttft_p99"]})
        print(f"  validate N={n}: meets={meets} "
              f"outcomes={s['outcomes']} "
              f"goodput={s['goodput_ratio']}", file=sys.stderr)
        if meets and found is None:
            found = n
            break
    return found, rows


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--spec", default="steady",
                    help="workload preset or spec JSON path")
    ap.add_argument("--qps", type=float, default=None,
                    help="target arrival rate (default: the spec's own "
                         "offered rate)")
    ap.add_argument("--slo-ttft-ms", type=float, default=None,
                    help="override the spec's TTFT SLO")
    ap.add_argument("--slo-tpot-ms", type=float, default=None,
                    help="override the spec's TPOT SLO")
    ap.add_argument("--headroom", type=float, default=0.75,
                    help="derate measured per-replica throughput (burst "
                         "absorption + failure-domain slack)")
    ap.add_argument("--time-scale", type=float, default=1.0)
    ap.add_argument("--validate", action="store_true",
                    help="measure the real requirement on harness "
                         "fleets and hold the prediction to 25%%")
    ap.add_argument("--max-replicas", type=int, default=4)
    ap.add_argument("--meet-goodput", type=float, default=0.85)
    ap.add_argument("--json", default=None)
    # engine/model sizing of the --validate harness fleets (a CPU toy)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-queue", type=int, default=8,
                    help="per-replica admission queue bound (slots + "
                         "queue = admission capacity per replica)")
    ap.add_argument("--prompt-max", type=int, default=48)
    ap.add_argument("--output-max", type=int, default=24)
    args = ap.parse_args(argv)

    spec = load_spec(args.spec)
    spec.prompt_len["max"] = min(int(spec.prompt_len.get("max", 48)),
                                 args.prompt_max)
    spec.output_len["max"] = min(int(spec.output_len.get("max", 24)),
                                 args.output_max)
    if spec.vocab > args.vocab:
        spec.vocab = args.vocab
    slo = dict(spec.slo or {})
    if args.slo_ttft_ms is not None:
        slo["ttft_s"] = args.slo_ttft_ms / 1e3
    if args.slo_tpot_ms is not None:
        slo["tpot_s"] = args.slo_tpot_ms / 1e3
    spec.slo = slo or None

    wl = generate(spec, max_model_len=args.prompt_max + args.output_max)
    mean_out = (sum(r.max_new_tokens for r in wl) / len(wl))
    qps = (args.qps if args.qps is not None
           else wl.offered_qps / max(args.time_scale, 1e-9))

    print("# calibrating (1-replica closed-loop)...", file=sys.stderr)
    measured = calibrate(args, spec, slo)

    service_s = (measured["ttft_base_s"]
                 + (measured["tpot_s"] or 0.0) * max(mean_out - 1, 0))
    peak = peak_concurrency(wl, max(service_s, 1e-3))
    result = plan(
        qps=qps, mean_out=mean_out,
        slo_ttft_s=slo.get("ttft_s"), slo_tpot_s=slo.get("tpot_s"),
        tok_per_sec=measured["tok_per_sec"],
        ttft_base_s=measured["ttft_base_s"],
        tpot_s=measured.get("tpot_s"),
        admission_per_replica=args.slots + args.max_queue,
        peak_conc=peak, headroom=args.headroom,
        max_replicas=args.max_replicas * 4)
    doc = {
        "spec": spec.to_dict(),
        "qps": qps,
        "mean_output_tokens": mean_out,
        "slo": slo,
        "measured": measured,
        "service_time_s": service_s,
        "plan": result,
    }
    print(f"predicted replicas for {qps:.1f} qps: "
          f"{result['replicas']} (binding: "
          f"{result['binding_constraint']}; throughput "
          f"{result['n_throughput']}, latency {result['n_latency']}, "
          f"admission {result['n_admission']})")

    rc = 0
    if args.validate:
        found, rows = measure_requirement(args, spec, slo,
                                          args.time_scale)
        doc["validation"] = {"measured_replicas": found, "rows": rows}
        if found is None:
            print(f"VALIDATE FAIL: no fleet up to {args.max_replicas} "
                  "replicas met the SLO (prediction "
                  f"{result['replicas']})")
            rc = 1
        else:
            err = abs(result["replicas"] - found) / found
            doc["validation"]["relative_error"] = err
            verdict = "within" if err <= 0.25 else "OUTSIDE"
            print(f"measured requirement: {found} replicas — "
                  f"prediction {result['replicas']} is {verdict} 25% "
                  f"({err:.0%})")
            if err > 0.25:
                rc = 1
    if args.json:
        blob = json.dumps(doc, indent=2, default=str)
        if args.json == "-":
            print(blob)
        else:
            with open(args.json, "w", encoding="utf-8") as f:
                f.write(blob)
    return rc


if __name__ == "__main__":
    sys.exit(main())
