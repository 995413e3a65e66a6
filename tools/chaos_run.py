"""Chaos sweep: drive the runtime through batteries of deterministic fault
plans and report survival / degradation stats per plan.

The suites:

``--suite serving`` (default) — the continuous-batching engine under fault
plans. For every plan the same request fleet runs on a fresh engine; the
fault-free run's outputs are the parity reference. A plan "survives" when
the engine drains without crashing, every non-targeted request matches the
reference token-for-token, every targeted request ends FAILED/CANCELLED
with an error attached, and all KV blocks return to the pool.

``--suite prefix`` — the prefix cache (docs/SERVING.md) under its own
fault battery on a shared-prefix fleet (``--prefix-share`` of every prompt
is one common template). The parity reference is a fault-free
prefix-cache-OFF engine, so survival additionally proves cache-on ==
cache-off token streams under faults: ``serving.kv.share:stale_hash``
(index corruption -> the match is dropped, full prefill), and
``serving.kv.cow:exhaust`` (copy-on-write allocation fails mid-decode ->
preempt/fail that request, never a corrupted shared block), plus allocator
exhaustion with eviction in play. The baseline plan must also show a real
cache hit rate.

``--suite spill`` — the tiered KV pool under memory pressure
(docs/ROBUSTNESS.md "Degradation ladder"): a deliberately undersized
device pool with the host-RAM spill tier and watermark backpressure
armed, driven through a seed -> flood -> rematch workload so demotions
and promotions are genuinely in flight when the faults land
(``serving.kv.spill:{error,corrupt}``,
``serving.kv.promote:{error,corrupt,delay}``, allocator exhaustion, and
a combined >=5-fault storm). Every plan is held to token-for-token
parity vs a fault-free cache-off engine — in particular, a *corrupt*
promotion must be caught by the CRC check and fall back to re-prefill,
never emit a wrong token — plus zero leaked device blocks (free + live
+ cached == usable at drain).

``--suite train`` — the resilient training loop (docs/ROBUSTNESS.md
"Training resilience"): kill-worker (SIGKILL mid-run under the launcher,
resume must be bit-identical), nan-injection (guarded step skips poisoned
steps, GradScaler backs off, the run completes), and
torn-checkpoint-on-resume (resume falls back past a torn newest snapshot).
Reports per scenario: survival, restarts/resume steps, bad steps, fallback
behavior.

``--suite perf`` — the performance-observability layer
(docs/OBSERVABILITY.md "Performance observability"): a deliberately
shape-unstable fleet (one prompt per power-of-two prefill bucket) must
trip the recompilation-storm detector with ``explain_recompile()`` naming
the churning ``tokens`` argument; the same churn under
``serving.compile:error`` + ``serving.kv.alloc:exhaust`` must degrade
gracefully (targeted requests FAILED with errors attached, no block
leak, storm still reported); the memory leak sentinel must flag a
simulated block leak while a clean drain stays quiet; and the same
stable fleet is run with telemetry on and off (both must survive; the
ratio of the two wall times is reported, a sanity bound and not a
measurement).

``--suite serve-fleet`` — the production front door (docs/SERVING.md
"Fleet serving"): a real gateway + FleetRouter over engine replica
*processes* (``serving/replica_worker.py``) driven by HTTP SSE clients.
Four scenarios, every one held to **zero lost requests** and
token-for-token parity with an uninterrupted single-engine reference:
(1) SIGKILL a replica mid-decode while clients stream — its requests
fail over with replay-and-suppress; (2) fault storms armed per replica
via ``FLAGS_fault_plan`` (``serving.compile:error`` on one replica →
engine-isolated failures retried on a sibling; a wedging
``serving.decode:delay`` + ``collective:delay`` storm on another → probe
timeout → failover); (3) load shedding under a full fleet — low-priority
requests get 429 + Retry-After, high-priority bypasses, no in-flight
stream is harmed; (4) ``drain_and_restart`` under a real
ElasticSupervisor ledger while traffic flows.

``--suite durable`` — the durable request lifecycle (docs/ROBUSTNESS.md
"Durable requests"): the *gateway* is the victim. (1) SIGKILL the gateway
process mid-stream → restart over the same write-ahead journal → recovery
re-submits every accepted-non-terminal request through the router's
replay-and-suppress path, clients reconnect with Idempotency-Key +
Last-Event-ID and receive exactly the missing suffix — zero lost accepted
requests, token-for-token parity vs an uninterrupted run; (2) a torn
final journal record (death mid-append) is detected by CRC and skipped,
never poisoning recovery; (3) a replica failing 100% of dispatches trips
its circuit breaker OPEN within the rolling window, placement routes
around it, and a HALF_OPEN probe restores it after it heals; (4) a
fleet-wide fault plan exhausts the global retry budget — requests
fast-fail with bounded re-dispatch volume instead of a retry storm.

``--suite kvfabric`` — the cluster-scale KV fabric (docs/SERVING.md "KV
fabric"): the fleet-wide prefix directory + cross-replica KV-block
migration under every failure mode it claims to survive, all held to
token-for-token parity vs a fabric-off engine: (1) stale directory
entries (the donor answers with zero frames; garbage documents sit in
the store) degrade to local prefill; (2) SIGKILL the donor process
mid-fetch (real ProcReplicas over a real TCPStore directory) — the
pending fetch fails fast and the dead donor's lease ages its entry out;
(3) a corrupt frame is refused by the receiver's CRC check — the
verified chain prefix is kept, zero wrong tokens; (4) a hot-prefix fetch
storm stays inside the migration budget with the retry budget untouched.

``--suite locksan`` — the runtime lock-order sanitizer
(docs/ANALYSIS.md): LockSan armed over real multi-threaded fleet
surfaces, in-process so every lock acquisition is observed. (1)
``fleet_under_load`` — journal appends (``fsync='always'``, crossing
the annotated durability-barrier waiver on every record) + directory
publish/lookup/snapshot from six named threads, **zero violations**
required; (2) ``telemetry_threads`` — a fresh metrics registry and
flight-recorder ring under concurrent inc/observe/record/dump traffic,
zero violations; (3) ``inversion_canary`` — a deliberate A→B/B→A
inversion across two named threads plus a ``time.sleep`` under a lock,
which LockSan **must report** (both thread names in the inversion's
edges) — proves the detector in this battery is live, not vacuously
quiet.

``--suite soak`` — the rolling-chaos soak (docs/WORKLOADS.md "Soak pass
criteria"): the seeded trace-driven workload replayed epoch after epoch
against a real fleet + gateway while the chaos action *rotates* —
fault-plan degradation, replica SIGKILL, drain/restart churn, explicit
journal compaction — with every epoch re-asserting zero lost accepted
requests, a quiet leak sentinel, journal segment/byte/retention bounds,
and the per-tenant goodput floor. ``degrade`` runs in-process (1
LocalReplica, degradation + compaction — the tier-1 smoke's shape);
``rolling`` is the full battery on 2 SIGKILL-able ProcReplicas. The
long-form driver with time budgets is ``tools/soak_run.py``.

``--suite alerts`` — the ops plane's detect→page→diagnose loop
(docs/OBSERVABILITY.md "Ops plane"): with burn windows time-scaled into
seconds, (1) a ``serving.decode:delay`` fault on a live gateway fleet
must trip the fast-burn SLO page within a bounded detection time, the
page carrying an exemplar trace id and showing on ``/v1/alerts``, and
recovery must resolve it; (2) a SIGKILL'd rank telemetry publisher must
trip the publisher-absence page (the watchdog for the watchers).

``--suite heal`` — the self-healing control plane (docs/ROBUSTNESS.md
"Self-healing & rollout"): the *act* half of detect→page→act on a real
ProcReplica fleet. (1) a wedged replica blows the SLO → the burn page
fires → the remediation engine drains+restarts it under the actuation
lease → the fleet recovers, the alert resolves, and the post-condition
bake closes ok — zero lost requests throughout; (2) a replica that is
sick *every* incarnation re-triggers after each restart — flap detection
must quarantine it (page + ledger) instead of a restart storm, with the
rest of the fleet still serving; (3) a rolling upgrade onto a
deliberately slow spec under live SSE traffic — the canary regresses
against the pre-rollout baseline and the rollout auto-rolls back
mid-traffic with token-for-token parity, driven end-to-end through the
gateway admin API and verified with ``tools/fleet_ctl.py``.

``--suite straggler`` — the cluster observability plane
(docs/OBSERVABILITY.md "Cluster observability"): a 4-rank job over a real
TCPStore where one rank carries a ``collective:delay`` fault plan.
Scenario A (persistent straggler): the ClusterMonitor must *name* the
delayed rank and the collective seq#s it lagged on, and the per-rank
Chrome traces must merge (clock-offset corrected) into one
``trace-merged.json`` with one row per rank. Scenario B (hang): a long
delay wedges one rank mid-job; the monitor's hang diagnosis must name it
as the suspect and a postmortem bundle must collect EVERY rank's flight
recorder + stack snapshot.

Usage:
    python tools/chaos_run.py
        [--suite serving|prefix|spill|train|straggler|perf|serve-fleet|
                 durable|kvfabric|tenancy|locksan|soak|alerts|heal]
        [--requests 6] [--prompt-len 24] [--max-new 16]
        [--slots 3] [--block-size 8] [--plan NAME:SPEC ...] [--json OUT.json]
        [--list] [--scenario NAME]

``--list`` prints every suite's scenario names; ``--scenario NAME`` re-runs
a single scenario of the chosen suite (the unit of re-run when one row of
the nightly battery fails) — see docs/ROBUSTNESS.md "Running the chaos
battery" for the CI lane wiring.

Custom plans: ``--plan storm "serving.prefill:error@2;serving.kv.alloc:exhaust@5"``
(repeatable) replaces the built-in serving battery.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

# a CPU battery: its replica children must not contend for a chip
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, ".")

import paddle_tpu  # noqa: E402
from paddle_tpu import telemetry  # noqa: E402
from paddle_tpu.models import LlamaForCausalLM, llama_tiny  # noqa: E402
from paddle_tpu.serving import (  # noqa: E402
    LLMEngine, RequestState, SamplingParams)
from paddle_tpu.utils.faults import FaultPlan  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# the built-in battery: one plan per degradation path the runtime claims to
# handle (docs/ROBUSTNESS.md), plus a combined storm
DEFAULT_PLANS = [
    ("baseline", ""),
    ("prefill_error", "serving.prefill:error@2"),
    ("decode_slot_error", "serving.decode.slot:error@5"),
    ("decode_batch_error", "serving.decode:error@2"),
    ("decode_delay", "serving.decode:delay=0.005@2x3"),
    ("pool_exhaust", "serving.kv.alloc:exhaust@4x2"),
    ("storm", "serving.prefill:error@3;serving.decode.slot:error@8;"
              "serving.decode:delay=0.005@2;serving.kv.alloc:exhaust@6"),
]

# the prefix-cache battery: every degradation path the prefix cache claims
# (stale index -> no-share fallback, CoW exhaustion -> preempt, allocator
# exhaustion with the evictable pool in play), plus a combined storm
PREFIX_PLANS = [
    ("baseline_prefix", ""),
    ("stale_hash", "serving.kv.share:stale_hash@3x2"),
    ("stale_hash_storm", "serving.kv.share:stale_hash%0.5"),
    ("cow_exhaust", "serving.kv.cow:exhaust@3"),
    ("cow_exhaust_storm", "serving.kv.cow:exhaust@2x6"),
    ("alloc_exhaust", "serving.kv.alloc:exhaust@4x2"),
    ("prefix_storm", "serving.kv.share:stale_hash@2;"
                     "serving.kv.cow:exhaust@5x2;"
                     "serving.kv.alloc:exhaust@7"),
]

# the spill-tier battery (docs/ROBUSTNESS.md "Degradation ladder"): a
# deliberately undersized device pool under a seed -> flood -> rematch
# workload, so every plan runs with real demotions and promotions in
# flight. Parity reference is a fault-free *cache-off* engine: a corrupt
# promotion that slipped through would show up as a wrong token.
SPILL_PLANS = [
    ("baseline_spill", ""),
    ("spill_error", "serving.kv.spill:error@2x2"),
    ("spill_corrupt", "serving.kv.spill:corrupt@1x2"),
    ("promote_error", "serving.kv.promote:error@1"),
    ("promote_corrupt", "serving.kv.promote:corrupt@1"),
    ("promote_delay", "serving.kv.promote:delay=0.002x3"),
    ("alloc_exhaust", "serving.kv.alloc:exhaust@6x2"),
    # the >=5-fault memory-pressure storm the acceptance gate names:
    # spill error + spill corruption + promote error + two injected
    # allocator exhaustions, all while demotions/promotions are in flight
    # (the promote fault sits at @1 — a dropped chain head means later
    # walks never reach the site again, so deeper indices can misfire)
    ("spill_storm", "serving.kv.spill:error@2;serving.kv.spill:corrupt@4;"
                    "serving.kv.promote:error@1;"
                    "serving.kv.alloc:exhaust@8x2"),
]


def _build(args, prefix_share=None):
    paddle_tpu.seed(0)
    max_len = args.prompt_len + args.max_new
    cfg = llama_tiny(vocab=args.vocab, hidden=args.hidden, layers=args.layers,
                     heads=4, kv_heads=2, inter=2 * args.hidden,
                     seq=2 * max_len)
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    if prefix_share:
        n_shared = int(args.prompt_len * prefix_share)
        shared = list(rng.randint(0, args.vocab, n_shared))
        prompts = [shared + list(rng.randint(
            0, args.vocab, args.prompt_len - n_shared))
            for _ in range(args.requests)]
    else:
        prompts = [list(rng.randint(0, args.vocab, args.prompt_len))
                   for _ in range(args.requests)]
    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    return model, prompts, sp, max_len


def _run_plan(model, prompts, sp, max_len, args, plan_text, reference=None,
              prefix_cache=True):
    eng = LLMEngine(model, block_size=args.block_size, max_slots=args.slots,
                    max_model_len=max_len, watchdog_timeout_s=0.002,
                    prefix_cache=prefix_cache)
    plan = FaultPlan.parse(plan_text) if plan_text else FaultPlan()
    t0 = time.perf_counter()
    crashed = None
    with plan:
        try:
            reqs = [eng.add_request(p, sp) for p in prompts]
            eng.run()
        except Exception as e:  # a crash = the robustness layer failed
            crashed = f"{type(e).__name__}: {e}"
            reqs = []
    wall = time.perf_counter() - t0

    finished = [r for r in reqs if r.state is RequestState.FINISHED]
    failed = [r for r in reqs if r.state is RequestState.FAILED]
    cancelled = [r for r in reqs if r.state is RequestState.CANCELLED]
    parity_ok = (reference is None or all(
        r.output_tokens == reference[r.rid] for r in finished))
    errors_attached = all(r.error is not None for r in failed + cancelled)
    st = eng.stats() if crashed is None else {}
    survived = (crashed is None and parity_ok and errors_attached
                and st.get("blocks_used") == 0
                and len(finished) + len(failed) + len(cancelled) == len(reqs))
    return {
        "plan": plan_text or "(none)",
        "survived": bool(survived),
        "crashed": crashed,
        "faults_fired": plan.summary(),
        "finished": len(finished),
        "failed": len(failed),
        "cancelled": len(cancelled),
        "survivor_parity_ok": bool(parity_ok),
        "errors_attached": bool(errors_attached),
        "blocks_leaked": int(st.get("blocks_used", -1)),
        "num_preemptions": st.get("num_preemptions"),
        "watchdog_trips": st.get("watchdog_trips"),
        "generated_tokens": st.get("total_generated_tokens"),
        "prefix": st.get("prefix_cache"),
        "wall_sec": round(wall, 4),
    }, [r.output_tokens for r in reqs] if reqs else None


# -- the prefix-cache battery ----------------------------------------------

def run_prefix_suite(args, scenario=None):
    """Shared-prefix fleet through the PREFIX_PLANS battery. The parity
    reference is a fault-free *prefix-cache-off* engine, so every surviving
    plan also proves cache-on == cache-off token streams under faults."""
    model, prompts, sp, max_len = _build(args,
                                         prefix_share=args.prefix_share)
    base_row, reference = _run_plan(model, prompts, sp, max_len, args, "",
                                    prefix_cache=False)
    base_wall = base_row["wall_sec"]
    plans = [(n, s) for n, s in PREFIX_PLANS
             if scenario is None or n == scenario]
    if not plans:
        raise SystemExit(f"unknown prefix scenario {scenario!r}; one of: "
                         f"{[n for n, _ in PREFIX_PLANS]}")
    rows = []
    for name, spec in plans:
        row, _ = _run_plan(model, prompts, sp, max_len, args, spec,
                           reference=reference, prefix_cache=True)
        row["name"] = name
        pc = row.get("prefix") or {}
        row["hit_rate"] = pc.get("hit_rate")
        if name == "baseline_prefix":
            # the fault-free plan must actually *hit*: a dead cache that
            # never shares would vacuously pass every degradation check
            row["survived"] = bool(row["survived"]
                                   and pc.get("hits", 0) > 0
                                   and pc.get("blocks_saved", 0) > 0)
        row["slowdown_vs_baseline"] = (
            round(row["wall_sec"] / base_wall, 3) if base_wall > 0 else None)
        rows.append(row)
    survived = sum(1 for r in rows if r["survived"])
    dump_path = telemetry.dump(reason="prefix chaos suite complete")
    return {
        "suite": "prefix",
        "config": {"requests": args.requests, "prompt_len": args.prompt_len,
                   "max_new_tokens": args.max_new, "slots": args.slots,
                   "block_size": args.block_size,
                   "prefix_share": args.prefix_share},
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "baseline_wall_sec": base_wall,
        "flight_recorder_dump": dump_path,
        "results": rows,
    }


# -- the spill-tier battery ------------------------------------------------

def _spill_waves(args):
    """Seed -> flood -> rematch: the memory-pressure workload. The seed
    wave populates the prefix cache, the flood wave (unique prompts) blows
    every cached block out of the undersized device pool (demoting them to
    the host tier), and the rematch wave can only be warm if the spill
    tier promotes the seeded prefix back."""
    rng = np.random.RandomState(0)
    n_shared = int(args.prompt_len * args.prefix_share)
    shared = list(rng.randint(0, args.vocab, n_shared))
    tail = args.prompt_len - n_shared

    def shared_prompt():
        return shared + list(rng.randint(0, args.vocab, tail))

    seed_wave = [shared_prompt() for _ in range(2)]
    flood = [list(rng.randint(0, args.vocab, args.prompt_len))
             for _ in range(args.slots + 1)]
    rematch = [shared_prompt() for _ in range(max(args.requests - 2, 2))]
    return [seed_wave, flood, rematch]


def _run_spill_plan(model, waves, sp, max_len, args, plan_text,
                    reference=None):
    """One plan against the undersized-pool engine with the spill tier and
    watermark backpressure armed. Survival = no crash, survivor parity vs
    the fault-free cache-off reference, all terminal handles carrying
    errors, zero leaked device blocks, and the device partition exact
    (free + live + cached == usable) at drain."""
    blocks_per_seq = -(-max_len // args.block_size)
    eng = LLMEngine(
        model, block_size=args.block_size, max_slots=args.slots,
        max_model_len=max_len,
        num_blocks=args.slots * blocks_per_seq + 2,   # barely fits slots
        prefix_cache=True, kv_spill_blocks=4 * blocks_per_seq,
        kv_high_watermark=0.9, kv_low_watermark=0.6,
        watchdog_timeout_s=0.002)
    plan = FaultPlan.parse(plan_text) if plan_text else FaultPlan()
    t0 = time.perf_counter()
    crashed = None
    reqs = []
    with plan:
        try:
            for wave in waves:
                reqs += [eng.add_request(p, sp) for p in wave]
                eng.run()
        except Exception as e:  # a crash = the degradation ladder failed
            crashed = f"{type(e).__name__}: {e}"
    wall = time.perf_counter() - t0

    finished = [r for r in reqs if r.state is RequestState.FINISHED]
    failed = [r for r in reqs if r.state is RequestState.FAILED]
    cancelled = [r for r in reqs if r.state is RequestState.CANCELLED]
    parity_ok = (reference is None or all(
        r.output_tokens == reference[r.rid] for r in finished))
    errors_attached = all(r.error is not None for r in failed + cancelled)
    st = eng.stats() if crashed is None else {}
    alloc = eng.cache.allocator
    partition_ok = (crashed is None and alloc.num_free + alloc.num_used
                    + alloc.num_cached == alloc.num_usable)
    pc = (st.get("prefix_cache") or {})
    spill = pc.get("spill") or {}
    survived = (crashed is None and parity_ok and errors_attached
                and partition_ok and st.get("blocks_used") == 0
                and len(finished) + len(failed) + len(cancelled)
                == len(reqs))
    return {
        "plan": plan_text or "(none)",
        "survived": bool(survived),
        "crashed": crashed,
        "faults_fired": plan.summary(),
        "num_faults_fired": len(plan.fired),
        "finished": len(finished),
        "failed": len(failed),
        "cancelled": len(cancelled),
        "survivor_parity_ok": bool(parity_ok),
        "errors_attached": bool(errors_attached),
        "blocks_leaked": int(st.get("blocks_used", -1)),
        "partition_ok": bool(partition_ok),
        "hit_rate": pc.get("hit_rate"),
        "spill": spill,
        "pressure_events": eng.scheduler.num_pressure_events,
        "num_preemptions": st.get("num_preemptions"),
        "wall_sec": round(wall, 4),
    }, [r.output_tokens for r in reqs] if reqs else None


def run_spill_suite(args, scenario=None):
    """Memory-pressure battery over the tiered KV pool: every plan must
    survive with token-for-token parity vs a fault-free cache-off engine.
    The fault-free baseline must actually spill AND promote (a dead tier
    would vacuously pass), and every corrupt plan must show the CRC check
    dropping entries while parity holds — a corrupt promotion re-prefills,
    it never emits a wrong token."""
    model, _, sp, max_len = _build(args)
    waves = _spill_waves(args)

    # fault-free cache-off reference with an ample pool: the parity target
    ref_eng = LLMEngine(model, block_size=args.block_size,
                        max_slots=args.slots, max_model_len=max_len,
                        prefix_cache=False)
    ref_reqs = []
    for wave in waves:
        ref_reqs += [ref_eng.add_request(p, sp) for p in wave]
        ref_eng.run()
    reference = [r.output_tokens for r in ref_reqs]

    plans = [(n, s) for n, s in SPILL_PLANS
             if scenario is None or n == scenario]
    if not plans:
        raise SystemExit(f"unknown spill scenario {scenario!r}; one of: "
                         f"{[n for n, _ in SPILL_PLANS]}")
    rows = []
    for name, spec in plans:
        row, _ = _run_spill_plan(model, waves, sp, max_len, args, spec,
                                 reference=reference)
        row["name"] = row["scenario"] = name
        sp_blk = row.get("spill") or {}
        if name == "baseline_spill":
            # the fault-free plan must exercise the tier end to end:
            # demotions, promotions, and at least one watermark latch
            row["survived"] = bool(
                row["survived"] and sp_blk.get("spills", 0) > 0
                and sp_blk.get("promotes", 0) > 0
                and row["pressure_events"] > 0)
        if name in ("spill_corrupt", "promote_corrupt"):
            # the CRC check must have caught the corruption (parity is
            # already asserted: no wrong token reached a client)
            row["survived"] = bool(
                row["survived"]
                and sp_blk.get("promote_corrupt_drops", 0) > 0)
        if name == "spill_storm":
            row["survived"] = bool(row["survived"]
                                   and row["num_faults_fired"] >= 5)
        rows.append(row)
    survived = sum(1 for r in rows if r["survived"])
    dump_path = telemetry.dump(reason="spill chaos suite complete")
    return {
        "suite": "spill",
        "config": {"requests": args.requests, "prompt_len": args.prompt_len,
                   "max_new_tokens": args.max_new, "slots": args.slots,
                   "block_size": args.block_size,
                   "prefix_share": args.prefix_share},
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "flight_recorder_dump": dump_path,
        "results": rows,
    }


# -- the train battery -----------------------------------------------------

def _train_model(seed=7):
    import paddle_tpu.nn as nn

    paddle_tpu.seed(seed)
    net = nn.Linear(4, 3)
    model = paddle_tpu.Model(net)
    model.prepare(
        optimizer=paddle_tpu.optimizer.Momentum(
            learning_rate=0.05, momentum=0.9, parameters=net.parameters()),
        loss=nn.MSELoss())
    return model, net


def _train_kill_worker(workdir):
    """SIGKILL one worker mid-run under the launcher; the relaunched pod
    must resume from the auto-checkpoint and finish bit-identical to an
    uninterrupted run."""
    import subprocess

    from paddle_tpu.resilience import demo

    base = dict(os.environ, PYTHONPATH=".", JAX_PLATFORMS="cpu",
                XLA_FLAGS="", RESIL_STEPS="16", RESIL_CKPT_EVERY="4")

    def launch(env, extra):
        return subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "1", "--backend", "cpu"] + extra
            + [demo.__file__],
            env=env, timeout=300, capture_output=True, text=True)

    ref_env = dict(base, RESIL_DIR=os.path.join(workdir, "ckpt_ref"),
                   RESIL_OUT=os.path.join(workdir, "ref.npz"))
    r0 = launch(ref_env, ["--log_dir", os.path.join(workdir, "log_ref")])
    kill_env = dict(base, RESIL_DIR=os.path.join(workdir, "ckpt_kill"),
                    RESIL_OUT=os.path.join(workdir, "kill.npz"),
                    RESIL_KILL_STEP="10")
    r1 = launch(kill_env, ["--max_restarts", "2", "--restart_backoff", "0.1",
                           "--log_dir", os.path.join(workdir, "log_kill")])
    identical = False
    ledger = {}
    if r0.returncode == 0 and r1.returncode == 0:
        ref = np.load(os.path.join(workdir, "ref.npz"))
        kill = np.load(os.path.join(workdir, "kill.npz"))
        identical = all(np.array_equal(ref[k], kill[k]) for k in ref.files)
        with open(os.path.join(workdir, "log_kill", "job_state.json")) as f:
            ledger = json.load(f)
    return {
        "scenario": "kill_worker",
        "survived": bool(r0.returncode == 0 and r1.returncode == 0
                         and identical and ledger.get("restarts") == 1),
        "ref_rc": r0.returncode,
        "kill_rc": r1.returncode,
        "bit_identical": bool(identical),
        "restarts": ledger.get("restarts"),
        "resume_steps": ledger.get("resume_steps"),
    }


def _train_nan_injection(workdir):
    """Poisoned-gradient steps must be skipped (scaler backed off, counters
    up) without killing the run or corrupting optimizer state."""
    from paddle_tpu.amp import GradScaler
    from paddle_tpu.resilience import HealthGuard, ResilientLoop
    from paddle_tpu.resilience.demo import data_fn

    model, _ = _train_model()
    scaler = GradScaler(init_loss_scaling=1024.0, decr_every_n_nan_or_inf=1)
    with FaultPlan.parse("optimizer.step:nan_grads@3x2") as plan:
        report = ResilientLoop(
            model, data_fn, ckpt_dir=os.path.join(workdir, "nan"),
            max_steps=10, ckpt_every_steps=4, scaler=scaler,
            health=HealthGuard(max_bad_streak=4, scaler=scaler)).run()
    return {
        "scenario": "nan_injection",
        "survived": bool(report["final_step"] == 10
                         and report["bad_steps"] == 2
                         and scaler.get_loss_scaling() < 1024.0),
        "bad_steps": report["bad_steps"],
        "final_step": report["final_step"],
        "loss_scale_after": scaler.get_loss_scaling(),
        "faults_fired": plan.summary(),
    }


def _train_torn_checkpoint(workdir):
    """A torn newest snapshot (writer killed before the manifest) must be
    skipped on resume: the loop falls back to the previous good one."""
    from paddle_tpu.resilience import ResilientLoop
    from paddle_tpu.resilience.demo import data_fn

    root = os.path.join(workdir, "torn")
    model, _ = _train_model()
    ResilientLoop(model, data_fn, ckpt_dir=root, max_steps=6,
                  ckpt_every_steps=2, save_final=False).run()
    newest = sorted(os.listdir(root))[-1]
    os.remove(os.path.join(root, newest, "manifest.0.json"))
    model2, _ = _train_model()
    loop = ResilientLoop(model2, data_fn, ckpt_dir=root, max_steps=8,
                         ckpt_every_steps=4)
    report = loop.run()
    skipped = (loop.ckpt.last_load_report or {}).get("skipped", [])
    return {
        "scenario": "torn_checkpoint_on_resume",
        "survived": bool(report["resume_step"] == 4
                         and report["final_step"] == 8 and skipped),
        "resume_step": report["resume_step"],
        "final_step": report["final_step"],
        "snapshots_skipped": [os.path.basename(p) for p, _ in skipped],
    }


# -- the perf battery ------------------------------------------------------

def _perf_fleet(args, lengths, plan_text="", **engine_kw):
    """Serve one request per prompt length on a fresh tiny engine; returns
    (engine, requests, crashed)."""
    paddle_tpu.seed(0)
    max_len = max(lengths) + args.max_new
    cfg = llama_tiny(vocab=args.vocab, hidden=args.hidden, layers=args.layers,
                     heads=4, kv_heads=2, inter=2 * args.hidden,
                     seq=2 * max_len)
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, args.vocab, n)) for n in lengths]
    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    eng = LLMEngine(model, block_size=4, max_slots=args.slots,
                    max_model_len=max_len, **engine_kw)
    plan = FaultPlan.parse(plan_text) if plan_text else FaultPlan()
    crashed = None
    with plan:
        try:
            reqs = [eng.add_request(p, sp) for p in prompts]
            eng.run()
        except Exception as e:
            crashed = f"{type(e).__name__}: {e}"
            reqs = []
    return eng, reqs, crashed, plan


def run_perf_suite(args):
    """Performance-observability battery (docs/OBSERVABILITY.md
    "Performance observability"): a deliberately shape-unstable workload
    must trip the recompilation-storm detector with the churning argument
    *named* by ``explain_recompile()``, the same workload must degrade
    gracefully under ``serving.kv``/``serving.compile`` faults, and the
    leak sentinel must flag a real block leak while staying quiet on a
    clean drain."""
    from paddle_tpu.telemetry import perf

    perf.reset()
    watcher = perf.compile_watcher()
    old_n = watcher.storm_threshold
    watcher.storm_threshold = 4     # tiny workload: storm at 4 signatures
    rows = []
    # one prompt per power-of-two bucket (block_size 4): every admission
    # retraces engine.prefill with a new `tokens` signature — the storm
    telemetry.flight().clear()
    lengths = [3, 6, 11, 21, 43, 85]
    try:
        # -- scenario 1: the storm is detected and *explained* ------------
        eng, reqs, crashed, _ = _perf_fleet(args, lengths)
        storms = [s for s in watcher.storms()
                  if s["callable"] == "engine.prefill"]
        explain = perf.explain_recompile("engine.prefill")
        named = bool(explain and any(
            c["arg"] == "tokens" and c["field"] == "shape"
            for c in explain["changed_args"]))
        st = eng.stats()
        rows.append({
            "scenario": "recompile_storm",
            "survived": bool(crashed is None and storms and named
                             and len(eng.finished) == len(reqs)),
            "crashed": crashed,
            "storm_detected": bool(storms),
            "distinct_signatures": (storms[0]["distinct_signatures"]
                                    if storms else 0),
            "explained": explain["text"] if explain else None,
            "offending_arg_named": named,
            "storm_in_stats": bool(st["perf"]["storms"]),
            "storm_flight_events": len(
                telemetry.flight().events("compile.storm")),
        })
        eng.close()

        # -- scenario 2: same churn under kv/compile faults ---------------
        perf.reset()
        watcher.storm_threshold = 4
        eng, reqs, crashed, plan = _perf_fleet(
            args, lengths,
            plan_text="serving.compile:error@2;serving.kv.alloc:exhaust@5x2")
        finished = [r for r in reqs if r.state is RequestState.FINISHED]
        failed = [r for r in reqs if r.state is RequestState.FAILED]
        errors_attached = all(r.error is not None for r in failed)
        st = eng.stats() if crashed is None else {}
        storms = [s for s in watcher.storms()
                  if s["callable"] == "engine.prefill"]
        rows.append({
            "scenario": "storm_under_faults",
            "survived": bool(
                crashed is None and errors_attached and storms
                and st.get("blocks_used") == 0 and failed
                and len(finished) + len(failed) == len(reqs)),
            "crashed": crashed,
            "finished": len(finished),
            "failed": len(failed),
            "errors_attached": bool(errors_attached),
            "blocks_leaked": int(st.get("blocks_used", -1)),
            "storm_still_detected": bool(storms),
            "faults_fired": plan.summary(),
        })
        eng.close()

        # -- scenario 3: leak sentinel — real leak flagged, clean drain
        # stays quiet -----------------------------------------------------
        perf.reset()
        mm = perf.memory_monitor()
        clean_leaks = dict(mm.leak_report())
        # simulate a block leak: watermark climbs every "drain"
        for i in range(mm.leak_window + 1):
            mm.set("kv_blocks", 4096 * (i + 1))
            mm.note_step()
        leak = mm.leak_report()
        rows.append({
            "scenario": "leak_sentinel",
            "survived": bool("kv_blocks" in leak and not clean_leaks),
            "clean_drain_flags": clean_leaks,
            "leak_flagged": list(leak),
            "leak_growth_bytes": (leak.get("kv_blocks") or {}).get(
                "growth_bytes"),
            "leak_flight_events": len(
                telemetry.flight().events("memory.leak")),
        })

        # -- scenario 4: observability overhead (informational gate) ------
        perf.reset()
        stable = [16] * args.requests
        t0 = time.perf_counter()
        eng, reqs, crashed, _ = _perf_fleet(args, stable)
        on_s = time.perf_counter() - t0
        eng.close()
        telemetry.disable()
        try:
            t0 = time.perf_counter()
            eng, reqs2, crashed2, _ = _perf_fleet(args, stable)
            off_s = time.perf_counter() - t0
            eng.close()
        finally:
            telemetry.enable()
        ratio = on_s / off_s if off_s > 0 else None
        rows.append({
            "scenario": "overhead",
            # generous bound: jit compiles dominate this tiny fleet and a
            # shared CI host is noisy; a sanity check, not a measurement
            "survived": bool(crashed is None and crashed2 is None
                             and ratio is not None and ratio < 2.0),
            "enabled_sec": round(on_s, 4),
            "disabled_sec": round(off_s, 4),
            "ratio": round(ratio, 3) if ratio else None,
        })
    finally:
        watcher.storm_threshold = old_n
    survived = sum(1 for r in rows if r["survived"])
    dump_path = telemetry.dump(reason="perf chaos suite complete")
    return {
        "suite": "perf",
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "flight_recorder_dump": dump_path,
        "results": rows,
    }


# -- the serve-fleet battery -----------------------------------------------

def _fleet_spec(args, workdir, max_len):
    return {
        "seed": 0,
        "llama_tiny": {"vocab": args.vocab, "hidden": args.hidden,
                       "layers": args.layers, "heads": 4, "kv_heads": 2,
                       "inter": 2 * args.hidden, "seq": 2 * max_len},
        "engine": {"block_size": args.block_size, "max_slots": args.slots,
                   "max_model_len": max_len},
        "warmup": list(range(1, args.prompt_len + 1)),
        "stats_interval_s": 0.05,
    }


def _fleet_reference(spec, prompts, sps):
    """Uninterrupted single-engine streams: the parity oracle every fleet
    scenario is held to (engine == naive decode is proven elsewhere)."""
    from paddle_tpu.serving.replica_worker import build_model

    eng = LLMEngine(build_model(spec), **spec["engine"])
    outs = eng.generate(prompts, sps)
    eng.close()
    return outs


def _start_fleet(workdir, spec, n, *, plans=None, scenario="fleet",
                 router_kw=None, supervisor=None):
    from paddle_tpu.serving import FleetRouter, Gateway, ProcReplica

    reps = []
    for i in range(n):
        env = {}
        if plans and i in plans:
            env["FLAGS_fault_plan"] = plans[i]
        reps.append(ProcReplica(
            f"p{i}", spec, env=env,
            log_path=os.path.join(workdir, f"{scenario}-p{i}.log")))
    kw = dict(probe_interval_s=0.1, probe_timeout_s=8.0,
              affinity_block_size=spec["engine"]["block_size"],
              supervisor=supervisor)
    kw.update(router_kw or {})
    router = FleetRouter(reps, **kw).start(wait_healthy_s=600)
    unhealthy = [r.rid for r in reps if r.state.value != "healthy"]
    if unhealthy:
        router.close()
        raise RuntimeError(f"fleet never became healthy: {unhealthy}")
    gateway = Gateway(router).start()
    return router, gateway, reps


class _SSEClient(threading.Thread):
    """One streaming HTTP client: POSTs a completion with stream=true and
    collects every token chunk until [DONE]."""

    def __init__(self, gw, prompt, sp, priority=0, api_key=None):
        super().__init__(daemon=True)
        self.gw, self.prompt, self.sp = gw, list(prompt), sp
        self.priority = priority
        self.api_key = api_key            # tenant identity (Bearer key)
        self.status = None
        self.tokens: list[int] = []
        self.finish = None
        self.error = None
        self.retry_after = None
        self.shed_tenant = None           # the 429 body's tenant field
        self.start()

    def run(self):
        import http.client
        import json as _json

        body = {"prompt": self.prompt,
                "max_tokens": self.sp.max_new_tokens,
                "temperature": self.sp.temperature,
                "top_k": self.sp.top_k, "top_p": self.sp.top_p,
                "seed": self.sp.seed, "priority": self.priority,
                "stream": True}
        headers = {"Content-Type": "application/json"}
        if self.api_key:
            headers["Authorization"] = f"Bearer {self.api_key}"
        try:
            conn = http.client.HTTPConnection(self.gw.host, self.gw.port,
                                              timeout=600)
            conn.request("POST", "/v1/completions", _json.dumps(body),
                         headers)
            resp = conn.getresponse()
            self.status = resp.status
            if resp.status != 200:
                doc = _json.loads(resp.read())
                self.error = doc.get("error", {}).get("message")
                self.shed_tenant = doc.get("error", {}).get("tenant")
                self.retry_after = resp.getheader("Retry-After")
                conn.close()
                return
            while True:
                line = resp.readline()
                if not line:
                    break
                line = line.decode().strip()
                if not line.startswith("data: "):
                    continue
                payload = line[6:]
                if payload == "[DONE]":
                    break
                doc = _json.loads(payload)
                ch = doc["choices"][0]
                self.tokens += ch.get("token_ids") or []
                if ch.get("finish_reason"):
                    self.finish = ch["finish_reason"]
                if doc.get("error"):
                    self.error = doc["error"]["message"]
            conn.close()
        except Exception as e:
            self.error = f"{type(e).__name__}: {e}"


def _affinity_prompt(router, rng, length, vocab, want_rid):
    """Deterministically craft a prompt whose affinity hash prefers
    ``want_rid`` — how the battery guarantees a fault-armed replica
    actually receives traffic."""
    order = router._order
    for _ in range(512):
        p = [int(t) for t in rng.randint(0, vocab, length)]
        key = router._affinity_key(p)
        if key is not None and order[key % len(order)] == want_rid:
            return p
    raise RuntimeError(f"could not craft a prompt preferring {want_rid}")


def _scenario_sigkill(args, workdir, spec, max_len):
    """SIGKILL a replica while its streams decode: every client stream
    completes on a survivor, token-for-token equal to the reference."""
    sp_greedy = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    sp_seeded = SamplingParams(max_new_tokens=args.max_new, temperature=0.9,
                               top_k=7, seed=123)
    rng = np.random.RandomState(0)
    prompts = [[int(t) for t in rng.randint(0, args.vocab, args.prompt_len)]
               for _ in range(args.requests)]
    sps = [sp_seeded if i % 3 == 2 else sp_greedy
           for i in range(len(prompts))]
    refs = _fleet_reference(spec, prompts, sps)
    router, gateway, reps = _start_fleet(workdir, spec, 3,
                                         scenario="sigkill")
    killed = None
    try:
        clients = [_SSEClient(gateway, p, s) for p, s in zip(prompts, sps)]
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline and killed is None:
            streamed = sum(len(c.tokens) for c in clients)
            if streamed >= 3:
                st = router.stats()
                loaded = sorted(st["replicas"].items(),
                                key=lambda kv: -kv[1]["inflight"])
                rid, info = loaded[0]
                if info["inflight"] > 0:
                    killed = rid
                    router.replicas[rid].kill()   # real SIGKILL
            time.sleep(0.02)
        for c in clients:
            c.join(600)
        st = router.stats()
        lost = [i for i, c in enumerate(clients)
                if c.status != 200 or c.finish != "length" or c.error]
        parity = [i for i, c in enumerate(clients) if c.tokens != refs[i]]
        # request tracing across the kill (ISSUE 11): a failed-over
        # request's merged trace must show BOTH replica hops joined by a
        # router.failover span with the replayed-token count annotated,
        # and no orphan spans
        trace_report = _check_failover_trace(router, workdir)
        ok = (killed is not None and not lost and not parity
              and st["failovers"] >= 1 and st["replica_deaths"] >= 1
              and st["replay_mismatches"] == 0
              and trace_report.get("ok", False))
        return {
            "scenario": "replica_sigkill",
            "survived": bool(ok),
            "killed_replica": killed,
            "lost_requests": len(lost),
            "parity_failures": len(parity),
            "failovers": st["failovers"],
            "replay_suppressed": st["replay_suppressed"],
            "replay_mismatches": st["replay_mismatches"],
            "replica_deaths": st["replica_deaths"],
            "request_trace": trace_report,
        }
    finally:
        gateway.stop()
        router.close()


def _check_failover_trace(router, workdir):
    """Merged-request-trace acceptance on a live fleet after a SIGKILL:
    two replica hop rows, a router.failover span annotated with the
    replayed/suppressed token count, no orphan spans."""
    victims = [rr for rr in router._requests.values() if rr.failovers >= 1]
    if not victims:
        return {"ok": False, "reason": "no failed-over request to trace"}
    rr = victims[0]
    # heartbeats flush spans every stats_interval_s; give the survivor a
    # beat to ship the tail of the request's spans
    time.sleep(0.3)
    out = os.path.join(workdir, f"request-trace-{rr.gid}.json")
    doc = router.request_trace(rr.gid, out_path=out)
    rows = {e["args"]["name"] for e in doc["traceEvents"]
            if e.get("ph") == "M" and e["name"] == "process_name"}
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    failover = [e for e in spans if e["name"] == "router.failover"]
    replica_rows = {h for h in rows if h != "gateway"}
    by_pid = {}
    for e in spans:
        by_pid.setdefault(e["pid"], set()).add(e["args"].get("span_id"))
    orphans = [e["name"] for e in spans
               if e["args"].get("parent_id") is not None
               and e["args"]["parent_id"] not in by_pid[e["pid"]]]
    annotated = [e for e in failover
                 if e["args"].get("replay_suppressed", 0) >= 1]
    ok = (len(replica_rows) >= 2 and len(failover) >= 1
          and len(annotated) >= 1 and not orphans)
    return {
        "ok": bool(ok),
        "trace_path": out,
        "gid": rr.gid,
        "rows": sorted(rows),
        "failover_spans": len(failover),
        "replay_suppressed_annotated": bool(annotated),
        "orphan_spans": orphans,
    }


def _scenario_fault_storms(args, workdir, spec, max_len):
    """Per-replica fault plans through the FaultPlan grammar: p1 cannot
    create any new jit trace (serving.compile:error) so its long-prompt
    requests fail over; p2 wedges mid-decode (serving.decode:delay storm,
    plus a collective:delay that is a no-op on single-chip engines but
    rides along for the future sharded engine) until the probe timeout
    fails it over. Zero lost requests, full parity."""
    long_len = 2 * args.prompt_len          # a prefill bucket nobody warmed
    spec = dict(spec, engine=dict(spec["engine"],
                                  max_model_len=long_len + args.max_new))
    plans = {
        1: "serving.compile:error@1x*",
        2: f"serving.decode:delay=30@4;collective:delay=0.1",
    }
    router, gateway, reps = _start_fleet(
        workdir, spec, 3, plans=plans, scenario="storm",
        router_kw=dict(probe_timeout_s=6.0, max_retries=2))
    try:
        rng = np.random.RandomState(1)
        sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
        # craft traffic that *must* hit the armed replicas: two long
        # prompts preferring p1 (new bucket -> compile error -> retry) and
        # two normal prompts preferring p2 (wedge -> probe -> failover)
        prompts = [
            _affinity_prompt(router, rng, long_len, args.vocab, "p1"),
            _affinity_prompt(router, rng, long_len, args.vocab, "p1"),
            _affinity_prompt(router, rng, args.prompt_len, args.vocab, "p2"),
            _affinity_prompt(router, rng, args.prompt_len, args.vocab, "p2"),
            _affinity_prompt(router, rng, args.prompt_len, args.vocab, "p0"),
        ]
        refs = _fleet_reference(spec, prompts, [sp] * len(prompts))
        clients = [_SSEClient(gateway, p, sp) for p in prompts]
        for c in clients:
            c.join(600)
        st = router.stats()
        lost = [i for i, c in enumerate(clients)
                if c.status != 200 or c.error]
        parity = [i for i, c in enumerate(clients) if c.tokens != refs[i]]
        ok = (not lost and not parity and st["retries"] >= 1
              and st["failovers"] >= 1 and st["replica_deaths"] >= 1)
        return {
            "scenario": "fault_storms",
            "survived": bool(ok),
            "plans": plans,
            "lost_requests": len(lost),
            "parity_failures": len(parity),
            "retries": st["retries"],
            "failovers": st["failovers"],
            "replica_deaths": st["replica_deaths"],
            "replica_states": {r: v["state"]
                               for r, v in st["replicas"].items()},
        }
    finally:
        gateway.stop()
        router.close()


def _scenario_shed(args, workdir, spec, max_len):
    """Fleet at capacity: low-priority arrivals shed with 429+Retry-After,
    a high-priority arrival bypasses, and no in-flight stream is failed.
    Local replicas (the shed path is router-side; process isolation adds
    nothing here)."""
    from paddle_tpu.serving import FleetRouter, Gateway, LLMEngine as _E
    from paddle_tpu.serving import LocalReplica
    from paddle_tpu.serving.replica_worker import build_model

    # longer decodes keep the fleet at capacity for the shed window
    spec = dict(spec, engine=dict(
        spec["engine"],
        max_model_len=args.prompt_len + 2 * args.max_new))

    def factory():
        return _E(build_model(spec), **spec["engine"])

    sp = SamplingParams(max_new_tokens=2 * args.max_new, temperature=0.0)
    rng = np.random.RandomState(2)
    fill = [[int(t) for t in rng.randint(0, args.vocab, args.prompt_len)]
            for _ in range(2)]
    refs = _fleet_reference(spec, fill, [sp] * 2)
    reps = [LocalReplica(f"p{i}", factory, stats_interval_s=0.05,
                         warmup=spec["warmup"]) for i in range(2)]
    router = FleetRouter(reps, probe_interval_s=0.1, probe_timeout_s=30.0,
                         affinity_block_size=spec["engine"]["block_size"],
                         max_inflight_per_replica=1,
                         shed_bypass_priority=1).start(wait_healthy_s=600)
    gateway = Gateway(router).start()
    try:
        streams = [_SSEClient(gateway, p, sp) for p in fill]
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline:           # both streams in flight
            st = router.stats()
            if all(v["inflight"] >= 1 for v in st["replicas"].values()):
                break
            time.sleep(0.01)
        low = [_SSEClient(gateway, fill[0], sp, priority=0)
               for _ in range(3)]
        high = _SSEClient(gateway, fill[1], sp, priority=5)
        for c in low + [high]:
            c.join(600)
        for c in streams:
            c.join(600)
        st = router.stats()
        shed_ok = all(c.status == 429 and c.retry_after is not None
                      for c in low)
        inflight_ok = all(
            c.status == 200 and c.error is None and c.tokens == refs[i]
            for i, c in enumerate(streams))
        ok = (shed_ok and inflight_ok and high.status == 200
              and st["shed"] >= 3)
        return {
            "scenario": "shed_under_load",
            "survived": bool(ok),
            "low_priority_statuses": [c.status for c in low],
            "retry_after": [c.retry_after for c in low],
            "high_priority_status": high.status,
            "inflight_streams_ok": bool(inflight_ok),
            "shed_total": st["shed"],
        }
    finally:
        gateway.stop()
        router.close()


def _scenario_drain_restart(args, workdir, spec, max_len):
    """Rolling restart under live traffic: drain the loaded replica (its
    streams finish within budget), stop it, bring it back through the
    ElasticSupervisor's ledger, and serve on it again."""
    from paddle_tpu.resilience import ElasticSupervisor, JobLedger

    ledger = JobLedger(os.path.join(workdir, "fleet_job_state.json"))
    supervisor = ElasticSupervisor(world_size=2, max_restarts=4,
                                   ledger=ledger)
    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    rng = np.random.RandomState(3)
    prompts = [[int(t) for t in rng.randint(0, args.vocab, args.prompt_len)]
               for _ in range(4)]
    refs = _fleet_reference(spec, prompts, [sp] * 4)
    router, gateway, reps = _start_fleet(workdir, spec, 2,
                                         scenario="drain",
                                         supervisor=supervisor)
    try:
        clients = [_SSEClient(gateway, p, sp) for p in prompts]
        target = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline and target is None:
            st = router.stats()
            for rid, v in st["replicas"].items():
                if v["inflight"] > 0:
                    target = rid
                    break
            time.sleep(0.01)
        report = router.drain_and_restart(target, budget_s=600.0)
        for c in clients:
            c.join(600)
        t0 = time.monotonic()
        while time.monotonic() - t0 < 300 and \
                router.replicas[target].state.value != "healthy":
            time.sleep(0.05)
        extra = _SSEClient(gateway, prompts[0], sp)
        extra.join(600)
        st = router.stats()
        events = [e["event"] for e in ledger.read()["events"]]
        lost = [i for i, c in enumerate(clients)
                if c.status != 200 or c.error]
        parity = [i for i, c in enumerate(clients) if c.tokens != refs[i]]
        ok = (report.get("drained") and not lost and not parity
              and router.replicas[target].state.value == "healthy"
              and extra.status == 200 and extra.tokens == refs[0]
              and "replica_drain" in events
              and "replica_restart" in events
              and st["drains"] >= 1 and st["replica_restarts"] >= 1)
        return {
            "scenario": "drain_restart",
            "survived": bool(ok),
            "drained_replica": target,
            "drain_report": report,
            "lost_requests": len(lost),
            "parity_failures": len(parity),
            "post_restart_state": router.replicas[target].state.value,
            "post_restart_request_ok": bool(extra.status == 200),
            "ledger_events": events,
        }
    finally:
        gateway.stop()
        router.close()


def run_serve_fleet_suite(args, workdir=None, scenario=None):
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="chaos-serve-fleet-")
    max_len = args.prompt_len + args.max_new
    spec = _fleet_spec(args, workdir, max_len)
    rows = []
    fns = _filter_scenarios(
        (_scenario_sigkill, _scenario_fault_storms,
         _scenario_shed, _scenario_drain_restart), "_scenario_", scenario)
    for scenario in fns:
        try:
            rows.append(scenario(args, workdir, spec, max_len))
        except Exception as e:
            rows.append({"scenario": scenario.__name__, "survived": False,
                         "crashed": f"{type(e).__name__}: {e}"})
    survived = sum(1 for r in rows if r["survived"])
    zero_lost = all(r.get("lost_requests", 0) == 0 for r in rows)
    dump_path = telemetry.dump(reason="serve-fleet chaos suite complete")
    return {
        "suite": "serve-fleet",
        "workdir": workdir,
        "config": {"requests": args.requests, "prompt_len": args.prompt_len,
                   "max_new_tokens": args.max_new, "slots": args.slots,
                   "block_size": args.block_size},
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "zero_lost_requests": bool(zero_lost),
        "flight_recorder_dump": dump_path,
        "results": rows,
    }


# -- the tenancy battery ---------------------------------------------------
#
# ``--suite tenancy`` (docs/ROBUSTNESS.md "Fleet degradation", ISSUE 17):
# multi-tenant QoS under abuse, and the autoscaler's closed loop under
# infrastructure failure. Two scenarios: (1) a noisy neighbor floods the
# gateway at ~10x its rate limit while background tenants keep their SLO
# windows — only the hot tenant is shed (per-tenant 429s with its own
# bucket-refill Retry-After), per-tenant roofline cost attribution
# reconciles with the fleet-total FLOPs, and a follow-up prefix-evict
# storm from an over-quota tenant degrades that tenant's cache hit rate,
# nobody else's correctness; (2) a demand burst drives the Autoscaler to
# revive a parked replica through the ElasticSupervisor restart budget,
# the new replica is SIGKILLed mid-warm (degrades to another cold
# revival, never lost requests), and sustained idle scales back down with
# hysteresis — the whole story recorded in the JobLedger.

def _tenant_registry_spec():
    """The battery's tenant table: a rate-limited hot tenant, two
    SLO-tracked background tenants, and a quota-capped spiky tenant."""
    from paddle_tpu.serving import Tenant, TenantRegistry

    return TenantRegistry([
        # burst covers exactly 2 requests at cost 40 (24 prompt + 16 new);
        # refill is negligible over the scenario, so a 20-request flood is
        # ~10x the tenant's admissible rate
        Tenant(name="hot", weight=1.0, rate_tokens_per_s=0.01,
               burst_tokens=80.0, api_keys=("sk-hot",)),
        Tenant(name="bg1", weight=4.0, ttft_slo_s=60.0, tpot_slo_s=5.0,
               api_keys=("sk-bg1",)),
        Tenant(name="bg2", weight=4.0, ttft_slo_s=60.0, tpot_slo_s=5.0,
               api_keys=("sk-bg2",)),
        Tenant(name="spiky", weight=1.0, block_quota=1,
               api_keys=("sk-spiky",)),
    ])


def _scenario_noisy_neighbor(args, workdir, spec, max_len):
    """Hot tenant floods at 10x its rate limit: background tenants hold
    their SLO windows and token parity, only the hot tenant is shed, and
    per-tenant cost attribution sums to the fleet's roofline FLOPs."""
    from paddle_tpu.serving import (FleetRouter, Gateway, LLMEngine as _E,
                                    LocalReplica)
    from paddle_tpu.serving.replica_worker import build_model

    # a modest block pool: phase 2's quota storm must actually evict
    spec = dict(spec, engine=dict(spec["engine"], num_blocks=26))
    reg = _tenant_registry_spec()

    def factory():
        return _E(build_model(spec), **spec["engine"], tenancy=reg.to_dict())

    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    rng = np.random.RandomState(11)

    def prompt():
        return [int(t) for t in rng.randint(0, args.vocab, args.prompt_len)]

    bg_prompts = [prompt() for _ in range(4)]
    hot_prompts = [prompt() for _ in range(20)]
    refs = _fleet_reference(spec, bg_prompts, [sp] * len(bg_prompts))
    reps = [LocalReplica(f"p{i}", factory, stats_interval_s=0.05,
                         warmup=spec["warmup"]) for i in range(2)]
    router = FleetRouter(reps, probe_interval_s=0.1, probe_timeout_s=30.0,
                         affinity_block_size=spec["engine"]["block_size"]
                         ).start(wait_healthy_s=600)
    gateway = Gateway(router, tenancy=reg).start()
    try:
        # -- phase 1: queue flood ------------------------------------------
        bg = [_SSEClient(gateway, p, sp,
                         api_key="sk-bg1" if i % 2 else "sk-bg2")
              for i, p in enumerate(bg_prompts)]
        hot = [_SSEClient(gateway, p, sp, api_key="sk-hot")
               for p in hot_prompts]
        for c in bg + hot:
            c.join(600)
        hot_ok = [c for c in hot if c.status == 200]
        hot_shed = [c for c in hot if c.status == 429]
        bg_lost = [i for i, c in enumerate(bg)
                   if c.status != 200 or c.error or c.tokens != refs[i]]
        shed_ok = (len(hot_ok) == 2 and len(hot_shed) == 18
                   and all(c.shed_tenant == "hot" and c.retry_after
                           for c in hot_shed))

        # per-tenant cost attribution vs the fleet total: every prompt in
        # phase 1 has the same length, so each engine ran exactly one
        # prefill bucket and the one decode bucket — bucket cost x execution
        # count reconstructs the engine's whole roofline spend. A request
        # is prefilled once, and once more after each preemption; the
        # engine's decode-step histogram counts every decode step
        attributed, modeled, single_bucket = 0.0, 0.0, True
        tenant_flops: dict[str, float] = {}
        for rep in reps:
            st = rep.engine.stats()
            for name, row in st["tenancy"]["tenants"].items():
                f = row["cost"]["flops"]
                attributed += f
                tenant_flops[name] = tenant_flops.get(name, 0.0) + f
            executions = {
                "prefill": st["num_preemptions"] + sum(
                    row["requests"]
                    for row in st["tenancy"]["tenants"].values()),
                "decode": rep.engine._m.decode_step.count}
            for kind in ("prefill", "decode"):
                entry = st["perf"]["roofline"][kind]
                if len(entry["buckets"]) != 1:
                    single_bucket = False
                    continue
                (est,) = entry["buckets"].values()
                modeled += est["flops"] * executions[kind]
        cost_ok = (single_bucket and modeled > 0
                   and abs(attributed - modeled) / modeled <= 0.05)

        # background SLO windows held (per-tenant trackers, worst replica)
        slo_ok, bg_p99 = True, 0.0
        for rep in reps:
            ten = rep.engine.stats()["tenancy"]["tenants"]
            for name in ("bg1", "bg2"):
                row = ten.get(name)
                if row is None or row["slo"] is None:
                    continue
                if row["slo"].get("empty"):      # window aged out: no data
                    continue
                if row["slo"]["goodput_ratio"] < 1.0:
                    slo_ok = False
                bg_p99 = max(bg_p99, row["slo"]["ttft"]["p99"] or 0.0)
        slo_ok = slo_ok and bg_p99 < 60.0

        # -- phase 2: prefix-evict storm from an over-quota tenant ---------
        shared = [int(t) for t in rng.randint(0, args.vocab, 16)]
        spiky = [_SSEClient(gateway, shared + prompt()[:8], sp,
                            api_key="sk-spiky") for _ in range(10)]
        bg2 = [_SSEClient(gateway, p, sp,
                          api_key="sk-bg1" if i % 2 else "sk-bg2")
               for i, p in enumerate(bg_prompts[:2])]
        for c in spiky + bg2:
            c.join(600)
        quota_evictions = sum(
            rep.engine.cache.prefix_stats()["tenants"]
            .get("spiky", {}).get("quota_evictions", 0) for rep in reps)
        storm_ok = (all(c.status == 200 and not c.error for c in spiky)
                    and all(c.status == 200 and c.tokens == refs[i]
                            for i, c in enumerate(bg2))
                    and quota_evictions >= 1)

        gw_stats = json.loads(_http_get(gateway, "/stats"))
        snap = gw_stats["tenancy"]["tenants"]
        counts_ok = (snap["hot"]["shed"] == 18
                     and all(snap[t]["shed"] == 0
                             for t in ("bg1", "bg2", "spiky")))
        ok = (shed_ok and not bg_lost and cost_ok and slo_ok and storm_ok
              and counts_ok)
        return {
            "scenario": "noisy_neighbor",
            "survived": bool(ok),
            "hot_admitted": len(hot_ok),
            "hot_shed_429": len(hot_shed),
            "lost_requests": len(bg_lost),
            "bg_ttft_p99_s": round(bg_p99, 4),
            "bg_slo_held": bool(slo_ok),
            "flops_attributed": attributed,
            "flops_modeled": modeled,
            "cost_attribution_ok": bool(cost_ok),
            "spiky_quota_evictions": quota_evictions,
            "per_tenant_shed": {t: snap[t]["shed"] for t in snap},
        }
    finally:
        gateway.stop()
        router.close()


def _http_get(gw, path):
    import http.client

    conn = http.client.HTTPConnection(gw.host, gw.port, timeout=120)
    conn.request("GET", path)
    body = conn.getresponse().read()
    conn.close()
    return body


def _scenario_autoscale_burst_kill(args, workdir, spec, max_len):
    """Closed-loop autoscaling under failure: a burst revives a parked
    replica through the restart budget, the new replica is SIGKILLed
    mid-warm (the autoscaler degrades to another revival), every stream
    completes with parity, and sustained idle scales back down without
    flapping — all of it in the JobLedger."""
    from paddle_tpu.resilience import ElasticSupervisor, JobLedger
    from paddle_tpu.serving import Autoscaler

    ledger = JobLedger(os.path.join(workdir, "autoscale_job_state.json"))
    supervisor = ElasticSupervisor(world_size=3, max_restarts=6,
                                   ledger=ledger)
    # longer decodes keep the burst's queue deep through the kill window
    spec = dict(spec, engine=dict(
        spec["engine"], max_model_len=args.prompt_len + 2 * args.max_new))
    sp = SamplingParams(max_new_tokens=2 * args.max_new, temperature=0.0)
    rng = np.random.RandomState(13)
    prompts = [[int(t) for t in rng.randint(0, args.vocab, args.prompt_len)]
               for _ in range(16)]
    refs = _fleet_reference(spec, prompts, [sp] * len(prompts))
    router, gateway, reps = _start_fleet(workdir, spec, 3,
                                         scenario="autoscale",
                                         supervisor=supervisor)
    scaler = Autoscaler(router, supervisor=supervisor, min_replicas=1,
                        max_replicas=3, scale_up_wait_s=1.2,
                        cooldown_s=0.25, down_hold_s=1.5)
    killed = None
    try:
        # park p1+p2: the warm pool the autoscaler may draw on (their jit
        # traces are in the shared compile cache, so a revival is warm)
        for rid in ("p1", "p2"):
            router.drain(rid, stop_replica=True)
        # wave 1 builds the pressure that revives the first parked
        # replica; wave 2 lands right after the SIGKILL so the queue
        # stays deep while the replacement warms (the scale-up signal is
        # queued work — a drained queue is not demand)
        clients = [_SSEClient(gateway, p, sp) for p in prompts[:8]]
        ups, deadline = [], time.monotonic() + 240
        while time.monotonic() < deadline:
            d = scaler.tick()
            if d["action"] == "up":
                ups.append(d["replica"])
                if killed is None:
                    # SIGKILL the revival mid-warm: it must degrade to a
                    # second revival, never to a lost request
                    killed = d["replica"]
                    router.replicas[killed].kill()
                    clients += [_SSEClient(gateway, p, sp)
                                for p in prompts[8:]]
            if scaler.stats()["scale_ups"]:
                break                      # a revival reached HEALTHY
            time.sleep(0.05)
        for c in clients:
            c.join(600)
        lost = [i for i, c in enumerate(clients)
                if c.status != 200 or c.error]
        parity = [i for i, c in enumerate(clients) if c.tokens != refs[i]]
        settled = scaler.stats()["scale_ups"]

        # sustained idle: hold the loop until exactly one scale-down fires,
        # then keep ticking — cooldown + down-hold must prevent flapping
        downs, t0 = 0, time.monotonic()
        while time.monotonic() - t0 < 8.0:
            d = scaler.tick()
            if d["action"] == "down":
                downs += 1
            time.sleep(0.05)
        healthy = [r.rid for r in reps if r.state.value == "healthy"]
        events = [e["event"] for e in ledger.read()["events"]]
        sig = router.load_signal()
        last_signal = {k: sig[k] for k in (
            "healthy", "starting", "stopped", "unhealthy", "queued",
            "inflight", "est_wait_s")}
        ok = (killed is not None and len(ups) >= 2 and settled
              and not lost and not parity and downs >= 1
              and len(healthy) >= scaler.min_replicas
              and supervisor.budget.used == len(ups)
              and events.count("scale_up") == len(ups)
              and "scale_up_healthy" in events
              and "scale_down" in events)
        return {
            "scenario": "autoscale_burst_kill",
            "survived": bool(ok),
            "killed_mid_warm": killed,
            "scale_ups": ups,
            "time_to_healthy_s": [round(s["time_to_healthy_s"], 3)
                                  for s in settled],
            "lost_requests": len(lost),
            "parity_failures": len(parity),
            "scale_downs": downs,
            "budget_used": supervisor.budget.used,
            "healthy_at_end": healthy,
            "last_signal": last_signal,
            "ledger_events": events,
        }
    finally:
        scaler.close()
        gateway.stop()
        router.close()


def run_tenancy_suite(args, workdir=None, scenario=None):
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="chaos-tenancy-")
    max_len = args.prompt_len + args.max_new
    spec = _fleet_spec(args, workdir, max_len)
    rows = []
    fns = _filter_scenarios(
        (_scenario_noisy_neighbor, _scenario_autoscale_burst_kill),
        "_scenario_", scenario)
    for fn in fns:
        try:
            rows.append(fn(args, workdir, spec, max_len))
        except Exception as e:  # lint: allow-silent(the crash is the row: survived=False fails the battery)
            rows.append({"scenario": fn.__name__[len("_scenario_"):],
                         "survived": False,
                         "crashed": f"{type(e).__name__}: {e}"})
    survived = sum(1 for r in rows if r["survived"])
    zero_lost = all(r.get("lost_requests", 0) == 0 for r in rows)
    dump_path = telemetry.dump(reason="tenancy chaos suite complete")
    return {
        "suite": "tenancy",
        "workdir": workdir,
        "config": {"prompt_len": args.prompt_len,
                   "max_new_tokens": args.max_new, "slots": args.slots,
                   "block_size": args.block_size},
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "zero_lost_requests": bool(zero_lost),
        "flight_recorder_dump": dump_path,
        "results": rows,
    }


# -- the durable battery ---------------------------------------------------
#
# ``--suite durable`` (docs/ROBUSTNESS.md "Durable requests"): the gateway
# itself is the victim. Four scenarios, all held to zero lost ACCEPTED
# requests: (1) SIGKILL the gateway process mid-stream -> restart over the
# same journal -> journal recovery re-submits every accepted-non-terminal
# request through replay-and-suppress, clients reconnect with
# Idempotency-Key + Last-Event-ID and the assembled streams are
# token-for-token equal to an uninterrupted run; (2) a torn final journal
# record (process died mid-append) is detected by CRC, skipped, and never
# poisons recovery; (3) a replica failing 100% of dispatches trips its
# circuit breaker OPEN, placement routes around it (zero lost), and a
# half-open probe restores it once it heals; (4) a fleet-wide fault plan
# exhausts the retry budget -> requests fast-fail with bounded re-dispatch
# volume instead of a retry storm.

def _gateway_spec(args, workdir, max_len, jdir, ready, *, n_replicas=2,
                  router_kw=None, gateway_kw=None):
    spec = _fleet_spec(args, workdir, max_len)
    gspec = dict(spec)
    gspec["n_replicas"] = n_replicas
    gspec["router"] = dict({"probe_interval_s": 0.1,
                            "probe_timeout_s": 60.0,
                            "affinity_block_size":
                                spec["engine"]["block_size"]},
                           **(router_kw or {}))
    gspec["gateway"] = dict({"journal_dir": jdir,
                             "journal_watermark_every": 2},
                            **(gateway_kw or {}))
    gspec["ready_file"] = ready
    return gspec


def _spawn_gateway_worker(gspec, workdir, *, tag, fault_plan=None):
    import subprocess

    if os.path.exists(gspec["ready_file"]):
        os.remove(gspec["ready_file"])
    env = dict(os.environ, PADDLE_GATEWAY_SPEC=json.dumps(gspec),
               PYTHONPATH=".", JAX_PLATFORMS="cpu")
    if fault_plan:
        env["FLAGS_fault_plan"] = fault_plan
    logf = open(os.path.join(workdir, f"gateway-{tag}.log"), "w")
    return subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.serving.gateway_worker"],
        env=env, stdout=logf, stderr=subprocess.STDOUT)


def _wait_gateway_ready(ready_file, proc, timeout=600):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            raise RuntimeError(
                f"gateway worker exited rc={proc.returncode} before ready")
        if os.path.exists(ready_file):
            with open(ready_file) as f:
                return json.load(f)
        time.sleep(0.05)
    raise RuntimeError("gateway worker never became ready")


class _DurableClient(threading.Thread):
    """A streaming client that survives its server's death: it records
    SSE event ids as it reads, treats a dropped connection as a pause
    (not a failure), and can resume against a new port with
    Idempotency-Key + Last-Event-ID — the reconnect contract a real
    durable client follows."""

    def __init__(self, port, prompt, sp, key):
        super().__init__(daemon=True)
        self.port = port
        self.prompt = list(prompt)
        self.sp = sp
        self.key = key
        self.tokens: list[int] = []
        self.last_id = 0
        self.finish = None
        self.error = None
        self.interrupted = False
        self.start()

    def _read_stream(self, port, last_id):
        import http.client as _http
        import json as _json

        body = {"prompt": self.prompt,
                "max_tokens": self.sp.max_new_tokens,
                "temperature": self.sp.temperature,
                "top_k": self.sp.top_k, "top_p": self.sp.top_p,
                "seed": self.sp.seed, "stream": True}
        headers = {"Content-Type": "application/json",
                   "Idempotency-Key": self.key}
        if last_id:
            headers["Last-Event-ID"] = str(last_id)
        conn = _http.HTTPConnection("127.0.0.1", port, timeout=600)
        conn.request("POST", "/v1/completions", _json.dumps(body), headers)
        resp = conn.getresponse()
        if resp.status != 200:
            self.error = f"HTTP {resp.status}"
            conn.close()
            return
        while True:
            line = resp.readline()
            if not line:
                self.interrupted = True        # server died mid-stream
                break
            line = line.decode().strip()
            if line.startswith("id: "):
                self.last_id = int(line[4:])
                continue
            if not line.startswith("data: "):
                continue
            if line == "data: [DONE]":
                break
            doc = _json.loads(line[6:])
            ch = doc["choices"][0]
            self.tokens += ch.get("token_ids") or []
            if ch.get("finish_reason"):
                self.finish = ch["finish_reason"]
            if doc.get("error"):
                self.error = doc["error"]["message"]
        conn.close()

    def run(self):
        try:
            self._read_stream(self.port, 0)
        except Exception:
            self.interrupted = True            # connection torn down

    def resume(self, port):
        """Reconnect against the restarted gateway; returns once the
        stream finishes (or errors)."""
        self.interrupted = False
        try:
            self._read_stream(port, self.last_id)
        except Exception as e:
            self.error = f"{type(e).__name__}: {e}"


def _scenario_gateway_sigkill(args, workdir, spec, max_len):
    """SIGKILL the gateway process while clients stream; restart it over
    the same journal; clients reconnect and every accepted request
    completes token-for-token equal to an uninterrupted run."""
    jdir = os.path.join(workdir, "journal-sigkill")
    ready = os.path.join(workdir, "gw-sigkill-ready.json")
    gspec = _gateway_spec(args, workdir, max_len, jdir, ready)
    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    sp_seeded = SamplingParams(max_new_tokens=args.max_new,
                               temperature=0.9, top_k=7, seed=31)
    rng = np.random.RandomState(7)
    prompts = [[int(t) for t in rng.randint(0, args.vocab, args.prompt_len)]
               for _ in range(4)]
    sps = [sp_seeded if i == 3 else sp for i in range(4)]
    refs = _fleet_reference(spec, prompts, sps)
    # a decode delay keeps the streams mid-flight long enough to kill
    proc = _spawn_gateway_worker(gspec, workdir, tag="sigkill-1",
                                 fault_plan="serving.decode:delay=0.05x*")
    killed_at = None
    try:
        info = _wait_gateway_ready(ready, proc)
        clients = [_DurableClient(info["port"], p, s, key=f"dur-{i}")
                   for i, (p, s) in enumerate(zip(prompts, sps))]
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            if sum(len(c.tokens) for c in clients) >= 3:
                killed_at = sum(len(c.tokens) for c in clients)
                os.kill(proc.pid, 9)           # the real thing
                break
            time.sleep(0.02)
        for c in clients:
            c.join(60)
    finally:
        if proc.poll() is None:
            proc.kill()
        proc.wait(30)
    interrupted = sum(1 for c in clients if c.interrupted)
    # restart over the same journal (no decode delay this time)
    proc2 = _spawn_gateway_worker(gspec, workdir, tag="sigkill-2")
    try:
        info2 = _wait_gateway_ready(ready, proc2)
        recovery = info2.get("recovery") or {}
        for c in clients:
            c.resume(info2["port"])
        lost = [i for i, c in enumerate(clients)
                if c.error or c.finish != "length"]
        parity = [i for i, c in enumerate(clients)
                  if c.tokens != refs[i]]
        ok = (killed_at is not None and interrupted >= 1
              and recovery.get("recovered", 0) + recovery.get(
                  "restored_terminal", 0) >= 1
              and not lost and not parity)
        return {
            "scenario": "gateway_sigkill_recovery",
            "survived": bool(ok),
            "tokens_streamed_before_kill": killed_at,
            "clients_interrupted": interrupted,
            "recovery_report": recovery,
            "lost_requests": len(lost),
            "parity_failures": len(parity),
        }
    finally:
        proc2.terminate()
        try:
            proc2.wait(30)
        except Exception:
            proc2.kill()


def _scenario_torn_journal_tail(args, workdir, spec, max_len):
    """Crash the gateway mid-append (in-process crash + a physically
    chopped journal tail): recovery must detect the torn record by CRC,
    skip it, and still recover every intact acceptance."""
    from paddle_tpu.serving import FleetRouter, Gateway, LLMEngine
    from paddle_tpu.serving import LocalReplica
    from paddle_tpu.serving.journal import scan_dir
    from paddle_tpu.serving.replica_worker import build_model

    jdir = os.path.join(workdir, "journal-torn")

    def factory():
        return LLMEngine(build_model(spec), **spec["engine"])

    def start_fleet():
        reps = [LocalReplica(f"t{i}", factory, stats_interval_s=0.05,
                             warmup=spec["warmup"]) for i in range(2)]
        router = FleetRouter(
            reps, probe_interval_s=0.1, probe_timeout_s=60.0,
            affinity_block_size=spec["engine"]["block_size"],
        ).start(wait_healthy_s=600)
        gw = Gateway(router, journal_dir=jdir,
                     journal_watermark_every=2).start()
        return gw, router

    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    rng = np.random.RandomState(8)
    prompt = [int(t) for t in rng.randint(0, args.vocab, args.prompt_len)]
    ref = _fleet_reference(spec, [prompt], [sp])[0]
    gw, router = start_fleet()
    got = []
    try:
        with FaultPlan.parse("serving.decode:delay=0.05x*"):
            client = _DurableClient(gw.port, prompt, sp, key="torn-1")
            deadline = time.monotonic() + 300
            while time.monotonic() < deadline and len(client.tokens) < 2:
                time.sleep(0.02)
            gw.crash()                      # no terminal records written
            client.join(30)
            got = list(client.tokens)
            last_id = client.last_id
    finally:
        router.close()
    # chop the journal tail mid-record: the torn frame must be skipped
    segs = sorted(p for p in os.listdir(jdir) if p.startswith("wal-"))
    tail_path = os.path.join(jdir, segs[-1])
    with open(tail_path, "r+b") as f:
        f.seek(0, 2)
        f.truncate(f.tell() - 6)
    pre_scan = scan_dir(jdir)
    gw2, router2 = start_fleet()
    try:
        report = gw2.recovery_report or {}
        client.resume(gw2.port)
        ok = (report.get("torn_records", 0) >= 1
              and report.get("recovered") == 1
              and not client.error
              and got + client.tokens[len(got):] == ref
              and client.tokens == ref
              and router2.stats()["replay_mismatches"] == 0)
        return {
            "scenario": "torn_journal_tail",
            "survived": bool(ok),
            "tokens_before_crash": len(got),
            "torn_records_detected": report.get("torn_records"),
            "recovered": report.get("recovered"),
            "lost_requests": 0 if client.tokens == ref else 1,
            "parity_failures": 0 if client.tokens == ref else 1,
            "replay_mismatches": router2.stats()["replay_mismatches"],
        }
    finally:
        gw2.stop()
        router2.close()


def _scenario_breaker_trip(args, workdir, spec, max_len):
    """One replica fails 100% of its dispatches (per-replica
    ``serving.prefill:error`` plan): its breaker trips OPEN inside the
    rolling window, placement routes around it with zero lost requests,
    and once the fault plan exhausts, a HALF_OPEN probe restores it."""
    plans = {1: "serving.prefill:error@1x4"}
    router, gateway, reps = _start_fleet(
        workdir, spec, 2, plans=plans, scenario="breaker",
        router_kw=dict(max_retries=2, breaker_min_samples=3,
                       breaker_failure_rate=0.5, breaker_cooldown_s=1.0))
    try:
        rng = np.random.RandomState(9)
        sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
        prompts = [_affinity_prompt(router, rng, args.prompt_len,
                                    args.vocab, "p1") for _ in range(4)]
        refs = _fleet_reference(spec, prompts, [sp] * len(prompts))
        clients = [_SSEClient(gateway, p, sp) for p in prompts]
        for c in clients:
            c.join(600)
        tripped = router.stats()["breaker_trips"] >= 1
        lost = [i for i, c in enumerate(clients)
                if c.status != 200 or c.error]
        parity = [i for i, c in enumerate(clients) if c.tokens != refs[i]]
        # the plan is exhausted (4 fires); keep offering affinity traffic
        # until the half-open probe lands and the breaker closes again
        deadline = time.monotonic() + 120
        recovered = False
        extra_lost = 0
        while time.monotonic() < deadline and not recovered:
            c = _SSEClient(gateway, prompts[0], sp)
            c.join(600)
            if c.status != 200 or c.error or c.tokens != refs[0]:
                extra_lost += 1
            if router.breakers["p1"].state == "closed" and \
                    router.stats()["breaker_probes"] >= 1:
                recovered = True
            time.sleep(0.2)
        st = router.stats()
        ok = (tripped and not lost and not parity and recovered
              and extra_lost == 0 and st["retries"] >= 1)
        return {
            "scenario": "breaker_trip_recovery",
            "survived": bool(ok),
            "breaker_tripped": tripped,
            "breaker_trips": st["breaker_trips"],
            "breaker_probes": st["breaker_probes"],
            "breaker_final_state": router.breakers["p1"].state,
            "retries": st["retries"],
            "lost_requests": len(lost) + extra_lost,
            "parity_failures": len(parity),
        }
    finally:
        gateway.stop()
        router.close()


def _scenario_retry_budget_storm(args, workdir, spec, max_len):
    """Every replica fails every request: the retry budget must cap total
    re-dispatch volume and every client must get a fast terminal answer —
    a sick fleet degrades into fast-failing, not a retry storm."""
    n_clients = 8
    plans = {0: "serving.prefill:error@1x*",
             1: "serving.prefill:error@1x*"}
    router, gateway, reps = _start_fleet(
        workdir, spec, 2, plans=plans, scenario="budget",
        router_kw=dict(max_retries=3, retry_budget_min=2,
                       retry_budget_ratio=0.0,
                       breaker_min_samples=10_000))  # isolate the budget
    try:
        rng = np.random.RandomState(10)
        sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
        prompts = [[int(t) for t in rng.randint(0, args.vocab,
                                                args.prompt_len)]
                   for _ in range(n_clients)]
        t0 = time.monotonic()
        clients = [_SSEClient(gateway, p, sp) for p in prompts]
        for c in clients:
            c.join(600)
        wall = time.monotonic() - t0
        st = router.stats()
        unanswered = [i for i, c in enumerate(clients)
                      if c.status is None
                      or (c.status == 200 and c.error is None
                          and c.finish is None)]
        # max_retries=3 would allow 24 re-dispatches; the budget caps at 2
        budget_bound = n_clients + 2
        ok = (not unanswered and st["retry_budget_denied"] >= 1
              and st["dispatches"] <= budget_bound)
        return {
            "scenario": "retry_budget_storm",
            "survived": bool(ok),
            "clients": n_clients,
            "wall_sec": round(wall, 2),
            "unanswered": len(unanswered),
            "lost_requests": len(unanswered),
            "dispatches": st["dispatches"],
            "dispatch_bound": budget_bound,
            "retry_budget_denied": st["retry_budget_denied"],
            "retries": st["retries"],
        }
    finally:
        gateway.stop()
        router.close()


def run_durable_suite(args, workdir=None, scenario=None):
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="chaos-durable-")
    max_len = args.prompt_len + args.max_new
    spec = _fleet_spec(args, workdir, max_len)
    rows = []
    fns = _filter_scenarios(
        (_scenario_gateway_sigkill, _scenario_torn_journal_tail,
         _scenario_breaker_trip, _scenario_retry_budget_storm),
        "_scenario_", scenario)
    for scenario in fns:
        try:
            rows.append(scenario(args, workdir, spec, max_len))
        except Exception as e:
            rows.append({"scenario": scenario.__name__, "survived": False,
                         "crashed": f"{type(e).__name__}: {e}"})
    survived = sum(1 for r in rows if r["survived"])
    zero_lost = all(r.get("lost_requests", 0) == 0 for r in rows)
    dump_path = telemetry.dump(reason="durable chaos suite complete")
    return {
        "suite": "durable",
        "workdir": workdir,
        "config": {"requests": args.requests, "prompt_len": args.prompt_len,
                   "max_new_tokens": args.max_new, "slots": args.slots,
                   "block_size": args.block_size},
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "zero_lost_requests": bool(zero_lost),
        "flight_recorder_dump": dump_path,
        "results": rows,
    }


# -- the straggler battery -------------------------------------------------

def _spawn_demo_ranks(endpoint, world, steps, scenario, workdir,
                      plans=None, skews=None):
    """Spawn `world` telemetry.cluster.demo_worker subprocesses; returns
    (procs, trace_paths)."""
    import subprocess

    procs, traces = [], {}
    for r in range(world):
        trace = os.path.join(workdir, f"trace-{scenario}-rank{r}.json")
        traces[r] = trace
        env = dict(os.environ, PYTHONPATH=".", JAX_PLATFORMS="cpu",
                   PADDLE_TELEMETRY_STORE=endpoint,
                   DEMO_RANK=str(r), DEMO_WORLD=str(world),
                   DEMO_STEPS=str(steps), DEMO_SCENARIO=scenario,
                   DEMO_TRACE_OUT=trace)
        if skews and r in skews:
            env["DEMO_CLOCK_SKEW"] = str(skews[r])
        if plans and r in plans:
            env["FLAGS_fault_plan"] = plans[r]
        logf = open(os.path.join(workdir,
                                 f"worker-{scenario}-{r}.log"), "w")
        procs.append(subprocess.Popen(
            [sys.executable, "-c",
             "from paddle_tpu.telemetry.cluster import demo_worker; "
             "demo_worker()"],
            env=env, stdout=logf, stderr=subprocess.STDOUT))
    return procs, traces


def _straggler_scenario(store, workdir, world=4, steps=8, delayed_rank=2,
                        delay_s=0.25):
    """One rank persistently slow before each collective: the monitor must
    name it, and the ranks' traces must merge into one timeline."""
    from paddle_tpu.telemetry.cluster import (ClusterAggregator,
                                              ClusterMonitor, merge_traces)

    endpoint = f"127.0.0.1:{store.port}"
    agg = ClusterAggregator(store, world)
    agg.start_clock_responder()
    mon = ClusterMonitor(store, world,
                         straggler_threshold_s=delay_s / 2,
                         straggler_min_seqs=3)
    procs, traces = _spawn_demo_ranks(
        endpoint, world, steps, "straggle", workdir,
        plans={delayed_rank: f"collective:delay={delay_s}x*"},
        skews={1: 3.0})   # prove offset correction with real skew too
    report = None
    try:
        while any(p.poll() is None for p in procs):
            report = mon.poll()
            time.sleep(0.02)
        report = mon.poll()
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        agg.stop()
    view = agg.fleet_view()
    bases = {r: (view["ranks"][r]["meta"] or {}).get("trace_epoch_unix")
             for r in range(world)}
    offs = {r: (view["ranks"][r]["meta"] or {}).get("clock_offset_s") or 0.0
            for r in range(world)}
    merged_path = os.path.join(workdir, "trace-merged.json")
    merged = merge_traces(
        {r: p for r, p in traces.items() if os.path.exists(p)},
        out_path=merged_path, offsets_s=offs,
        bases_unix={r: b for r, b in bases.items() if b is not None})
    rows = {e["pid"] for e in merged["traceEvents"] if e.get("ph") == "X"}
    named = (report or {}).get("straggler")
    ok = (named is not None and named["rank"] == delayed_rank
          and len(named["seqs"]) >= 3 and len(rows) == world
          and all(p.returncode == 0 for p in procs))
    return {
        "scenario": "persistent_straggler",
        "survived": bool(ok),
        "delayed_rank": delayed_rank,
        "straggler_named": named and named["rank"],
        "straggle_seqs": named and named["seqs"],
        "mean_lag_ms": named and round(named["mean_lag_s"] * 1e3, 1),
        "clock_offset_rank1_s": round(offs.get(1, 0.0), 3),
        "trace_merged": merged_path,
        "trace_rows": len(rows),
        "worker_rcs": [p.returncode for p in procs],
    }


def _hang_scenario(store, workdir, world=4, steps=8, hung_rank=1,
                   hang_at_step=5):
    """One rank wedges mid-job: the hang diagnosis must suspect it, and a
    postmortem bundle must contain EVERY rank's flight dump + stacks."""
    from paddle_tpu.telemetry.cluster import (ClusterAggregator,
                                              ClusterMonitor)

    endpoint = f"127.0.0.1:{store.port}"
    agg = ClusterAggregator(store, world)
    agg.start_clock_responder()
    mon = ClusterMonitor(store, world, hang_threshold_s=1.0)
    procs, _ = _spawn_demo_ranks(
        endpoint, world, steps, "hang", workdir,
        plans={hung_rank: f"collective:delay=120@{hang_at_step + 1}"})
    report, bundle = None, None
    deadline = time.monotonic() + 60.0
    try:
        while time.monotonic() < deadline:
            report = mon.poll()
            if report["hang"]["hung"]:
                break
            time.sleep(0.05)
        bundle = agg.collect_postmortem(
            reason=f"chaos hang: rank {hung_rank}", out_dir=workdir,
            timeout_s=10.0)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        agg.stop()
    manifest = {}
    if bundle:
        with open(os.path.join(bundle, "manifest.json")) as f:
            manifest = json.load(f)
    hang = (report or {}).get("hang", {})
    ok = (hang.get("hung") and hang.get("suspect_ranks") == [hung_rank]
          and manifest.get("ranks_collected") == list(range(world)))
    return {
        "scenario": "collective_hang",
        "survived": bool(ok),
        "hung_rank": hung_rank,
        "suspect_ranks": hang.get("suspect_ranks"),
        "waiting_ranks": hang.get("waiting_ranks"),
        "waiting_seq": hang.get("waiting_seq"),
        "bundle": bundle,
        "bundle_ranks": manifest.get("ranks_collected"),
        "bundle_missing": manifest.get("missing"),
    }


def run_straggler_suite(workdir=None, scenario=None):
    import tempfile

    from paddle_tpu.distributed.tcp_store import TCPStore

    workdir = workdir or tempfile.mkdtemp(prefix="chaos-straggler-")
    by_name = {"straggler": _straggler_scenario, "hang": _hang_scenario}
    if scenario is not None and scenario not in by_name:
        raise SystemExit(f"unknown straggler scenario {scenario!r}; one "
                         f"of: {sorted(by_name)}")
    fns = ([by_name[scenario]] if scenario is not None
           else [_straggler_scenario, _hang_scenario])
    rows = []
    for scenario in fns:
        store = TCPStore(is_master=True)
        try:
            rows.append(scenario(store, workdir))
        finally:
            store.close()
    survived = sum(1 for r in rows if r["survived"])
    return {
        "suite": "straggler",
        "workdir": workdir,
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "results": rows,
    }


def run_train_suite(workdir=None, scenario=None):
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="chaos-train-")
    by_name = {"kill_worker": _train_kill_worker,
               "nan_injection": _train_nan_injection,
               "torn_checkpoint": _train_torn_checkpoint}
    if scenario is not None and scenario not in by_name:
        raise SystemExit(f"unknown train scenario {scenario!r}; one of: "
                         f"{sorted(by_name)}")
    fns = ([by_name[scenario]] if scenario is not None
           else list(by_name.values()))
    rows = [fn(workdir) for fn in fns]
    survived = sum(1 for r in rows if r["survived"])
    dump_path = telemetry.dump(reason="train chaos suite complete")
    return {
        "suite": "train",
        "workdir": workdir,
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "flight_recorder_dump": dump_path,
        "results": rows,
    }


# scenario catalog per suite, for ``--list`` and ``--scenario`` selection
# ("perf" runs as one interdependent battery and cannot be sliced)
# -- the kvfabric battery --------------------------------------------------
#
# ``--suite kvfabric`` (docs/SERVING.md "KV fabric"): the fleet-wide prefix
# directory + cross-replica KV-block migration under its failure modes,
# every scenario held to token-for-token parity against a fabric-off
# engine — the fabric is advisory and may only ever degrade to prefill:
# (1) stale directory: the donor answers a fetch with zero frames
# (serving.kv.fetch:stale) while a garbage document and a ghost roster
# entry sit in the store — every request prefills locally; (2) SIGKILL
# the donor *process* mid-fetch (a real ProcReplica fleet over a real
# TCPStore directory, the fetch delayed by serving.kv.fetch:delay so the
# kill lands inside the transfer window) — the pending fetch fails fast,
# the target prefills, and the dead donor's directory entry ages out with
# its lease; (3) corrupt frame: one exported frame bit-rots after its CRC
# stamp (serving.kv.fetch:corrupt) — the receiver's CRC check refuses it,
# the surviving chain prefix is still used, zero wrong tokens; (4) fetch
# storm: a hot-prefix burst against a tiny migration budget — fetches are
# capped, the overflow prefills locally, and the router's retry budget is
# untouched (a fetch storm must not become a dispatch storm).

def _kvf_build_model(spec):
    from paddle_tpu.serving.replica_worker import build_model

    return build_model(spec)


def _kvf_reference(spec, prompts, sp):
    """Fabric-off parity oracle: one plain engine, same weights."""
    eng = LLMEngine(_kvf_build_model(spec), **spec["engine"])
    outs = eng.generate(prompts, [sp] * len(prompts))
    eng.close()
    return outs


def _kvf_local_fleet(spec, store, n, *, router_kw=None, fabric_kw=None):
    from paddle_tpu.serving import FleetRouter, LocalReplica

    fab = {"store": store, "lease_s": 5.0, "refresh_s": 0.05}
    fab.update(fabric_kw or {})

    def factory():
        return LLMEngine(_kvf_build_model(spec), **spec["engine"])

    reps = [LocalReplica(f"l{i}", factory, stats_interval_s=0.02,
                         fabric=fab, warmup=spec.get("warmup"))
            for i in range(n)]
    kw = dict(probe_interval_s=0.1, probe_timeout_s=30.0,
              affinity_block_size=spec["engine"]["block_size"],
              kv_fabric={"store": store, "fetch_timeout_s": 10.0,
                         "cache_ttl_s": 0.02})
    kw.update(router_kw or {})
    router = FleetRouter(reps, **kw).start(wait_healthy_s=600)
    unhealthy = [r.rid for r in reps if r.state.value != "healthy"]
    if unhealthy:
        router.close()
        raise RuntimeError(f"kvfabric fleet never became healthy: "
                           f"{unhealthy}")
    return router, reps


def _kvf_workload(args, shared=None):
    """Shared-prefix prompts: one common template covering >= 2 full
    blocks (the migratable chain), divergent tails."""
    rng = np.random.RandomState(7)
    bs = args.block_size
    n_shared = max(2 * bs, (int(args.prompt_len * 0.75) // bs) * bs)
    if shared is None:
        shared = [int(t) for t in rng.randint(0, args.vocab, n_shared)]
    tail = max(2, args.prompt_len - len(shared))
    return [list(shared) + [int(t) for t in rng.randint(0, args.vocab,
                                                        tail)]
            for _ in range(args.requests)], shared


def _kvf_overload(router, rid, n=6):
    """Pile phantom in-flight load onto one replica so placement (and
    thus migration) must spread the hot prefix to its siblings."""
    with router._lock:
        for g in range(n):
            router._inflight[rid].add(900_000 + g)


def _kvf_release(router, rid, n=6):
    with router._lock:
        for g in range(n):
            router._inflight[rid].discard(900_000 + g)


def _kvf_wave(router, prompts, sp, timeout=600):
    """Submit every prompt from its own thread (a genuinely concurrent
    burst: lookups race migrations, like real traffic) and wait all."""
    rrs = [None] * len(prompts)
    errs = [None] * len(prompts)

    def one(i):
        try:
            rrs[i] = router.submit(prompts[i], sp)
        except Exception as e:         # shed/no-capacity is a lost request
            errs[i] = f"{type(e).__name__}: {e}"

    threads = [threading.Thread(target=one, args=(i,), daemon=True,
                                name=f"kvf-wave:{i}")
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout)
    for rr in rrs:
        if rr is not None:
            rr.wait(timeout)
    return rrs, errs


def _kvf_parity(rrs, refs, skip=()):
    bad = []
    for i, rr in enumerate(rrs):
        if i in skip or rr is None:
            continue
        if rr.state != "finished" or rr.tokens != refs[i]:
            bad.append(i)
    return bad


def _kvf_fabric_totals(router):
    """Sum the per-replica fabric counters off the heartbeated stats."""
    tot = {}
    for v in router.stats()["replicas"].values():
        fab = ((v.get("prefix_cache") or {}).get("fabric")) or {}
        for k, x in fab.items():
            tot[k] = tot.get(k, 0) + int(x or 0)
    return tot


def _kvf_stale_directory(args, workdir, spec, max_len):
    """A directory that lies — stale entries (donor answers no frames)
    plus garbage documents — must cost only prefills, never tokens."""
    from paddle_tpu.serving import kv_fabric as kvf

    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    prompts, _ = _kvf_workload(args)
    refs = _kvf_reference(spec, prompts, sp)
    store = kvf.MemStore()
    router, reps = _kvf_local_fleet(spec, store, 2)
    try:
        r0 = router.submit(prompts[0], sp)
        assert r0.wait(300) and r0.state == "finished", r0.error
        owner = r0.replica
        time.sleep(0.4)                 # directory beat
        # store-level garbage the reader must skip: an undecodable
        # document under a roster entry (StoreCorruptValue path)
        store.set(f"{kvf.DIR_PREFIX}/dir/ghost", b"\x01 not json \xff")
        roster = store.get_json(f"{kvf.DIR_PREFIX}/roster") or []
        store.set_json(f"{kvf.DIR_PREFIX}/roster", roster + ["ghost"])
        _kvf_overload(router, owner)
        try:
            with FaultPlan.parse("serving.kv.fetch:stale@1x*"):
                rrs, errs = _kvf_wave(router, prompts[1:], sp)
        finally:
            _kvf_release(router, owner)
        st = router.stats()
        bad = _kvf_parity(rrs, refs[1:])
        lost = [i for i, rr in enumerate(rrs) if rr is None] + bad
        ok = (not lost and not any(errs)
              and r0.tokens == refs[0]
              and st["directory_hits"] >= 1
              and st["directory_stale"] >= 1
              and st["migrations"] == 0
              and _kvf_fabric_totals(router).get("ingested_blocks",
                                                 0) == 0)
        return {"scenario": "stale_directory", "survived": bool(ok),
                "lost_requests": len(lost), "parity_failures": len(bad),
                "directory_hits": st["directory_hits"],
                "directory_stale": st["directory_stale"],
                "migrations": st["migrations"],
                "migration_failures": st["migration_failures"]}
    finally:
        router.close()


def _kvf_donor_kill_mid_fetch(args, workdir, spec, max_len):
    """SIGKILL the donor *process* while a migration fetch is in flight
    (real ProcReplicas, real TCPStore directory): the pending fetch fails
    fast, the target prefills, the dead donor's lease ages its directory
    entry out, and every stream stays token-for-token correct."""
    from paddle_tpu.distributed.tcp_store import TCPStore
    from paddle_tpu.serving import FleetRouter, ProcReplica
    from paddle_tpu.serving import kv_fabric as kvf

    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    master = TCPStore(is_master=True)
    endpoint = f"127.0.0.1:{master.port}"
    lease_s = 2.0
    fspec = dict(spec)
    fspec["fabric"] = {"store": endpoint, "lease_s": lease_s,
                       "refresh_s": 0.2}
    reps = [ProcReplica(
        f"p{i}", fspec,
        env=({"FLAGS_fault_plan": "serving.kv.fetch:delay=30@1x*"}
             if i == 0 else {}),
        log_path=os.path.join(workdir, f"kvfabric-p{i}.log"))
        for i in range(2)]
    router = FleetRouter(
        reps, probe_interval_s=0.1, probe_timeout_s=30.0,
        affinity_block_size=spec["engine"]["block_size"],
        kv_fabric={"store": endpoint, "fetch_timeout_s": 60.0,
                   "cache_ttl_s": 0.02}).start(wait_healthy_s=600)
    try:
        unhealthy = [r.rid for r in reps if r.state.value != "healthy"]
        if unhealthy:
            raise RuntimeError(f"fleet never became healthy: {unhealthy}")
        rng = np.random.RandomState(11)
        shared = _affinity_prompt(
            router, rng, 2 * args.block_size, args.vocab, "p0")
        prompts, _ = _kvf_workload(args, shared=shared)
        refs = _kvf_reference(spec, prompts, sp)
        r0 = router.submit(prompts[0], sp)      # affinity -> p0, publishes
        assert r0.wait(600) and r0.state == "finished", r0.error
        assert r0.replica == "p0", f"warm request landed on {r0.replica}"
        time.sleep(0.5)                          # directory beat
        _kvf_overload(router, "p0")
        killed_mid_fetch = False
        t_fail = None
        try:
            done = threading.Event()
            box = {}

            def second():
                t0 = time.monotonic()
                rr = router.submit(prompts[1], sp)
                rr.wait(600)
                box["rr"] = rr
                box["wall"] = time.monotonic() - t0
                done.set()

            threading.Thread(target=second, daemon=True,
                             name="kvf-second-admit").start()
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                with router._fetch_lock:
                    pending = bool(router._fetches)
                if pending:
                    reps[0].kill()               # SIGKILL mid-fetch
                    killed_mid_fetch = True
                    break
                time.sleep(0.005)
            assert done.wait(600), "second request never finished"
            rr1 = box["rr"]
            t_fail = box["wall"]
        finally:
            _kvf_release(router, "p0")
        # the dead donor's lease must age its directory entry out
        time.sleep(lease_s + 0.5)
        directory = kvf.KVDirectory(
            kvf.connect_store(endpoint),
            cfg=kvf.FabricConfig(cache_ttl_s=0.0))
        hashes = kvf.chain_hashes(prompts[2], args.block_size)
        donors_after = directory.lookup(hashes, rids=["p0", "p1"])
        # and the fleet keeps serving the prefix from the survivor
        rrs, errs = _kvf_wave(router, prompts[2:], sp)
        st = router.stats()
        bad = _kvf_parity(rrs, refs[2:])
        lost = [i for i, rr in enumerate(rrs) if rr is None] + bad
        ok = (killed_mid_fetch and not lost and not any(errs)
              and rr1.state == "finished" and rr1.tokens == refs[1]
              and t_fail is not None and t_fail < 30.0
              and st["migration_failures"] >= 1
              and st["directory_stale"] >= 1
              and st["replica_deaths"] >= 1
              and "p0" not in donors_after)
        return {"scenario": "donor_kill_mid_fetch", "survived": bool(ok),
                "killed_mid_fetch": killed_mid_fetch,
                "lost_requests": len(lost), "parity_failures": len(bad),
                "second_request_wall_s": (round(t_fail, 2)
                                          if t_fail else None),
                "migration_failures": st["migration_failures"],
                "directory_stale": st["directory_stale"],
                "replica_deaths": st["replica_deaths"],
                "donors_after_lease": sorted(donors_after)}
    finally:
        router.close()
        master.close()


def _kvf_corrupt_frame(args, workdir, spec, max_len):
    """One migrated frame bit-rots in transit (after its CRC stamp): the
    receiver must refuse it, keep the verified chain prefix, and the
    request's tokens must be exactly the fabric-off stream."""
    from paddle_tpu.serving import kv_fabric as kvf

    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    prompts, _ = _kvf_workload(args)
    refs = _kvf_reference(spec, prompts, sp)
    store = kvf.MemStore()
    router, reps = _kvf_local_fleet(spec, store, 2)
    try:
        r0 = router.submit(prompts[0], sp)
        assert r0.wait(300) and r0.state == "finished", r0.error
        owner = r0.replica
        time.sleep(0.4)
        _kvf_overload(router, owner)
        try:
            with FaultPlan.parse("serving.kv.fetch:corrupt@1x*"):
                rrs, errs = _kvf_wave(router, prompts[1:], sp)
        finally:
            _kvf_release(router, owner)
        st = router.stats()
        tot = _kvf_fabric_totals(router)
        bad = _kvf_parity(rrs, refs[1:])
        lost = [i for i, rr in enumerate(rrs) if rr is None] + bad
        ok = (not lost and not any(errs)
              and r0.tokens == refs[0]
              and st["migrations"] >= 1
              and tot.get("ingest_corrupt", 0) >= 1)
        return {"scenario": "corrupt_frame", "survived": bool(ok),
                "lost_requests": len(lost), "parity_failures": len(bad),
                "migrations": st["migrations"],
                "migrated_blocks": st["migrated_blocks"],
                "ingest_corrupt": tot.get("ingest_corrupt", 0),
                "ingested_blocks": tot.get("ingested_blocks", 0)}
    finally:
        router.close()


def _kvf_fetch_storm(args, workdir, spec, max_len):
    """A hot-prefix burst against a tiny migration budget: fetch volume
    stays capped, the overflow prefills locally, the router's retry
    budget is untouched, and nothing is lost."""
    from paddle_tpu.serving import kv_fabric as kvf

    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    budget = 1
    prompts, shared = _kvf_workload(args)
    storm = prompts + prompts[1:]          # double the burst
    refs = _kvf_reference(spec, storm, sp)
    store = kvf.MemStore()
    router, reps = _kvf_local_fleet(
        spec, store, 3,
        router_kw={"kv_fabric": {
            "store": store, "fetch_timeout_s": 10.0, "cache_ttl_s": 0.02,
            "fetch_window_s": 60.0, "max_fetches_per_window": budget}},
        fabric_kw={"refresh_s": 0.5})
    try:
        r0 = router.submit(storm[0], sp)
        assert r0.wait(300) and r0.state == "finished", r0.error
        owner = r0.replica
        time.sleep(0.6)
        _kvf_overload(router, owner)
        try:
            rrs, errs = _kvf_wave(router, storm[1:], sp)
        finally:
            _kvf_release(router, owner)
        st = router.stats()
        bad = _kvf_parity(rrs, refs[1:])
        lost = [i for i, rr in enumerate(rrs) if rr is None] + bad
        ok = (not lost and not any(errs)
              and r0.tokens == refs[0]
              and st["migrations"] <= budget
              and st["fetch_skipped"] >= 1
              and st["retry_budget_denied"] == 0)
        return {"scenario": "fetch_storm", "survived": bool(ok),
                "lost_requests": len(lost), "parity_failures": len(bad),
                "burst": len(storm),
                "migrations": st["migrations"],
                "fetch_skipped": st["fetch_skipped"],
                "directory_placements": st["directory_placements"],
                "retry_budget_denied": st["retry_budget_denied"]}
    finally:
        router.close()


def run_kvfabric_suite(args, workdir=None, scenario=None):
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="chaos-kvfabric-")
    max_len = args.prompt_len + args.max_new
    spec = _fleet_spec(args, workdir, max_len)
    rows = []
    fns = _filter_scenarios(
        (_kvf_stale_directory, _kvf_donor_kill_mid_fetch,
         _kvf_corrupt_frame, _kvf_fetch_storm), "_kvf_", scenario)
    for fn in fns:
        try:
            rows.append(fn(args, workdir, spec, max_len))
        except Exception as e:
            rows.append({"scenario": fn.__name__[len("_kvf_"):],
                         "survived": False,
                         "crashed": f"{type(e).__name__}: {e}"})
    survived = sum(1 for r in rows if r["survived"])
    zero_lost = all(r.get("lost_requests", 0) == 0 for r in rows)
    dump_path = telemetry.dump(reason="kvfabric chaos suite complete")
    return {
        "suite": "kvfabric",
        "workdir": workdir,
        "config": {"requests": args.requests, "prompt_len": args.prompt_len,
                   "max_new_tokens": args.max_new, "slots": args.slots,
                   "block_size": args.block_size},
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "zero_lost_requests": bool(zero_lost),
        "flight_recorder_dump": dump_path,
        "results": rows,
    }


# -- the locksan battery ---------------------------------------------------
#
# ``--suite locksan`` (docs/ANALYSIS.md): arm the runtime lock-order
# sanitizer and drive real multi-threaded fleet surfaces in-process —
# the components' own locks (journal.state, kv_fabric.directory,
# metrics.*, flight.ring) are created *after* arming so every
# acquisition is observed. Two load scenarios must come back with zero
# violations; the inversion canary deliberately violates to prove the
# detector is live (a sanitizer that never fires proves nothing).


def _locksan_fleet_under_load(workdir):
    """Journal appends + directory publish/lookup from six named threads
    with LockSan armed: the serving tier's lock discipline under real
    contention. The journal runs ``fsync='always'`` so every append
    crosses its annotated durability barrier — the waiver path counts in
    ``locksan_allowed_blocking_total`` instead of reporting."""
    from paddle_tpu.analysis import locksan
    from paddle_tpu.serving.journal import Journal
    from paddle_tpu.serving.kv_fabric import (KVDirectory, MemStore,
                                              _ROSTER_KEY, _dir_key)

    locksan.reset()
    root = os.path.join(workdir, "locksan-journal")
    journal = Journal(root, fsync="always")
    store = MemStore()
    directory = KVDirectory(store)
    rids = ["r0", "r1", "r2"]
    store.set_json(_ROSTER_KEY, rids)
    chain = [f"h{i:03d}" for i in range(16)]

    def publish(rid, depth, epoch):
        store.set_json(_dir_key(rid), {
            "v": 1, "rid": rid, "epoch": epoch,
            "published_unix": time.time(),
            # lint: allow-wallclock(lease_until is a cross-process wall stamp in the store)
            "lease_until": time.time() + 60.0,
            "block_size": 8, "hashes": chain[:depth],
            "spill_hashes": [], "truncated": False,
        })

    for i, rid in enumerate(rids):
        publish(rid, 4 * (i + 1), 1.0)

    stop = threading.Event()
    errors = []

    def appender(tag):
        try:
            for i in range(150):
                journal.append({"t": "accepted", "jid": f"{tag}-{i}"})
        except Exception as e:  # lint: allow-silent(captured into thread_errors; any entry fails the scenario)
            errors.append(f"{tag}: {type(e).__name__}: {e}")

    def looker(tag):
        try:
            n = 0
            while not stop.is_set():
                directory.lookup(chain, rids)
                n += 1
                if n % 7 == 0:
                    directory.snapshot(rids)
        except Exception as e:  # lint: allow-silent(captured into thread_errors; any entry fails the scenario)
            errors.append(f"{tag}: {type(e).__name__}: {e}")

    def publisher():
        try:
            epoch = 2.0
            while not stop.is_set():
                for i, rid in enumerate(rids):
                    publish(rid, 4 * (i + 1), epoch)
                epoch += 1.0
        except Exception as e:  # lint: allow-silent(captured into thread_errors; any entry fails the scenario)
            errors.append(f"publisher: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=appender, args=(f"append-{i}",),
                                name=f"locksan-append-{i}")
               for i in range(2)]
    threads += [threading.Thread(target=looker, args=(f"lookup-{i}",),
                                 name=f"locksan-lookup-{i}")
                for i in range(3)]
    threads.append(threading.Thread(target=publisher,
                                    name="locksan-publisher"))
    for t in threads:
        t.start()
    for t in threads[:2]:       # appenders run a fixed count
        t.join(60)
    stop.set()
    for t in threads[2:]:
        t.join(60)
    journal.close()

    rep = locksan.report()
    vs = locksan.violations()
    ok = (not errors and not vs
          and "journal.state" in rep["locks_tracked"]
          and "kv_fabric.directory" in rep["locks_tracked"]
          and "kv_fabric.memstore" in rep["locks_tracked"])
    return {"scenario": "fleet_under_load", "survived": bool(ok),
            "violations": len(vs),
            "violation_summaries": [v["summary"] for v in vs],
            "locks_tracked": len(rep["locks_tracked"]),
            "edges": rep["num_edges"],
            "thread_errors": errors}


def _locksan_telemetry_threads(workdir):
    """A fresh metrics registry + flight recorder hammered from four
    named threads — the lock-per-child metric family tree and the
    recorder ring under concurrent inc/observe/record/dump traffic.
    Zero violations expected."""
    from paddle_tpu.analysis import locksan
    from paddle_tpu.telemetry.flight_recorder import FlightRecorder
    from paddle_tpu.telemetry.metrics import MetricsRegistry

    locksan.reset()
    reg = MetricsRegistry()
    reqs = reg.counter("locksan_chaos_requests_total",
                       "locksan chaos suite scratch counter",
                       labels=("path",))
    depth = reg.gauge("locksan_chaos_depth", "scratch gauge")
    rec = FlightRecorder(capacity=512)
    errors = []

    def worker(tag):
        try:
            for i in range(400):
                reqs.labels(path=tag).inc()
                depth.set(i)
                rec.record("locksan.chaos", tag=tag, i=i)
                if i % 97 == 0:
                    rec.dump(os.path.join(workdir, f"rec-{tag}.json"),
                             reason="locksan chaos checkpoint")
        except Exception as e:  # lint: allow-silent(captured into thread_errors; any entry fails the scenario)
            errors.append(f"{tag}: {type(e).__name__}: {e}")

    threads = [threading.Thread(target=worker, args=(f"w{i}",),
                                name=f"locksan-telemetry-{i}")
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)

    vs = locksan.violations()
    rep = locksan.report()
    ok = (not errors and not vs
          and any(n.startswith("metrics.") for n in rep["locks_tracked"])
          and "flight.ring" in rep["locks_tracked"])
    return {"scenario": "telemetry_threads", "survived": bool(ok),
            "violations": len(vs),
            "violation_summaries": [v["summary"] for v in vs],
            "locks_tracked": len(rep["locks_tracked"]),
            "edges": rep["num_edges"],
            "thread_errors": errors}


def _locksan_inversion_canary(workdir):
    """Deliberately violate both detector halves — an A→B/B→A
    inversion across two named threads and a ``time.sleep`` under a
    lock — and require LockSan to report both. Proves the armed
    detector in *this* battery actually fires; a clean suite with a
    dead detector would be vacuous."""
    from paddle_tpu.analysis import locksan

    locksan.reset()
    a = locksan.Lock("canary.A")
    b = locksan.Lock("canary.B")
    order = threading.Barrier(2, timeout=10)

    def take_ab():
        with a:
            with b:
                pass
        order.wait()

    def take_ba():
        order.wait()        # strictly after the A->B edge exists
        with b:
            with a:
                pass

    t1 = threading.Thread(target=take_ab, name="canary-ab")
    t2 = threading.Thread(target=take_ba, name="canary-ba")
    t1.start()
    t2.start()
    t1.join(30)
    t2.join(30)

    hold = locksan.Lock("canary.hold")
    with hold:
        time.sleep(0)       # the blocking-call half

    vs = locksan.violations()
    kinds = sorted({v["type"] for v in vs})
    inv = [v for v in vs if v["type"] == "lock_order_inversion"]
    both_named = bool(inv) and \
        {"canary-ab", "canary-ba"} <= {e["thread"] for e in inv[0]["edges"]}
    ok = (kinds == ["blocking_call_under_lock", "lock_order_inversion"]
          and both_named)
    out = {"scenario": "inversion_canary", "survived": bool(ok),
           "violations_reported": len(vs), "types": kinds,
           "both_threads_named": both_named}
    locksan.reset()         # the canary's graph must not leak onward
    return out


def run_locksan_suite(workdir=None, scenario=None):
    import tempfile

    from paddle_tpu.analysis import locksan

    workdir = workdir or tempfile.mkdtemp(prefix="chaos-locksan-")
    fns = _filter_scenarios(
        (_locksan_fleet_under_load, _locksan_telemetry_threads,
         _locksan_inversion_canary), "_locksan_", scenario)
    locksan.arm()
    rows = []
    try:
        for fn in fns:
            try:
                rows.append(fn(workdir))
            except Exception as e:  # lint: allow-silent(the crash is the row: survived=False fails the battery)
                rows.append({"scenario": fn.__name__[len("_locksan_"):],
                             "survived": False,
                             "crashed": f"{type(e).__name__}: {e}"})
    finally:
        locksan.reset()
        locksan.disarm()
    survived = sum(1 for r in rows if r["survived"])
    dump_path = telemetry.dump(reason="locksan chaos suite complete")
    return {
        "suite": "locksan",
        "workdir": workdir,
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "flight_recorder_dump": dump_path,
        "results": rows,
    }


def run_soak_suite(args, workdir=None, scenario=None):
    """Rolling-chaos soak (docs/WORKLOADS.md "Soak pass criteria"): the
    trace-driven workload replayed epoch after epoch against a real
    fleet while the chaos action rotates, every epoch re-asserting zero
    lost accepted requests, leak-sentinel silence, journal bounds, and
    the per-tenant goodput floor.

    ``rolling`` is the full battery — 2 ProcReplicas + gateway, with
    SIGKILL and drain/restart churn in the rotation; ``degrade`` is the
    in-process variant (1 LocalReplica, fault-plan degradation +
    compaction only) that mirrors the tier-1 smoke.
    """
    import tempfile

    from paddle_tpu.serving.soak import SoakConfig, run_soak
    from paddle_tpu.serving.workload import preset

    workdir = workdir or tempfile.mkdtemp(prefix="chaos-soak-")

    def _cfg(name):
        spec = preset("burst")
        spec.vocab = args.vocab
        spec.prompt_len["max"] = 32
        spec.output_len["max"] = 16
        # generous SLO: the soak's goodput floor guards liveness under
        # chaos (did requests finish at all), not latency — a shared-core
        # proc fleet mid-SIGKILL legitimately runs seconds of TTFT
        spec.slo = {"ttft_s": 10.0, "tpot_s": 2.0}
        max_len = 48
        fleet_spec = {
            "seed": 0,
            "llama_tiny": {"vocab": args.vocab, "hidden": args.hidden,
                           "layers": args.layers, "heads": 4,
                           "kv_heads": 2, "inter": 2 * args.hidden,
                           "seq": 2 * max_len},
            "engine": {"block_size": args.block_size,
                       "max_slots": args.slots, "max_model_len": max_len},
            # one prompt per power-of-two prefill bucket up to the
            # prompt cap (32 needs a >16-token warmup to compile P=32)
            "warmup": [4, 8, 16, 24, 32],
            "stats_interval_s": 0.05,
        }
        degrade = [
            {"kind": "plan",
             "plan": "gateway.journal.append:delay=0.01%0.2"},
            {"kind": "compact"},
            {"kind": "plan", "plan": "serving.decode:delay=0.005%0.1"},
        ]
        rolling = [
            {"kind": "plan",
             "plan": "gateway.journal.append:delay=0.01%0.2"},
            {"kind": "kill"},
            {"kind": "plan", "plan": "serving.decode:delay=0.005%0.1"},
            {"kind": "churn"},
            {"kind": "compact"},
            {"kind": "plan", "plan": "router.probe:delay=0.05%0.2"},
        ]
        chaos = rolling if name == "rolling" else degrade
        return SoakConfig(
            spec=spec, fleet_spec=fleet_spec,
            workdir=os.path.join(workdir, name),
            epochs=len(chaos), chaos=chaos,
            replicas=2 if name == "rolling" else 1,
            fleet="proc" if name == "rolling" else "local",
            epoch_wait_s=120.0,
            journal={"segment_max_records": 16, "compact_segments": 2,
                     "retain_terminal": 32},
            goodput_floor=0.3,
            kill_allowed=(name == "rolling"))

    names = [n for n in ("degrade", "rolling")
             if scenario is None or n == scenario]
    rows = []
    for name in names:
        rep = run_soak(_cfg(name))
        rows.append({
            "scenario": name,
            "survived": rep["passed"],
            "epochs": len(rep["epochs"]),
            "lost": sum(r["lost"] for r in rep["epochs"]),
            "compaction_cycles": rep["compaction_cycles_observed"],
            "wall_sec": round(rep["wall_s"], 1),
            "violations": rep["violations"],
        })
    survived = sum(1 for r in rows if r["survived"])
    dump_path = telemetry.dump(reason="soak chaos suite complete")
    return {
        "suite": "soak",
        "workdir": workdir,
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "flight_recorder_dump": dump_path,
        "results": rows,
    }


# -- the alerts battery ----------------------------------------------------
#
# ``--suite alerts`` (docs/OBSERVABILITY.md "Ops plane", ISSUE 19): prove
# the detect half of detect→page→diagnose end to end, with the SRE burn
# windows shrunk (``time_scale``) so real page timing runs in seconds.
# Two scenarios: (1) a ``serving.decode:delay`` fault degrades TPOT past
# the SLO on a live gateway fleet — the fast-burn window PAGES within a
# bounded detection time, the page names an exemplar trace id, the
# gateway's /v1/alerts shows it, and recovery resolves the alert; (2) a
# SIGKILL'd rank publisher trips the publisher-absence rule (the watchdog
# for the watchers).

def _alerts_exemplar_fn(router):
    """The page's exemplar: the trace id behind the worst replica's
    window p99 (``GET /v1/traces/<id>`` renders its timeline)."""
    def fn():
        try:
            for rep in (router.stats().get("replicas") or {}).values():
                ex = ((rep.get("slo") or {}).get("exemplars") or {})
                tid = ex.get("tpot_p99") or ex.get("ttft_p99")
                if tid:
                    return tid
        except Exception:  # lint: allow-silent(exemplars are garnish; the page still goes out)
            pass
        return None
    return fn


def _alerts_wait(pred, timeout_s, poll_s=0.05):
    """Poll until pred() is truthy; returns elapsed seconds or None."""
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        if pred():
            return time.monotonic() - t0
        time.sleep(poll_s)
    return None


def _scenario_slo_burn_page(args, workdir, spec, max_len):
    """Decode-delay fault blows the TPOT SLO on a live fleet: the
    fast-burn window pages within a bounded detection time with an
    exemplar trace id, /v1/alerts surfaces it, recovery resolves it."""
    from paddle_tpu.serving import FleetRouter, Gateway, LocalReplica
    from paddle_tpu.serving import LLMEngine as _E
    from paddle_tpu.serving.replica_worker import build_model
    from paddle_tpu.telemetry import alerts as alerts_mod
    from paddle_tpu.telemetry import history as history_mod

    # fast window = 14.4s long / 1.2s short; resolve hysteresis 0.12s
    ts = 0.004
    # a short SLO window so goodput recovers quickly once the fault
    # lifts; the 0.5s TPOT SLO leaves a wide margin over the healthy tail
    # (~0.08s p95 on a shared CPU host) while the 1.2s/step delay fault
    # violates it on every token
    spec = dict(spec, engine=dict(
        spec["engine"], slo_tpot_s=0.5, slo_window_s=4.0))

    def factory():
        return _E(build_model(spec), **spec["engine"])

    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    rng = np.random.RandomState(5)

    def prompts(n):
        return [[int(t) for t in rng.randint(0, args.vocab,
                                             args.prompt_len)]
                for _ in range(n)]

    reps = [LocalReplica(f"p{i}", factory, stats_interval_s=0.05,
                         warmup=spec["warmup"]) for i in range(2)]
    router = FleetRouter(reps, probe_interval_s=0.1, probe_timeout_s=30.0,
                         affinity_block_size=spec["engine"]["block_size"]
                         ).start(wait_healthy_s=600)

    # warmup requests legitimately violate the TPOT SLO (they pay XLA
    # compiles); wait for them to age out of the 4s SLO window so the
    # history the rules read starts from a genuinely healthy fleet
    def goodput_clean():
        fams = telemetry.registry().snapshot().get("slo_goodput_ratio", {})
        series = fams.get("series") or []
        return bool(series) and all(s["value"] >= 1.0 for s in series)

    if _alerts_wait(goodput_clean, 30.0, poll_s=0.2) is None:
        router.close()
        return {"scenario": "slo_burn_page", "survived": False,
                "failed": "fleet goodput never settled to 1.0 post-warmup"}

    hist = history_mod.TimeSeriesStore(interval_s=0.05)
    hist.start()
    engine = alerts_mod.AlertEngine(
        hist,
        alerts_mod.default_rules(objective=0.99, time_scale=ts,
                                 exemplar_fn=_alerts_exemplar_fn(router)),
        interval_s=0.1)
    engine.start()
    gateway = Gateway(router, history=hist, alerts=engine).start()
    plan = FaultPlan.parse("serving.decode:delay=1.2x1000000")

    def firing(name, key=None):
        return next((a for a in engine.firing() if a["rule"] == name
                     and (key is None or a["key"] == key)), None)

    try:
        # -- healthy phase: goodput 1.0, nothing may fire ------------------
        for c in [_SSEClient(gateway, p, sp) for p in prompts(4)]:
            c.join(600)
        time.sleep(0.5)
        if engine.firing():
            return {"scenario": "slo_burn_page", "survived": False,
                    "failed": f"fired while healthy: {engine.firing()}"}

        # -- fault phase: every decode step +1.2s >> the 0.5s TPOT SLO -----
        plan.__enter__()
        try:
            clients = [_SSEClient(gateway, p, sp) for p in prompts(6)]
            detect = _alerts_wait(
                lambda: firing("slo-goodput-burn", "fast") is not None,
                60.0)
            page = firing("slo-goodput-burn", "fast")
            for c in clients:
                c.join(600)
        finally:
            plan.__exit__(None, None, None)
        if detect is None:
            return {"scenario": "slo_burn_page", "survived": False,
                    "failed": "fast-burn page never fired under the "
                              "decode-delay fault",
                    "state": engine.state()}
        page_ok = (page["severity"] == "page" and page["key"] == "fast")
        exemplar = page.get("exemplar")

        # the operator's view: the gateway endpoint shows the same page
        gw_doc = json.loads(_http_get(gateway, "/v1/alerts"))
        gw_ok = any(a["rule"] == "slo-goodput-burn"
                    and a["state"] == "firing"
                    for a in gw_doc.get("alerts", []))

        # -- recovery: healthy traffic drains the fast window (the slow
        # 86.4s ticket window keeps burning much longer, by design) -------
        t_lift = time.monotonic()
        resolved = None
        # first let the fault-era samples age out of the SLO window —
        # traffic sent while the replicas still shed records failures,
        # which would keep the burn alive forever
        time.sleep(spec["engine"]["slo_window_s"] + 1.0)
        for _ in range(20):
            for c in [_SSEClient(gateway, p, sp) for p in prompts(2)]:
                c.join(600)
            if firing("slo-goodput-burn", "fast") is None:
                resolved = time.monotonic() - t_lift
                break
            time.sleep(0.3)
        return {
            "scenario": "slo_burn_page",
            "survived": bool(page_ok and gw_ok and exemplar
                             and resolved is not None),
            "detection_s": round(detect, 2),
            "resolved_s": (round(resolved, 2)
                           if resolved is not None else None),
            "exemplar": exemplar,
            "page_severity": page["severity"],
            "gateway_alerts_ok": gw_ok,
            "burn_at_page": page.get("value"),
        }
    finally:
        engine.stop()
        hist.stop()
        gateway.stop()
        router.close()


def _scenario_publisher_absence(args, workdir, spec, max_len):
    """SIGKILL the rank's telemetry publisher: its publish counter goes
    flat and the zero-mode absence rule pages — the watchdog that
    catches a silently dead observability plane."""
    import signal
    import subprocess

    from paddle_tpu.distributed.tcp_store import TCPStore
    from paddle_tpu.telemetry import alerts as alerts_mod
    from paddle_tpu.telemetry import history as history_mod
    from paddle_tpu.telemetry.cluster import _get_json, _k

    store = TCPStore(is_master=True)
    code = (
        "import sys, time\n"
        "sys.path.insert(0, %r)\n"
        "from paddle_tpu.distributed.tcp_store import TCPStore\n"
        "from paddle_tpu.telemetry.cluster import RankPublisher\n"
        "store = TCPStore(host='127.0.0.1', port=%d)\n"
        "RankPublisher(store, 0, 1, interval_s=0.1,\n"
        "              sync_clock=False).start()\n"
        "print('up', flush=True)\n"
        "time.sleep(600)\n" % (REPO_ROOT, store.port))
    log = open(os.path.join(workdir, "publisher.log"), "w")
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=log, stderr=subprocess.STDOUT)

    # monitor side: the fleet's publish seq enters the local history as a
    # counter — alive publisher => nonzero rate; dead => flat => absence
    def fleet_publish_source():
        meta = _get_json(store, _k(0, "meta")) or {}
        seq = meta.get("publish_seq")
        if seq is None:
            return {}
        return {"cluster_publish_total": {
            "type": "counter",
            "series": [{"labels": {"rank": "0"}, "value": float(seq)}]}}

    # absence window 15s*ts = 3.0s against a 0.1s publish interval: a
    # 30x margin so a scheduler stall on a loaded box cannot read as a
    # dead publisher (0.05 flaked exactly that way), while a real kill
    # still detects in ~3s
    ts = 0.2
    hist = history_mod.TimeSeriesStore(interval_s=0.05)
    hist.add_source("fleet", fleet_publish_source)
    hist.start()
    rules = [r for r in alerts_mod.default_rules(time_scale=ts)
             if r.name == "publisher-absence"]
    engine = alerts_mod.AlertEngine(hist, rules, interval_s=0.1)
    engine.start()

    def firing():
        return [a for a in engine.firing()
                if a["rule"] == "publisher-absence"]

    try:
        alive = _alerts_wait(
            lambda: (_get_json(store, _k(0, "meta")) or {}).get(
                "publish_seq", 0) >= 3, 60.0)
        if alive is None:
            return {"scenario": "publisher_absence", "survived": False,
                    "failed": "publisher subprocess never published"}
        time.sleep(1.5)             # presence held under a live publisher
        if firing():
            return {"scenario": "publisher_absence", "survived": False,
                    "failed": "absence fired while the publisher was alive"}

        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=30)
        detect = _alerts_wait(lambda: bool(firing()), 30.0)
        if detect is None:
            return {"scenario": "publisher_absence", "survived": False,
                    "failed": "absence alert never fired after SIGKILL",
                    "state": engine.state()}
        alert = firing()[0]
        return {
            "scenario": "publisher_absence",
            "survived": alert["severity"] == "page",
            "detection_s": round(detect, 2),
            "severity": alert["severity"],
        }
    finally:
        engine.stop()
        hist.stop()
        if proc.poll() is None:
            proc.kill()
        log.close()
        store.close()


def run_alerts_suite(args, workdir=None, scenario=None):
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="chaos-alerts-")
    max_len = args.prompt_len + args.max_new
    spec = _fleet_spec(args, workdir, max_len)
    rows = []
    fns = _filter_scenarios(
        (_scenario_slo_burn_page, _scenario_publisher_absence),
        "_scenario_", scenario)
    for fn in fns:
        try:
            rows.append(fn(args, workdir, spec, max_len))
        except Exception as e:  # lint: allow-silent(the crash is the row: survived=False fails the battery)
            rows.append({"scenario": fn.__name__[len("_scenario_"):],
                         "survived": False,
                         "crashed": f"{type(e).__name__}: {e}"})
    survived = sum(1 for r in rows if r["survived"])
    dump_path = telemetry.dump(reason="alerts chaos suite complete")
    return {
        "suite": "alerts",
        "workdir": workdir,
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "flight_recorder_dump": dump_path,
        "results": rows,
    }


# -- the heal battery ------------------------------------------------------
# The self-healing control plane end to end (docs/ROBUSTNESS.md
# "Self-healing & rollout") on a real ProcReplica fleet under live SSE
# traffic: (1) a wedged replica blows the SLO -> burn page -> the
# remediation engine drains+restarts it under the actuation lease -> the
# alert resolves and the post-condition bake closes ok, zero lost; (2) a
# replica sick EVERY incarnation re-triggers after each restart -> flap
# detection quarantines it instead of a restart storm; (3) a rolling
# upgrade onto a deliberately slow spec regresses the canary against the
# pre-rollout baseline and auto-rolls back mid-traffic with token parity,
# driven through the gateway admin API and read back by fleet_ctl.

def _http_post(gw, path, body):
    import http.client

    conn = http.client.HTTPConnection(gw.host, gw.port, timeout=120)
    conn.request("POST", path, json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = resp.read()
    conn.close()
    return resp.status, out


def _heal_fleet(workdir, spec, n, *, scenario, plans=None, supervisor=None):
    """A gateway-less fleet start: heal scenarios wire their own Gateway
    (alerts / remediation / rollout_factory) around the router."""
    from paddle_tpu.serving import FleetRouter, ProcReplica

    reps = []
    for i in range(n):
        env = {}
        if plans and i in plans:
            env["FLAGS_fault_plan"] = plans[i]
        reps.append(ProcReplica(
            f"p{i}", spec, env=env,
            log_path=os.path.join(workdir, f"{scenario}-p{i}.log")))
    router = FleetRouter(reps, probe_interval_s=0.1, probe_timeout_s=8.0,
                         affinity_block_size=spec["engine"]["block_size"],
                         supervisor=supervisor).start(wait_healthy_s=600)
    unhealthy = [r.rid for r in reps if r.state.value != "healthy"]
    if unhealthy:
        router.close()
        raise RuntimeError(f"fleet never became healthy: {unhealthy}")
    return router, reps


def _heal_goodput_source(router):
    """ProcReplica SLO windows live in the child processes; re-export each
    replica's goodput ratio into the parent's history store so the stock
    burn-rate rule sees the fleet."""
    def fn():
        series = []
        for rid, rep in (router.stats().get("replicas") or {}).items():
            slo = rep.get("slo") or {}
            g = slo.get("goodput_ratio")
            if g is None:
                if not slo.get("empty"):
                    continue
                g = 1.0          # empty window = nothing failing
            series.append({"labels": {"replica": rid}, "value": float(g)})
        if not series:
            return {}
        return {"slo_goodput_ratio": {"type": "gauge", "series": series}}
    return fn


def _scenario_wedged_replica_heal(args, workdir, spec, max_len):
    """A wedged replica blows the TPOT SLO: the burn page fires, the
    remediation engine drains+restarts it under the actuation lease, the
    alert resolves, and the post-condition bake closes ok — with zero
    lost requests end to end."""
    from paddle_tpu.resilience import JobLedger
    from paddle_tpu.serving import Gateway
    from paddle_tpu.serving.remediation import Playbook, RemediationEngine
    from paddle_tpu.telemetry import alerts as alerts_mod
    from paddle_tpu.telemetry import history as history_mod

    ts = 0.004                      # fast burn = 14.4s long / 1.2s short
    spec = dict(spec, engine=dict(spec["engine"], slo_tpot_s=0.5,
                                  slo_window_s=4.0))
    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    rng = np.random.RandomState(11)

    def prompts(n):
        return [[int(t) for t in rng.randint(0, args.vocab,
                                             args.prompt_len)]
                for _ in range(n)]

    router, reps = _heal_fleet(
        workdir, spec, 2, scenario="heal-wedge",
        plans={1: "serving.decode:delay=1.2x1000000"})
    # the wedge is this incarnation's disease, not the spec's: the
    # remediation restart must come back clean
    reps[1].extra_env.pop("FLAGS_fault_plan", None)
    wedged_pid = reps[1].pid

    ledger = JobLedger(os.path.join(workdir, "heal_wedge_state.json"))
    hist = history_mod.TimeSeriesStore(interval_s=0.05)
    hist.add_source("fleet", _heal_goodput_source(router))
    hist.start()
    rem = RemediationEngine(
        router,
        playbooks=[Playbook("slo-*burn*", "restart_replica",
                            target="worst_slo", severity="page")],
        ledger=ledger, cooldown_s=30.0, global_window_s=120.0,
        global_max_actions=1, blast_radius=1.0, flap_n=10,
        bake_timeout_s=90.0, lease_wait_s=30.0)
    engine = alerts_mod.AlertEngine(
        hist, alerts_mod.default_rules(objective=0.99, time_scale=ts),
        interval_s=0.1, notifier=rem.notify)
    engine.start()
    gateway = Gateway(router, history=hist, alerts=engine,
                      remediation=rem).start()
    try:
        # live traffic, part of it pinned to the wedged replica so its
        # SLO window fills with violations
        ps = prompts(4) + [_affinity_prompt(router, rng, args.prompt_len,
                                            args.vocab, "p1")
                           for _ in range(2)]
        clients = [_SSEClient(gateway, p, sp) for p in ps]

        acted = _alerts_wait(lambda: rem.stats()["actions"] >= 1, 240.0,
                             poll_s=0.2)
        for c in clients:
            c.join(600)
        if acted is None:
            return {"scenario": "wedged_replica_heal", "survived": False,
                    "failed": "remediation never acted on the burn page",
                    "remediation": rem.stats()}

        # the restart: a NEW healthy p1 process, fault plan gone
        healed = _alerts_wait(
            lambda: reps[1].state.value == "healthy"
            and reps[1].pid != wedged_pid, 180.0, poll_s=0.2)

        # recovery traffic until the alert resolves and the bake closes
        baked = None
        for _ in range(40):
            for c2 in [_SSEClient(gateway, p, sp) for p in prompts(2)]:
                c2.join(600)
                clients.append(c2)
            rem.check_bakes()
            st = rem.stats()
            if st["bakes_ok"] >= 1:
                baked = st
                break
            if st["escalations"] >= 1:
                break
            time.sleep(0.5)

        lost = [i for i, c in enumerate(clients)
                if c.status != 200 or c.finish is None or c.error]
        st = rem.stats()
        gw_stats = json.loads(_http_get(gateway, "/stats"))
        acts = [e for e in rem.audit_tail(64) if e["kind"] == "acted"]
        ok = (baked is not None and healed is not None and not lost
              and st["escalations"] == 0 and st["quarantines"] == 0
              and acts and acts[0]["target"] == "p1"
              and gw_stats.get("remediation") is not None)
        return {
            "scenario": "wedged_replica_heal",
            "survived": bool(ok),
            "paged_and_acted_s": round(acted, 2),
            "healed": healed is not None,
            "bake_ok": baked is not None,
            "actions": st["actions"],
            "suppressed": st["suppressed"],
            "lost_requests": len(lost),
            "acted_target": acts[0]["target"] if acts else None,
            "ledger_events": sorted({e["event"] for e in
                                     ledger.read().get("events", [])}),
        }
    finally:
        engine.stop()
        hist.stop()
        gateway.stop()
        router.close()


def _scenario_flap_quarantine(args, workdir, spec, max_len):
    """A replica that is sick EVERY incarnation re-triggers its playbook
    after each restart: flap detection must quarantine it (page + ledger)
    instead of a restart storm, with the rest of the fleet serving on."""
    from paddle_tpu.resilience import JobLedger
    from paddle_tpu.serving import Gateway
    from paddle_tpu.serving.remediation import Playbook, RemediationEngine

    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    rng = np.random.RandomState(12)
    # the fault plan STAYS in extra_env: every restarted incarnation of
    # p1 comes back just as sick (slow, not dead)
    router, reps = _heal_fleet(
        workdir, spec, 2, scenario="heal-flap",
        plans={1: "serving.decode:delay=0.4x1000000"})
    ledger = JobLedger(os.path.join(workdir, "heal_flap_state.json"))
    rem = RemediationEngine(
        router,
        playbooks=[Playbook("wedge-*", "restart_replica",
                            target="alert_key", cooldown_s=0.0,
                            bake_s=0.0)],
        ledger=ledger, global_window_s=30.0, global_max_actions=10,
        blast_radius=1.0, flap_n=3, flap_window_s=600.0,
        lease_wait_s=30.0)
    gateway = Gateway(router, remediation=rem).start()

    def fire():
        rem.notify({"event": "firing",
                    "alert": {"rule": "wedge-tpot", "key": "p1",
                              "severity": "page", "state": "firing"}})

    def resolve():
        rem.notify({"event": "resolved",
                    "alert": {"rule": "wedge-tpot", "key": "p1",
                              "severity": "page", "state": "resolved"}})

    try:
        restarts = 0
        for round_ in range(3):
            pid = reps[1].pid
            fire()                  # synchronous: acts (or quarantines)
            if rem.stats()["quarantined"]:
                break
            if _alerts_wait(lambda: reps[1].pid != pid
                            and reps[1].state.value == "healthy",
                            180.0, poll_s=0.2) is None:
                return {"scenario": "flap_quarantine", "survived": False,
                        "failed": f"restart {round_} never came healthy"}
            restarts += 1
            resolve()
        # a further page against the quarantined target stays suppressed
        pid = reps[1].pid
        fire()
        suppressed = [e for e in rem.audit_tail(8)
                      if e["kind"] == "suppressed"]
        # the sick-but-quarantined fleet still serves: p0 fast, p1 slow
        clients = [_SSEClient(gateway,
                              [int(t) for t in rng.randint(
                                  0, args.vocab, args.prompt_len)], sp)
                   for _ in range(4)]
        for c in clients:
            c.join(600)
        lost = [i for i, c in enumerate(clients)
                if c.status != 200 or c.finish is None or c.error]
        gw_rem = (json.loads(_http_get(gateway, "/stats"))
                  .get("remediation") or {})
        led = {e["event"] for e in ledger.read().get("events", [])}
        st = rem.stats()
        ok = (restarts == 2 and st["quarantined"] == ["p1"]
              and reps[1].pid == pid          # no 3rd/4th restart
              and st["actions"] == 2 and st["quarantines"] == 1
              and suppressed
              and suppressed[-1]["reason"] == "quarantined"
              and gw_rem.get("quarantined") == ["p1"]
              and "remediation_quarantine" in led and not lost)
        return {
            "scenario": "flap_quarantine",
            "survived": bool(ok),
            "restarts_before_quarantine": restarts,
            "quarantined": st["quarantined"],
            "suppressed_reason": (suppressed[-1]["reason"]
                                  if suppressed else None),
            "actions": st["actions"],
            "lost_requests": len(lost),
            "ledger_has_quarantine": "remediation_quarantine" in led,
        }
    finally:
        gateway.stop()
        router.close()


def _scenario_canary_rollback(args, workdir, spec, max_len):
    """Rolling upgrade onto a deliberately slow spec under live SSE
    traffic, driven through the gateway admin API: the canary regresses
    against the pre-rollout baseline, the rollout auto-rolls back
    mid-traffic, and every stream survives with token parity. The
    fleet_ctl CLI then reads the whole aftermath."""
    import subprocess

    from paddle_tpu.resilience import JobLedger
    from paddle_tpu.serving import Gateway
    from paddle_tpu.serving.rollout import RollingUpgrade

    # a lenient TPOT SLO (never violated — nothing sheds) whose window
    # still yields the tpot p95 baseline the canary is judged against;
    # the 12s window lets boot-warmup compile samples age out before the
    # baseline is captured
    spec = dict(spec, engine=dict(spec["engine"], slo_tpot_s=10.0,
                                  slo_window_s=12.0))
    sp = SamplingParams(max_new_tokens=args.max_new, temperature=0.0)
    rng = np.random.RandomState(13)
    ledger = JobLedger(os.path.join(workdir, "heal_rollout_state.json"))
    router, reps = _heal_fleet(workdir, spec, 2, scenario="heal-canary")

    def factory(new_spec, env, **kw):
        kw.setdefault("canary_bake_s", 90.0)
        return RollingUpgrade(router, new_spec, env=env, ledger=ledger,
                              healthy_wait_s=240.0, **kw)

    gateway = Gateway(router, rollout_factory=factory).start()
    try:
        # craft the full prompt schedule up front so one reference run
        # yields the parity oracle; p2c load-based placement would route
        # AROUND a slow canary, so half the rollout-phase prompts are
        # pinned to p0 (the first replica the plan upgrades) and the warm
        # phase pins one to each replica so both get an SLO baseline
        warm = [_affinity_prompt(router, rng, args.prompt_len, args.vocab,
                                 f"p{i % 2}") for i in range(4)]
        wave = [(_affinity_prompt(router, rng, args.prompt_len, args.vocab,
                                  "p0") if i % 2 == 0
                 else [int(t) for t in rng.randint(0, args.vocab,
                                                   args.prompt_len)])
                for i in range(10)]
        all_prompts = warm + wave
        refs = _fleet_reference(spec, all_prompts, [sp] * len(all_prompts))

        clients = []                       # (prompt index, client)
        for i, p in enumerate(warm):
            clients.append((i, _SSEClient(gateway, p, sp)))
        for _, c in clients:
            c.join(600)

        # the first pass through each replica pays XLA compile for the
        # serving shapes, and those multi-second inter-token gaps sit in
        # the sliding SLO window as tpot p95 — a baseline captured then
        # is so inflated the slow canary could never regress 2x past
        # it. Trickle the warm prompts until every replica's window
        # holds only steady-state samples (clean tpot p95 is ~5ms here;
        # 0.2s leaves the 0.6s/step canary far beyond 2x any baseline
        # that passes this gate)
        def clean_baseline():
            st = router.stats()["replicas"]
            ps = [((r.get("slo") or {}).get("tpot") or {}).get("p95")
                  for r in st.values()]
            return all(p is not None and p < 0.2 for p in ps)

        t_end = time.monotonic() + 90
        while not clean_baseline() and time.monotonic() < t_end:
            rnd = [(i, _SSEClient(gateway, warm[i], sp)) for i in (0, 1)]
            clients.extend(rnd)
            for _, c in rnd:
                c.join(600)
            time.sleep(1.0)
        if not clean_baseline():
            return {"scenario": "canary_rollback", "survived": False,
                    "failed": "no clean SLO baseline after warm traffic"}

        # -- the upgrade: the new spec ships a 0.6s/step decode delay --
        status, raw = _http_post(gateway, "/v1/admin/rollout", {
            "spec": spec,
            "env": {"FLAGS_fault_plan": "serving.decode:delay=0.6x1000000"},
            "canary_bake_s": 90.0, "drain_budget_s": 8.0,
            "regression_ratio": 2.0})
        if status != 202:
            return {"scenario": "canary_rollback", "survived": False,
                    "failed": f"rollout POST -> {status}: {raw[:200]}"}

        # -- live traffic while the rollout drains / bakes / rolls back --
        # the canary verdict needs >= min_samples COMPLETED requests
        # inside the canary's sliding SLO window at once; a lone pinned
        # stream every few seconds never gets there (the window drains
        # between completions and the bake passes vacuously). Bursts of
        # 3 concurrent pinned streams — exactly the engine's max_slots,
        # and within the +2 affinity load slack so p2c does not route
        # around the slow canary — finish batched together and land 3
        # samples in the window in one shot; recycle the pinned prompts
        # until the rollout reaches a terminal state
        pinned_idx = [j for j in range(len(wave)) if j % 2 == 0]
        doc, burst_n = None, 0
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            doc = json.loads(_http_get(gateway, "/v1/admin/rollout"))
            if doc.get("state") in ("done", "rolled_back", "failed"):
                break
            batch = []
            for m in range(3):
                j = pinned_idx[(burst_n * 3 + m) % len(pinned_idx)]
                batch.append((len(warm) + j,
                              _SSEClient(gateway, wave[j], sp)))
            burst_n += 1
            clients.extend(batch)
            for _, c in batch:
                c.join(600)
        # every wave prompt runs after the terminal state: post-rollback
        # service plus full parity coverage (repeats are fine — greedy
        # decode is deterministic, so the oracle is per prompt index)
        for j in range(len(wave)):
            clients.append((len(warm) + j,
                            _SSEClient(gateway, wave[j], sp)))
        for _, c in clients:
            c.join(600)

        rolled_back = (doc or {}).get("state") == "rolled_back"
        reason = str((doc or {}).get("reason") or "")
        healthy = _alerts_wait(
            lambda: all(r.state.value == "healthy" for r in reps),
            120.0, poll_s=0.2) is not None
        clean_env = all("FLAGS_fault_plan" not in r.extra_env
                        for r in reps)
        lost = [i for i, c in clients
                if c.status != 200 or c.finish is None or c.error]
        parity = [i for i, c in clients if c.tokens != refs[i]]
        led = {e["event"] for e in ledger.read().get("events", [])}
        ledger_ok = {"rollout_started", "rollout_replica_done",
                     "rollout_rollback", "rollout_rolled_back"} <= led

        # the operator CLI reads the whole story end to end
        ctl = subprocess.run(
            [sys.executable,
             os.path.join(REPO_ROOT, "tools", "fleet_ctl.py"), "status",
             "--gateway", f"http://{gateway.host}:{gateway.port}",
             "--ledger", ledger.path],
            capture_output=True, text=True, timeout=60, cwd=REPO_ROOT)
        ctl_ok = (ctl.returncode == 0
                  and "tool_parse_errors: 0" in ctl.stdout
                  and "rolled_back" in ctl.stdout)

        ok = (rolled_back and "canary" in reason and healthy
              and clean_env and not lost and not parity and ledger_ok
              and ctl_ok)
        return {
            "scenario": "canary_rollback",
            "survived": bool(ok),
            "state": (doc or {}).get("state"),
            "reason": reason,
            "fleet_healthy": healthy,
            "env_restored": clean_env,
            "lost_requests": len(lost),
            "parity_failures": len(parity),
            "ledger_ok": ledger_ok,
            "fleet_ctl_ok": ctl_ok,
        }
    finally:
        gateway.stop()
        router.close()


def run_heal_suite(args, workdir=None, scenario=None):
    import tempfile

    workdir = workdir or tempfile.mkdtemp(prefix="chaos-heal-")
    max_len = args.prompt_len + args.max_new
    spec = _fleet_spec(args, workdir, max_len)
    rows = []
    fns = _filter_scenarios(
        (_scenario_wedged_replica_heal, _scenario_flap_quarantine,
         _scenario_canary_rollback), "_scenario_", scenario)
    for fn in fns:
        try:
            rows.append(fn(args, workdir, spec, max_len))
        except Exception as e:  # lint: allow-silent(the crash is the row: survived=False fails the battery)
            rows.append({"scenario": fn.__name__[len("_scenario_"):],
                         "survived": False,
                         "crashed": f"{type(e).__name__}: {e}"})
    survived = sum(1 for r in rows if r["survived"])
    dump_path = telemetry.dump(reason="heal chaos suite complete")
    return {
        "suite": "heal",
        "workdir": workdir,
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "flight_recorder_dump": dump_path,
        "results": rows,
    }


SUITE_SCENARIOS = {
    "serving": lambda: [n for n, _ in DEFAULT_PLANS],
    "prefix": lambda: [n for n, _ in PREFIX_PLANS],
    "spill": lambda: [n for n, _ in SPILL_PLANS],
    "perf": lambda: ["(runs as one battery; --scenario unsupported)"],
    "serve-fleet": lambda: ["sigkill", "fault_storms", "shed",
                            "drain_restart"],
    "durable": lambda: ["gateway_sigkill", "torn_journal_tail",
                        "breaker_trip", "retry_budget_storm"],
    "kvfabric": lambda: ["stale_directory", "donor_kill_mid_fetch",
                         "corrupt_frame", "fetch_storm"],
    "tenancy": lambda: ["noisy_neighbor", "autoscale_burst_kill"],
    "train": lambda: ["kill_worker", "nan_injection", "torn_checkpoint"],
    "straggler": lambda: ["straggler", "hang"],
    "locksan": lambda: ["fleet_under_load", "telemetry_threads",
                        "inversion_canary"],
    "soak": lambda: ["degrade", "rolling"],
    "alerts": lambda: ["slo_burn_page", "publisher_absence"],
    "heal": lambda: ["wedged_replica_heal", "flap_quarantine",
                     "canary_rollback"],
}


def _print_scenarios():
    for suite, names in SUITE_SCENARIOS.items():
        print(suite)
        for n in names():
            print(f"  {n}")


def _filter_scenarios(fns, prefix, scenario):
    """Select scenario functions by their ``<prefix><name>`` suffix; the
    whole list with ``scenario=None``."""
    if scenario is None:
        return list(fns)
    keep = [f for f in fns if f.__name__ == prefix + scenario]
    if not keep:
        names = [f.__name__[len(prefix):] for f in fns]
        raise SystemExit(f"unknown scenario {scenario!r}; one of: {names}")
    return keep


def run_sweep(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite",
                    choices=["serving", "prefix", "spill", "train",
                             "straggler", "perf", "serve-fleet", "durable",
                             "kvfabric", "tenancy", "locksan", "soak",
                             "alerts", "heal"],
                    default="serving")
    ap.add_argument("--list", action="store_true",
                    help="print every suite's scenario names and exit")
    ap.add_argument("--scenario", default=None, metavar="NAME",
                    help="run a single scenario of the suite (see --list) "
                         "— re-run one failing scenario without the whole "
                         "battery")
    ap.add_argument("--prefix-share", type=float, default=0.75,
                    help="--suite prefix: fraction of every prompt that is "
                         "the common template")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--vocab", type=int, default=128)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--plan", nargs=2, action="append", default=None,
                    metavar=("NAME", "SPEC"),
                    help="custom fault plan (repeatable; replaces battery)")
    ap.add_argument("--json", default=None)
    args = ap.parse_args(argv)

    if args.list:
        _print_scenarios()
        raise SystemExit(0)
    if args.scenario is not None and args.suite == "perf":
        raise SystemExit("--suite perf runs as one interdependent battery "
                         "and cannot be sliced with --scenario")
    if args.scenario is not None:
        # one validation gate for every suite, before any fleet spins
        # up: an unknown name exits non-zero naming the whole catalog
        valid = ([n for n, _ in args.plan]
                 if args.suite == "serving" and args.plan
                 else SUITE_SCENARIOS[args.suite]())
        if args.scenario not in valid:
            catalog = "\n".join(
                f"  --suite {s}: {', '.join(f())}"
                for s, f in SUITE_SCENARIOS.items())
            raise SystemExit(
                f"unknown scenario {args.scenario!r} for --suite "
                f"{args.suite} (valid: {', '.join(valid)})\n"
                f"full catalog:\n{catalog}")

    if args.suite in ("train", "straggler", "prefix", "spill", "perf",
                      "serve-fleet", "durable", "kvfabric", "tenancy",
                      "locksan", "soak", "alerts", "heal"):
        report = (run_train_suite(scenario=args.scenario)
                  if args.suite == "train"
                  else run_straggler_suite(scenario=args.scenario)
                  if args.suite == "straggler"
                  else run_locksan_suite(scenario=args.scenario)
                  if args.suite == "locksan"
                  else run_perf_suite(args) if args.suite == "perf"
                  else run_serve_fleet_suite(args,
                                             scenario=args.scenario)
                  if args.suite == "serve-fleet"
                  else run_durable_suite(args, scenario=args.scenario)
                  if args.suite == "durable"
                  else run_kvfabric_suite(args, scenario=args.scenario)
                  if args.suite == "kvfabric"
                  else run_tenancy_suite(args, scenario=args.scenario)
                  if args.suite == "tenancy"
                  else run_soak_suite(args, scenario=args.scenario)
                  if args.suite == "soak"
                  else run_alerts_suite(args, scenario=args.scenario)
                  if args.suite == "alerts"
                  else run_heal_suite(args, scenario=args.scenario)
                  if args.suite == "heal"
                  else run_spill_suite(args, scenario=args.scenario)
                  if args.suite == "spill"
                  else run_prefix_suite(args, scenario=args.scenario))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(report, f, indent=2)
        return report

    model, prompts, sp, max_len = _build(args)
    plans = args.plan if args.plan else DEFAULT_PLANS
    if args.scenario is not None:
        plans = [(n, s) for n, s in plans if n == args.scenario]
        if not plans:
            raise SystemExit(
                f"unknown scenario {args.scenario!r}; one of: "
                f"{[n for n, _ in (args.plan or DEFAULT_PLANS)]}")

    # fault-free reference first (also warms the traces)
    base_row, reference = _run_plan(model, prompts, sp, max_len, args, "")
    base_wall = base_row["wall_sec"]

    rows = []
    for name, spec in plans:
        if not spec:
            row = dict(base_row)
        else:
            row, _ = _run_plan(model, prompts, sp, max_len, args, spec,
                               reference=reference)
        row["name"] = name
        row["slowdown_vs_baseline"] = (
            round(row["wall_sec"] / base_wall, 3) if base_wall > 0 else None)
        rows.append(row)

    survived = sum(1 for r in rows if r["survived"])
    # the postmortem artifact: the ring's tail covers the last plans' fault
    # injections, scheduler decisions, and allocator traffic — plus any
    # dump a timeout/stall already wrote mid-sweep (last_dump_path)
    dump_path = telemetry.dump(reason="chaos sweep complete")
    report = {
        "config": {"requests": args.requests, "prompt_len": args.prompt_len,
                   "max_new_tokens": args.max_new, "slots": args.slots,
                   "block_size": args.block_size},
        "plans_run": len(rows),
        "plans_survived": survived,
        "all_survived": survived == len(rows),
        "baseline_wall_sec": base_wall,
        "flight_recorder_dump": dump_path,
        "results": rows,
    }
    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2)
    return report


def main(argv=None):
    telemetry.install_excepthook()   # a crashed sweep still leaves a dump
    report = run_sweep(argv)
    print(json.dumps(report, indent=2))
    for r in report["results"]:
        status = "OK " if r["survived"] else "DIED"
        if report.get("suite") in ("train", "straggler", "perf",
                                   "serve-fleet", "durable", "spill",
                                   "kvfabric", "tenancy", "locksan",
                                   "soak", "alerts", "heal"):
            detail = " ".join(f"{k}={v}" for k, v in r.items()
                              if k not in ("scenario", "survived"))
            print(f"[{status}] {r['scenario']:<26} {detail}",
                  file=sys.stderr)
        else:
            hit = (f" hit_rate={r['hit_rate']:.2f}"
                   if r.get("hit_rate") is not None else "")
            print(f"[{status}] {r['name']:<20} finished={r['finished']} "
                  f"failed={r['failed']} cancelled={r['cancelled']} "
                  f"parity={'yes' if r['survivor_parity_ok'] else 'NO'} "
                  f"slowdown={r['slowdown_vs_baseline']}x{hit}",
                  file=sys.stderr)
    if not report["all_survived"]:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
