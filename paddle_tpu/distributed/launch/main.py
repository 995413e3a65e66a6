"""Launcher implementation: env layout, worker spawn, watch, restart."""
from __future__ import annotations

import argparse
import os
import signal
import socket
import subprocess
import sys
import time

__all__ = ["launch", "main"]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _parse(argv):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="Launch N training processes with distributed env set "
                    "(reference paddle.distributed.launch parity).")
    p.add_argument("--nnodes", type=int, default=1,
                   help="number of nodes (this CLI drives one)")
    p.add_argument("--node_rank", type=int, default=0)
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="worker processes on this node")
    p.add_argument("--master", default=None,
                   help="coordinator host:port (default: local free port)")
    p.add_argument("--log_dir", default="log",
                   help="per-rank worker logs directory (workerlog.N)")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="elastic level-1: restart the whole pod up to K "
                        "times when any worker fails")
    p.add_argument("--elastic_level", type=int, choices=[1, 2], default=1,
                   help="2: on worker failure relaunch at the SURVIVING "
                        "world size within [--min_procs, nproc_per_node] "
                        "and let workers resume from checkpoint (reference "
                        "fleet/elastic/manager.py ElasticLevel)")
    p.add_argument("--min_procs", type=int, default=1,
                   help="elastic level-2 lower bound on workers per node")
    p.add_argument("--restart_backoff", type=float, default=0.5,
                   help="initial delay before a pod relaunch; doubles per "
                        "attempt (exponential backoff)")
    p.add_argument("--restart_backoff_max", type=float, default=30.0,
                   help="backoff ceiling in seconds")
    p.add_argument("--job_state", default=None,
                   help="path of the job_state.json ledger (default: "
                        "<log_dir>/job_state.json); workers see it as "
                        "$PADDLE_JOB_STATE and record resume steps there")
    p.add_argument("--cluster_telemetry", action="store_true",
                   help="host a telemetry TCPStore for the pod: workers "
                        "that call telemetry.cluster.start_from_env() "
                        "publish per-rank metrics/flight/heartbeats to it; "
                        "the launcher answers clock-sync probes, writes a "
                        "merged cluster_metrics.json into --log_dir at "
                        "exit, and on a failed pod collects a postmortem "
                        "bundle (every rank's flight dump + stacks) there, "
                        "recording its path in the job ledger")
    p.add_argument("--backend", choices=["auto", "cpu", "tpu"], default="auto",
                   help="cpu: force workers onto the CPU backend (the "
                        "reference's Gloo-mode analogue for machines "
                        "without accelerators); tpu: workers fail without "
                        "a TPU. One process drives all the chips of its "
                        "host, so tpu takes --nproc_per_node 1")
    p.add_argument("training_script", help="script to run")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if args.backend == "tpu" and args.nproc_per_node > 1:
        # a chip belongs to one process: the first worker to start would
        # take every local chip and the others would fail or hang
        p.error("--backend tpu runs one process per host, which drives all "
                "of its chips (mesh axes shard work over them); got "
                f"--nproc_per_node {args.nproc_per_node}")
    return args


def _worker_env(args, master, local_rank):
    world = args.nnodes * args.nproc_per_node
    rank = args.node_rank * args.nproc_per_node + local_rank
    env = dict(os.environ)
    env.update({
        # our bootstrap (read by init_parallel_env -> jax.distributed)
        "PADDLE_TPU_COORDINATOR": master,
        "PADDLE_TPU_NUM_PROCESSES": str(world),
        "PADDLE_TPU_PROCESS_ID": str(rank),
        # reference-compatible names so existing scripts keep working
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_RANK_IN_NODE": str(local_rank),
        "PADDLE_MASTER": master,
        # incarnation counter: scripts use it to resume from checkpoint
        # instead of starting fresh (reference PADDLE_ELASTIC_* env family)
        "PADDLE_RESTART_ATTEMPT": str(getattr(args, "_attempt", 0)),
    })
    if getattr(args, "_ledger_path", None):
        # resilience.JobLedger.from_env(): workers append resume records
        env["PADDLE_JOB_STATE"] = args._ledger_path
    if getattr(args, "_telemetry_endpoint", None):
        # telemetry.cluster.start_from_env(): workers publish per-rank
        # telemetry to the launcher-hosted store
        env["PADDLE_TELEMETRY_STORE"] = args._telemetry_endpoint
    if args.backend != "auto":
        env["JAX_PLATFORMS"] = args.backend
    return env


def _spawn(args, master):
    os.makedirs(args.log_dir, exist_ok=True)
    procs = []
    for lr in range(args.nproc_per_node):
        rank = args.node_rank * args.nproc_per_node + lr
        log_path = os.path.join(args.log_dir, f"workerlog.{rank}")
        logf = open(log_path, "w")
        cmd = [sys.executable, args.training_script] + args.training_script_args
        proc = subprocess.Popen(cmd, env=_worker_env(args, master, lr),
                                stdout=logf, stderr=subprocess.STDOUT)
        procs.append((proc, logf, rank))
    return procs


def _watch(procs, poll_s=0.2):
    """Reference watcher role (launch/controllers/watcher.py): first failure
    aborts the pod; returns (rc, n_failed, interrupted, dead_ranks) — rc 0
    only if every worker exits 0."""
    try:
        while procs:
            alive, failed = [], []
            # sweep the WHOLE pod before aborting so simultaneous failures
            # are all counted (the elastic scale plan needs the true
            # surviving size)
            for proc, logf, rank in procs:
                rc = proc.poll()
                if rc is None:
                    alive.append((proc, logf, rank))
                elif rc != 0:
                    failed.append((rank, rc))
                else:
                    logf.close()
            if failed:
                for rank, rc in failed:
                    sys.stderr.write(
                        f"[launch] rank {rank} failed with exit {rc}; "
                        f"aborting pod (see workerlog.{rank})\n")
                for p2, f2, _ in procs:
                    if p2.poll() is None:
                        p2.terminate()
                for p2, f2, _ in procs:
                    try:
                        p2.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        p2.kill()
                    if not f2.closed:
                        f2.close()
                return failed[0][1], len(failed), False, [r for r, _ in failed]
            procs = alive
            if procs:
                time.sleep(poll_s)
        return 0, 0, False, []
    except KeyboardInterrupt:
        # interrupted=True distinguishes the operator's Ctrl-C from a worker
        # that itself exited 130 (e.g. SIGINT preemption — that one SHOULD
        # go through the elastic restart path)
        for proc, logf, _ in procs:
            proc.send_signal(signal.SIGINT)
        for proc, logf, _ in procs:
            proc.wait()
            logf.close()
        return 130, 0, True, []


def _start_telemetry_plane(args):
    """Host the pod's telemetry store + clock responder in the launcher.
    Returns (store, aggregator) or (None, None) — missing native runtime
    degrades to no cluster telemetry, never a failed launch."""
    try:
        from ...telemetry.cluster import ClusterAggregator
        from ..tcp_store import TCPStore

        store = TCPStore(is_master=True)
        args._telemetry_endpoint = f"127.0.0.1:{store.port}"
        agg = ClusterAggregator(store, args.nproc_per_node)
        agg.start_clock_responder()
        return store, agg
    except Exception as e:
        sys.stderr.write(f"[launch] cluster telemetry unavailable: {e}\n")
        return None, None


def launch(argv):
    # the supervisor owns restart POLICY (budget, backoff, scale plan,
    # job_state.json ledger); this loop stays the mechanism (spawn/watch)
    from ...resilience.supervisor import ElasticSupervisor, JobLedger

    args = _parse(argv)
    master = args.master or f"127.0.0.1:{_free_port()}"
    os.makedirs(args.log_dir, exist_ok=True)
    tele_store, tele_agg = (None, None)
    if args.cluster_telemetry:
        tele_store, tele_agg = _start_telemetry_plane(args)
    ledger_path = args.job_state or os.path.join(args.log_dir,
                                                 "job_state.json")
    args._ledger_path = os.path.abspath(ledger_path)
    sup = ElasticSupervisor(
        args.nproc_per_node, max_restarts=args.max_restarts,
        elastic_level=args.elastic_level, min_procs=args.min_procs,
        backoff_s=args.restart_backoff,
        backoff_max_s=args.restart_backoff_max,
        ledger=JobLedger(args._ledger_path))
    sup.ledger.record("start", world=args.nproc_per_node,
                      max_restarts=args.max_restarts,
                      elastic_level=args.elastic_level,
                      script=args.training_script)
    attempt = 0
    while True:
        args._attempt = attempt
        procs = _spawn(args, master)
        rc, n_failed, interrupted, dead_ranks = _watch(procs)
        if tele_agg is not None and rc != 0 and not interrupted:
            # whole-job postmortem BEFORE the survivors get torn down:
            # every publishing rank answers with its flight dump + stacks
            bundle = tele_agg.collect_postmortem(
                reason=f"pod exit rc={rc} (ranks {dead_ranks} failed)",
                out_dir=args.log_dir, timeout_s=5.0)
            if bundle:
                sup.ledger.record("postmortem", bundle=bundle, rc=rc,
                                  dead_ranks=list(dead_ranks))
                sys.stderr.write(f"[launch] postmortem bundle: {bundle}\n")
        decision = sup.decide(rc, n_failed, interrupted,
                              world_size=args.nproc_per_node,
                              dead_ranks=dead_ranks)
        if decision["action"] != "restart":
            if decision["reason"] == "below min_procs":
                sys.stderr.write(
                    f"[launch] fewer than --min_procs={args.min_procs} "
                    "workers would survive; aborting\n")
            elif decision["action"] == "abort" and not interrupted:
                sys.stderr.write(
                    f"[launch] {decision['reason']}; giving up\n")
            if tele_agg is not None:
                try:
                    import json as _json

                    path = os.path.join(args.log_dir,
                                        "cluster_metrics.json")
                    with open(path, "w") as f:
                        _json.dump(tele_agg.merged_snapshot(), f, indent=1,
                                   default=str)
                except Exception:  # lint: allow-silent(final snapshot dump is best-effort at teardown)
                    pass
                tele_agg.stop()
                tele_store.close()
            return rc
        attempt += 1
        if decision["world"] != args.nproc_per_node:
            # ElasticLevel 2 (reference fleet/elastic/manager.py:219-256):
            # relaunch at the surviving world size; workers see the new
            # PADDLE_TRAINERS_NUM and resume from their (resharded on
            # load) checkpoints
            sys.stderr.write(
                f"[launch] elastic scale-down: {args.nproc_per_node} "
                f"-> {decision['world']} workers\n")
            args.nproc_per_node = decision["world"]
        sys.stderr.write(
            f"[launch] restarting pod (attempt {attempt}/"
            f"{args.max_restarts}) after {decision['backoff_s']:.1f}s "
            "backoff\n")
        time.sleep(decision["backoff_s"])
        # a fresh coordinator port avoids stale-rendezvous collisions
        if args.master is None:
            master = f"127.0.0.1:{_free_port()}"


def main():
    return launch(sys.argv[1:])
