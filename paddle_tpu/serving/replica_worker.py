"""Engine replica child process (``python -m paddle_tpu.serving.replica_worker``).

One :class:`~paddle_tpu.serving.engine.LLMEngine` behind a newline-JSON
pipe protocol, spawned and owned by a
:class:`~paddle_tpu.serving.router.ProcReplica`. The model/engine spec
arrives in ``$PADDLE_REPLICA_SPEC`` (JSON) so every replica of a fleet
builds **bit-identical weights** (same seed, same config) — the property
that makes failover replay token-for-token exact:

    {"seed": 0,
     "llama_tiny": {"vocab": 128, "hidden": 64, ...},   # model config
     "engine": {"block_size": 8, "max_slots": 3, ...},  # LLMEngine kwargs
     "stats_interval_s": 0.1}

Protocol (one JSON object per line):

    stdin  <- {"op": "add", "gid": 7, "prompt": [...],
               "sampling": {...}, "deadline_s": 1.5 | null,
               "trace_id": "req-ab12cd" | null,
               "tenant": "acme" | null, "priority": 0,
               "t_front_unix": 1.7e9 | null, "t_sent_unix": 1.7e9 | null}
              {"op": "cancel", "gid": 7}
              {"op": "kv_fetch", "fid": 3, "hashes": [...],
               "max_frames": 64, "max_bytes": 33554432}
              {"op": "kv_ingest", "frames": [...]}
              {"op": "close"}
    stdout -> {"ev": "hello", "pid": 1234}
              {"ev": "token", "gid": 7, "tok": 42, "i": 0, "t": 1.7e9}
              {"ev": "done", "gid": 7, "state": "finished",
               "reason": "length", "error": null, "n": 16}
              {"ev": "stats", "stats": {... replica_stats() ...},
               "spans": [... optional: request-scoped spans since the
                         last heartbeat, unix-stamped wire format —
                         telemetry.reqtrace ...]}
              {"ev": "kv_blocks", "fid": 3, "frames": [...],
               "error": null}
              {"ev": "kv_ingested", "ingested": 4, "corrupt": 0,
               "errors": 0}
              {"ev": "bye"}

``kv_fetch`` / ``kv_ingest`` are the KV-fabric migration verbs
(serving/kv_fabric.py): the router pulls CRC32-stamped block frames from
this replica (the donor half) or lands frames fetched from a sibling
(the receiver half, which re-verifies every stamp before promotion).
With ``"fabric": {"store": "host:port", ...}`` in the spec, the worker
additionally publishes its prefix-cache inventory to the fleet-wide
directory on every heartbeat (lease-fenced: a SIGKILL simply lets the
lease expire).

``trace_id`` is the router/gateway-minted request-trace context: the
engine stamps it on every span the request produces, and the heartbeat
streams those spans back so the router can merge one Chrome trace per
request across replica hops (docs/OBSERVABILITY.md "Request tracing").
``t_front_unix`` (the gateway had read the request; null on a re-dispatch)
and ``t_sent_unix`` (the router sent this command) end in
``router.note_admit`` when the engine accepts the request; a token's ``t``
is when the engine emitted it, which the gateway measures its relay from.
All three are unix time, optional, and ignored by a peer that predates them.

Anything that is not protocol (import-time warnings, stray prints) fails
JSON parsing on the router side and is ignored; diagnostics belong on
stderr. Fault plans arm per replica through ``FLAGS_fault_plan`` in the
child environment — this is how ``chaos_run.py --suite serve-fleet`` turns
one replica into a compile-error or delay-storm victim while its siblings
stay clean. A SIGKILL needs no cooperation from this file at all; the
router sees the pipe EOF.
"""
from __future__ import annotations

import json
import os
import queue
import sys
import threading
import time
from ..analysis import locksan


def build_model(spec: dict):
    """The deterministic model build both the worker and any in-process
    parity reference must share: seed first, then config, then weights."""
    import paddle_tpu
    from ..models import LlamaForCausalLM, llama_tiny

    paddle_tpu.seed(int(spec.get("seed", 0)))
    cfg = llama_tiny(**(spec.get("llama_tiny") or {}))
    return LlamaForCausalLM(cfg)


def main() -> int:
    spec = json.loads(os.environ["PADDLE_REPLICA_SPEC"])
    # starved-host guard (same as tests/conftest.py): XLA CPU's
    # multi-threaded Eigen kernels crash on 1-2 core hosts — must be set
    # before jax imports, which is why it lives up here
    flags = os.environ.get("XLA_FLAGS", "")
    if (os.cpu_count() or 1) <= 2 and \
            "xla_cpu_multi_thread_eigen" not in flags:
        os.environ["XLA_FLAGS"] = \
            flags + " --xla_cpu_multi_thread_eigen=false"
    # one persistent compilation cache for the fleet: every replica compiles
    # the same traces, only the first should pay XLA
    from ..utils import compile_cache

    compile_cache.enable()
    from .. import telemetry
    from ..telemetry import reqtrace
    from . import kv_fabric
    from .engine import LLMEngine
    from .router import handle_command, replica_stats

    model = build_model(spec)
    engine = LLMEngine(model, **(spec.get("engine") or {}))
    stats_interval = float(spec.get("stats_interval_s", 0.1))
    publisher = None
    fab = spec.get("fabric")
    if fab:
        # fleet-wide prefix directory: own store connection (the wire
        # protocol is one-request-per-conn), publish piggybacks on the
        # heartbeat cadence. A dead store disables the fabric, never the
        # replica — the directory is advisory.
        try:
            rid = str(fab.get("rid") or os.environ.get(
                "PADDLE_REPLICA_RID") or f"pid{os.getpid()}")
            cfg = kv_fabric.FabricConfig(**{
                k: fab[k] for k in ("lease_s", "refresh_s", "max_hashes")
                if k in fab})
            publisher = kv_fabric.DirectoryPublisher(
                kv_fabric.connect_store(fab["store"]), rid, engine.cache,
                cfg=cfg,
                counters_fn=lambda: engine.cache.prefix_stats()["fabric"])
        except Exception as e:
            print(f"replica_worker: kv fabric disabled: "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            publisher = None
    warmup = spec.get("warmup")
    if warmup:
        # compile the prefill bucket + decode traces before reporting
        # ready: the router's liveness timeout starts at the first
        # heartbeat, and a first-compile stall must not look like a hang
        from .scheduler import SamplingParams

        engine.generate([list(warmup)],
                        SamplingParams(max_new_tokens=2, temperature=0.0))

    out_lock = locksan.Lock("replica_worker.stdout")

    def emit(ev: dict):
        with out_lock:
            sys.stdout.write(json.dumps(ev) + "\n")
            sys.stdout.flush()

    cmds: queue.Queue = queue.Queue()

    def read_stdin():
        for line in sys.stdin:
            line = line.strip()
            if not line:
                continue
            try:
                cmds.put(json.loads(line))
            except json.JSONDecodeError:
                print(f"replica_worker: bad command line {line!r}",
                      file=sys.stderr)
        cmds.put({"op": "close"})          # router hung up

    threading.Thread(target=read_stdin, daemon=True,
                     name="replica-stdin-reader").start()
    # pipe-protocol handshake: the hello carries this worker's protocol
    # version so a rolling upgrade can mix versions behind one router
    # (PADDLE_PROTO_VERSION overrides it — how chaos exercises the
    # router's refusal path without shipping a genuinely old binary)
    from .router import PROTO_VERSION

    proto = int(os.environ.get("PADDLE_PROTO_VERSION", PROTO_VERSION))
    emit({"ev": "hello", "pid": os.getpid(), "proto_version": proto})

    tracked: dict[int, object] = {}        # gid -> engine Request

    def on_token(gid: int):
        def cb(req, tok):
            emit({"ev": "token", "gid": gid, "tok": int(tok),
                  "i": len(req.output_tokens) - 1,
                  "t": telemetry.mono_to_unix(time.monotonic())})
        return cb

    def sweep():
        for gid, req in list(tracked.items()):
            if req.state.is_terminal:
                del tracked[gid]
                emit({"ev": "done", "gid": gid, "state": req.state.value,
                      "reason": req.finish_reason,
                      "error": (f"{type(req.error).__name__}: {req.error}"
                                if req.error is not None else None),
                      "n": len(req.output_tokens)})

    span_wm = 0                            # request-span drain watermark

    def heartbeat():
        nonlocal span_wm
        ev = {"ev": "stats", "stats": replica_stats(engine)}
        # request-scoped spans (trace-context-carrying) stream back with
        # every heartbeat, unix-stamped, so a SIGKILL mid-request still
        # leaves this hop's spans on the router for the merged trace
        spans, span_wm = reqtrace.drain_request_spans(
            span_wm, engine_label=engine.engine_label)
        if spans:
            ev["spans"] = spans
        emit(ev)
        if publisher is not None:
            try:
                publisher.maybe_publish()
            except Exception:  # lint: allow-silent(advisory publish; never kill the beat)
                pass

    last_pub = 0.0
    closing = False
    # the same flat phase spans as LocalReplica._drive (docs/
    # OBSERVABILITY.md "Phase spans")
    while not closing:
        cmd = None
        if not engine.has_work():
            with telemetry.span("replica.idle"):
                try:
                    cmd = cmds.get(timeout=0.02)
                except queue.Empty:
                    pass
        with telemetry.span("replica.inbox"):
            if cmd is None:
                try:
                    cmd = cmds.get_nowait()
                except queue.Empty:
                    pass
            if cmd is not None:
                closing = handle_command(engine, cmd, tracked,
                                         on_token, emit)
        if closing:
            break
        if engine.has_work():
            engine.step()
        with telemetry.span("replica.sweep"):
            sweep()
            now = time.monotonic()
            if now - last_pub >= stats_interval:
                last_pub = now
                heartbeat()

    engine.close()
    sweep()
    heartbeat()
    if publisher is not None:
        publisher.close()                  # graceful: lease-zero tombstone
    emit({"ev": "bye"})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
