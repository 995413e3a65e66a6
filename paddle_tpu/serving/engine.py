"""Continuous-batching LLM serving engine.

``LLMEngine`` drives a cache-aware causal LM (``models.llama``,
``models.laguna``) through two jitted step functions over the paged KV
cache. What the model keeps there it says itself (``model.cache_layers()``:
KV heads, head size and window of each layer); the engine reads no other
field of the model's shape:

- **prefill** (per admitted request, batch 1): the prompt — padded to a
  power-of-two number of KV blocks so trace count stays logarithmic — runs
  densely causal, its K/V scattered into the request's blocks, and the
  first new token is sampled from the last valid position's logits (TTFT).
- **decode** (all running slots, one fused call): one token per slot with
  *static* shapes — the whole pool, [slots, max_blocks] block tables, and
  per-slot context lengths/sampling params are traced inputs, so the step
  compiles exactly once no matter how sequences grow, join, or finish.
  A trace counter asserts this (the ``static.Executor`` discipline).

The decode loop is a pipeline of depth one (docs/SERVING.md "The pipelined
loop"): step N+1 is dispatched before step N's tokens are read. The sampled
``[slots]`` vector goes from step to step on the device (a prefill's first
token is put into its slot's place there), the host counts the tokens in
flight (``Request.in_flight``), and reading, emitting, accounting and the
next step's preparation run while the device works. A request that ends on
``eos_token_id`` is found one step late: its row of the step dispatched
past it is thrown away. Anything but the common case *drains* first (reads
what is in flight, then goes on in the serial order): a preemption, a
cancel or a deadline that hits a running request, a fault, ``close()``, a
K/V export.

Sampling is seeded per (request, output index) — batch composition,
preemption, and re-prefill cannot change a request's tokens, which is what
makes continuous batching output-equivalent to one-at-a-time decoding.

Failure containment (docs/ROBUSTNESS.md): every per-request step runs
inside an isolation boundary — an exception during a request's prefill or
decode marks *that request* ``FAILED`` with the error attached and returns
its slot and blocks to the pool; the engine keeps serving everyone else and
their token streams are unchanged (seeded sampling makes this provable,
see ``tests/test_chaos.py``). Per-request deadlines and :meth:`cancel`
bound tail latency; a bounded admission queue pushes back instead of
buffering without limit; a watchdog counts slow decode steps and a stall
detector fails the queue head rather than spinning when no progress is
possible. Chaos sites (``serving.prefill``, ``serving.decode.slot``,
``serving.decode``, ``serving.kv.alloc``, ``serving.kv.share``,
``serving.kv.cow``, ``serving.kv.spill``, ``serving.kv.promote``,
``serving.kv.fetch``, ``serving.admit``, ``serving.compile`` — the last
fires once per new prefill/decode trace creation) let
``paddle_tpu.utils.faults`` drive all of these paths deterministically.

Memory pressure (docs/ROBUSTNESS.md "Degradation ladder"):
``kv_spill_blocks=N`` arms a bounded host-RAM spill tier under the
prefix cache — LRU eviction demotes CRC32-stamped K/V to numpy instead
of destroying it, prefix hits promote it back (CRC verified; corrupt or
faulted promotions re-prefill, never serve wrong K/V) — and
``kv_high_watermark``/``kv_low_watermark`` latch scheduler backpressure
that is forced into ``stats()["slo"]["shed"]`` so the fleet router and
gateway shed at the front door.

Prefix caching (on by default; ``prefix_cache=False`` disables): admission
maps the longest cached block-aligned prefix of each prompt into the new
sequence's table as refcounted shared blocks and prefills only the
divergent tail with a positional offset (``_run_tail_prefill``); decode
registers each block it fills, copy-on-write protects shared blocks, and
completed prefixes linger in an evictable LRU pool (docs/SERVING.md).
Token streams are unchanged — sampling stays keyed by (request seed,
output index) and cached K/V is exactly what a full prefill would
recompute.

``naive_generate`` is the uncached baseline (full re-prefill every step)
used by the parity tests.
"""
from __future__ import annotations

import itertools
import time
from collections import deque
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np

from .. import telemetry
from ..nn.decode import sample_logits
from ..nn.functional.attention import StateLayer
from ..nn.layer import functional_call, functional_state
from ..utils import faults
from .kv_cache import PagedCacheView, PagedKVCache
from .scheduler import (DeadlineExceeded, Request, RequestState,
                        SamplingParams, Scheduler)
from .tenancy import TenantAccounting, TenantRegistry

__all__ = ["LLMEngine", "naive_generate", "STATS_KEYS"]

# canonical stats() schema — the single source of truth the gateway /stats
# endpoint and the telemetry tests assert against (satellite: defined once,
# imported everywhere, so adding a key is a one-line change here)
STATS_KEYS = frozenset({
    "queue_depth", "num_running", "num_finished", "num_failed",
    "num_cancelled", "num_rejected", "blocks_used", "blocks_free",
    "block_high_water", "cache_utilization", "num_preemptions",
    "decode_traces", "prefill_traces", "total_generated_tokens",
    "tokens_per_sec", "mean_ttft", "watchdog_trips", "last_decode_s",
    "slo", "prefix_cache", "perf", "tenancy",
})

# distinguishes concurrent engines' series in the process-global registry
_ENGINE_IDS = itertools.count()

# TTFT/queue-time land in the default latency buckets; TPOT and decode steps
# are per-token-scale, so give them sub-millisecond resolution too
_TOKEN_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                  0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5)

# stats()["perf"]["token_gaps"] and ["first_token"]: the latest gaps between
# a request's consecutive tokens (a row each: the gap in seconds, and the
# prefills, their padded tokens and the drains between its two reads), and
# the latest first tokens (a row each: the three parts)
_GAP_RING = 65536
_FIRST_TOKEN_RING = 4096
_GAP_CLASSES = ("plain", "prefill", "drain")
_FIRST_TOKEN_PARTS = ("queue", "prep", "behind")


def _engine_metrics(label: str) -> SimpleNamespace:
    """Resolve this engine's labeled children in the global registry once;
    the hot paths touch only the returned handles."""
    reg = telemetry.registry()
    ls = ("engine",)

    def C(name, help):
        return reg.counter(name, help, ls).labels(engine=label)

    def G(name, help):
        return reg.gauge(name, help, ls).labels(engine=label)

    def H(name, help, buckets=telemetry.DEFAULT_BUCKETS):
        return reg.histogram(name, help, ls, buckets=buckets).labels(
            engine=label)

    return SimpleNamespace(
        finished=C("serving_requests_finished_total",
                   "requests that reached FINISHED"),
        failed=C("serving_requests_failed_total",
                 "requests that reached FAILED"),
        cancelled=C("serving_requests_cancelled_total",
                    "requests that reached CANCELLED"),
        rejected=C("serving_requests_rejected_total",
                   "requests rejected by the bounded admission queue"),
        preemptions=C("serving_preemptions_total",
                      "running requests preempted for pool pressure"),
        tokens=C("serving_generated_tokens_total", "tokens emitted"),
        watchdog=C("serving_watchdog_trips_total",
                   "decode steps slower than watchdog_timeout_s"),
        stalls=C("serving_stall_failures_total",
                 "requests failed by the no-progress stall detector"),
        pressure_events=C("serving_kv_pressure_events_total",
                          "device-pool high-watermark latches"),
        pressure=G("serving_kv_pressure",
                   "1 while the device pool is above the high watermark "
                   "(admissions queue, the SLO shed signal is forced)"),
        queue_depth=G("serving_queue_depth", "requests waiting"),
        running=G("serving_running_requests", "requests in decode slots"),
        blocks_used=G("serving_kv_blocks_used", "live KV blocks"),
        blocks_free=G("serving_kv_blocks_free", "free KV blocks"),
        blocks_cached=G("serving_kv_blocks_cached",
                        "evictable cached prefix blocks (rc==0)"),
        high_water=G("serving_kv_block_high_water",
                     "peak live KV blocks this run"),
        utilization=G("serving_cache_utilization",
                      "live / usable KV block fraction"),
        ttft=H("serving_ttft_seconds",
               "request arrival to first emitted token"),
        tpot=H("serving_tpot_seconds",
               "mean inter-token time per finished request",
               _TOKEN_BUCKETS),
        queue_time=H("serving_queue_time_seconds",
                     "request arrival to slot admission"),
        decode_step=H("serving_decode_step_seconds",
                      "one fused decode step, from its dispatch until its "
                      "tokens are on the host",
                      _TOKEN_BUCKETS),
        admit_delay=H("serving_admit_delay_seconds",
                      "gateway read of a request to the engine accepting "
                      "it (observed by the replica on add_request)"),
    )


@jax.jit
def _put_token(tokens, slot, tok):
    """A prefill's first token into its slot's place of the vector the next
    decode step takes as it is."""
    return tokens.at[slot].set(tok)


class LLMEngine:
    """Continuous-batching serving engine over a paged KV cache.

    model:         a cache-aware causal LM: ``forward(ids, cache=,
                   positions=)`` and ``cache_layers()``, one
                   ``CacheLayer`` an attention layer (all layers
                   share one pool, so one KV width; windows may differ)
                   and one ``StateLayer`` a recurrent layer (all alike:
                   per-slot state arrays beside the pool; such a model
                   runs without prefix reuse, whatever ``prefix_cache``
                   says, because nothing snapshots a state)
    block_size:    tokens per KV block (pool granularity)
    num_blocks:    pool size incl. the reserved scratch block; default sizes
                   the pool so every slot can reach ``max_model_len``
    max_slots:     decode batch width (concurrent running requests)
    max_model_len: hard cap on prompt + generated tokens per request
    eos_token_id:  optional early-stop token
    max_queue:     bound on the waiting queue; beyond it ``add_request``
                   raises ``QueueFull`` (None = unbounded)
    max_preemptions_per_request: requeue cap before a thrashing request is
                   failed (preemption-storm protection)
    watchdog_timeout_s: decode steps slower than this — dispatch to the
                   tokens' arrival on the host, so a slow device step
                   trips it — are counted as watchdog trips in ``stats()``
                   (None = off)
    stall_limit:   consecutive no-progress engine steps tolerated before
                   the queue head is failed instead of spinning forever
    slo_ttft_s / slo_tpot_s: latency SLOs for the rolling-window
                   :class:`telemetry.SLOTracker`; ``stats()["slo"]``
                   reports window p50/p95/p99, goodput (tokens within
                   SLO), and the boolean admit/shed health signal a fleet
                   gateway polls (None = track percentiles, never shed)
    slo_window_s:  SLO observation window
    prefix_cache:  content-addressed KV-block prefix caching (refcounted
                   shared blocks, copy-on-write, LRU eviction of
                   unreferenced prefixes — docs/SERVING.md). Requests whose
                   prompt shares a block-aligned prefix with anything
                   previously served prefill only the divergent tail;
                   token streams are unchanged (``stats()["prefix_cache"]``
                   reports hits/blocks saved).
    kv_spill_blocks: bound on the host-RAM spill tier (entries = KV
                   blocks). With it set, LRU eviction *demotes* an
                   unreferenced cached prefix block to a CRC32-stamped
                   numpy copy instead of destroying it; a later prefix
                   hit promotes it back (CRC verified — corrupt/faulted
                   promotions fall back to full prefill, never wrong
                   tokens). None/0 = eviction destroys (the old
                   behavior). ``stats()["prefix_cache"]["spill"]``
                   reports the tier.
    kv_high_watermark / kv_low_watermark: device-pool backpressure
                   (fractions of usable blocks referenced). Above high,
                   admissions queue and ``stats()["slo"]["shed"]`` is
                   forced True so a fleet router routes around and the
                   gateway answers 429 + Retry-After; the latch clears
                   below low (default 0.75 * high). None = off.
    """

    def __init__(self, model, *, block_size=16, num_blocks=None, max_slots=4,
                 max_model_len=None, eos_token_id=None, kv_dtype=None,
                 max_queue=None, max_preemptions_per_request=16,
                 watchdog_timeout_s=None, stall_limit=8,
                 slo_ttft_s=None, slo_tpot_s=None, slo_window_s=120.0,
                 prefix_cache=True, kv_spill_blocks=None,
                 kv_high_watermark=None, kv_low_watermark=None,
                 tenancy=None):
        cfg = model.config
        self.model = model
        self.block_size = int(block_size)
        self.max_model_len = int(max_model_len or cfg.max_position_embeddings)
        self.max_slots = int(max_slots)
        self.eos_token_id = eos_token_id
        # static per-sequence table width
        self.max_blocks = -(-self.max_model_len // self.block_size)
        if num_blocks is None:
            num_blocks = self.max_slots * self.max_blocks + 1
        if num_blocks - 1 < self.max_blocks:
            raise ValueError(
                f"pool of {num_blocks} blocks (1 reserved) cannot hold one "
                f"max_model_len={self.max_model_len} sequence "
                f"({self.max_blocks} blocks); shrink max_model_len or grow "
                f"num_blocks")
        self.params, self.buffers = functional_state(model)
        if kv_dtype is None:
            kv_dtype = next(iter(self.params.values())).dtype
        declared = tuple(model.cache_layers())
        state_layers = tuple(l for l in declared if isinstance(l, StateLayer))
        layers = tuple(l for l in declared if not isinstance(l, StateLayer))
        # a prefix hit skips the prefill that would build a state
        self.prefix_cache = bool(prefix_cache) and not state_layers
        widths = {(l.kv_heads, l.head_dim) for l in layers}
        if len(widths) != 1:
            raise ValueError(
                f"one pool holds every layer's K/V, so the layers must "
                f"agree on (kv_heads, head_dim); the model has {widths}")
        (kv_heads, head_dim), = widths
        # static per layer: None, or the latest positions a query sees
        self._windows = tuple(l.window for l in layers)
        self.cache = PagedKVCache(
            len(layers), num_blocks, kv_heads,
            self.block_size, head_dim, dtype=kv_dtype,
            prefix_cache=self.prefix_cache,
            spill_blocks=kv_spill_blocks if self.prefix_cache else None,
            state_layers=state_layers, max_slots=self.max_slots)
        self.engine_label = str(next(_ENGINE_IDS))
        self._m = _engine_metrics(self.engine_label)
        self.slo = telemetry.SLOTracker(
            ttft_slo_s=slo_ttft_s, tpot_slo_s=slo_tpot_s,
            window_s=slo_window_s, engine_label=self.engine_label)
        # multi-tenant QoS (serving.tenancy): the registry defines weights
        # and quotas (a plain dict rides through a ProcReplica spec); with
        # tenancy=None everything runs as the "anonymous" tenant and the
        # fair queue degrades to exact FIFO — no feature flag, one path.
        if isinstance(tenancy, dict):
            tenancy = TenantRegistry.from_dict(tenancy)
        self.tenancy = tenancy if tenancy is not None else TenantRegistry()
        self.cache.set_tenant_quotas(self.tenancy.block_quotas())
        self._tenancy_acct = TenantAccounting(
            self.tenancy, self.engine_label, ttft_slo_s=slo_ttft_s,
            tpot_slo_s=slo_tpot_s, window_s=slo_window_s)
        self.scheduler = Scheduler(
            self.cache, self.max_slots, self.max_model_len,
            max_queue=max_queue,
            max_preemptions_per_request=max_preemptions_per_request,
            on_event=self._on_sched_event,
            high_watermark=kv_high_watermark,
            low_watermark=kv_low_watermark,
            tenancy=self.tenancy)

        self._next_rid = 0
        self._counter_names: tuple = ()    # set when a step is traced
        # the model's counters of the latest prefills (stats()["perf"])
        self._prefill_counters: dict[str, deque] = {}
        self._decode_fn = None
        self._prefill_fns: dict[int, object] = {}
        self._py_fns: dict = {}            # trace key -> python callable
        self.decode_traces = 0
        self.prefill_traces: dict[int, int] = {}
        # prefill attention layers traced on each path (stats()["perf"])
        self._prefill_attention = {"kernel": 0, "materialised": 0}
        # sparse layers traced on each path of their experts' gated MLP
        self._moe_paths = {program: {"fused": 0, "plain": 0}
                           for program in ("decode", "prefill")}
        # the KV pool is donated to every step: the step's output pool
        # reuses its buffer instead of holding two pools in device memory;
        # a model's state arrays ride at the end of a step's arguments and
        # are donated with it
        self._donate = (2,)
        self._has_state = self.cache.state is not None
        self._state_bytes = self.cache.state_nbytes
        self._state_slot_bytes = self._state_bytes // self.max_slots
        # what the latest decode steps moved of the state arrays: this
        # engine's own (the decode StepTimeline is the process's)
        self._state_moved: dict[str, deque] = {
            name: deque(maxlen=128)
            for name in ("bytes_moved", "share_of_cache_bytes")}

        # roofline cost model (telemetry.cost): each new trace is walked
        # for FLOPs/HBM bytes at creation (jaxpr only, no extra compile):
        # counts for stats()["perf"]["roofline"] and for charging tenants.
        # The fingerprint keys the process-global cost registry so
        # identical engines (fleet replicas, tests) share one estimate.
        self._cost_fp = (
            type(model).__name__,
            tuple(sorted((n, tuple(v.shape)) for n, v in self.params.items())),
            layers, self.block_size, self.max_slots, self.max_blocks,
            str(kv_dtype))
        self._suspend_trace_counts = False  # cost tracing must not count
        self._trace_costs: dict[tuple, dict] = {}   # (kind, bucket) -> est

        # performance observability (telemetry.perf): compile watching on
        # the bucketed prefill/decode traces, per-tag memory accounting,
        # and the decode StepTimeline feeding stats()["perf"]
        self._watcher = telemetry.compile_watcher()
        self._mm = telemetry.memory_monitor()
        self._decode_tl = telemetry.step_timeline("decode")
        self._params_bytes = sum(
            int(getattr(v, "nbytes", 0)) for v in self.params.values()
        ) + sum(int(getattr(v, "nbytes", 0)) for v in self.buffers.values())
        self._pool_bytes = int(self.cache.pool.nbytes)
        self._block_bytes = self._pool_bytes // max(num_blocks, 1)
        self._mm.add("params", self._params_bytes)
        self._mm.add("kv_pool", self._pool_bytes)
        if self._has_state:
            self._mm.add("state_pool", self._state_bytes)
        if self.cache.spill_blocks:
            # the host spill pool legitimately grows monotonically under
            # sustained pressure up to its capacity — exempt it from the
            # leak sentinel below that bound (past it, something is wrong)
            self._mm.expect_bounded(
                "kv_spill_host",
                cap_bytes=self.cache.spill_blocks * self._block_bytes)

        self.finished: list[Request] = []
        self.failed: list[Request] = []
        self.cancelled: list[Request] = []
        self._failed_rids: set[int] = set()
        self._requests: dict[int, Request] = {}   # rid -> handle
        self._total_generated = 0
        self._serve_start: float | None = None

        # the decode pipeline (depth one). _tokens: each slot's next input
        # token, on the device: a decode step's result as it is, a
        # prefill's first token put into its slot's place. _inflight: the
        # decode step dispatched and not yet read; _unread_prefills: the
        # prefills of this turn whose first token is not yet read.
        self._tokens = jnp.zeros(self.max_slots, jnp.int32)
        self._inflight: SimpleNamespace | None = None
        self._unread_prefills: list[SimpleNamespace] = []
        self._last_result_t: float | None = None
        # of the last 128 decode steps, those dispatched while the step
        # before was unread; drains by reason (stats()["perf"]), counted
        # once the drain has read what was in flight
        self._pipelined: deque = deque(maxlen=128)
        self._drains: dict[str, int] = {}

        # what each token waited for (stats()["perf"]["token_gaps"]). The
        # device runs programs in the order they are dispatched, so a gap
        # holds a prefill exactly when one was dispatched after the program
        # of the earlier token and before that of the later. Every program
        # is stamped with the prefills dispatched so far and their padded
        # tokens (a prefill counts itself). Per slot, of its request's last
        # read token: the read's instant, its program's two stamps and the
        # count of drains then.
        self._prefills = 0
        self._prefill_padded = 0
        self._slot_last = np.zeros((self.max_slots, 4))
        self._gap_ring = np.zeros((_GAP_RING, 4), np.float32)
        self._gaps_booked = 0
        # queue, prep and behind of the latest first tokens
        self._first_token_ring = np.zeros(
            (_FIRST_TOKEN_RING, len(_FIRST_TOKEN_PARTS)))
        self._first_tokens_booked = 0

        self.watchdog_timeout_s = watchdog_timeout_s
        self.watchdog_trips = 0
        self.last_decode_s = 0.0
        self.stall_limit = int(stall_limit)
        self._stall_steps = 0
        self._progressed = False
        self.closed = False

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------
    def add_request(self, prompt, sampling: SamplingParams | None = None,
                    on_token=None, deadline_s: float | None = None,
                    trace_id: str | None = None,
                    trace_parent: int | None = None,
                    on_watermark=None, watermark_every: int = 8,
                    tenant: str = "anonymous", priority: int = 0) -> Request:
        """Queue a prompt (list/array of token ids); returns the live
        request handle (``output_tokens`` grows as the engine steps;
        ``on_token(req, tok)`` streams each new token). ``deadline_s``
        bounds the request's total wall time: past it, the request is
        CANCELLED with :class:`DeadlineExceeded` attached. ``trace_id``
        is the request-trace context a gateway/router minted: every span
        this request produces carries it, and the replica protocol streams
        those spans back for the per-request merged Chrome trace.
        ``on_watermark(req, n)`` fires whenever the output length crosses
        a multiple of ``watermark_every`` — the coarse durable-progress
        signal the gateway's write-ahead journal records
        (docs/ROBUSTNESS.md "Durable requests"). ``tenant`` attributes the
        request to a tenant for weighted-fair admission, quota accounting
        and cost attribution (docs/SERVING.md "Multi-tenancy"); ``priority``
        orders requests *within* a tenant only — fairness across tenants is
        the scheduler's job, never the caller's."""
        req = Request(rid=self._next_rid, prompt=[int(t) for t in prompt],
                      sampling=sampling or SamplingParams(),
                      on_token=on_token, trace_id=trace_id,
                      trace_parent=trace_parent,
                      on_watermark=on_watermark,
                      watermark_every=watermark_every,
                      tenant=str(tenant or "anonymous"),
                      priority=int(priority))
        if deadline_s is not None:
            req.deadline = time.monotonic() + float(deadline_s)
        self._next_rid += 1
        self.scheduler.add(req)           # raises EngineClosed / QueueFull
        self._requests[req.rid] = req
        self._tenancy_acct.note_request(req.tenant)
        return req

    def cancel(self, rid: int, reason: str = "cancelled") -> bool:
        """Cancel a request by id wherever it is (waiting or running); its
        blocks and slot return immediately. Idempotent: cancelling an
        unknown or already-terminal request (including one that just
        finished, failed, or was already cancelled) returns False instead
        of raising, so a fleet router can fan out cancels without racing
        the engine's own terminal transitions. A running request's tokens
        in flight are read first (a drain): it may finish there."""
        req = self._requests.get(rid)
        if req is not None and req.in_flight:
            self._drain("cancel")
        ok = self.scheduler.cancel(rid, reason=reason)
        if ok and req is not None:
            self.cancelled.append(req)
            self._record_lifecycle(req)
        return ok

    def close(self):
        """Shut down: still-queued (never-prefilled) requests end FAILED
        with ``EngineClosed`` attached, running ones end CANCELLED (reason
        "shutdown") — every handle reaches a terminal state a router can
        act on; future add_request calls raise ``EngineClosed``. Tokens
        in flight are read and emitted first."""
        if self.closed:
            return
        self._drain("close")
        self.closed = True
        self._mm.sub("params", self._params_bytes)
        self._mm.sub("kv_pool", self._pool_bytes)
        if self._has_state:
            self._mm.sub("state_pool", self._state_bytes)
        if self.cache.spill_blocks:
            self._mm.set("kv_spill_host", 0)
        dropped = self.scheduler.close(cancel_pending=True)
        for req in dropped:
            if req.state is RequestState.FAILED:
                self.failed.append(req)
                self._failed_rids.add(req.rid)
            else:
                self.cancelled.append(req)
            self._record_lifecycle(req)

    def has_work(self) -> bool:
        """Requests waiting or running, or a step in flight to be read."""
        return self.scheduler.has_work() or self._unread()

    def step(self) -> bool:
        """One engine iteration: sweep deadlines, admit and dispatch the
        prefills of new requests (each inside its own failure boundary),
        dispatch one batched decode step over the running slots, and only
        then read the decode step dispatched an iteration ago and this
        iteration's first tokens: the device has the next step queued
        while the host emits and books this one. Returns True while there
        is work left (a step in flight is work left)."""
        if self.closed:
            return False
        if self._serve_start is None and self.scheduler.has_work():
            self._serve_start = time.monotonic()
        had_work = self.scheduler.has_work()
        self._progressed = False
        if not (self.scheduler.running or self._unread()):
            self._last_result_t = None      # no step to follow on from
        now = time.monotonic()
        if self._unread() and any(
                req.past_deadline(now)
                for req in self.scheduler.running.values()):
            self._drain("deadline")
        # the phases below are flat spans that tile the iteration (docs/
        # OBSERVABILITY.md "Phase spans"): no span encloses another, so a
        # device-idle gap in a profiler trace is named by the phase the
        # engine thread spent in it
        with telemetry.span("engine.schedule"):
            self._sweep_deadlines(now)
            admitted = self.scheduler.admit()
        for slot, req in admitted:
            self._progressed = True
            try:
                faults.inject("serving.prefill", rid=req.rid)
                self._run_prefill(slot, req)
            except Exception as e:          # isolate: fail ONE request
                self._drain("fault")
                if self.scheduler.running.get(slot) is req:
                    self._fail(slot, e)
        while self.scheduler.running:
            with telemetry.span("engine.schedule"):
                fits = self.scheduler.ensure_decode_capacity(
                    may_preempt=not self._unread())
                self._collect_scheduler_failures()
            if fits is not None:
                break
            # a victim's tokens in flight belong in its re-prefill
            self._drain("preemption")
        step = self._dispatch_decode()
        prev, self._inflight = self._inflight, step
        if prev is not None:
            self._read_decode(prev)
        self._read_prefills()
        if self._inflight is not None:
            self._book_dispatched(self._inflight)
        with telemetry.span("engine.account"):
            self._check_stall(had_work)
            self._sync_gauges()
            # steady-state watermark: stamp only when no request is
            # mid-decode (blocks legitimately grow while sequences do) —
            # blocks that never return to the pool across drains show up as
            # monotonic "kv_blocks" growth and trip the leak sentinel
            if not self.scheduler.running:
                self._mm.note_step()
        return self.has_work()

    def run(self):
        """Drive until every queued request has reached a terminal state
        (FINISHED, FAILED, or CANCELLED)."""
        while self.step():
            pass

    def generate(self, prompts, sampling=None):
        """Batch convenience: serve all ``prompts`` to completion, return
        their output token lists in order (partial for failed/cancelled
        requests — check the handles' ``state``/``error`` for those)."""
        if isinstance(sampling, (SamplingParams, type(None))):
            sampling = [sampling] * len(prompts)
        reqs = [self.add_request(p, s) for p, s in zip(prompts, sampling)]
        self.run()
        return [r.output_tokens for r in reqs]

    def stream(self, prompt, sampling: SamplingParams | None = None):
        """Generator yielding tokens of one request as the engine produces
        them (other queued requests keep batching along)."""
        req = self.add_request(prompt, sampling)
        emitted = 0
        while True:
            while emitted < len(req.output_tokens):
                yield req.output_tokens[emitted]
                emitted += 1
            if req.state.is_terminal:
                if req.state is RequestState.FAILED and req.error:
                    raise req.error
                return
            self.step()

    # ------------------------------------------------------------------
    # KV fabric (cross-replica block migration — serving/kv_fabric.py)
    # ------------------------------------------------------------------
    def export_kv_frames(self, hashes, *, max_frames: int | None = None,
                         max_bytes: int | None = None) -> list[dict]:
        """Donor half of a KV-block migration: serialize the longest
        consecutive run of ``hashes`` (prefix chain-hashes) this engine's
        cache holds, as CRC32-stamped wire frames. Chaos site
        ``serving.kv.fetch``: ``error`` raises (the fetch fails at the
        router), ``delay`` sleeps (the router's fetch timeout fires),
        ``stale`` answers empty (the directory entry aged out from under
        the caller), ``corrupt`` bit-rots one frame after its stamp (the
        receiver's CRC check must refuse it). Every kind degrades the
        admitting side to local prefill — never wrong K/V."""
        from . import kv_fabric

        self._no_frames_with_state_layers()
        self._drain("kv_export")          # the pool at rest
        act = faults.inject("serving.kv.fetch", hashes=len(list(hashes)),
                            engine=self.engine_label)
        if act == "stale":
            telemetry.record_event("kv.fabric.export", stale=True,
                                   engine=self.engine_label)
            return []
        frames = kv_fabric.export_frames(self.cache, hashes,
                                         max_frames=max_frames,
                                         max_bytes=max_bytes)
        if act == "corrupt" and frames:
            kv_fabric.corrupt_frame(frames[-1])
        return frames

    def ingest_kv_frames(self, frames) -> dict:
        """Receiver half: CRC-verify and promote migrated frames into the
        local prefix cache through the spill-tier promotion machinery
        (``PagedKVCache._promote`` re-verifies every stamp). Returns the
        ``{"ingested", "corrupt", "errors"}`` counts; whatever did not
        land verified simply prefills locally on admission."""
        from . import kv_fabric

        self._no_frames_with_state_layers()
        return kv_fabric.ingest_frames(self.cache, frames)

    def _no_frames_with_state_layers(self):
        if self._has_state:
            raise ValueError(
                "this engine's model has state layers: K/V frames of a "
                "prefix are of no use without the recurrent state at its "
                "end, which is neither exported nor ingested; the request "
                "prefills here")

    def stats(self) -> dict:
        """Serving counters, read back from this engine's registry series
        (the dict shape predates the telemetry subsystem and is preserved;
        the same numbers are scrapeable as ``serving_*{engine=...}`` via
        ``telemetry.prometheus_text()``). With telemetry disabled the
        registry stops updating, so the few live values (queue depth,
        block gauges) fall back to direct reads."""
        self._sync_gauges()
        elapsed = (time.monotonic() - self._serve_start
                   if self._serve_start else 0.0)
        m = self._m
        alloc = self.cache.allocator
        live = telemetry.enabled()
        return {
            "queue_depth": (int(m.queue_depth.value) if live
                            else self.scheduler.queue_depth),
            "num_running": (int(m.running.value) if live
                            else len(self.scheduler.running)),
            "num_finished": (int(m.finished.value) if live
                             else len(self.finished)),
            "num_failed": (int(m.failed.value) if live
                           else len(self.failed)),
            "num_cancelled": (int(m.cancelled.value) if live
                              else len(self.cancelled)),
            "num_rejected": (int(m.rejected.value) if live
                             else self.scheduler.num_rejected),
            "blocks_used": (int(m.blocks_used.value) if live
                            else alloc.num_used),
            "blocks_free": (int(m.blocks_free.value) if live
                            else alloc.num_free),
            "block_high_water": (int(m.high_water.value) if live
                                 else alloc.high_water),
            "cache_utilization": (m.utilization.value if live
                                  else self.cache.utilization()),
            "num_preemptions": (int(m.preemptions.value) if live
                                else self.scheduler.num_preemptions),
            "decode_traces": self.decode_traces,
            "prefill_traces": dict(self.prefill_traces),
            "total_generated_tokens": (int(m.tokens.value) if live
                                       else self._total_generated),
            "tokens_per_sec": (self._total_generated / elapsed
                               if elapsed > 0 else 0.0),
            "mean_ttft": m.ttft.mean if live else self._mean_ttft_direct(),
            "watchdog_trips": (int(m.watchdog.value) if live
                               else self.watchdog_trips),
            "last_decode_s": self.last_decode_s,
            # rolling-window SLO view; "healthy"/"shed" is the admit
            # signal the fleet gateway's router/load-shedder consumes
            "slo": self.slo.summary(),
            # prefix-cache effectiveness: hit rate, blocks/tokens saved,
            # CoW copies, evictions, and the evictable-pool size
            "prefix_cache": self.cache.prefix_stats(),
            # a model with state layers: the per-slot arrays beside the pool
            **({"state_cache": {
                "slots": self.max_slots, "bytes": self._state_bytes,
                "bytes_per_slot": self._state_slot_bytes,
                "prefix_reuse": "off: state layers"}}
               if self._has_state else {}),
            # performance observability (telemetry.perf): compile/retrace
            # counts per engine callable (+ any active storm with its
            # signature diff), the decode step's period and shares, what
            # each token waited for, and the per-tag memory accounting
            # incl. the leak sentinel
            "perf": self._perf_block(),
            # per-tenant counters, roofline cost attribution and tenant
            # SLO windows (serving.tenancy.TenantAccounting.summary());
            # requests without a tenant land under "anonymous"
            "tenancy": self._tenancy_acct.summary(),
        }

    def _perf_block(self) -> dict:
        storms = [s for s in self._watcher.storms()
                  if s["callable"].startswith(("engine.", "pallas."))]
        block = {
            "compiles": self._watcher.summary(prefix="engine."),
            "storms": storms,
            "explain_recompile": (
                self._watcher.explain(storms[0]["callable"])
                if storms else None),
            "decode_step": self._decode_tl.report(),
            "memory": self._mm.snapshot(),
            "roofline": self._roofline_block(),
        }
        block["decode_step"]["pipelined_step_share"] = (
            sum(tuple(self._pipelined)) / len(self._pipelined)
            if self._pipelined else None)
        block["decode_step"]["drains"] = dict(self._drains)
        block["token_gaps"] = self._token_gaps_report()
        block["first_token"] = self._first_token_report()
        if self._has_state and self._state_moved["bytes_moved"]:
            block["decode_step"]["state"] = {
                name: {"mean": sum(v) / len(v),
                       "p50": float(np.median(v))}
                for name, v in self._state_moved.items()}
        if self._prefill_counters:
            # the model's own counters over the latest prefills (the decode
            # steps' are in the StepTimeline's report)
            block["prefill"] = telemetry.perf.nest_dotted(
                {n: {"mean": sum(v) / len(v)}
                 for n, v in self._prefill_counters.items()})
        if any(self._prefill_attention.values()):
            # the prefill traces' attention layers by path: the flash
            # kernel, or the einsum composition's [H, P, P] scores
            block.setdefault("prefill", {})["attention"] = dict(
                self._prefill_attention)
        for program, paths in self._moe_paths.items():
            if any(paths.values()):
                # the traces' sparse layers by path: the experts' gated MLP
                # as one kernel call, or the plain form's two products
                at = block["decode_step"] if program == "decode" else \
                    block.setdefault("prefill", {})
                at.setdefault("moe", {})["gated_mlp"] = dict(paths)
        return block

    @staticmethod
    def _percentiles(v, qs) -> dict:
        if not len(v):
            return {f"p{q}": None for q in qs}
        return {f"p{q}": float(x) for q, x in zip(qs, np.percentile(v, qs))}

    def _token_gaps_report(self) -> dict:
        """The ring's gaps (seconds) by what lay between their two reads: a
        drain, else one or more prefills, else nothing (``plain``)."""
        n = min(self._gaps_booked, _GAP_RING)
        ring = self._gap_ring[:n].astype(np.float64)
        gap, prefills, padded, drains = ring.T
        code = np.where(drains > 0, 2, np.where(prefills > 0, 1, 0))
        out = {"gaps": n, "all": self._percentiles(gap, (50, 95, 99))}
        for c, name in enumerate(_GAP_CLASSES):
            sel = code == c
            k = int(sel.sum())
            out[name] = {"share": k / n if n else None,
                         **self._percentiles(gap[sel], (50, 95))}
            if name == "prefill":
                out[name]["prefills_mean"] = (
                    float(prefills[sel].mean()) if k else None)
                out[name]["padded_tokens_mean"] = (
                    float(padded[sel].mean()) if k else None)
        return out

    def _first_token_report(self) -> dict:
        """Of the latest first tokens, the engine's time to each in three
        parts: accepted -> admitted to a slot (``queue``), -> its prefill
        dispatched (``prep``), -> the token read on the host (``behind``)."""
        n = min(self._first_tokens_booked, _FIRST_TOKEN_RING)
        ring = self._first_token_ring[:n]
        return {"first_tokens": n,
                **{part: self._percentiles(ring[:, i], (50, 95))
                   for i, part in enumerate(_FIRST_TOKEN_PARTS)}}

    # ------------------------------------------------------------------
    # roofline cost model (telemetry.cost)
    # ------------------------------------------------------------------
    def _trace_cost(self, kind: str, bucket: str, py_key,
                    call_args) -> dict | None:
        """FLOPs/bytes of one compiled trace, estimated once at trace
        creation: jaxpr walk over the exact python callable + concrete
        arguments the engine just jitted (no extra XLA compile). The
        process-global registry (fingerprinted by model config + engine
        geometry) dedupes across fleet replicas and repeated engines."""
        name = f"engine.{kind}"
        est = telemetry.cost.lookup(name, bucket, self._cost_fp)
        if est is None and telemetry.enabled():
            try:
                self._suspend_trace_counts = True
                est = telemetry.cost.estimate_fn_cost(
                    self._py_fns[py_key], *call_args)
            except Exception:  # lint: allow-silent(cost estimate is advisory; absence skips one log line)
                est = None
            finally:
                self._suspend_trace_counts = False
            if est is not None:
                est = telemetry.cost.register_trace(
                    name, bucket, est, fingerprint=self._cost_fp,
                    engine=self.engine_label)
        if est is not None:
            self._trace_costs[(kind, bucket)] = est
        return est

    def _charge_tenant(self, tenant: str, kind: str, bucket: str,
                       share: float = 1.0):
        """Attribute one executed step's roofline-modeled cost to a tenant:
        a prefill charges its request's tenant in full; a fused decode step
        splits evenly across the batch snapshot (``share=1/batch``), so the
        per-tenant FLOPs always sum back to the engine's total."""
        est = self._trace_costs.get((kind, bucket))
        if est is None:
            return
        self._tenancy_acct.note_cost(
            tenant, est["flops"] * share, est["bytes"] * share)

    def _roofline_block(self) -> dict:
        """stats()["perf"]["roofline"]: each trace's modeled FLOPs and HBM
        bytes. Counts only: a share of the chip's peak is the benchmark's
        to take, from the device trace."""
        out = {}
        for kind in ("prefill", "decode"):
            out[kind] = {"buckets": {
                b: {"flops": e["flops"], "bytes": e["bytes"],
                    "arithmetic_intensity":
                        round(e["arithmetic_intensity"], 3)}
                for (k, b), e in sorted(self._trace_costs.items())
                if k == kind}}
        dec = self._trace_costs.get(("decode", "decode"))
        out["decode_ai"] = (round(dec["arithmetic_intensity"], 3)
                            if dec else None)
        return out

    def _mean_ttft_direct(self):
        ttfts = [r.ttft for r in self.finished if r.ttft is not None]
        return float(np.mean(ttfts)) if ttfts else None

    # ------------------------------------------------------------------
    # telemetry plumbing
    # ------------------------------------------------------------------
    def _on_sched_event(self, kind: str, rid=None, req=None, slot=None):
        """Scheduler decisions feed this engine's labeled registry series
        (the flight-recorder events are recorded by the scheduler itself)."""
        m = self._m
        if kind == "finish":
            m.finished.inc()
        elif kind == "fail":
            m.failed.inc()
        elif kind == "cancel":
            m.cancelled.inc()
        elif kind == "reject":
            m.rejected.inc()
        elif kind == "preempt":
            m.preemptions.inc()
            if req is not None:
                # its last token is on the host (a victim's is read before
                # it is re-queued): the gap its re-prefill closes starts here
                req.last_read_t = float(self._slot_last[slot, 0])
        elif kind == "admit" and req is not None:
            m.queue_time.observe(req.admit_time - req.arrival_time)
            # admitted-token attribution mirrors the DRR charge: the
            # worst-case tokens this admission occupies the engine for
            self._tenancy_acct.note_admitted(
                req.tenant, len(req.prompt) + req.sampling.max_new_tokens)
        elif kind == "deadline_queued" and req is not None:
            # scheduler fail-fast: the request expired while still queued
            # and never reached a prefill slot — it is CANCELLED with
            # DeadlineExceeded attached, and must land in the engine's
            # terminal bookkeeping like every other cancel
            m.cancelled.inc()
            self.cancelled.append(req)
            self._record_lifecycle(req)
        elif kind == "kv_pressure":
            m.pressure_events.inc()
            m.pressure.set(1)
        elif kind == "kv_pressure_clear":
            m.pressure.set(0)

    def _record_slo(self, req: Request):
        """One rolling-window observation per terminal request: finished
        requests contribute latency samples; failed/cancelled ones count
        their (wasted) tokens against goodput."""
        if req.state is RequestState.FINISHED:
            n = len(req.output_tokens)
            tpot = ((req.finish_time - req.first_token_time) / (n - 1)
                    if n > 1 and req.first_token_time is not None else None)
            queue_time = (req.admit_time - req.arrival_time
                          if req.admit_time is not None else None)
            self.slo.record_finished(ttft=req.ttft, tpot=tpot,
                                     queue_time=queue_time, tokens=n,
                                     trace_id=req.trace_id)
        else:
            self.slo.record_failed(tokens=len(req.output_tokens),
                                   trace_id=req.trace_id)

    def _sync_gauges(self):
        alloc = self.cache.allocator
        m = self._m
        m.queue_depth.set(self.scheduler.queue_depth)
        m.running.set(len(self.scheduler.running))
        m.blocks_used.set(alloc.num_used)
        m.blocks_free.set(alloc.num_free)
        m.blocks_cached.set(alloc.num_cached)
        m.high_water.set(alloc.high_water)
        m.utilization.set(self.cache.utilization())
        self._mm.set("kv_blocks", alloc.num_used * self._block_bytes)
        if self.cache.spill_blocks:
            self._mm.set("kv_spill_host", self.cache.spilled_bytes)
        # memory-pressure shed: refresh the watermark latch (admit() may
        # not run again once the queue drains) and ride the SLO tracker —
        # the existing stats()["slo"]["shed"] -> router -> gateway 429
        # path needs no new plumbing
        self.scheduler._update_pressure()
        self.slo.set_pressure(self.scheduler.mem_pressure,
                              reason="kv_watermark")

    def _record_lifecycle(self, req: Request):
        """Emit the request's queued -> prefill -> decode lifecycle as
        nested spans on its own virtual trace row, reconstructed from the
        timestamps the scheduler stamped. Called once per terminal
        request (at FINISHED / FAILED / CANCELLED)."""
        if req.finish_time is None or getattr(req, "_spans_recorded", False):
            return
        req._spans_recorded = True
        self._record_slo(req)
        self._tenancy_acct.note_terminal(req)
        tr = telemetry.tracer()
        tid = 100_000 + req.rid
        tid_name = f"request-{req.rid}"
        # request-trace context rides every lifecycle span (incl. the
        # engine label, so a LocalReplica driver sharing this process's
        # tracer can heartbeat only its own engine's spans)
        ctx = {"engine": self.engine_label}
        if req.trace_id:
            ctx["trace_id"] = req.trace_id
        root_attrs = {"rid": req.rid,
                      "state": req.state.value, "reason": req.finish_reason,
                      "prompt_tokens": len(req.prompt),
                      "output_tokens": len(req.output_tokens),
                      "preemptions": req.num_preemptions, **ctx}
        if req.trace_parent is not None:
            root_attrs["trace_parent"] = req.trace_parent
        root = tr.emit("request", req.arrival_time, req.finish_time,
                       attrs=root_attrs, tid=tid, tid_name=tid_name)
        if root is None:          # telemetry disabled
            return
        queued_end = req.admit_time or req.finish_time
        tr.emit("queued", req.arrival_time, queued_end,
                attrs={"rid": req.rid, **ctx}, parent_id=root.span_id,
                tid=tid)
        if req.admit_time is not None:
            prefill_end = req.first_token_time or req.finish_time
            tr.emit("prefill", req.admit_time, prefill_end,
                    attrs={"rid": req.rid, "tokens": len(req.prompt),
                           **ctx},
                    parent_id=root.span_id, tid=tid)
        if req.first_token_time is not None:
            tr.emit("decode", req.first_token_time, req.finish_time,
                    attrs={"rid": req.rid,
                           "tokens": len(req.output_tokens), **ctx},
                    parent_id=root.span_id, tid=tid)

    # ------------------------------------------------------------------
    # degradation machinery
    # ------------------------------------------------------------------
    def _fail(self, slot: int, error: BaseException):
        req = self.scheduler.running[slot]
        self.scheduler.fail(slot, error)
        self.failed.append(req)
        self._failed_rids.add(req.rid)
        self._record_lifecycle(req)

    def _collect_scheduler_failures(self):
        """Requests the scheduler failed on its own (pool exhaustion,
        preemption storm) still need to land in ``self.failed``."""
        for req in self._requests.values():
            if (req.state is RequestState.FAILED
                    and req.rid not in self._failed_rids):
                self.failed.append(req)
                self._failed_rids.add(req.rid)
                self._record_lifecycle(req)

    def _sweep_deadlines(self, now: float):
        for req in list(self.scheduler.waiting) + list(
                self.scheduler.running.values()):
            if req.past_deadline(now):
                err = DeadlineExceeded(
                    f"request {req.rid} missed its deadline "
                    f"({len(req.output_tokens)} of "
                    f"{req.sampling.max_new_tokens} tokens generated)")
                self.scheduler.cancel(req.rid, reason="deadline", error=err)
                self.cancelled.append(req)
                self._record_lifecycle(req)

    def _check_stall(self, had_work: bool):
        """A step that had work but admitted nothing and emitted nothing is
        a stall (e.g. injected allocator exhaustion keeps the queue head
        out forever). After ``stall_limit`` consecutive stalls, fail the
        head instead of spinning."""
        if not had_work or self._progressed or self.scheduler.running:
            self._stall_steps = 0
            return
        self._stall_steps += 1
        if self._stall_steps >= self.stall_limit and self.scheduler.waiting:
            req = self.scheduler.waiting.popleft()
            req.state = RequestState.FAILED
            req.finish_time = time.monotonic()
            req.finish_reason = "stalled"
            req.error = RuntimeError(
                f"request {req.rid} failed after {self._stall_steps} engine "
                f"steps with no progress (blocks free="
                f"{self.cache.allocator.num_free}) — pool exhausted or "
                f"allocator faulted")
            self.scheduler.num_failed += 1
            self.failed.append(req)
            self._failed_rids.add(req.rid)
            self._stall_steps = 0
            # postmortem: the stall's run-up (alloc attempts, admissions
            # that bounced, injected faults) is exactly what the ring holds
            self._m.failed.inc()
            self._m.stalls.inc()
            self._record_lifecycle(req)
            telemetry.record_event(
                "engine.stall", rid=req.rid, engine=self.engine_label,
                blocks_free=self.cache.allocator.num_free)
            telemetry.dump(reason="engine stall detector", error=req.error)

    # ------------------------------------------------------------------
    # the model's own counters of a step
    # ------------------------------------------------------------------
    def _pack_counters(self, view):
        """Trace side: what the model counted about itself in this step
        (``PagedCacheView.count``: per-layer sums under dotted names, with
        ``<group>.layers`` the layers that added to a group) as one float32
        vector beside the step's tokens, or None, which adds nothing to the
        program, for a model that counts nothing."""
        names = tuple(sorted(view.counters))
        if not names:
            return None
        # lint: allow-tracer-leak(names are static strings, set once a trace)
        self._counter_names = names
        return jnp.stack([jnp.asarray(view.counters[n], jnp.float32)
                          for n in names])

    def _read_counters(self, packed) -> dict:
        """Host side, once the step's result is here: each counter's mean
        over the layers that added to its group (none: empty)."""
        if packed is None:
            return {}
        vals = dict(zip(self._counter_names, np.asarray(packed).tolist()))
        out = {}
        for name, v in vals.items():
            group, _, leaf = name.rpartition(".")
            if leaf != "layers":
                out[name] = v / (vals.get(group + ".layers") or 1.0)
        return out

    def _window_block_share(self, ctx_lens) -> float | None:
        """Of the live block-table entries of the layers that have a window,
        the share those layers' attention walks this step (from the host's
        context lengths); None for a model without windows."""
        windows = [w for w in self._windows if w is not None]
        if not windows:
            return None
        live = -(-ctx_lens // self.block_size)
        walked = sum(
            int((live - np.maximum(ctx_lens - w, 0) // self.block_size).sum())
            for w in windows)
        return walked / (len(windows) * float(live.sum()))

    def _donate_from(self, first: int) -> tuple:
        """A step's donated arguments: the pool and, for a model with state
        layers, the two state arrays, which start at argument ``first``."""
        state = (first, first + 1) if self._has_state else ()
        return self._donate + state

    def _book_state_bytes_moved(self, ctx_lens):
        """What a decode step moves of the state arrays (every live slot's
        rows read and written once) and that as a share of the step's
        cache bytes, itself plus the live K/V the paged kernel walks (from
        the host's context lengths): which cache sets the step. Kept over
        this engine's last 128 steps; nothing for a model without state
        layers."""
        if not self._has_state:
            return
        moved = len(ctx_lens) * 2 * self._state_slot_bytes
        kv_token = self._block_bytes // self.block_size
        walked = sum(
            int(np.minimum(ctx_lens, w or ctx_lens).sum())
            for w in self._windows) * kv_token // len(self._windows)
        self._state_moved["bytes_moved"].append(float(moved))
        self._state_moved["share_of_cache_bytes"].append(
            moved / (moved + walked))

    # ------------------------------------------------------------------
    # prefill
    # ------------------------------------------------------------------
    def _bucket(self, length: int) -> int:
        """Pad prompts to a power-of-two number of blocks (capped at the
        model max) so distinct prefill traces stay O(log max_len)."""
        nb = max(1, -(-length // self.block_size))
        nb = 1 << (nb - 1).bit_length()
        return min(nb, self.max_blocks) * self.block_size

    def _act_estimate(self, tokens: int) -> int:
        """Rough live-activation bytes for a forward over ``tokens`` tokens
        (residual stream + one layer's MLP working set, f32): the
        "activations_estimate" memory tag is an attribution aid, not an
        allocator truth — XLA owns the real numbers
        (``memory_monitor().device_stats()`` when the backend exposes
        them)."""
        cfg = self.model.config
        width = cfg.hidden_size + getattr(cfg, "intermediate_size",
                                          4 * cfg.hidden_size)
        return int(tokens) * width * 4

    def _book_paths(self, view, program):
        """Trace side: which path each of a prefill's attention layers and
        each sparse layer of ``program`` ("decode" or "prefill") took
        (``PagedCacheView.attention_paths``, ``.moe_paths``), counted once a
        trace (the cost walk's retrace not)."""
        if self._suspend_trace_counts:
            return
        if program == "prefill":
            for path, n in view.attention_paths.items():
                self._prefill_attention[path] += n
        for path, n in view.moe_paths.items():
            self._moe_paths[program][path] += n

    @staticmethod
    def _sampled_row(view, logits, length):
        """The logits a batch-1 prefill samples from: the last valid
        position's, which is the only one there is where the model kept it
        alone (``PagedCacheView.last_rows``; the view says so)."""
        return logits[0, 0] if view.kept_last_rows else logits[0, length - 1]

    def _get_prefill_fn(self, P: int):
        fn = self._prefill_fns.get(P)
        if fn is not None:
            return fn
        faults.inject("serving.compile", callable="engine.prefill", P=P)
        model = self.model

        def prefill(params, buffers, pool, tokens, length, bt,
                    temp, top_k, top_p, seed, step_idx, slot=None, *state):
            if not self._suspend_trace_counts:   # cost walks retrace too
                self.prefill_traces[P] = self.prefill_traces.get(P, 0) + 1
            view = PagedCacheView(pool, bt[None, :], None, self.block_size,
                                  windows=self._windows, valid_len=length,
                                  state=state or None, slot=slot)
            positions = jnp.arange(P, dtype=jnp.int32)[None]
            logits, _ = functional_call(
                model, params, buffers, tokens[None], cache=view,
                positions=positions, training=False)
            self._book_paths(view, "prefill")
            last = self._sampled_row(view, logits, length)
            key = jax.random.fold_in(jax.random.PRNGKey(seed), step_idx)
            tok = sample_logits(last, temp, top_k, top_p, key)
            return (tok, view.pool, self._pack_counters(view),
                    *(view.state or ()))

        fn = jax.jit(prefill, donate_argnums=self._donate_from(12))
        self._prefill_fns[P] = fn
        self._py_fns[P] = prefill
        return fn

    def _get_tail_prefill_fn(self, P: int, NPB: int):
        """Tail-only prefill after a prefix-cache hit: same contract as the
        plain prefill function plus the (padded, static-width) prefix block
        table and the true prefix length; traces are keyed ``(P, NPB)`` —
        both power-of-two bucketed, so the count stays O(log^2 max_len)."""
        key = (P, NPB)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        faults.inject("serving.compile", callable="engine.prefill",
                      P=P, NPB=NPB)
        model = self.model

        def tail_prefill(params, buffers, pool, tokens, length, bt, pbt,
                         prefix_len, temp, top_k, top_p, seed, step_idx):
            if not self._suspend_trace_counts:
                self.prefill_traces[key] = self.prefill_traces.get(key, 0) + 1
            view = PagedCacheView(
                pool, bt[None, :], None, self.block_size,
                prefix_block_tables=pbt[None, :], prefix_len=prefix_len,
                windows=self._windows, valid_len=length)
            positions = (prefix_len
                         + jnp.arange(P, dtype=jnp.int32))[None]
            logits, _ = functional_call(
                model, params, buffers, tokens[None], cache=view,
                positions=positions, training=False)
            self._book_paths(view, "prefill")
            last = self._sampled_row(view, logits, length)
            k = jax.random.fold_in(jax.random.PRNGKey(seed), step_idx)
            tok = sample_logits(last, temp, top_k, top_p, k)
            return tok, view.pool, self._pack_counters(view)

        fn = jax.jit(tail_prefill, donate_argnums=self._donate)
        self._prefill_fns[key] = fn
        self._py_fns[key] = tail_prefill
        return fn

    def _run_prefill(self, slot: int, req: Request):
        toks = req.prefill_tokens
        cached = req.cached_tokens if self.prefix_cache else 0
        if cached:
            self._run_tail_prefill(slot, req, toks, cached)
            return
        t0 = time.monotonic()
        with telemetry.span("engine.prefill", rid=req.rid, tokens=len(toks),
                            engine=self.engine_label,
                            **({"trace_id": req.trace_id}
                               if req.trace_id else {})) as span:
            L = len(toks)
            P = span.attrs["padded"] = self._bucket(L)
            padded = np.zeros(P, np.int32)
            padded[:L] = toks
            bt = self.cache.table_array([req.rid], P // self.block_size)[0]
            sp = req.sampling
            new_trace = P not in self._prefill_fns
            self._mm.set("activations_estimate", self._act_estimate(P))
            fn = self._get_prefill_fn(P)
            call_args = (
                self.params, self.buffers, self.cache.pool,
                jnp.asarray(padded), jnp.int32(L), jnp.asarray(bt),
                jnp.float32(sp.temperature), jnp.int32(sp.top_k),
                jnp.float32(sp.top_p), jnp.int32(sp.seed),
                jnp.int32(len(req.output_tokens)))
            if self._has_state:
                call_args += (jnp.int32(slot), *self.cache.state)
            cost_est = (self._trace_cost("prefill", f"P{P}", P, call_args)
                        if new_trace else None)
            tok, self.cache.pool, counters, *state = fn(*call_args)
            if state:
                self.cache.state = tuple(state)
            self._tokens = _put_token(self._tokens, jnp.int32(slot), tok)
        self._finish_prefill(
            slot, req, toks, tok, t0, f"P{P}", P,
            (("tokens", (P,), "int32"),
             ("block_table", (P // self.block_size,), "int32")),
            new_trace, cost_est, counters)

    def _run_tail_prefill(self, slot: int, req: Request, toks, cached: int):
        """Prefill only the tokens past the matched prefix: the cached
        blocks are already mapped (shared) into the request's table, so the
        jitted step gathers their K/V, writes the tail's, and samples from
        the last valid position — positionally offset by the hit length."""
        bs = self.block_size
        t0 = time.monotonic()
        with telemetry.span("engine.prefill", rid=req.rid,
                            tokens=len(toks) - cached, cached=cached,
                            engine=self.engine_label,
                            **({"trace_id": req.trace_id}
                               if req.trace_id else {})) as span:
            npb = cached // bs                      # matched blocks (full)
            tail = toks[cached:]
            L = len(tail)
            P = span.attrs["padded"] = self._bucket(L)
            NPB = 1 << (npb - 1).bit_length()       # pad to power of two
            table = self.cache.tables[req.rid]
            pbt = np.zeros(NPB, np.int32)
            pbt[:npb] = table[:npb]
            bt = np.zeros(P // bs, np.int32)
            tail_blocks = table[npb:npb + P // bs]
            bt[:len(tail_blocks)] = tail_blocks
            padded = np.zeros(P, np.int32)
            padded[:L] = tail
            sp = req.sampling
            new_trace = (P, NPB) not in self._prefill_fns
            self._mm.set("activations_estimate", self._act_estimate(P))
            fn = self._get_tail_prefill_fn(P, NPB)
            call_args = (
                self.params, self.buffers, self.cache.pool,
                jnp.asarray(padded), jnp.int32(L), jnp.asarray(bt),
                jnp.asarray(pbt), jnp.int32(cached),
                jnp.float32(sp.temperature), jnp.int32(sp.top_k),
                jnp.float32(sp.top_p), jnp.int32(sp.seed),
                jnp.int32(len(req.output_tokens)))
            bucket = f"P{P}-NPB{NPB}"
            cost_est = (self._trace_cost("prefill", bucket, (P, NPB),
                                         call_args)
                        if new_trace else None)
            tok, self.cache.pool, counters = fn(*call_args)
            self._tokens = _put_token(self._tokens, jnp.int32(slot), tok)
        self._finish_prefill(
            slot, req, toks, tok, t0, bucket, P,
            (("tokens", (P,), "int32"),
             ("block_table", (P // bs,), "int32"),
             ("prefix_table", (NPB,), "int32")),
            new_trace, cost_est, counters)

    def _finish_prefill(self, slot: int, req: Request, toks, tok,
                        t0: float, bucket: str, padded: int, signature,
                        new_trace: bool, cost_est, counters):
        """What both prefills do once the step is dispatched and its first
        token is in its slot's place on the device: book what needs no
        result, and leave the token to be read once the iteration's decode
        step is dispatched behind it (:meth:`_read_prefills`)."""
        t_dispatch = time.monotonic()
        req.in_flight += 1
        self._prefills += 1
        self._prefill_padded += padded
        self._send_home(tok, counters)
        self._unread_prefills.append(SimpleNamespace(
            slot=slot, req=req, tok=tok, counters=counters, t0=t0,
            t_dispatch=t_dispatch,
            stamp=(self._prefills, self._prefill_padded),
            signature=signature, new_trace=new_trace, cost_est=cost_est))
        with telemetry.span("engine.overlap"):
            self.cache.commit_prefix(req.rid, toks)
            self._charge_tenant(req.tenant, "prefill", bucket)

    def _read_prefills(self):
        """Wait for each dispatched prefill's first token, hand it on and
        book the step's time. ``wall`` ends at the result — at dispatch the
        device has only been asked. A prefill that failed on the device is
        found here and fails its one request."""
        unread, self._unread_prefills = self._unread_prefills, []
        for p in unread:
            p.req.in_flight -= 1
            live = self.scheduler.running.get(p.slot) is p.req
            try:
                with telemetry.span("engine.prefill_wait"):
                    tok = int(self._fetch(p.tok))
                    counters = self._read_counters(p.counters)
            except Exception as e:
                if live:
                    self._fail(p.slot, e)
                continue
            t_read = time.monotonic()
            first = p.req.first_token_time is None
            if live:        # else it ended while its token was in flight
                with telemetry.span("engine.emit"):
                    self._emit(p.slot, p.req, tok)
            with telemetry.span("engine.account"):
                if live and telemetry.enabled():
                    self._book_prefill_token(p, t_read, first)
                self._watcher.record_call(
                    "engine.prefill", p.signature,
                    wall_s=t_read - p.t0 if p.new_trace else None,
                    cost=p.cost_est)
                for name, v in counters.items():
                    self._prefill_counters.setdefault(
                        name, deque(maxlen=128)).append(v)

    def _book_prefill_token(self, p, t_read: float, first: bool):
        """A prefill's token, read at ``t_read``: a request's first token in
        its three parts (they sum to its TTFT), or the token of a re-prefill
        after preemption, a gap with a drain in it. Either way the slot's
        record starts over from this read."""
        req = p.req
        if first:
            at = self._first_tokens_booked % _FIRST_TOKEN_RING
            self._first_token_ring[at] = (
                req.admit_time - req.arrival_time,
                p.t_dispatch - req.admit_time,
                req.first_token_time - p.t_dispatch)
            self._first_tokens_booked += 1
        elif req.last_read_t is not None:
            self._put_gaps(np.array([(t_read - req.last_read_t, 0, 0, 1)]))
        self._slot_last[p.slot] = (t_read, *p.stamp,
                                   sum(self._drains.values()))

    def _put_gaps(self, rows):
        """Rows of (gap, prefills, padded tokens, drains) into the ring."""
        n = len(rows)
        at = self._gaps_booked % _GAP_RING
        end = at + n
        if end <= _GAP_RING:
            self._gap_ring[at:end] = rows
        else:
            self._gap_ring[at:] = rows[:_GAP_RING - at]
            self._gap_ring[:end - _GAP_RING] = rows[_GAP_RING - at:]
        self._gaps_booked += n

    def _book_decode_gaps(self, slots, t_read: float, stamp):
        """The gaps a decode step's rows close, read at ``t_read``: per row,
        what lay between its request's last read and this one, from the
        stamps in the slot's record, which then takes this step's."""
        idx = np.array(slots)
        now = np.array((t_read, *stamp, sum(self._drains.values())))
        self._put_gaps(now - self._slot_last[idx])
        self._slot_last[idx] = now

    # ------------------------------------------------------------------
    # decode
    # ------------------------------------------------------------------
    def _get_decode_fn(self):
        if self._decode_fn is not None:
            return self._decode_fn
        faults.inject("serving.compile", callable="engine.decode")
        model = self.model

        def decode(params, buffers, pool, tokens, bt, ctx,
                   temps, top_ks, top_ps, seeds, step_idx, *state):
            if not self._suspend_trace_counts:
                # lint: allow-tracer-leak(trace-time compile counter, runs once per trace)
                self.decode_traces += 1
            view = PagedCacheView(pool, bt, ctx, self.block_size,
                                  windows=self._windows, state=state or None)
            logits, _ = functional_call(
                model, params, buffers, tokens[:, None], cache=view,
                positions=ctx[:, None], training=False)
            self._book_paths(view, "decode")
            # in the model's dtype: the sampler casts, behind a barrier
            last = logits[:, -1]
            keys = jax.vmap(
                lambda s, t: jax.random.fold_in(jax.random.PRNGKey(s), t)
            )(seeds, step_idx)
            toks = sample_logits(last, temps, top_ks, top_ps, keys)
            return (toks, view.pool, self._pack_counters(view),
                    *(view.state or ()))

        self._decode_fn = jax.jit(decode,
                                  donate_argnums=self._donate_from(11))
        self._py_fns["decode"] = decode
        return self._decode_fn

    @staticmethod
    def _send_home(*results):
        """Ask for a dispatched step's results on the host as soon as the
        device has them, not when the host comes to wait for them."""
        for x in results:
            if x is not None:
                x.copy_to_host_async()

    @staticmethod
    def _fetch(x):
        """Where the host waits for a step's result."""
        return np.asarray(x)

    def _unread(self) -> bool:
        return self._inflight is not None or bool(self._unread_prefills)

    def _assemble_decode(self):
        """The slots the step runs and its host-side batch: one NumPy array
        per traced input of ``decode`` past the tokens (which are on the
        device), inactive slots reading one garbage scratch token. Lengths
        and sampler indices count the tokens in flight; a request whose
        last token is in flight has no row."""
        # per-slot chaos boundary: a fault targeted at one request drops
        # only that request from the batch (FAILED, error attached)
        for slot, req in sorted(self.scheduler.running.items()):
            try:
                faults.inject("serving.decode.slot", rid=req.rid)
            except Exception as e:
                self._drain("fault")
                if self.scheduler.running.get(slot) is req:
                    self._fail(slot, e)
        rows = {slot: req for slot, req in self.scheduler.running.items()
                if req.dispatched < req.sampling.max_new_tokens}
        if not rows:
            return rows, None
        S = self.max_slots
        ctx = np.ones(S, np.int32)       # inactive: 1 garbage scratch token
        temps = np.zeros(S, np.float32)
        top_ks = np.zeros(S, np.int32)
        top_ps = np.ones(S, np.float32)
        seeds = np.zeros(S, np.int32)
        steps = np.zeros(S, np.int32)
        sids = [None] * S
        for slot, req in rows.items():
            sids[slot] = req.rid
            ctx[slot] = len(req.prompt) + req.dispatched - 1
            temps[slot] = req.sampling.temperature
            top_ks[slot] = req.sampling.top_k
            top_ps[slot] = req.sampling.top_p
            seeds[slot] = req.sampling.seed
            steps[slot] = req.dispatched
        bt = self.cache.table_array(sids, self.max_blocks)
        return rows, (bt, ctx, temps, top_ks, top_ps, seeds, steps)

    def _dispatch_decode(self):
        """Assemble, upload and dispatch one fused decode step over the
        running slots, its tokens operand the device's own vector. Returns
        the step in flight, or None when there was no row to run or the
        dispatch failed (then every request of the batch has failed)."""
        t0 = time.monotonic()
        with telemetry.span("engine.assemble"):
            rows, host = self._assemble_decode()
        if not rows:
            return None
        _, ctx, temps, *_ = host
        step = SimpleNamespace(
            rows=rows, ctx=ctx, temps=temps, toks=None, counters=None,
            new_trace=self._decode_fn is None, cost_est=None,
            live_share=None, window_share=None, sampled=None,
            pipelined=self._inflight is not None,
            # the prefills dispatched ahead of it, and their padded tokens
            stamp=(self._prefills, self._prefill_padded),
            # its assembly began, its dispatch began, its tokens were read
            t0=t0, t_dispatch=None, t_result=None)
        try:
            with telemetry.span("engine.upload"):
                self._mm.set("activations_estimate",
                             self._act_estimate(self.max_slots))
                faults.inject("serving.decode", batch=len(rows))
                fn = self._get_decode_fn()
                call_args = (self.params, self.buffers, self.cache.pool,
                             self._tokens, *(jnp.asarray(a) for a in host),
                             *(self.cache.state or ()))
                if step.new_trace:
                    step.cost_est = self._trace_cost(
                        "decode", "decode", "decode", call_args)
            step.t_dispatch = time.monotonic()
            # batch-level decode ticks carry every member request's
            # trace context so per-request merged traces include them
            tids = [r.trace_id for r in rows.values() if r.trace_id]
            with telemetry.span("engine.decode", batch=len(rows),
                                engine=self.engine_label,
                                **({"trace_ids": tids} if tids else {})):
                (step.toks, self.cache.pool, step.counters,
                 *state) = fn(*call_args)
                self._tokens = step.toks
                if state:
                    self.cache.state = tuple(state)
                self._send_home(step.toks, step.counters)
        except Exception as e:  # lint: allow-silent(_fail_rows attaches the error to every request of the batch)
            # the fused step died on its way out: what is in flight is
            # read, then every request in the batch fails; the engine
            # itself (and the waiting queue) survives
            self._drain("fault")
            self._fail_rows(step, e)
            self._clock_decode(step, None, ())      # as far as it got
            return None
        for req in rows.values():
            req.in_flight += 1
        self._pipelined.append(step.pipelined)
        return step

    def _book_dispatched(self, step):
        """Bookkeeping of the step just dispatched that needs no result of
        its own, under the device's time; the tokens the step before
        brought are on the host by now."""
        with telemetry.span("engine.overlap"):
            rows = step.rows
            share = 1.0 / len(rows)
            for req in rows.values():
                self._charge_tenant(req.tenant, "decode", "decode", share)
            # how much of the running slots' block tables the paged kernel
            # walks this step (its context is ctx + 1: the token being
            # written counts)
            ctx_lens = step.ctx[list(rows)] + 1
            live = -(-ctx_lens // self.block_size)
            step.live_share = float(live.sum()) / (
                len(rows) * self.max_blocks)
            step.window_share = self._window_block_share(ctx_lens)
            self._book_state_bytes_moved(ctx_lens)
            # some row samples (idle slots upload temperature 0): the
            # sampler's conditional takes its sort-and-draw branch
            step.sampled = bool((step.temps > 0).any())
            if self.prefix_cache:
                # a decode write that fills its block completes another
                # full token-block: index it so later admissions can share
                # it (its last token came with the step before)
                for slot, req in rows.items():
                    if (self.scheduler.running.get(slot) is req
                            and req.total_len % self.block_size == 0):
                        self.cache.commit_prefix(req.rid, req.prefill_tokens)

    def _read_decode(self, step):
        """Wait for a dispatched step's tokens and emit them. A step that
        failed on the device is found here: the rows of every step in
        flight fail, the engine and the waiting queue survive."""
        toks = counters = None
        try:
            with telemetry.span("engine.decode_wait"):
                toks = self._fetch(step.toks)
                # the model's counters came with the tokens
                counters = self._read_counters(step.counters)
                if step.window_share is not None:
                    counters["window_block_share"] = step.window_share
        except Exception as e:
            later, self._inflight = self._inflight, None
            self._fail_rows(step, e)
            if later is not None:       # dispatched behind it: dropped
                self._fail_rows(later, e)
                for req in later.rows.values():
                    req.in_flight -= 1
            self._tokens = jnp.zeros(self.max_slots, jnp.int32)
        step.t_result = time.monotonic()
        read = []
        with telemetry.span("engine.emit"):
            for slot, req in step.rows.items():
                req.in_flight -= 1
                # a request that ended while this row was in flight (on
                # eos_token_id, found a step late) leaves the row unread
                if toks is not None and \
                        self.scheduler.running.get(slot) is req:
                    read.append(slot)
                    self._emit(slot, req, int(toks[slot]))
        self._clock_decode(step, counters, read)

    def _fail_rows(self, step, error):
        for slot, req in step.rows.items():
            if self.scheduler.running.get(slot) is req:
                self._fail(slot, error)

    def _clock_decode(self, step, counters, read):
        """Book a decode step once its result is here (or it has failed:
        then as far as it got). Its clocks end at the result: at dispatch
        the device has only been asked. ``step_s`` is the interval between
        consecutive results reaching the host, the period a client's gap is
        made of; the watchdog and ``last_decode_s`` run from the step's
        dispatch. ``step_s``, occupancy, live share of the block tables,
        whether a row sampled and the model's own counters go into the
        StepTimeline, the gaps of the rows ``read`` into the engine's ring,
        the call into the compile watcher."""
        now = time.monotonic()              # an instant not reached: now
        t_result = step.t_result if step.t_result is not None else now
        since = self._last_result_t
        step_s = t_result - (since if since is not None else step.t0)
        self._last_result_t = t_result
        self.last_decode_s = t_result - (
            step.t_dispatch if step.t_dispatch is not None else now)
        self._m.decode_step.observe(self.last_decode_s)
        if (self.watchdog_timeout_s is not None
                and self.last_decode_s > self.watchdog_timeout_s):
            self.watchdog_trips += 1
            self._m.watchdog.inc()
            telemetry.record_event(
                "engine.watchdog_trip", engine=self.engine_label,
                decode_s=self.last_decode_s,
                limit_s=self.watchdog_timeout_s)
        with telemetry.span("engine.account"):
            self._decode_tl.record_step(
                step_s, occupancy=len(step.rows) / self.max_slots,
                live_block_share=step.live_share, sampled=step.sampled,
                counters=counters)
            if read and telemetry.enabled():
                self._book_decode_gaps(read, t_result, step.stamp)
            self._watcher.record_call(
                "engine.decode",
                (("tokens", (self.max_slots,), "int32"),
                 ("block_tables", (self.max_slots, self.max_blocks),
                  "int32")),
                wall_s=(t_result - step.t0) if step.new_trace else None,
                cost=step.cost_est)

    def _drain(self, reason: str):
        """Read and emit what is in flight (the decode step, then the
        unread first tokens), so that what follows sees every token on the
        host and goes on in the serial order. Counted by reason when there
        was something to read; a token read after it closes a ``drain``
        gap, one read in it not yet."""
        if not self._unread():
            return
        telemetry.record_event("engine.drain", reason=reason,
                               engine=self.engine_label)
        step, self._inflight = self._inflight, None
        if step is not None:
            self._read_decode(step)
        self._read_prefills()
        self._drains[reason] = self._drains.get(reason, 0) + 1

    def _emit(self, slot: int, req: Request, token: int):
        req.emit(token)
        self._progressed = True
        self._total_generated += 1
        self._m.tokens.inc()
        self._tenancy_acct.note_tokens(req.tenant)
        if len(req.output_tokens) == 1:
            # the trace-id exemplar links a slow TTFT bucket straight to
            # the request trace that landed in it (OpenMetrics exemplars)
            self._m.ttft.observe(
                req.ttft,
                exemplar=({"trace_id": req.trace_id}
                          if req.trace_id else None))
        if (self.eos_token_id is not None and token == self.eos_token_id):
            self._finish(slot, "stop")
        elif len(req.output_tokens) >= req.sampling.max_new_tokens:
            self._finish(slot, "length")

    def _finish(self, slot: int, reason: str):
        req = self.scheduler.running[slot]
        self.scheduler.finish(slot, reason)
        self.finished.append(req)
        n = len(req.output_tokens)
        if n > 1 and req.first_token_time is not None:
            self._m.tpot.observe(
                (req.finish_time - req.first_token_time) / (n - 1),
                exemplar=({"trace_id": req.trace_id}
                          if req.trace_id else None))
        self._record_lifecycle(req)


# ---------------------------------------------------------------------------
# uncached baseline
# ---------------------------------------------------------------------------

# one program a shape: called eagerly, the sampler's ``cond`` would be
# compiled anew at every token
_sample_once = jax.jit(sample_logits)


def naive_generate(model, prompt, sampling: SamplingParams | None = None,
                   eos_token_id=None):
    """Reference decode loop with NO KV cache: every step re-runs the full
    forward over the whole prefix (what L9's one-shot Predictor amounts to).
    Tokens are keyed exactly like the engine — (seed, output index) — so the
    engine must reproduce this stream token-for-token."""
    sp = sampling or SamplingParams()
    params, buffers = functional_state(model)
    toks = [int(t) for t in prompt]
    out = []
    for i in range(sp.max_new_tokens):
        logits, _ = functional_call(
            model, params, buffers, jnp.asarray([toks], jnp.int32),
            training=False)
        last = logits[0, -1]
        key = jax.random.fold_in(jax.random.PRNGKey(sp.seed), i)
        tok = int(_sample_once(last, sp.temperature, sp.top_k, sp.top_p,
                               key))
        out.append(tok)
        toks.append(tok)
        if eos_token_id is not None and tok == eos_token_id:
            break
    return out
