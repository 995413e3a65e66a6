"""Continuous-batching request scheduler.

Requests queue through a weighted-fair :class:`~.tenancy.FairQueue`
(deficit round robin over tenants — with a single tenant it degenerates
to exact arrival-order FIFO); the scheduler admits them into a fixed set
of decode *slots* under admission control against the block pool (a request
enters only when its prefill blocks plus one decode block of headroom are
free). Running requests join the batched decode step; when one finishes its
slot and blocks return immediately and the next waiting request takes over
— join-on-finish, no batch-wide barrier.

When the pool runs dry mid-decode (a running sequence crosses a block
boundary with no free block), the latest-arrived *other* running request is
preempted: its blocks are freed and it re-queues at the front with its
generated tokens folded into the prompt, so its re-prefill resumes exactly
where it left off. Sampling stays deterministic across preemption because
the engine keys every sampled token by (request seed, output index), not by
wall-clock step.

Failure semantics (docs/ROBUSTNESS.md): a request can also leave the system
as ``FAILED`` (an error during its prefill/decode, attached on
``req.error``) or ``CANCELLED`` (explicit :meth:`Scheduler.cancel`, a missed
deadline, or engine shutdown). Either way its slot and blocks return to the
pool and the rest of the batch is untouched — one bad request never takes
the engine down. The waiting queue is bounded (``max_queue``): beyond it
:meth:`add` raises :class:`QueueFull` so callers see backpressure instead
of unbounded memory growth, and ``num_rejected`` counts the pushback. A
request preempted more than ``max_preemptions_per_request`` times is failed
rather than requeued (preemption-storm protection: a pool thrashing under
pressure must converge, not livelock). After :meth:`close`, :meth:`add`
raises :class:`EngineClosed` instead of silently dropping the request.
"""
from __future__ import annotations

import enum
import time
from dataclasses import dataclass, field

from .. import telemetry
from ..utils import faults
from .kv_cache import PagedKVCache
from .tenancy import FairQueue

__all__ = ["SamplingParams", "Request", "RequestState", "Scheduler",
           "EngineClosed", "QueueFull", "DeadlineExceeded",
           "PreemptionStorm"]


class EngineClosed(RuntimeError):
    """add() after shutdown — the request would otherwise vanish silently."""


class QueueFull(RuntimeError):
    """Bounded admission queue rejected the request (backpressure)."""


class DeadlineExceeded(TimeoutError):
    """The request's per-request deadline passed before it finished."""


class PreemptionStorm(RuntimeError):
    """Requeued more than max_preemptions_per_request times; failing the
    request instead of livelocking the pool."""


@dataclass
class SamplingParams:
    """Per-request decode controls. ``temperature=0`` is greedy (argmax);
    ``top_k=0`` / ``top_p=1.0`` disable those filters."""

    max_new_tokens: int = 16
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0


class RequestState(enum.Enum):
    WAITING = "waiting"
    RUNNING = "running"
    FINISHED = "finished"
    FAILED = "failed"
    CANCELLED = "cancelled"

    @property
    def is_terminal(self) -> bool:
        return self in (RequestState.FINISHED, RequestState.FAILED,
                        RequestState.CANCELLED)


@dataclass
class Request:
    rid: int
    prompt: list[int]
    sampling: SamplingParams
    on_token: object = None            # callable(req, token) per new token
    # durable-lifecycle watermark (serving/journal.py): called with
    # (req, n_tokens) whenever the output length crosses a multiple of
    # watermark_every — the coarse progress signal a write-ahead journal
    # records without paying one append per token
    on_watermark: object = None
    watermark_every: int = 8
    # tenancy (serving/tenancy.py): the tenant this request is accounted
    # to (weighted-fair admission, cache quota, cost attribution) and its
    # priority *within* that tenant — fairness arbitrates across tenants,
    # priority orders one tenant's own line
    tenant: str = "anonymous"
    priority: int = 0
    state: RequestState = RequestState.WAITING
    output_tokens: list[int] = field(default_factory=list)
    # tokens a dispatched step (a prefill, a decode step) has sampled for
    # this request and the host has not read yet: the engine dispatches a
    # step ahead of its readback (docs/SERVING.md "The pipelined loop")
    in_flight: int = 0
    cached_tokens: int = 0             # prefix-cache hit at last admission
    cached_tokens_total: int = 0       # summed across (re-)admissions
    arrival_time: float = field(default_factory=time.monotonic)
    admit_time: float | None = None    # first admission into a slot
    deadline: float | None = None      # absolute monotonic() cutoff
    first_token_time: float | None = None
    finish_time: float | None = None
    num_preemptions: int = 0
    finish_reason: str | None = None
    error: BaseException | None = None
    # request-trace context (telemetry.reqtrace): stamped on every span
    # this request produces so the router can merge its hops into one
    # Chrome trace; trace_parent is the submitter's span id (propagated
    # over the replica pipe, opaque here)
    trace_id: str | None = None
    trace_parent: int | None = None

    @property
    def prefill_tokens(self) -> list[int]:
        """What a (re-)prefill must process: the prompt plus anything already
        generated (non-empty only after preemption)."""
        return self.prompt + self.output_tokens

    @property
    def total_len(self) -> int:
        return len(self.prompt) + len(self.output_tokens)

    @property
    def dispatched(self) -> int:
        """Output tokens, those in flight counted: what the next step's
        sampler index, context length and ``max_new_tokens`` go by."""
        return len(self.output_tokens) + self.in_flight

    @property
    def ttft(self) -> float | None:
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.arrival_time

    def past_deadline(self, now: float | None = None) -> bool:
        return (self.deadline is not None
                and (now if now is not None else time.monotonic())
                > self.deadline)

    def emit(self, token: int):
        self.output_tokens.append(int(token))
        if self.first_token_time is None:
            self.first_token_time = time.monotonic()
        if self.on_token is not None:
            self.on_token(self, int(token))
        if self.on_watermark is not None and \
                len(self.output_tokens) % max(1, self.watermark_every) == 0:
            self.on_watermark(self, len(self.output_tokens))


class Scheduler:
    """Slots + queues over a :class:`PagedKVCache`."""

    def __init__(self, cache: PagedKVCache, max_slots: int,
                 max_model_len: int, max_queue: int | None = None,
                 max_preemptions_per_request: int = 16, on_event=None,
                 high_watermark: float | None = None,
                 low_watermark: float | None = None,
                 tenancy=None):
        self.cache = cache
        # weighted-fair admission: ``tenancy`` is a TenantRegistry whose
        # weights drive the DRR queue; without one every request is the
        # anonymous tenant and the queue IS the FIFO deque it replaced
        self.tenancy = tenancy
        # telemetry hook: the owning engine passes a callback(kind, **ctx)
        # so scheduler decisions feed its labeled metrics; standalone
        # schedulers (tests) run without one
        self._on_event = on_event or (lambda kind, **ctx: None)
        self.max_slots = int(max_slots)
        self.max_model_len = int(max_model_len)
        self.max_queue = None if max_queue is None else int(max_queue)
        self.max_preemptions = int(max_preemptions_per_request)
        # watermark-driven backpressure (docs/ROBUSTNESS.md "Degradation
        # ladder"): past high_watermark (fraction of usable device blocks
        # referenced) new admissions queue and `mem_pressure` latches —
        # the engine surfaces it through stats()["slo"]["shed"] so a
        # fleet router routes around and the gateway answers 429. The
        # latch clears below low_watermark (hysteresis: no flapping at
        # the boundary).
        self.high_watermark = (None if high_watermark is None
                               else float(high_watermark))
        if self.high_watermark is not None:
            self.low_watermark = (0.75 * self.high_watermark
                                  if low_watermark is None
                                  else float(low_watermark))
            if not 0.0 < self.high_watermark <= 1.0:
                raise ValueError(
                    f"high_watermark must be in (0, 1], got "
                    f"{self.high_watermark}")
            if not 0.0 <= self.low_watermark < self.high_watermark:
                raise ValueError(
                    f"low_watermark ({self.low_watermark}) must be below "
                    f"high_watermark ({self.high_watermark})")
        else:
            self.low_watermark = None
        self.mem_pressure = False
        self.num_pressure_events = 0
        self.waiting: FairQueue = FairQueue(
            weight_fn=tenancy.weight if tenancy is not None else None)
        self.running: dict[int, Request] = {}       # slot -> request
        self._free_slots = list(range(max_slots))
        self.num_preemptions = 0
        self.num_rejected = 0
        self.num_failed = 0
        self.num_cancelled = 0
        self.closed = False

    # -- intake -----------------------------------------------------------
    def add(self, req: Request):
        if self.closed:
            raise EngineClosed(
                f"request {req.rid} rejected: the engine has been shut down "
                f"(close() was called); create a new engine or add requests "
                f"before closing")
        if self.max_queue is not None and len(self.waiting) >= self.max_queue:
            self.num_rejected += 1
            telemetry.record_event("scheduler.reject", rid=req.rid,
                                   waiting=len(self.waiting),
                                   running=len(self.running))
            self._on_event("reject", rid=req.rid)
            raise QueueFull(
                f"request {req.rid} rejected: admission queue is full "
                f"({len(self.waiting)}/{self.max_queue} waiting, "
                f"{len(self.running)} running) — back off and retry")
        worst = len(req.prompt) + req.sampling.max_new_tokens
        if worst > self.max_model_len:
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + "
                f"max_new_tokens ({req.sampling.max_new_tokens}) exceeds "
                f"max_model_len ({self.max_model_len})")
        if self.cache.blocks_for(worst) > self.cache.allocator.num_usable:
            raise ValueError(
                f"request {req.rid} can never fit: needs "
                f"{self.cache.blocks_for(worst)} blocks, pool has "
                f"{self.cache.allocator.num_usable} usable")
        self.waiting.append(req)

    @property
    def queue_depth(self) -> int:
        return len(self.waiting)

    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # -- decode-time / admission-time pressure ----------------------------
    def _update_pressure(self) -> bool:
        """Refresh the watermark latch from the device pool's referenced
        fraction. Hysteresis: latches at >= high_watermark, clears below
        low_watermark."""
        if self.high_watermark is None:
            return False
        a = self.cache.allocator
        used_frac = a.num_used / max(a.num_usable, 1)
        if not self.mem_pressure and used_frac >= self.high_watermark:
            self.mem_pressure = True
            self.num_pressure_events += 1
            telemetry.record_event(
                "scheduler.kv_pressure", state="high",
                used_frac=round(used_frac, 4),
                waiting=len(self.waiting), running=len(self.running))
            self._on_event("kv_pressure", rid=None)
        elif self.mem_pressure and used_frac < self.low_watermark:
            self.mem_pressure = False
            telemetry.record_event(
                "scheduler.kv_pressure", state="low",
                used_frac=round(used_frac, 4))
            self._on_event("kv_pressure_clear", rid=None)
        return self.mem_pressure

    def _expire_queued(self, req: Request):
        """Fail-fast for a request whose deadline passed while still
        queued: terminal as ``deadline`` *before* any prefill work is
        spent on it (a prefill slot is the scarce resource under
        pressure; a dead request must not burn one)."""
        self.waiting.popleft()
        req.state = RequestState.CANCELLED
        req.finish_time = time.monotonic()
        req.finish_reason = "deadline"
        req.error = DeadlineExceeded(
            f"request {req.rid} missed its deadline while still queued "
            f"(never admitted to a prefill slot)")
        self.num_cancelled += 1
        telemetry.record_event("scheduler.deadline_queued", rid=req.rid,
                               waiting=len(self.waiting))
        self._on_event("deadline_queued", rid=req.rid, req=req)

    # -- admission --------------------------------------------------------
    def admit(self) -> list[tuple[int, Request]]:
        """Move waiting requests into free slots while the pool can hold
        their prefill plus one block of decode headroom. Admission is
        checked against *effective* free blocks (free + evictable cached
        prefixes) — a pool full of unreferenced completed prefixes is not
        a full pool, and any cached prefix the request matches shrinks its
        real footprint further. Above the high watermark admissions stop
        entirely (the queue holds; running requests drain the pressure),
        and a queued request whose deadline already passed terminates as
        ``deadline`` instead of being admitted."""
        admitted = []
        now = time.monotonic()
        self._update_pressure()      # latch/clear even with an empty queue
        while self.waiting and self._free_slots:
            req = self.waiting[0]
            if req.past_deadline(now):
                self._expire_queued(req)
                continue
            if self._update_pressure():
                break
            faults.inject("serving.admit", rid=req.rid)
            need = self.cache.blocks_for(len(req.prefill_tokens)) + 1
            if self.cache.num_effective_free < need:
                break
            self.waiting.popleft()
            slot = self._free_slots.pop(0)
            if not self.cache.allocate(req.rid, len(req.prefill_tokens),
                                       tokens=req.prefill_tokens,
                                       tenant=req.tenant):
                # effective-free check passed but alloc failed (injected
                # exhaustion): put everything back and retry next step
                self._free_slots.insert(0, slot)
                self.waiting.appendleft(req)
                break
            req.cached_tokens = self.cache.seq_cached_tokens.get(req.rid, 0)
            req.cached_tokens_total += req.cached_tokens
            req.state = RequestState.RUNNING
            if req.admit_time is None:
                req.admit_time = time.monotonic()
            self.running[slot] = req
            admitted.append((slot, req))
            telemetry.record_event(
                "scheduler.admit", rid=req.rid, slot=slot,
                blocks=len(self.cache.tables.get(req.rid, ())),
                cached_tokens=req.cached_tokens,
                queue_depth=len(self.waiting))
            self._on_event("admit", rid=req.rid, req=req)
        return admitted

    # -- decode-time capacity ---------------------------------------------
    def ensure_decode_capacity(self, may_preempt: bool = True
                               ) -> list[Request] | None:
        """Before a decode step, every running sequence must own the block
        its next token writes into. On exhaustion, preempt the
        latest-arrived other running request and retry; returns the
        preempted requests (already re-queued). A sequence that cannot get
        a block even with no victims left is FAILED (not a crash): the
        engine stays up for everyone else. Lengths count the tokens in
        flight (``Request.in_flight``); a victim's must be on the host
        before it is re-queued, so with ``may_preempt`` false the first
        shortage returns None with nothing preempted or failed: the engine
        reads what is in flight and calls again (what was extended stays)."""
        preempted = []
        for slot in sorted(self.running):
            req = self.running.get(slot)
            if req is None:  # preempted/failed earlier in this very loop
                continue
            if req.dispatched >= req.sampling.max_new_tokens:
                continue     # its last token is in flight: no further step
            # the incoming token writes its K/V at position n - 1, so the
            # table must cover n tokens AND the block it writes into must
            # be privately owned (copy-on-write if it is shared with
            # another sequence or the prefix index)
            n = len(req.prompt) + req.dispatched
            while True:
                ok = self.cache.extend(req.rid, n)
                if ok:
                    ok = self.cache.ensure_writable(req.rid, n - 1)
                if ok:
                    break
                if not may_preempt:
                    return None
                victim = self._pick_victim(exclude=req)
                if victim is None:
                    self.fail(slot, RuntimeError(
                        f"request {req.rid} cannot obtain a KV block "
                        f"(extend or copy-on-write) with no victim left to "
                        f"preempt — pool exhausted "
                        f"(usable={self.cache.allocator.num_usable})"))
                    break
                preempted.append(victim)
                self._preempt(victim)
        return preempted

    def _pick_victim(self, exclude: Request):
        cands = [r for r in self.running.values() if r is not exclude]
        if not cands:
            return None
        return max(cands, key=lambda r: r.arrival_time)

    def _preempt(self, victim: Request):
        slot = next(s for s, r in self.running.items() if r is victim)
        if victim.num_preemptions >= self.max_preemptions:
            # preemption-storm protection: requeue count is capped; beyond
            # it the request fails with the storm attached instead of
            # bouncing between prefill and eviction forever
            self.fail(slot, PreemptionStorm(
                f"request {victim.rid} preempted {victim.num_preemptions} "
                f"times (cap {self.max_preemptions}); failing instead of "
                f"requeueing — pool too small for the offered load"))
            return
        del self.running[slot]
        self._free_slots.append(slot)
        self._free_slots.sort()
        self.cache.free_seq(victim.rid)
        victim.state = RequestState.WAITING
        victim.num_preemptions += 1
        self.num_preemptions += 1
        self.waiting.appendleft(victim)   # front: keep its progress hot
        telemetry.record_event("scheduler.preempt", rid=victim.rid,
                               slot=slot, nth=victim.num_preemptions)
        self._on_event("preempt", rid=victim.rid)

    # -- completion / removal ---------------------------------------------
    def _release_slot(self, slot: int) -> Request:
        req = self.running.pop(slot)
        self._free_slots.append(slot)
        self._free_slots.sort()
        if req.rid in self.cache.tables:
            self.cache.free_seq(req.rid)
        return req

    def finish(self, slot: int, reason: str = "length"):
        req = self._release_slot(slot)
        req.state = RequestState.FINISHED
        req.finish_time = time.monotonic()
        req.finish_reason = reason
        self._on_event("finish", rid=req.rid)

    def fail(self, slot: int, error: BaseException):
        """Error isolation: tear down ONE slot, attach the error, keep the
        engine alive for every other request."""
        req = self._release_slot(slot)
        req.state = RequestState.FAILED
        req.finish_time = time.monotonic()
        req.finish_reason = "error"
        req.error = error
        self.num_failed += 1
        telemetry.record_event("scheduler.fail", rid=req.rid, slot=slot,
                               error=f"{type(error).__name__}: {error}")
        self._on_event("fail", rid=req.rid)

    def cancel(self, rid: int,
               reason: str = "cancelled",
               error: BaseException | None = None) -> bool:
        """Cancel a waiting or running request by id. Returns False if the
        request is unknown or already terminal."""
        for req in list(self.waiting):
            if req.rid == rid:
                self.waiting.remove(req)
                req.state = RequestState.CANCELLED
                req.finish_time = time.monotonic()
                req.finish_reason = reason
                req.error = error
                self.num_cancelled += 1
                self._on_event("cancel", rid=rid)
                return True
        for slot, req in list(self.running.items()):
            if req.rid == rid:
                self._release_slot(slot)
                req.state = RequestState.CANCELLED
                req.finish_time = time.monotonic()
                req.finish_reason = reason
                req.error = error
                self.num_cancelled += 1
                self._on_event("cancel", rid=rid)
                return True
        return False

    def close(self, cancel_pending: bool = True) -> list[Request]:
        """Shut the intake down. Still-queued requests that never reached a
        prefill slot end ``FAILED`` with :class:`EngineClosed` attached — a
        fleet router keyed on terminal states must see an *error* it can
        re-dispatch on, not a cancel that looks user-initiated; running
        requests are ``CANCELLED`` (reason "shutdown"). Returns every
        request transitioned."""
        self.closed = True
        dropped = []
        if cancel_pending:
            while self.waiting:
                req = self.waiting.popleft()
                req.state = RequestState.FAILED
                req.finish_time = time.monotonic()
                req.finish_reason = "engine_closed"
                req.error = EngineClosed(
                    f"request {req.rid} was still queued (never prefilled) "
                    f"when the engine closed")
                self.num_failed += 1
                telemetry.record_event(
                    "scheduler.fail", rid=req.rid,
                    error="EngineClosed: still queued at close()")
                self._on_event("fail", rid=req.rid)
                dropped.append(req)
            for req in list(self.running.values()):
                if self.cancel(req.rid, reason="shutdown"):
                    dropped.append(req)
        return dropped
