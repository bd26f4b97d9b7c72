"""Scheduler: the continuous-batching front end of the serve stack.

This layer owns *requests*: a bounded wait queue with FIFO-by-wait-start
admission, streaming per-token callbacks, and planner-priced preemption.
It composes the other layers — :class:`~repro.serve.state.SlotTable`
(host mirrors + device state), :mod:`repro.serve.sampling` (per-request
params, computed in-jit), and the :class:`~repro.serve.engine.Executor`
(every jitted dispatch) — behind the public :class:`Server`, plus an
asyncio front end (:class:`Scheduler`) for callers that want
``await submit()`` / ``async for token in stream()``.

Request lifecycle::

            submit/add_request          admit (FIFO by wait start)
    new ───────────────────────▶ queued ─────────────▶ active (decode)
             QueueFullError when            ▲                 │
             cfg.max_queue waiting          │ promote         │ preempt
                                            │ (slot frees)    ▼
                                         spilled ◀──── KV rows parked on the
                                                       planner-priced spill
                                                       tier; re-queued FIFO

    active ──▶ done: stop token (in-jit match) | max_new_tokens |
               cache extent; slot freed, rid evicted, mirrors re-synced

**Planner-priced preemption** (the paper's §IV decision made per slot at
runtime): when the oldest waiter has starved for ``preempt_wait`` ticks
and no slot is free, the scheduler asks the runtime what eviction
*costs* — ``Runtime.preemption_price`` prices the round trip of one
slot's cache rows to the cheapest realizable far tier (host DRAM, or the
peer/remote donor pools when the mesh has the axis) through the datapath
``copy_bound`` model — and what waiting costs — the planner-predicted
decode step time times the fewest remaining tokens of any active
request.  Only when spilling is cheaper than waiting does it evict, and
the victim is the active request with the *most* remaining work
(shortest-remaining-work-first keeps slots churning).  The victim's KV
rows are extracted in one jitted slice, parked off-cache, and scattered
back bit-identically when a slot frees — so greedy tokens are invariant
under any preemption/promotion history, which the tests and the CI soak
assert.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import time
from typing import Callable

import numpy as np
from jax.profiler import TraceAnnotation as span

from repro.core.faults import (
    FaultKind,
    FaultPlan,
    SpillCorruptionError,
    TierLossError,
    checksum_tree,
    corrupt_tree,
    verify_spill,
)
from repro.core.hardware import MemoryTier
from repro.core.placement import PlacementPolicy, Role
from repro.runtime.supervisor import Watchdog, WatchdogConfig
from repro.serve.engine import Executor
from repro.serve.sampling import GREEDY, SamplingParams
from repro.serve.state import SlotTable, SpilledSequence

log = logging.getLogger("repro.serve.scheduler")


class QueueFullError(RuntimeError):
    """Backpressure: the bounded wait queue is at ``cfg.max_queue``.

    The sync surface raises so callers can shed or retry;
    :meth:`Scheduler.submit` absorbs it by awaiting queue space instead.
    """


class ServeHangError(RuntimeError):
    """The serve loop failed to make progress: ``run_until_done``
    exhausted its step budget with live requests still queued, or the
    watchdog escalated past its last rung.  Carries the diagnostics a
    post-mortem needs: queue depth, the live rids, and the last stats
    snapshot."""

    def __init__(
        self,
        message: str,
        *,
        queue_depth: int = 0,
        live_rids=(),
        stats: dict | None = None,
    ):
        self.queue_depth = int(queue_depth)
        self.live_rids = tuple(live_rids)
        self.stats = dict(stats or {})
        super().__init__(
            f"{message} [queue_depth={self.queue_depth} "
            f"live_rids={list(self.live_rids)} stats={self.stats}]"
        )


class SchedulerClosed(RuntimeError):
    """:meth:`Scheduler.close` was called: pending ``submit()`` waiters
    (and streams that can no longer finish) are cancelled with this
    instead of waiting forever."""


@dataclasses.dataclass
class Request:
    """One generation request.

    ``sampling`` defaults to greedy (temperature 0 — bit-identical to
    the pre-sampler engine); ``on_token`` streams each generated token
    as ``on_token(request, token)`` the tick it is decoded (check
    ``request.done`` inside the callback for end-of-stream; a cancelled
    or expired request streams one terminal ``-1`` sentinel with
    ``done`` already set).  The ``*_s`` fields are
    ``time.perf_counter`` stamps the benchmarks turn into queue-wait /
    time-to-first-token / completion latencies.  ``deadline_s`` bounds
    the request's *total* wall time from submission: past it the server
    expires the request at the next tick (slot freed, counted in
    ``stats()["expired"]``).
    """

    rid: int
    prompt: np.ndarray            # (L,) int32
    max_new_tokens: int
    sampling: SamplingParams = GREEDY
    on_token: Callable[["Request", int], None] | None = None
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    preemptions: int = 0
    submitted_s: float | None = None
    first_token_s: float | None = None
    finished_s: float | None = None
    #: total wall-time budget from submission (None = unbounded)
    deadline_s: float | None = None
    cancelled: bool = False

    def cancel(self) -> None:
        """Cooperative cancellation: the server finalizes the request on
        its next tick — slot freed through ``_free_slot``, terminal
        ``-1`` sentinel streamed to ``on_token``, counted in
        ``stats()["cancelled"]``.  Idempotent; a no-op once done."""
        self.cancelled = True


@dataclasses.dataclass
class ServeConfig:
    batch_slots: int = 8
    max_len: int = 512
    #: tokens per chunked-prefill dispatch during admission
    prefill_chunk: int = 32
    #: None -> consult the placement planner (datapath-bound model);
    #: otherwise any ``parse_policy`` spelling: a PlacementPolicy value,
    #: a registered name, ``"kv=host:stream,..."``, or policy JSON.
    policy: PlacementPolicy | str | dict | None = None
    rules: dict | None = None
    #: re-run the planner (and migrate KV/params if the pick changes)
    #: whenever cache occupancy crosses a band boundary — the live form
    #: of the paper's phase-dependent placement decision.
    auto_replan: bool = False
    #: number of occupancy bands for auto_replan (4 -> re-price at 25%
    #: occupancy steps)
    replan_bands: int = 4
    #: bound on *waiting* (not yet admitted) requests; None = unbounded.
    #: add_request raises QueueFullError beyond it — the documented
    #: backpressure path (spilled sequences hold progress and do not
    #: count against it).
    max_queue: int | None = None
    #: enable planner-priced KV preemption (spill a victim's slot rows
    #: to the cheapest realizable far tier when waiters starve)
    preempt: bool = False
    #: ticks the oldest waiter must starve before preemption is
    #: considered — also the thrash guard: a freshly (re)admitted slot
    #: cannot be re-evicted sooner
    preempt_wait: int = 8
    #: assert at Executor build time that every donation the policy
    #: requires actually materialized as input/output aliasing in the
    #: compiled module (repro.analysis.hlo_audit.DonationAliasError
    #: instead of a silent cache-sized copy per dispatch)
    verify_donation: bool = True
    #: injected-fault schedule (core.faults.FaultPlan); None = NO_FAULTS.
    #: Lives on the executor's Runtime so every site consults one plan.
    faults: FaultPlan | None = None
    #: checksum spilled rows at park time and verify at promotion; a
    #: mismatch drops the parked rows and replays the request
    #: (bit-identical continuation).  Always on while faults are active.
    verify_spills: bool = False
    #: step watchdog (stall -> retry -> evacuate -> ServeHangError);
    #: None disables it.  The deadline follows the runtime's
    #: measured-else-analytic decode-step price.
    watchdog: WatchdogConfig | None = dataclasses.field(
        default_factory=WatchdogConfig
    )
    #: pool label for disaggregated clusters ("prefill"/"decode"); tags
    #: the Executor's donation-audit reports so each pool's builds stay
    #: separately attributable.  Empty for colocated serving.
    pool: str = ""


class Server:
    """Single-model continuous-batching server.

    The public serve surface: composes the scheduler's queue/preemption
    policy with the :class:`~repro.serve.engine.Executor` (reachable as
    ``server.engine`` — jits, caches, params, Runtime) and the
    :class:`~repro.serve.state.SlotTable` (``server.table``).
    """

    def __init__(self, bundle, cfg: ServeConfig, params, mesh=None):
        self.bundle = bundle
        self.cfg = cfg
        self.engine = Executor(bundle, cfg, params, mesh)
        self.table = SlotTable(cfg.batch_slots)
        self._requests: dict[int, Request] = {}
        #: FIFO by wait start: ("fresh", rid) never yet admitted,
        #: ("spilled", rid) preempted and re-queued
        self._waitq: list[tuple[str, int]] = []
        self._spilled: dict[int, SpilledSequence] = {}
        self._wait_since: dict[int, int] = {}
        self._tick = 0
        self._state = self.engine.place_state(self.table.device_state())
        self._replan_band: int | None = None
        self._next_rid = 0
        #: rid -> replacement prompt for the next "fresh" admission: a
        #: replayed request (corrupted spill, tier loss mid-flight)
        #: prefills prompt + everything generated so far instead of its
        #: original prompt — bit-identical continuation
        self._replay_prompts: dict[int, np.ndarray] = {}
        #: disaggregation hook (repro.serve.disagg): when set,
        #: _requeue_fresh offers the request back to the cluster —
        #: ``hook(rid, replay_prompt) -> True`` means the cluster took it
        #: (it replays through the prefill pool and re-adopts), so this
        #: server drops its bookkeeping instead of re-queuing locally
        self.requeue_hook: Callable[[int, np.ndarray], bool] | None = None
        self._counters = {
            "preemptions": 0, "promotions": 0, "peak_queue": 0,
            "cancelled": 0, "expired": 0,
            "tier_losses": 0, "spill_corruptions": 0, "requeued_fresh": 0,
            "watchdog_stalls": 0, "watchdog_retries": 0,
            "watchdog_evacuations": 0,
        }
        #: serve-step watchdog: deadlines each decode against the
        #: runtime's measured-else-analytic step price (see
        #: repro.runtime.supervisor.Watchdog); None = disabled
        self.watchdog = (
            None if cfg.watchdog is None
            else Watchdog(
                lambda: self.rt.decode_step_seconds(
                    cfg.batch_slots, cfg.max_len
                ),
                cfg.watchdog,
            )
        )

    # -- introspection -----------------------------------------------------
    @property
    def rt(self):
        """The executor's :class:`repro.api.Runtime` (mesh + policy +
        planner)."""
        return self.engine.rt

    @property
    def policy(self) -> PlacementPolicy:
        """The placement policy currently in force (may change across
        :meth:`replan` migrations)."""
        return self.engine.policy

    @property
    def params(self):
        return self.engine.params

    @property
    def queue_depth(self) -> int:
        """Fresh (never admitted) requests waiting — what ``max_queue``
        bounds."""
        return sum(1 for kind, _ in self._waitq if kind == "fresh")

    @property
    def live_rids(self) -> tuple[int, ...]:
        """rids of all live (queued, active, or spilled) requests."""
        return tuple(self._requests)

    def has_work(self) -> bool:
        """Anything queued, spilled, or decoding?"""
        return bool(self._waitq or self._spilled or self.table.active_slots())

    def occupancy(self) -> float:
        """Live cache utilization — what replan pricing feeds the
        planner."""
        return self.table.occupancy(self.cfg.max_len)

    def stats(self) -> dict:
        """Counters across all layers: executor phase tokens/seconds and
        lifecycle events (``replans``/``migrations``/``evacuations``/
        ``migration_retries``/``decode_replay_prefills``/``spill_s``/
        ``restore_s``) merged with the scheduler's (``preemptions``/
        ``promotions``/``peak_queue``, plus the robustness set:
        ``cancelled``/``expired``/``tier_losses``/``spill_corruptions``/
        ``requeued_fresh``/``watchdog_stalls``/``watchdog_retries``/
        ``watchdog_evacuations``) and the live ``queued``/``spilled``
        depths; ``decode_cache_copies`` is the build-time audit's count of
        cache-sized copies in the compiled decode step (0 when every K/V
        row is written in place)."""
        return {
            **self.engine.counters,
            **self._counters,
            "queued": self.queue_depth,
            "spilled": len(self._spilled),
            "decode_cache_copies":
                self.engine.audit_reports["decode"].cache_sized_copies,
        }

    def throughput(self) -> dict:
        """Prefill/decode split tokens-per-second from the counters."""
        c = self.engine.counters
        return {
            "prefill_tokens": c["prefill_tokens"],
            "decode_tokens": c["decode_tokens"],
            "prefill_tps": (
                c["prefill_tokens"] / c["prefill_s"] if c["prefill_s"]
                else 0.0
            ),
            "decode_tps": (
                c["decode_tokens"] / c["decode_s"] if c["decode_s"]
                else 0.0
            ),
        }

    # -- request intake ----------------------------------------------------
    def add_request(self, req: Request) -> None:
        """Queue a request, validating it against the cache extent.

        Oversubscription is first-class: when every slot is busy the
        request simply waits its turn (and may trigger a preemption once
        it starves past ``preempt_wait``).  The only rejection paths are
        malformed requests and the bounded-queue backpressure:
        ``cfg.max_queue`` caps *waiting* requests, and the cap raises
        :class:`QueueFullError` so a front end can shed load or block —
        never a silent drop.
        """
        if req.rid < 0:
            raise ValueError(f"request rid must be >= 0, got {req.rid}")
        if req.rid in self._requests:
            raise ValueError(
                f"request {req.rid}: rid already queued or being served "
                "(rids must be unique among live requests; a duplicate "
                "would orphan the live request's slot bookkeeping — "
                "finished rids are evicted and may be reused)"
            )
        if req.max_new_tokens < 1:
            raise ValueError(
                f"request {req.rid}: max_new_tokens must be >= 1, got "
                f"{req.max_new_tokens}"
            )
        if len(req.prompt) == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if len(req.prompt) >= self.cfg.max_len:
            log.warning(
                "rejecting request %d: prompt of %d tokens needs "
                "len(prompt)+1 cache positions but max_len=%d",
                req.rid, len(req.prompt), self.cfg.max_len,
            )
            raise ValueError(
                f"request {req.rid}: prompt of {len(req.prompt)} tokens "
                f"does not fit max_len={self.cfg.max_len} "
                "(need len(prompt) < max_len)"
            )
        req.sampling.validate()
        if (
            self.cfg.max_queue is not None
            and self.queue_depth >= self.cfg.max_queue
        ):
            raise QueueFullError(
                f"request {req.rid}: wait queue is full "
                f"({self.cfg.max_queue} waiting); retry after a slot "
                "drains or raise ServeConfig.max_queue"
            )
        req.submitted_s = time.perf_counter()
        self._requests[req.rid] = req
        self._waitq.append(("fresh", req.rid))
        self._wait_since[req.rid] = self._tick
        self._counters["peak_queue"] = max(
            self._counters["peak_queue"], self.queue_depth
        )

    def add_requests(self, reqs) -> None:
        """Batched admission entry point: queue several requests at once
        (they prefill together in the next tick's chunked dispatches)."""
        for req in reqs:
            self.add_request(req)

    def adopt_spilled(self, req: Request, spilled: SpilledSequence) -> None:
        """Admit a request whose KV was prepared *elsewhere* — the
        decode-side entry point of a disaggregated handoff
        (``repro.serve.disagg``).

        ``spilled`` carries the rows a prefill pool filled and the
        handoff moved onto this server's mesh, shaped exactly like a
        preemption spill — so admission rides the existing promotion
        path (:meth:`_promote`: checksum verify, jitted row insert,
        mirror resume) with zero new machinery on the per-token path.
        Queued FIFO like any other waiter; a promotion-time integrity
        failure takes the same replay-as-fresh ladder (routed back to
        the cluster by the ``requeue_hook`` when installed).
        """
        if spilled.rid != req.rid:
            raise ValueError(
                f"ticket rid {spilled.rid} != request rid {req.rid}"
            )
        if req.rid in self._requests:
            raise ValueError(
                f"request {req.rid}: rid already live on this server"
            )
        if req.submitted_s is None:
            req.submitted_s = time.perf_counter()
        self._requests[req.rid] = req
        self._spilled[req.rid] = spilled
        self._waitq.append(("spilled", req.rid))
        self._wait_since[req.rid] = self._tick
        self._counters["peak_queue"] = max(
            self._counters["peak_queue"], self.queue_depth
        )

    def submit(
        self,
        prompt,
        *,
        max_new_tokens: int,
        sampling: SamplingParams = GREEDY,
        rid: int | None = None,
        on_token: Callable[[Request, int], None] | None = None,
    ) -> Request:
        """Convenience intake: build + queue a request, auto-assigning a
        free rid, and return it (tokens stream into ``out_tokens`` /
        ``on_token``)."""
        if rid is None:
            while self._next_rid in self._requests:
                self._next_rid += 1
            rid = self._next_rid
            self._next_rid += 1
        req = Request(
            rid=rid,
            prompt=np.asarray(prompt, np.int32),
            max_new_tokens=max_new_tokens,
            sampling=sampling,
            on_token=on_token,
        )
        self.add_request(req)
        return req

    # -- admission / preemption -------------------------------------------
    def _sync_state(self) -> None:
        """Re-upload the small state arrays after a slot lifecycle event
        (admission / free / spill / promote).  Steady-state decode never
        calls this: the state lives on device and the host mirror
        advances from the *returned* token vector."""
        with span("serve.scheduler.sync"):
            self._state = self.engine.place_state(self.table.device_state())

    def _free_slot(self, i: int) -> int | None:
        """The one place an *occupied* slot returns to the pool: clears
        the table row and evicts the rid's request bookkeeping together
        (requests map, wait-start stamp).  Returns the evicted rid."""
        rid = self.table.free(i)
        if rid is not None:
            self._requests.pop(rid, None)
            self._wait_since.pop(rid, None)
            self._replay_prompts.pop(rid, None)
        return rid

    def _requeue_fresh(self, rid: int) -> None:
        """Re-queue a live request as a ``"fresh"`` waiter whose next
        admission replays prompt + everything generated so far.

        The recovery primitive behind corrupted spills and lost spill
        tiers: chunked prefill ≡ decode replay and sampling draws are
        (seed, position)-deterministic, so the replayed continuation is
        bit-identical to never having been interrupted.  Inserted at
        the queue head — the request already waited its turn once.

        With a disaggregation ``requeue_hook`` installed, the cluster
        gets first refusal: a hook returning True takes the request back
        (replay routes through the *prefill* pool and re-enters via
        :meth:`adopt_spilled`), and this server forgets it entirely."""
        req = self._requests[rid]
        replay = np.asarray(req.prompt, np.int32)
        if req.out_tokens:
            replay = np.concatenate(
                [replay, np.asarray(req.out_tokens, np.int32)]
            )
        self._waitq = [(k, r) for k, r in self._waitq if r != rid]
        self._counters["requeued_fresh"] += 1
        if self.requeue_hook is not None and self.requeue_hook(rid, replay):
            self._requests.pop(rid, None)
            self._wait_since.pop(rid, None)
            self._replay_prompts.pop(rid, None)
            self._spilled.pop(rid, None)
            return
        if req.out_tokens:
            self._replay_prompts[rid] = replay
        self._waitq.insert(0, ("fresh", rid))
        self._wait_since[rid] = self._tick

    def _reap_cancelled_expired(self) -> None:
        """Finalize cancelled and deadline-expired requests (start of
        every tick): slot freed via :meth:`_free_slot`, queue/spill
        entries dropped, terminal ``-1`` sentinel streamed, counted in
        ``stats()["cancelled"]`` / ``["expired"]``."""
        now = time.perf_counter()
        freed = False
        for req in list(self._requests.values()):
            if req.done:
                continue
            expired = (
                req.deadline_s is not None
                and req.submitted_s is not None
                and now - req.submitted_s > req.deadline_s
            )
            if not (req.cancelled or expired):
                continue
            why = "cancelled" if req.cancelled else "expired"
            i = self.table.slot_of(req.rid)
            if i is not None:
                self._free_slot(i)
                freed = True
            else:
                self._waitq = [
                    (k, r) for k, r in self._waitq if r != req.rid
                ]
                self._spilled.pop(req.rid, None)
                self._requests.pop(req.rid, None)
                self._wait_since.pop(req.rid, None)
                self._replay_prompts.pop(req.rid, None)
            req.done = True
            req.finished_s = time.perf_counter()
            self._counters[why] += 1
            log.info(
                "request %d %s after %d generated token(s)",
                req.rid, why, len(req.out_tokens),
            )
            if req.on_token is not None:
                req.on_token(req, -1)
        if freed:
            self._sync_state()

    def _admit(self) -> None:
        """Fill free slots from the wait queue, FIFO by wait start.

        Fresh requests are claimed and prefilled *batched* (one chunked
        dispatch set for all of them); spilled sequences are promoted —
        their parked rows verified (when spill verification is on) and
        scattered back, no prefill (the KV is intact).  A promotion
        whose rows fail their integrity check does not consume the
        slot: the rows are dropped and the request replays as a fresh
        waiter.
        """
        free = self.table.free_slots()
        fresh: list[tuple[int, Request, np.ndarray]] = []
        changed = False
        while free and self._waitq:
            kind, rid = self._waitq.pop(0)
            i = free.pop(0)
            changed = True
            if kind == "fresh":
                req = self._requests[rid]
                self.table.claim(i, rid, req.sampling, self._tick)
                fresh.append(
                    (i, req, self._replay_prompts.pop(rid, req.prompt))
                )
            else:
                spilled = self._spilled.pop(rid)
                try:
                    self._promote(i, spilled)
                except SpillCorruptionError as e:
                    log.warning("%s", e)
                    self._counters["spill_corruptions"] += 1
                    free.insert(0, i)       # verify-first: slot untouched
                    self._requeue_fresh(rid)
        if fresh:
            self.engine.prefill(
                [(i, prompt) for i, _, prompt in fresh], self.table
            )
            for i, req, prompt in fresh:
                self.table.last_tokens[i, 0] = prompt[-1]
                self.table.active[i] = True
        if changed:
            self._sync_state()

    def _admitted_this_tick(self) -> list[int]:
        """rids claimed or promoted into a slot this tick."""
        t = self.table
        return [rid for rid, tick in zip(t.slots, t.claimed_tick)
                if rid is not None and tick == self._tick]

    def _promote(self, i: int, spilled: SpilledSequence) -> None:
        """Scatter a spilled sequence's parked rows back into slot ``i``
        and resume its mirrors — bit-identical to never having moved.
        Verifies the rows against their park-time checksum first
        (:class:`~repro.core.faults.SpillCorruptionError` on mismatch,
        before anything is touched)."""
        verify_spill(spilled.rows, spilled.checksum, spilled.rid)
        self.engine.insert_slot(i, spilled.rows)
        self.table.resume(i, spilled, self._tick)
        self._wait_since.pop(spilled.rid, None)
        self._counters["promotions"] += 1
        log.info(
            "promoted rid %d into slot %d after %d ticks spilled",
            spilled.rid, i, self._tick - spilled.since_tick,
        )

    def _remaining(self, i: int) -> int:
        req = self._requests[self.table.slots[i]]
        return max(req.max_new_tokens - len(req.out_tokens), 0)

    def _maybe_preempt(self) -> None:
        """Evict one victim iff the oldest waiter has starved past
        ``preempt_wait`` ticks AND the planner prices the spill round
        trip below the predicted natural wait for a slot."""
        if not self.cfg.preempt or not self._waitq:
            return
        if self.table.free_slots():
            return
        _, head = self._waitq[0]
        if self._tick - self._wait_since.get(head, self._tick) \
                < self.cfg.preempt_wait:
            return
        # thrash guard: never evict a slot that was (re)occupied within
        # the same starvation window
        candidates = [
            i for i in self.table.active_slots()
            if self._tick - int(self.table.claimed_tick[i])
            >= self.cfg.preempt_wait
        ]
        if not candidates:
            return
        spill_to, price_s = self.rt.preemption_price(
            self.engine.slot_bytes()
        )
        # wait side: the runtime's decode-step price — the measured EWMA
        # once the Executor's warm steps have fed it (the observed cost
        # of waiting), the planner's analytic prediction before that.
        step_s = self.rt.decode_step_seconds(
            self.cfg.batch_slots, self.cfg.max_len
        )
        natural_wait_s = step_s * min(
            self._remaining(i) for i in self.table.active_slots()
        )
        if price_s >= natural_wait_s:
            log.debug(
                "preemption not worth it: spill round trip %.3gs >= "
                "natural slot free in %.3gs", price_s, natural_wait_s,
            )
            return
        # victim: most remaining work (shortest-remaining-first keeps
        # slots churning); deterministic tie-break on rid
        victim = max(
            candidates, key=lambda i: (self._remaining(i),
                                       self.table.slots[i])
        )
        self._spill(victim, spill_to)

    def _spill(self, i: int, spill_to) -> None:
        rid = self.table.slots[i]
        t0 = time.perf_counter()
        rows = self.engine.extract_slot(i, spill_to)
        spilled = self.table.suspend(i, self._tick)
        spilled.rows = rows
        spilled.tier = spill_to.tier
        faults = self.rt.faults
        if self.cfg.verify_spills or faults:
            # park-time checksum, verified at promotion; off the
            # per-token path (spill lifecycle events only) and off
            # entirely unless verification or fault injection is on
            spilled.checksum = checksum_tree(rows)
        if faults:
            ev = faults.check("spill")
            if ev is not None and ev.kind is FaultKind.SPILL_CORRUPT:
                spilled.rows = corrupt_tree(spilled.rows)
        spilled.spill_s = time.perf_counter() - t0
        self._spilled[rid] = spilled
        self._waitq.append(("spilled", rid))
        self._wait_since[rid] = self._tick
        self._requests[rid].preemptions += 1
        self._counters["preemptions"] += 1
        self._sync_state()
        log.info(
            "preempted rid %d (slot %d, %d tokens resident) -> %s",
            rid, i, spilled.length, spill_to.to_str(),
        )

    # -- live re-placement -------------------------------------------------
    def replan(self, policy=None, *, force: bool = False) -> bool:
        """Re-place the live KV cache (and params) mid-serve — see
        :meth:`repro.serve.engine.Executor.replan`.  Priced against the
        live :meth:`occupancy`."""
        return self.engine.replan(
            policy, force=force, occupancy=self.occupancy(),
            inflight=self._state["tokens"],
        )

    def _maybe_auto_replan(self) -> None:
        """Fire :meth:`replan` when occupancy crosses a band boundary —
        only for planner-owned policies (a forced ``cfg.policy`` pins
        placement; call :meth:`replan` explicitly to move it)."""
        if not self.cfg.auto_replan or self.cfg.policy is not None:
            return
        band = int(self.occupancy() * max(self.cfg.replan_bands, 1))
        if band != self._replan_band:
            self._replan_band = band
            self.replan()

    # -- tier-loss recovery ------------------------------------------------
    def _lose_tier(self, tier) -> None:
        """Degrade off ``tier`` and keep serving: evacuate the live
        KV/params roles (planner re-pick excluding the lost tier, jits
        rebuilt), replay any spilled sequence whose parked rows lived
        there, and re-sync the device state."""
        # un-claim any slot caught mid-admission (claimed, prefill never
        # completed): free the row and put its request back at the queue
        # head — _requeue_fresh rebuilds the replay prompt if it had
        # already generated tokens
        for i in range(self.table.batch_slots):
            rid = self.table.slots[i]
            if rid is not None and not bool(self.table.active[i]):
                self.table.free(i)
                self._requeue_fresh(rid)
        self.engine.evacuate(
            tier, occupancy=self.occupancy(),
            inflight=self._state["tokens"],
        )
        # parked rows on a lost tier: drop them and replay the request
        # from its prompt + generated tokens (bit-identical continuation)
        for rid, sp in list(self._spilled.items()):
            if sp.tier is not None and sp.tier in self.rt.lost_tiers:
                self._spilled.pop(rid)
                self._requeue_fresh(rid)
        self._sync_state()

    def _recover_tier_loss(self, e: TierLossError) -> None:
        self._counters["tier_losses"] += 1
        log.warning(
            "tier loss at tick %d: %s — evacuating and continuing "
            "degraded", self._tick, e,
        )
        self._lose_tier(e.tier)

    def _escalate(self, action: str) -> None:
        """Act on a watchdog verdict: ``stall`` warns and counts;
        ``retry`` rebuilds the jitted dispatch path; ``evacuate``
        degrades off the presumed-slow far tier (the GH200 failure
        mode: an access-path fault showing up as a slowdown, not an
        error); ``hang`` raises :class:`ServeHangError`."""
        if action == "stall":
            self._counters["watchdog_stalls"] += 1
            return
        if action == "retry":
            self._counters["watchdog_retries"] += 1
            log.warning(
                "watchdog retry: rebuilding the jitted dispatch path"
            )
            self.engine._build_steps()
            return
        if action == "evacuate":
            far = [
                self.policy.placement(r).tier
                for r in (Role.KV_CACHE, Role.PARAMS)
                if self.policy.placement(r).tier is not MemoryTier.HBM
                and self.policy.placement(r).tier not in self.rt.lost_tiers
            ]
            if not far:
                # nothing left to degrade; the ladder continues to hang
                self._counters["watchdog_stalls"] += 1
                return
            self._counters["watchdog_evacuations"] += 1
            log.warning(
                "watchdog evacuate: abandoning presumed-degraded tier %s",
                far[0].value,
            )
            self._lose_tier(far[0])
            return
        if action == "hang":
            raise ServeHangError(
                f"watchdog: {self.watchdog.breaches} consecutive steps "
                f"over the {self.watchdog.deadline_s():.3g}s deadline "
                f"(last step {self.watchdog.last_step_s:.3g}s)",
                queue_depth=self.queue_depth,
                live_rids=self.live_rids,
                stats=self.stats(),
            )

    # -- one decode tick ---------------------------------------------------
    def step(self) -> int:
        """Preempt/admit/promote, then decode one token for every active
        slot.  Returns the number of active slots.

        The decode step consumes and returns the on-device state; the
        only per-step host↔device traffic is the packed (2, B)
        token/stopped vector coming back (one async transfer, then
        blocked on).  Tokens stream to ``on_token`` callbacks the tick
        they are decoded.

        Self-healing: a :class:`~repro.core.faults.TierLossError` from
        any dispatch is caught here — the server evacuates the lost
        tier, rebuilds its jits, replays what was parked there, and
        continues degraded (greedy tokens bit-identical for requests
        untouched by the fault).  The watchdog deadlines the decode
        against the runtime's step price and escalates consecutive
        breaches stall → retry → evacuate → :class:`ServeHangError`.
        """
        self._tick += 1
        # profiler spans (no-ops unless a trace is recording) name each
        # phase of the tick; the executor's own nest inside them
        with span("serve.scheduler.step") as sp:
            if sp.is_enabled():
                sp.set_metadata(tick=self._tick)
            with span("serve.scheduler.reap"):
                self._reap_cancelled_expired()
            try:
                return self._step_inner()
            except TierLossError as e:
                self._recover_tier_loss(e)
                return 0

    def _step_inner(self) -> int:
        with span("serve.scheduler.preempt"):
            self._maybe_preempt()
        with span("serve.scheduler.admit") as sp:
            self._admit()
            if sp.is_enabled():
                sp.set_metadata(rids=str(self._admitted_this_tick()))
        with span("serve.scheduler.replan"):
            self._maybe_auto_replan()
        active = self.table.active_slots()
        if not active:
            return 0
        now = time.perf_counter
        t0 = now()
        tokens, stopped, self._state = self.engine.decode(self._state)
        decode_dt = now() - t0
        self.engine.counters["decode_tokens"] += len(active)
        freed = False
        with span("serve.scheduler.deliver") as sp:
            if sp.is_enabled():
                sp.set_metadata(live=len(active))
            for i in active:
                req = self._requests[self.table.slots[i]]
                # host numpy already (the engine's one sanctioned fetch)
                tok = int(tokens[i])  # repro: lint-disable=blocking-transfer-in-hot-path
                req.out_tokens.append(tok)
                if req.first_token_s is None:
                    req.first_token_s = now()
                self.table.advance(i, tok)
                if (
                    bool(stopped[i])
                    or len(req.out_tokens) >= req.max_new_tokens
                    or self.table.lengths[i] >= self.cfg.max_len - 1
                ):
                    req.done = True
                    req.finished_s = now()
                    self._free_slot(i)
                    freed = True
                if req.on_token is not None:
                    req.on_token(req, tok)
        if freed:
            self._sync_state()
            with span("serve.scheduler.replan"):
                self._maybe_auto_replan()
        # feed the watchdog the decode wall time (admission/compile
        # excluded — the first step after a jit build is compile-
        # dominated and skipped, same warm-up rule as the step EWMA)
        if self.watchdog is not None and self.engine._steps_since_build > 1:
            self._escalate(self.watchdog.observe(decode_dt))
        return len(active)

    def run_until_done(self, max_steps: int = 10_000) -> None:
        """Drive :meth:`step` until nothing is live.  Exhausting
        ``max_steps`` with work still queued raises
        :class:`ServeHangError` with full queue/slot diagnostics —
        never a silent return with requests stranded."""
        for _ in range(max_steps):
            if not self.has_work():
                return
            self.step()
        if not self.has_work():
            return
        raise ServeHangError(
            f"serve loop did not drain within max_steps={max_steps}",
            queue_depth=self.queue_depth,
            live_rids=self.live_rids,
            stats=self.stats(),
        )


class Scheduler:
    """Asyncio front end over a :class:`Server`.

    ``await submit()`` absorbs :class:`QueueFullError` by waiting for
    queue space (backpressure as flow control instead of an exception);
    :meth:`stream` yields tokens as the driver loop decodes them; and
    :meth:`run` drives the server until it is closed *and* drained —
    decode steps run in a worker thread (``asyncio.to_thread``) so the
    event loop keeps serving submissions and streams between ticks::

        server = Server(bundle, ServeConfig(...), params)
        sched = Scheduler(server)
        async def client():
            req = await sched.submit(prompt, max_new_tokens=32)
            async for tok in sched.stream(req):
                ...
            sched.close()
        await asyncio.gather(sched.run(), client())
    """

    def __init__(self, server: Server, *, step_timeout_s: float | None = 60.0):
        self.server = server
        #: off-thread bound on one server.step(); a step that outlives it
        #: surfaces as ServeHangError instead of wedging the event loop's
        #: driver task forever.  None = unbounded.
        self.step_timeout_s = step_timeout_s
        self._tick_ev = asyncio.Event()
        self._closed = False

    def _notify(self) -> None:
        ev, self._tick_ev = self._tick_ev, asyncio.Event()
        ev.set()

    async def _wait_tick(self) -> None:
        ev = self._tick_ev
        await ev.wait()

    async def submit(self, prompt, **kw) -> Request:
        """Queue a request, awaiting queue space under backpressure.
        Raises :class:`SchedulerClosed` (immediately, or on wake while
        waiting for space) once :meth:`close` has been called."""
        while True:
            if self._closed:
                raise SchedulerClosed(
                    "scheduler closed; submission cancelled"
                )
            try:
                return self.server.submit(prompt, **kw)
            except QueueFullError:
                await self._wait_tick()

    async def stream(self, req: Request):
        """Async-yield ``req``'s tokens as they are decoded.  A stream
        that can no longer finish — the scheduler closed and the server
        drained without completing ``req`` — raises
        :class:`SchedulerClosed` instead of waiting forever."""
        sent = 0
        while True:
            while sent < len(req.out_tokens):
                yield req.out_tokens[sent]
                sent += 1
            if req.done:
                return
            if self._closed and not self.server.has_work():
                raise SchedulerClosed(
                    f"scheduler closed with request {req.rid} unfinished"
                )
            await self._wait_tick()

    async def run(self) -> None:
        """Drive the server until :meth:`close` is called and every live
        request has drained.  Each off-thread step is bounded by
        ``step_timeout_s``: a wedged dispatch raises
        :class:`ServeHangError` with the server's diagnostics instead of
        blocking the driver task indefinitely."""
        try:
            while not (self._closed and not self.server.has_work()):
                if self.server.has_work():
                    step = asyncio.to_thread(self.server.step)
                    if self.step_timeout_s is None:
                        await step
                    else:
                        try:
                            await asyncio.wait_for(
                                step, self.step_timeout_s
                            )
                        except asyncio.TimeoutError:
                            raise ServeHangError(
                                "serve step exceeded the scheduler's "
                                f"{self.step_timeout_s:.3g}s off-thread "
                                "bound",
                                queue_depth=self.server.queue_depth,
                                live_rids=self.server.live_rids,
                                stats=self.server.stats(),
                            ) from None
                else:
                    await asyncio.sleep(0.001)
                self._notify()
        finally:
            self._notify()

    def close(self) -> None:
        """Let :meth:`run` return once the last live request drains, and
        wake every ``submit()``/``stream()`` waiter so those that can no
        longer complete fail fast with :class:`SchedulerClosed`."""
        self._closed = True
        self._notify()
