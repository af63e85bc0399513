"""Continuous-batching serving engine over the block-paged KV cache.

The fixed-capacity :func:`repro.launch.serve.serve_batch` allocates
``prompt_len + gen`` cache rows per sequence and runs one batch to
completion — ragged real traffic wastes cache memory on short requests and
stalls everyone behind the longest prompt.  This engine serves a *stream*:

  * **Page pool** — every layer's KV lives in a global pool of fixed-size
    pages (``models.paged_cache_init``); a request holds only the pages its
    tokens actually fill, via a per-slot page table.  Page 0 is a dummy:
    unmapped table entries point at it, so dead slots/rows write there and
    never corrupt live state.
  * **Scheduler** — FIFO admission while free pages last; decode pages are
    allocated on demand, and when the pool runs dry the *youngest* admitted
    request is evicted (pages freed, request requeued at the front for
    recompute) so the oldest always completes — no livelock.
  * **Chunked prefill** — prompts prefill ``chunk`` tokens per tick
    (``steps.build_prefill_chunk_plan``), interleaved with decode steps, so
    a long prompt never stalls the decode batch.
  * **Fixed-shape steps** — every tick reuses two jitted step functions
    (chunk prefill + paged decode burst) with constant shapes: slot
    activity is encoded in the *data* (dead rows: positions -1, page-table
    rows 0), never in the shapes, so the engine never recompiles no matter
    the arrival pattern.  Pools are donated through every step.

Decode semantics match ``serve_batch`` token for token: token 1 is sampled
from the prefill logits at the prompt's last live row, decode step k runs
at position ``prompt_len + k - 1``.  The parity tests pin the engine to the
PR 2 ``loop='scan'`` path bitwise under greedy sampling.

**Failure semantics** (PR 7): every request ends in exactly one terminal
status — ``completed`` / ``timeout`` / ``rejected`` / ``failed`` — and
``Engine.run`` *returns* its stats dict under every fault the hardening
layer covers instead of raising away completed work:

  * **Deadlines.**  ``Request.deadline_s`` (relative to arrival) cancels a
    late request wherever it is — queued or mid-decode — reclaiming its
    pages and recording ``status='timeout', reason='deadline'`` with the
    tokens it did produce.  The global ``timeout_s`` is a *drain guard*:
    on expiry the engine stops admitting, cancels in-flight work with
    partial results, marks unserved requests ``timeout``, and returns.
  * **Retry + requeue.**  A step-compute failure requeues its participants
    for recompute with a per-request retry budget (``max_retries``);
    exhausted budgets end in ``failed``.  Injected failures
    (:class:`repro.robustness.InjectedFault`, raised *before* the launch)
    are request-scoped — bystander slots keep their KV; an organic
    mid-launch failure cannot trust the donated pools, so the pool is
    rebuilt and every active sequence recomputes.
  * **Overload shedding.**  ``admission_budget`` bounds the admission
    queue; arrivals beyond it are rejected immediately
    (``status='rejected', reason='overload'``) instead of growing an
    unbounded backlog.
  * **Non-finite quarantine.**  The paged steps sample through
    ``sample_token_guarded``: a slot whose logits go NaN/Inf emits the
    ``NONFINITE_TOKEN`` marker, and the engine quarantines *that slot
    only* (``failed/non_finite``, pages scrubbed then reclaimed) while the
    rest of the batch keeps decoding.
  * **Graceful drain.**  A ``PreemptionGuard`` (or the ``engine.preempt``
    fault point) flips the engine into drain: waiting requests are
    rejected with ``reason='preempted'``, in-flight requests run to
    completion, and the stats report ``preempted=True``.

**Elastic execution** (PR 10): the engine also survives *infrastructure*
faults, injected through the mesh-aware ``dist.*`` points:

  * **Device loss** (``dist.device_loss``) triggers an elastic mesh
    rebuild: the mesh shrinks (data axis halves first), the step plans and
    jits are rebuilt on the survivors, params reshard onto the new layout,
    the page pool is rebuilt, and every in-flight request is requeued for
    recompute without being charged a retry — bounded by
    ``max_mesh_rebuilds``.
  * **Collective timeouts** (``dist.collective_timeout``) surface as
    injected step failures riding the retry + requeue path, counted
    separately in ``stats['collective_timeouts']``.
  * **Straggler watchdog**: per-shard ``dist.straggler`` injection streams
    (one RNG per shard index) pair with an EMA z-score over tick wall time;
    flagged ticks land in ``stats['straggler_flags']`` with the slow shard
    indices.

Every recovery action is counted in ``Engine.stats`` (``evictions``,
``retries``, ``step_failures``, ``quarantined``, ``shed``,
``deadline_cancels``, ``mesh_rebuilds``, ``lost_devices``,
``resharded_restores``, ``collective_timeouts``) and
:meth:`Engine.audit_pages` checks the page-pool invariant
(``free + held == total_pages - 1``, no page in two places) after each
recovery when faults are active and always at exit.

**Spans**: ``run()`` times its phases as nested spans on the
profiler's clock.  Each span enters a ``jax.profiler.TraceAnnotation`` (a
no-op unless a profiler is running) and adds ``count``, ``ms`` and
``self_ms`` (its time less its child spans') under ``stats['spans']``:
``engine.start``; one ``engine.tick`` per loop iteration (a
``StepTraceAnnotation`` numbered by the tick) holding ``engine.intake``,
``engine.admit``, ``engine.claim``, ``engine.pack``, ``engine.step`` (kept
as ``engine.step.<plan>`` for ``chunk | decode | burst``; children
``engine.dispatch``, the jitted call, and ``engine.fetch``, the blocking
read of its tokens) and ``engine.commit``; then ``engine.finish``.
``prefill_ms`` and ``decode_ms`` are the ``engine.step`` sums of their
plans.  ``stats['compiles']`` counts the executables built during the run
(compiled or loaded from the persistent cache, eager ops included).
``stats['decode_pages']`` counts, over the decode steps of every launch,
the pages paged attention reads for the live rows (``live``: ⌈(pos+1)/ps⌉
each, within the window) against their whole page windows (``window``:
rows × ``max_pages``); each decode ``engine.step`` is tagged with its
``pages``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.fault_tolerance import StragglerMonitor
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (
    NONFINITE_TOKEN,
    build_paged_generate_plan,
    build_prefill_chunk_plan,
)
from repro.models import model_init, paged_cache_init, split_tree
from repro.robustness import NO_FAULTS, InjectedFault

__all__ = ["Request", "Engine", "TERMINAL_STATUSES"]

TERMINAL_STATUSES = ("completed", "timeout", "rejected", "failed")
# jax.monitoring event around every executable build of a jit call
# (pxla._cached_compilation: a compile or a persistent-cache load)
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Request:
    """One generation request: ``tokens`` is the prompt (1-D int array),
    ``max_new`` the generation budget, ``arrival`` the trace-relative
    arrival time in seconds (0 = available immediately), ``deadline_s`` an
    optional per-request latency budget relative to arrival (None = no
    deadline) — expiry cancels the request wherever it is and records a
    ``timeout`` status with whatever tokens it produced."""
    rid: int
    tokens: np.ndarray
    max_new: int
    arrival: float = 0.0
    deadline_s: float | None = None


_FREE, _PREFILL, _DECODE = "free", "prefill", "decode"


@dataclasses.dataclass
class _Slot:
    state: str = _FREE
    req: Request | None = None
    pages: list = dataclasses.field(default_factory=list)
    chunk_done: int = 0       # prompt tokens already prefilled
    tok: int = 0              # last generated token (next decode input)
    pos: int = 0              # next decode write position
    out: list = dataclasses.field(default_factory=list)
    admit_seq: int = -1       # admission order (eviction picks the max)
    admit_t: float = 0.0
    first_tok_t: float | None = None


class Engine:
    """Continuous-batching engine; see the module docstring.

    Geometry: ``slots`` concurrent sequences, a pool of ``total_pages``
    pages of ``page_size`` tokens (page 0 reserved), per-slot page tables
    of ``max_pages`` entries (the per-request capacity ceiling), prompts
    prefilled ``chunk`` tokens at a time (``chunk % page_size == 0``).
    ``burst`` decode steps run as one on-device scan when no prefill or
    arrival is waiting (1 while interleaving, so prompts never stall).

    Robustness knobs: ``faults`` (a :class:`repro.robustness.FaultPlan`;
    default :data:`NO_FAULTS` — zero cost), ``admission_budget`` (max
    queued requests before shedding; None = unbounded),``max_retries``
    (per-request step-failure budget), ``preemption_guard`` (a
    :class:`repro.distributed.fault_tolerance.PreemptionGuard` polled each
    tick for graceful drain).
    """

    def __init__(self, cfg, *, slots: int, total_pages: int, page_size: int,
                 max_pages: int, chunk: int, burst: int = 8, mesh=None,
                 kernel_backend: str | None = None,
                 temperature: float = 0.0, seed: int = 0, params=None,
                 faults=None, admission_budget: int | None = None,
                 max_retries: int = 2, preemption_guard=None,
                 max_mesh_rebuilds: int = 4):
        if cfg.input_kind != "tokens":
            raise ValueError("the paged engine serves token models")
        if chunk % page_size:
            raise ValueError(f"chunk {chunk} % page_size {page_size}")
        if total_pages < 2:
            raise ValueError("need at least one real page beyond the dummy")
        self.cfg = cfg
        self.slots = slots
        self.total_pages = total_pages
        self.page_size = page_size
        self.max_pages = max_pages
        self.chunk = chunk
        self.burst = max(int(burst), 1)
        self.temperature = temperature
        self.mesh = mesh or make_host_mesh()
        self.faults = faults or NO_FAULTS
        self.admission_budget = admission_budget
        self.max_retries = max_retries
        self.max_mesh_rebuilds = max_mesh_rebuilds
        self.audit_every = False   # force post-recovery audits sans faults
        self._guard = preemption_guard

        self._step_kw = dict(slots=slots, total_pages=total_pages,
                             page_size=page_size, max_pages=max_pages,
                             temperature=temperature,
                             kernel_backend=kernel_backend)
        self._build_plans()

        if params is None:
            params, _ = split_tree(model_init(jax.random.PRNGKey(seed), cfg))
        pools, _ = split_tree(
            paged_cache_init(cfg, total_pages, page_size))
        # committed to the plan layout up front: the steps' outputs carry
        # that layout in their types, so inputs that already do compile
        # each step exactly once
        self.params = jax.device_put(params, self.chunk_plan.in_shardings[0])
        self.pools = jax.device_put(pools, self.chunk_plan.in_shardings[2])
        self._key = jax.random.PRNGKey(seed + 1)

        self._slots = [_Slot() for _ in range(slots)]
        self._free_pages = list(range(1, total_pages))  # page 0 = dummy
        self._admit_seq = 0
        self._warm = False
        self._poisoned: set = set()     # pages holding injected NaNs
        self._records: list = []
        self._recorded: set = set()
        self._retries: dict = {}
        self._drain_reason: str | None = None
        self.stats: dict = {}
        self._span_stack: list = []   # child ms of each open span

    def _build_plans(self):
        """(Re)build the three fixed-shape step plans and their jits on
        ``self.mesh`` — at construction and again after an elastic mesh
        rebuild (device loss shrinks the mesh; the plans' shardings and
        compiled steps must follow it)."""
        self.chunk_plan = build_prefill_chunk_plan(
            self.cfg, self.mesh, chunk=self.chunk, **self._step_kw)
        self.decode_plan = build_paged_generate_plan(
            self.cfg, self.mesh, gen=1, **self._step_kw)
        self.burst_plan = (build_paged_generate_plan(
            self.cfg, self.mesh, gen=self.burst, **self._step_kw)
            if self.burst > 1 else self.decode_plan)
        # each step returns the pools in the layout it takes them in, so the
        # pools one step hands the next never select another executable
        jit = lambda plan: jax.jit(  # noqa: E731
            plan.step_fn, out_shardings=plan.out_shardings,
            donate_argnums=(2,))
        self._chunk_step = jit(self.chunk_plan)
        self._decode_step = jit(self.decode_plan)
        self._burst_step = (jit(self.burst_plan) if self.burst > 1
                            else self._decode_step)
        self._warm = False

    def warmup(self):
        """Compile every step function before serving, so a compile error
        raises here and no compile lands inside a timed run.  Params and
        pools are committed to the plan layout and every step returns the
        pools in it, so one call per step is the steady state
        (:meth:`compile_counts`).  All-dead inputs (positions -1, page tables 0)
        only ever write the dummy page, so the pools stay semantically
        empty."""
        if self._warm:
            return
        z_tok = jnp.zeros((self.slots, self.chunk), jnp.int32)
        z_qpos = jnp.full((self.slots, self.chunk), -1, jnp.int32)
        z_pos = jnp.zeros((self.slots,), jnp.int32)
        z_pt = jnp.zeros((self.slots, self.max_pages), jnp.int32)
        z_t = jnp.zeros((self.slots,), jnp.int32)
        tok1, self.pools = self._chunk_step(
            self.params, z_tok, self.pools, z_pt, z_qpos, z_pos,
            self._split_key())
        toks, self.pools = self._decode_step(
            self.params, z_t, self.pools, z_pt, z_pos, self._split_key())
        if self._burst_step is not self._decode_step:
            toks, self.pools = self._burst_step(
                self.params, z_t, self.pools, z_pt, z_pos, self._split_key())
        jax.block_until_ready((tok1, toks))
        self._warm = True

    def compile_counts(self) -> dict:
        """Executables each step function holds.  Each is 1 after
        ``warmup()``; a count that grows during ``run()`` means a compile
        landed inside the serving window."""
        steps = {"chunk": self._chunk_step, "decode": self._decode_step}
        if self._burst_step is not self._decode_step:
            steps["burst"] = self._burst_step
        return {name: fn._cache_size() for name, fn in steps.items()}

    # ---- page accounting ------------------------------------------------

    def _pages_needed(self, req: Request) -> int:
        """Pages a request holds at peak: prompt chunks round up to the
        chunk grid, and decode writes through plen + max_new - 2."""
        plen = len(req.tokens)
        hi = max(-(-plen // self.chunk) * self.chunk,
                 plen + req.max_new - 1)
        return -(-hi // self.page_size)

    def _validate(self, req: Request):
        need = self._pages_needed(req)
        cap = min(self.max_pages, self.total_pages - 1)
        if need > cap:
            raise ValueError(
                f"request {req.rid} needs {need} pages "
                f"(prompt {len(req.tokens)} + gen {req.max_new}, page size "
                f"{self.page_size}) but the ceiling is {cap} "
                f"(max_pages={self.max_pages}, pool={self.total_pages})")
        if not req.max_new:
            raise ValueError(f"request {req.rid}: max_new must be >= 1")

    def _free_slot_pages(self, slot: _Slot):
        """Return a slot's pages to the free pool, scrubbing any that hold
        injected NaNs first (a reclaimed page must never leak non-finite
        state into its next owner)."""
        doomed = [p for p in slot.pages if p in self._poisoned]
        if doomed:
            idx = jnp.asarray(doomed, jnp.int32)
            self.pools = jax.tree.map(lambda l: l.at[:, idx].set(0),
                                      self.pools)
            self._poisoned.difference_update(doomed)
        self._free_pages.extend(slot.pages)

    def _release(self, slot: _Slot):
        self._free_slot_pages(slot)
        self._reset(slot)

    def _evict_youngest(self, queue: deque) -> bool:
        """Free the youngest admitted slot and requeue its request at the
        front (recompute-on-readmit).  Returns False if nothing is active."""
        active = [s for s in self._slots if s.state != _FREE]
        if not active:
            return False
        victim = max(active, key=lambda s: s.admit_seq)
        req = victim.req
        self._release(victim)
        queue.appendleft(req)
        self.stats["evictions"] += 1
        self._post_recovery_audit("eviction")
        return True

    def _try_page(self, slot: _Slot, logical: int) -> bool:
        """Grow slot's page list through logical index ``logical`` from the
        free pool; False (no allocation rollback needed — partial growth is
        still valid) if the pool runs dry.  The ``engine.page_alloc`` fault
        point makes an allocation fail as if the pool were empty."""
        while len(slot.pages) <= logical:
            if not self._free_pages or self.faults.fires("engine.page_alloc"):
                return False
            slot.pages.append(self._free_pages.pop())
        return True

    def _claim(self, slots_, need_fn, queue: deque, can_wait: bool):
        """Partition a phase's slots into those whose pages are available
        this tick.  A starved slot *stalls* — skips the tick and keeps its
        pages; the pool refills as siblings complete, so stalling is almost
        always cheaper than eviction-recompute.  Eviction is the last
        resort: only when no slot in the phase can move and there is no
        other progress to wait on (``can_wait``) does the scheduler evict
        the youngest admitted request to break the deadlock."""
        ready, stalled = [], []
        for s in slots_:
            (ready if self._try_page(s, need_fn(s)) else stalled).append(s)
        while not ready and stalled and not can_wait:
            if not self._evict_youngest(queue):
                break
            # the victim may have been anywhere, including `stalled`
            stalled = [s for s in stalled if s.req is not None]
            retry, stalled = stalled, []
            for s in retry:
                (ready if self._try_page(s, need_fn(s))
                 else stalled).append(s)
        return [s for s in ready if s.req is not None]

    def _reset(self, slot: _Slot):
        slot.state = _FREE
        slot.req = None
        slot.pages = []
        slot.chunk_done = 0
        slot.tok = 0
        slot.pos = 0
        slot.out = []
        slot.admit_seq = -1
        slot.first_tok_t = None

    def _split_key(self):
        self._key, sub = jax.random.split(self._key)
        return sub

    # ---- fault handling / accounting ------------------------------------

    def audit_pages(self) -> dict:
        """Page-pool invariant check: every page except the dummy is in
        exactly one place (the free list or one slot's table) and nothing
        is duplicated.  Cheap host-side bookkeeping — safe to run after
        every recovery action."""
        held = [p for s in self._slots for p in s.pages]
        free = list(self._free_pages)
        issues = []
        if len(held) != len(set(held)):
            issues.append("page held by two slots")
        if len(free) != len(set(free)):
            issues.append("free-list duplicate")
        if set(held) & set(free):
            issues.append("page both free and held")
        if 0 in held or 0 in free:
            issues.append("dummy page 0 circulating")
        if len(set(held)) + len(set(free)) != self.total_pages - 1:
            issues.append(
                f"leak: held {len(set(held))} + free {len(set(free))} "
                f"!= {self.total_pages - 1}")
        return {"ok": not issues, "free": len(free), "held": len(held),
                "total_pages": self.total_pages, "issues": issues}

    def _post_recovery_audit(self, label: str):
        if not (self.faults.enabled or self.audit_every):
            return
        a = self.audit_pages()
        if not a["ok"]:
            self.stats.setdefault("audit_failures", []).append(
                dict(a, after=label))

    def _now(self) -> float:
        return time.perf_counter() - self._t0

    def _record(self, req: Request, status: str, *, reason=None,
                tokens=(), slot: _Slot | None = None):
        """Append a request's single terminal record (idempotent per rid)."""
        if req.rid in self._recorded:
            return
        self._recorded.add(req.rid)
        t = self._now()
        self._records.append({
            "rid": req.rid,
            "arrival": req.arrival,
            "status": status,
            "reason": reason,
            "admitted": slot.admit_t if slot is not None else None,
            "first_token": slot.first_tok_t if slot is not None else None,
            "finished": t,
            "latency": t - req.arrival,
            "prompt_len": int(len(req.tokens)),
            "tokens": list(tokens),
        })

    def _finish(self, slot: _Slot):
        self._record(slot.req, "completed", tokens=slot.out, slot=slot)
        self._release(slot)

    def _quarantine(self, slot: _Slot):
        """Non-finite logits in this slot only: record the failure with the
        tokens generated before the poison, scrub + reclaim its pages (its
        own KV writes are suspect too), and keep every other slot going."""
        self._poisoned.update(slot.pages)
        self._record(slot.req, "failed", reason="non_finite",
                     tokens=slot.out, slot=slot)
        self._release(slot)
        self.stats["quarantined"] += 1
        self._post_recovery_audit("quarantine")

    def _reinit_pools(self):
        """Rebuild the page pool from scratch (organic step failure: the
        donated pools' state is unknown)."""
        pools, _ = split_tree(
            paged_cache_init(self.cfg, self.total_pages, self.page_size))
        self.pools = jax.device_put(pools, self.chunk_plan.in_shardings[2])
        self._free_pages = list(range(1, self.total_pages))
        self._poisoned = set()

    def _elastic_rebuild(self, queue: deque) -> bool:
        """Elastic recovery from a (injected) device loss: shrink the mesh
        — the data axis halves first, the model axis only once data
        parallelism is exhausted — rebuild the step plans and their jits on
        the surviving devices, reshard the live params onto the new layout
        (an elastic restore: same bytes, new placement), rebuild the page
        pool, and requeue every in-flight request for recompute *without*
        charging its retry budget — the hardware failed, not the request.
        Returns False when the mesh is already a single device (nothing
        left to lose)."""
        shape = dict(self.mesh.shape)
        data = int(shape.get("data", 1))
        model = int(shape.get("model", 1))
        old = data * model
        if old <= 1:
            return False
        if data > 1:
            data //= 2
        else:
            model //= 2
        self.stats["lost_devices"] += old - data * model
        self.mesh = make_host_mesh(data=data, model=model)
        self._build_plans()
        self.params = jax.device_put(self.params,
                                     self.chunk_plan.in_shardings[0])
        self.stats["resharded_restores"] += 1
        # every active sequence's KV lived (in part) on the lost devices:
        # requeue oldest-frontmost for recompute, then rebuild the pool on
        # the new mesh
        active = [s for s in self._slots if s.state != _FREE]
        for s in sorted(active, key=lambda s: s.admit_seq, reverse=True):
            req = s.req
            self._reset(s)
            queue.appendleft(req)
        self._reinit_pools()
        self.stats["mesh_rebuilds"] += 1
        self.warmup()
        self._post_recovery_audit("mesh_rebuild")
        return True

    def _step_failure(self, participants, queue: deque, *, injected: bool,
                      phase: str, error: Exception | None = None):
        """Recover from a failed step launch.  Participants are charged a
        retry (``failed`` once the budget is gone) and requeued at the
        front for recompute.  Injected faults fire *before* the launch, so
        bystander slots keep their pages and KV; an organic failure cannot
        trust the donated pool state, so the pool is rebuilt and every
        active sequence recomputes.  An organic error is kept in
        ``stats['step_errors']`` so a caller can report why."""
        self.stats["step_failures"] += 1
        if error is not None:
            self.stats["step_errors"].append(f"{phase}: {error!r}")
        affected = (list(participants) if injected
                    else [s for s in self._slots if s.state != _FREE])
        charged = {id(s) for s in participants}
        # appendleft in reverse admission order keeps the oldest frontmost
        for s in sorted(affected, key=lambda s: s.admit_seq, reverse=True):
            req = s.req
            if id(s) in charged:
                n = self._retries[req.rid] = self._retries.get(req.rid, 0) + 1
                self.stats["retries"] += 1
                if n > self.max_retries:
                    self._record(req, "failed",
                                 reason=f"{phase}_step_failure",
                                 tokens=s.out, slot=s)
                    if injected:
                        self._free_slot_pages(s)
                    self._reset(s)
                    continue
            if injected:
                self._free_slot_pages(s)
            self._reset(s)
            queue.appendleft(req)
        if not injected:
            self._reinit_pools()
        self._post_recovery_audit(f"{phase}_step_failure")

    def _enforce_deadlines(self, queue: deque):
        """Cancel deadline-expired requests wherever they are: queued ones
        are recorded unserved; in-flight ones free their pages and keep the
        tokens they produced."""
        expired = [r for r in queue
                   if r.deadline_s is not None
                   and self._now() - r.arrival > r.deadline_s]
        for r in expired:
            queue.remove(r)
            self._record(r, "timeout", reason="deadline")
            self.stats["deadline_cancels"] += 1
        for s in self._slots:
            if s.state == _FREE or s.req.deadline_s is None:
                continue
            if self._now() - s.req.arrival > s.req.deadline_s:
                self._record(s.req, "timeout", reason="deadline",
                             tokens=s.out, slot=s)
                self._release(s)
                self.stats["deadline_cancels"] += 1
                self._post_recovery_audit("deadline_cancel")

    def _drain_all(self, pending: deque, queue: deque, reason: str):
        """Global-timeout drain: cancel in-flight work keeping partial
        output, mark everything still waiting unserved.  Nothing raises —
        the caller returns the stats dict with all completed records."""
        for s in self._slots:
            if s.state != _FREE:
                self._record(s.req, "timeout", reason=reason,
                             tokens=s.out, slot=s)
                self._release(s)
        while queue:
            self._record(queue.popleft(), "timeout", reason="unserved")
        while pending:
            self._record(pending.popleft(), "timeout", reason="unserved")
        self._post_recovery_audit("drain")

    # ---- run loop -------------------------------------------------------

    def run(self, requests, *, timeout_s: float = 300.0) -> dict:
        """Replay ``requests`` (any order; sorted by arrival) to completion
        or controlled degradation.

        Returns a stats dict: one terminal record per request (status in
        ``completed | timeout | rejected | failed``), goodput (completed
        generated tokens / wall second), latency percentiles over completed
        requests, per-phase prefill/decode milliseconds, the spans and
        compile count of the module docstring, recovery counters and the
        exit page-pool audit.  ``timeout_s`` is a drain guard, not an
        exception: on expiry the engine stops admitting, keeps partial
        results, and returns.
        """
        self.stats = {"evictions": 0, "chunk_steps": 0, "decode_steps": 0,
                      "prefill_ms": 0.0, "decode_ms": 0.0,
                      "step_failures": 0, "retries": 0, "quarantined": 0,
                      "shed": 0, "deadline_cancels": 0, "nan_injections": 0,
                      "preempted": False, "mesh_rebuilds": 0,
                      "lost_devices": 0, "resharded_restores": 0,
                      "collective_timeouts": 0, "straggler_flags": [],
                      "step_errors": [], "spans": {}, "compiles": 0,
                      "decode_pages": {"live": 0, "window": 0}}

        def count_compile(event, duration_secs, **kwargs):
            if event == _BACKEND_COMPILE_EVENT:
                self.stats["compiles"] += 1

        jax.monitoring.register_event_duration_secs_listener(count_compile)
        try:
            return self._run(requests, timeout_s)
        finally:
            jax.monitoring.unregister_event_duration_listener(count_compile)

    @contextlib.contextmanager
    def _span(self, name: str, *, key: str | None = None,
              tick: int | None = None, **tags):
        """One span of the module docstring: a profiler annotation named
        ``name`` with ``tags`` (a step annotation numbered ``tick``), timed
        into ``stats['spans'][key or name]``."""
        ann = (jax.profiler.StepTraceAnnotation(name, step_num=tick, **tags)
               if tick is not None
               else jax.profiler.TraceAnnotation(name, **tags))
        stack = self._span_stack
        stack.append(0.0)
        t0 = time.perf_counter()
        try:
            with ann:
                yield
        finally:
            ms = (time.perf_counter() - t0) * 1e3
            child = stack.pop()
            if stack:
                stack[-1] += ms
            rec = self.stats["spans"].setdefault(
                key or name, {"count": 0, "ms": 0.0, "self_ms": 0.0})
            rec["count"] += 1
            rec["ms"] += ms
            rec["self_ms"] += ms - child

    def _run(self, requests, timeout_s: float) -> dict:
        span = self._span
        with span("engine.start"):
            for r in requests:
                self._validate(r)
            self.warmup()
            pending = deque(sorted(requests, key=lambda r: r.arrival))
            queue: deque = deque()
            self._records = []
            self._recorded = set()
            self._retries = {}
            self._poisoned = set()
            self._drain_reason = None
        t0 = time.perf_counter()
        self._t0 = t0
        now = self._now
        tick = 0
        mon = StragglerMonitor(warmup_steps=5)
        n_shards = int(np.prod(tuple(self.mesh.shape.values())))

        while pending or queue or any(s.state != _FREE for s in self._slots):
            tick += 1
            with span("engine.tick", tick=tick):
                # timeout: nothing more is admitted or stepped; the drain
                # runs in engine.finish
                if now() > timeout_s:
                    self._drain_reason = "timeout"
                    break
                # fast-forward: nothing is runnable and the next arrival
                # lands beyond the drain guard — declare the timeout now
                # instead of sleeping into it
                if (not queue and pending
                        and all(s.state == _FREE for s in self._slots)
                        and pending[0].arrival > timeout_s):
                    self._drain_reason = "timeout"
                    break

                if self._drain_reason is None and (
                        (self._guard is not None and self._guard.preempted)
                        or self.faults.fires("engine.preempt")):
                    # graceful drain: reject everything waiting (structured,
                    # immediate), let in-flight slots run to completion
                    self._drain_reason = "preempted"
                    self.stats["preempted"] = True
                    while queue:
                        self._record(queue.popleft(), "rejected",
                                     reason="preempted")
                    while pending:
                        self._record(pending.popleft(), "rejected",
                                     reason="preempted")

                if (self.faults.enabled and self.stats["mesh_rebuilds"]
                        < self.max_mesh_rebuilds
                        and self.faults.fires("dist.device_loss")):
                    self._elastic_rebuild(queue)
                    n_shards = int(np.prod(tuple(self.mesh.shape.values())))

                self.faults.fires("engine.straggler")  # sleeps when it fires
                # straggler watchdog: per-shard injection streams (one RNG
                # per shard index — deterministic across process counts)
                # plus an EMA z-score over tick wall time that flags organic
                # slowness
                mon.start_step()
                slow_shards = []
                if self.faults.enabled:
                    for sidx in range(n_shards):
                        if self.faults.fires("dist.straggler", index=sidx):
                            slow_shards.append(sidx)  # fires() slept in-line

                with span("engine.intake"):
                    while pending and pending[0].arrival <= now():
                        r = pending.popleft()
                        if (self.admission_budget is not None
                                and len(queue) >= self.admission_budget):
                            self._record(r, "rejected", reason="overload")
                            self.stats["shed"] += 1
                        else:
                            queue.append(r)
                    self._enforce_deadlines(queue)

                with span("engine.admit"):
                    self._admit(queue)

                prefilling = [s for s in self._slots if s.state == _PREFILL]
                if prefilling:
                    self._run_chunk(prefilling, queue)

                decoding = [s for s in self._slots if s.state == _DECODE]
                if decoding:
                    # burst only when nothing competes for the device: no
                    # prefill in flight, and no admissible work waiting (a
                    # non-empty queue with every slot busy can't be
                    # admitted, so it doesn't force single-stepping)
                    can_admit = any(s.state == _FREE for s in self._slots)
                    waiting = bool(queue) or (
                        pending and pending[0].arrival <= now() + 1e-3)
                    quiet = not prefilling and not (can_admit and waiting)
                    n = self.burst if quiet else 1
                    n = min(n, max(len(s.req.tokens) + s.req.max_new
                                   - s.pos - 1 for s in decoding))
                    self._run_decode(decoding, max(n, 1), queue)

                if (prefilling or decoding) and (
                        mon.end_step(tick) or slow_shards):
                    flagged = mon.flags[-1] if mon.flags else None
                    self.stats["straggler_flags"].append({
                        "tick": tick, "shards": slow_shards,
                        "injected": bool(slow_shards),
                        "dt_s": flagged[1] if flagged else None,
                        "zscore": flagged[2] if flagged else None})

            if not prefilling and not decoding and not queue and pending:
                time.sleep(min(max(pending[0].arrival - now(), 0.0), 0.05))

        with span("engine.finish"):
            if self._drain_reason == "timeout":
                self._drain_all(pending, queue, "global_timeout")
            return self._summary(requests, now())

    def _admit(self, queue: deque):
        """FIFO admission while a slot is free and the pool can cover the
        whole prompt (gating on full prompt pages, not just the first
        chunk, keeps overcommit — and eviction thrash — down; pages past
        the first chunk are still allocated lazily)."""
        for slot in self._slots:
            if not queue or slot.state != _FREE:
                continue
            req = queue[0]
            if len(self._free_pages) < -(-len(req.tokens) // self.page_size):
                break
            first = -(-min(len(req.tokens), self.chunk) // self.page_size)
            queue.popleft()
            slot.state = _PREFILL
            slot.req = req
            slot.pages = [self._free_pages.pop() for _ in range(first)]
            slot.admit_seq = self._admit_seq
            self._admit_seq += 1
            slot.admit_t = self._now()

    def _summary(self, requests, wall: float) -> dict:
        """The run's stats: records, statuses, goodput, latency, the step
        time of each phase (its engine.step spans) and the exit audit."""
        records = self._records
        completed = [r for r in records if r["status"] == "completed"]
        lat = sorted(r["latency"] for r in completed)

        def pct(p):
            return lat[min(int(p * len(lat)), len(lat) - 1)] if lat else 0.0

        def step_ms(*plans):
            spans = self.stats["spans"]
            return sum(spans[f"engine.step.{p}"]["ms"] for p in plans
                       if f"engine.step.{p}" in spans)

        statuses: dict = {}
        for r in records:
            statuses[r["status"]] = statuses.get(r["status"], 0) + 1
        gen_tokens = sum(len(r["tokens"]) for r in completed)
        self.stats.update({
            "requests": len(records),
            "completed": len(completed),
            "statuses": statuses,
            "all_completed": len(completed) == len(requests),
            "drained": self._drain_reason,
            "wall_s": wall,
            "goodput_tok_s": gen_tokens / max(wall, 1e-9),
            "generated_tokens": gen_tokens,
            "latency_p50_s": pct(0.50),
            "latency_p99_s": pct(0.99),
            "prefill_ms": step_ms("chunk"),
            "decode_ms": step_ms("decode", "burst"),
            "records": records,
            "page_audit": self.audit_pages(),
            "faults": self.faults.summary(),
        })
        return dict(self.stats)

    # ---- phase steps ----------------------------------------------------

    def _launch(self, plan: str, step, host, participants, queue, *,
                tokens: int, **tags):
        """One step launch under its ``engine.step`` span (tagged with
        ``tags`` too): ``step(params, host[0], pools, *host[1:], key)``,
        then the blocking fetch of the tokens it returns.  Returns them as
        numpy, or None after a failed launch has been recovered
        (participants requeued or failed)."""
        phase = "prefill" if plan == "chunk" else "decode"
        failure = None
        with self._span("engine.step", key=f"engine.step.{plan}", plan=plan,
                        live=len(participants), tokens=tokens, **tags):
            try:
                if self.faults.fires("dist.collective_timeout"):
                    self.stats["collective_timeouts"] += 1
                    raise InjectedFault(
                        f"injected collective timeout ({phase})")
                if self.faults.fires("engine.step"):
                    raise InjectedFault(f"injected {plan}-step failure")
                with self._span("engine.dispatch"):
                    first, *rest = (jnp.asarray(a) for a in host)
                    toks, self.pools = step(self.params, first, self.pools,
                                            *rest, self._split_key())
            except InjectedFault:
                failure = (True, None)
            except Exception as e:  # noqa: BLE001 — any launch error retries
                failure = (False, e)
            else:
                with self._span("engine.fetch"):
                    toks = np.asarray(toks)
        if failure is not None:
            injected, error = failure
            self._step_failure(participants, queue, injected=injected,
                               phase=phase, error=error)
            return None
        return toks

    def _page_tables(self, live) -> np.ndarray:
        pt = np.zeros((self.slots, self.max_pages), np.int32)
        ids = {id(s) for s in live}
        for i, s in enumerate(self._slots):
            if id(s) in ids:
                pt[i, : len(s.pages)] = s.pages
        return pt

    def _run_chunk(self, prefilling, queue):
        cs = self.chunk

        def pages_for_chunk(s):
            # pages ahead of this chunk are allocated lazily so a long
            # prompt doesn't hold its whole footprint from tick 0
            return (min(s.chunk_done + cs, len(s.req.tokens)) - 1) \
                // self.page_size

        with self._span("engine.claim"):
            prefilling = self._claim(
                prefilling, pages_for_chunk, queue,
                can_wait=any(s.state == _DECODE for s in self._slots))
        if not prefilling:
            return
        with self._span("engine.pack"):
            tokens = np.zeros((self.slots, cs), np.int32)
            qpos = np.full((self.slots, cs), -1, np.int32)
            pos0 = np.zeros((self.slots,), np.int32)
            for s in prefilling:
                i = self._slots.index(s)
                seg = np.asarray(
                    s.req.tokens[s.chunk_done: s.chunk_done + cs], np.int32)
                tokens[i, : len(seg)] = seg
                qpos[i, : len(seg)] = s.chunk_done + np.arange(len(seg))
                pos0[i] = s.chunk_done
            pt = self._page_tables(prefilling)
        tok1 = self._launch("chunk", self._chunk_step,
                            (tokens, pt, qpos, pos0), prefilling, queue,
                            tokens=int((qpos >= 0).sum()))
        if tok1 is None:
            return
        with self._span("engine.commit"):
            self.stats["chunk_steps"] += 1
            for s in prefilling:
                i = self._slots.index(s)
                s.chunk_done += cs
                if s.chunk_done < len(s.req.tokens):
                    continue
                if int(tok1[i]) == NONFINITE_TOKEN:
                    self._quarantine(s)
                    continue
                s.state = _DECODE
                s.tok = int(tok1[i])
                s.pos = len(s.req.tokens)
                s.out = [s.tok]
                s.first_tok_t = time.perf_counter() - self._t0
                if len(s.out) >= s.req.max_new:
                    self._finish(s)

    def _poison_page(self, page: int):
        """Inject NaNs into one physical page across every float pool leaf
        (bf16 KV directly; int8 pools through their f32 scales) — the real
        in-graph non-finite guard then trips on the next read."""
        def f(leaf):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                return leaf.at[:, page].set(float("nan"))
            return leaf

        self.pools = jax.tree.map(f, self.pools)
        self._poisoned.add(int(page))
        self.stats["nan_injections"] += 1

    def _run_decode(self, decoding, n, queue):
        def pages_for_burst(s):
            # decode writes positions pos .. pos+n-1, capped at the
            # request's true last write (plen + max_new - 2); overrun
            # steps past that land in the dummy page
            return min((s.pos + n - 1) // self.page_size,
                       (len(s.req.tokens) + s.req.max_new - 2)
                       // self.page_size)

        with self._span("engine.claim"):
            decoding = self._claim(decoding, pages_for_burst, queue,
                                   can_wait=False)
        if not decoding:
            return
        if self.faults.fires("engine.nan_logits"):
            victim = min(decoding, key=lambda s: s.admit_seq)
            if victim.pages:
                self._poison_page(victim.pages[0])
        with self._span("engine.pack"):
            tok = np.zeros((self.slots,), np.int32)
            pos = np.zeros((self.slots,), np.int32)
            for s in decoding:
                i = self._slots.index(s)
                tok[i] = s.tok
                pos[i] = s.pos
            pt = self._page_tables(decoding)
        if n == self.burst and self.burst > 1:
            plan, step = "burst", self._burst_step
        else:
            plan, step, n = "decode", self._decode_step, 1
        # pages paged decode attention reads for the live rows: each row's
        # pos + 1 tokens at every step of the launch, within its window
        rows = pos[[self._slots.index(s) for s in decoding]]
        lens = rows[:, None] + np.arange(1, n + 1)
        live_pages = int(np.minimum(-(-lens // self.page_size),
                                    self.max_pages).sum())
        toks = self._launch(plan, step, (tok, pt, pos), decoding, queue,
                            tokens=n * len(decoding), pages=live_pages)
        if toks is None:
            return
        with self._span("engine.commit"):
            self.stats["decode_steps"] += n
            self.stats["decode_pages"]["live"] += live_pages
            self.stats["decode_pages"]["window"] += \
                n * len(decoding) * self.max_pages
            for s in decoding:
                i = self._slots.index(s)
                poisoned = False
                for j in range(toks.shape[1]):
                    if len(s.out) >= s.req.max_new:
                        break
                    t = int(toks[i, j])
                    if t == NONFINITE_TOKEN:
                        poisoned = True
                        break
                    s.out.append(t)
                    s.tok = t
                    s.pos += 1
                if poisoned:
                    self._quarantine(s)
                elif len(s.out) >= s.req.max_new:
                    self._finish(s)
