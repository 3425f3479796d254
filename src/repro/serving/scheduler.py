"""Continuous-batching request scheduler over the on-demand engine
(DESIGN.md §9).

``GenerationEngine.generate()`` serves exactly one batch synchronously —
one request's cold-unit fault-in stalls the whole host. The scheduler
turns the same fault-in/pin/prefetch machinery into a serving loop:

  * **slots** — a fixed ``max_batch`` of decode lanes over ONE compiled
    masked-decode executable (``ColdStartServer.compiled_decode_masked``).
    Per slot: the owning request, its token position, its last emitted
    token; the done/free state is the ``active`` mask fed to the compiled
    step. Inactive rows ride the batch as pad lanes: their routing never
    reaches the usage masks (so a free slot can never fault a unit in),
    and whatever garbage they write to their own cache row is overwritten
    wholesale at the slot's next admission — pad lanes cost compute,
    never correctness.
  * **admission** — between decode steps, queued prompts fill free slots:
    prefill runs on its own compiled (1, S) shape, then the prefill cache
    is grafted into the slot row of the batched decode cache
    (``_graft_slot_cache``). Over-length requests are *rejected* at
    admission (``Request.error``), never raised out of the loop.
  * **union fault handling** — each decode step issues one
    ``ensure(pin=True)`` over the union of all active slots' vocab
    row-groups, and one expert fault/retry loop over the union of routed
    expert misses. A request whose units are cold adds latency to the
    *step*, not a serialization point per request — all slots' misses
    load in a single offset-sorted batch.
  * **fairness** — admission is strictly FIFO (arrival order), every
    active slot advances exactly one token per step, and predictive hints
    are round-robin-merged across slots (``core.prefetch.merge_hints``)
    so one request's long tail can't starve another's next-step units.
  * **paged KV lifecycle** (DESIGN.md §16.2) — a ``PagePool`` carves the
    decode-cache capacity into fixed-size pages: admission grants each
    request the pages its ``prompt + n_steps`` positions need (atomic —
    on exhaustion the request is *rejected* with slot state untouched),
    retire and both failure paths return them, and per-step accounting
    (``kv_tokens_dense`` vs ``kv_tokens_paged``) feeds the roofline
    gate's achieved-vs-max-shape KV bytes. The default pool exactly
    covers ``max_batch × max_seq``, so page exhaustion is impossible and
    admission decisions are byte-identical to the pre-paging scheduler.

Greedy outputs are per-slot identical to running each request alone
through ``generate()`` (tested in tests/test_scheduler.py): decode rows
are computationally independent, dropless MoE dispatch is per-token
exact, and admission rebuilds the slot's cache row from scratch.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.prefetch import merge_hints
from repro.serving.engine import GenerationEngine, RequestStats
from repro.serving.paged_kv import PagePool
from repro.utils.spans import span
from repro.utils.tree import flatten_with_paths, tree_from_flat


@dataclass
class Request:
    """One generation request moving through the scheduler."""

    rid: int
    tokens: np.ndarray  # (S,) int32 prompt
    n_steps: int
    submitted_t: float = 0.0
    admitted_t: float = 0.0
    first_token_t: float = 0.0
    finished_t: float = 0.0
    # SLO admission (DESIGN.md §15.2): seconds after submit by which the
    # LAST token must land (None → no deadline), and a tie-breaking
    # priority (higher first under burst re-ordering). Both are ignored
    # by the default FIFO policy.
    deadline_s: Optional[float] = None
    priority: int = 0
    out: list = field(default_factory=list)  # emitted token ids
    stats: RequestStats = field(default_factory=RequestStats)
    error: Optional[str] = None
    _done: threading.Event = field(default_factory=threading.Event, repr=False)

    @property
    def done(self) -> bool:
        return self._done.is_set()

    def wait(self, timeout: Optional[float] = None) -> bool:
        return self._done.wait(timeout)

    def finish(self, error: Optional[str] = None) -> None:
        self.error = error
        self.finished_t = time.perf_counter()
        self._done.set()

    @property
    def output(self) -> np.ndarray:
        return np.asarray(self.out, np.int32)

    @property
    def latency_s(self) -> float:
        """Submit → last token (0 until finished)."""
        return max(0.0, self.finished_t - self.submitted_t)

    @property
    def ttft_s(self) -> float:
        """Submit → first token (prefill wait included)."""
        return max(0.0, self.first_token_t - self.submitted_t)

    @property
    def deadline_t(self) -> Optional[float]:
        """Absolute perf_counter deadline (None without one)."""
        if self.deadline_s is None:
            return None
        return self.submitted_t + self.deadline_s

    @property
    def shed(self) -> bool:
        """True when an SLO policy dropped this request unserved."""
        return self.error is not None and self.error.startswith("shed:")


class RequestQueue:
    """Thread-safe FIFO of pending requests.

    Arrival order IS the admission order — the scheduler's fairness
    contract (DESIGN.md §9) starts here. ``submit`` is safe from any
    thread (a traffic generator, an RPC handler); the scheduler thread
    pops."""

    def __init__(self) -> None:
        self._q: deque[Request] = deque()
        self._lock = threading.Lock()
        self._next_rid = 0

    def submit(self, tokens, n_steps: int, *, deadline_s: Optional[float] = None,
               priority: int = 0) -> Request:
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        with self._lock:
            rid = self._next_rid
            self._next_rid += 1
            req = Request(rid, tokens, int(n_steps),
                          submitted_t=time.perf_counter(),
                          deadline_s=deadline_s, priority=int(priority))
            self._q.append(req)
        return req

    def pop(self) -> Optional[Request]:
        with self._lock:
            return self._q.popleft() if self._q else None

    def __len__(self) -> int:
        with self._lock:
            return len(self._q)


class AdmissionPolicy:
    """Pluggable admission decision for the scheduler (DESIGN.md §15.2).

    Each admission round the scheduler calls ``select(queue, free, now,
    validate)``: the policy may pop from the thread-safe queue and must
    return ``(admit, drop)`` — at most ``free`` requests to admit this
    round, plus ``(request, kind, error)`` triples to retire unserved
    (``kind`` is ``"rejected"`` for structurally invalid requests,
    ``"shed"`` for load/deadline drops; ``error`` becomes
    ``Request.error``). ``validate(req)`` returns the canonical rejection
    message or None. A policy may hold popped-but-unadmitted requests in
    an internal backlog; it then reports them via ``pending()`` so the
    scheduler's idle/run logic still sees them as outstanding work.
    ``note_prefill``/``note_step`` feed it observed service times.
    """

    def select(self, queue: RequestQueue, free: int, now: float, validate):
        raise NotImplementedError

    def pending(self) -> int:
        return 0

    def note_prefill(self, seconds: float) -> None:
        pass

    def note_step(self, seconds: float, n_active: int) -> None:
        pass


class FIFOAdmission(AdmissionPolicy):
    """The default: strict arrival order, no deadlines, never sheds —
    byte-identical admission decisions to the pre-policy scheduler (the
    §9 fairness contract; parity-tested by rq5/rq7/rq8)."""

    def select(self, queue: RequestQueue, free: int, now: float, validate):
        admit: list[Request] = []
        drop: list[tuple[Request, str, str]] = []
        while len(admit) < free:
            req = queue.pop()
            if req is None:
                break
            err = validate(req)
            if err is not None:
                # reject, don't crash: the loop must survive bad requests
                drop.append((req, "rejected", err))
                continue
            admit.append(req)
        return admit, drop


class SLOAdmission(AdmissionPolicy):
    """Deadline/queue-depth-aware admission (DESIGN.md §15.2).

    Opt-in via ``cold_start(admission=...)`` or the scheduler's
    ``admission=`` kwarg; the FIFO default is untouched. Three behaviors
    replace tail-latency-by-timeout with shed-at-admission:

      * **shed-on-hopeless** — a request whose projected finish already
        exceeds its deadline is dropped *before* any prefill/decode is
        spent on it, with ``error="shed: ..."``. The projection is
        slot-granular: ranked-ahead work fills the host's admission
        slots in waves, each wave holding its slot for a full decode
        residence, so a request ``w`` waves deep projects ``now +
        prefill_est + (1 + w) × n_steps × step_est``. Estimates are
        EMAs of observed service times, so projections track the live
        fault/decode cost.
      * **priority re-order under burst** — the backlog admits by
        (priority desc, deadline asc, arrival), so when a burst
        overflows the slots, urgent work jumps the queue; with equal
        priorities and no deadlines the order degenerates to FIFO.
      * **bounded backlog wait** — requests the round couldn't admit
        stay in the policy's backlog (counted by ``pending()``) and are
        re-projected every round: one that becomes hopeless while
        queued is shed then, not after burning a slot.

    ``default_deadline_s`` applies to requests submitted without one
    (None → such requests are never shed).
    """

    def __init__(
        self,
        *,
        default_deadline_s: Optional[float] = None,
        step_est_s: float = 2e-3,      # decode-step EMA seed (refined online)
        prefill_est_s: float = 10e-3,  # prefill EMA seed
        ema: float = 0.2,              # weight of each new observation
    ):
        self.default_deadline_s = default_deadline_s
        self.ema = float(ema)
        self._step_est = float(step_est_s)
        self._prefill_est = float(prefill_est_s)
        self._backlog: list[Request] = []
        self._slots = 1  # widest admission round seen ≈ the host's slot count
        self.shed_total = 0

    def pending(self) -> int:
        return len(self._backlog)

    def note_prefill(self, seconds: float) -> None:
        self._prefill_est += self.ema * (seconds - self._prefill_est)

    def note_step(self, seconds: float, n_active: int) -> None:
        self._step_est += self.ema * (seconds - self._step_est)

    def _deadline_t(self, req: Request) -> Optional[float]:
        if req.deadline_s is not None:
            return req.submitted_t + req.deadline_s
        if self.default_deadline_s is not None:
            return req.submitted_t + self.default_deadline_s
        return None

    def select(self, queue: RequestQueue, free: int, now: float, validate):
        drop: list[tuple[Request, str, str]] = []
        self._slots = max(self._slots, free)
        # drain arrivals into the backlog (validating on entry, so a bad
        # request is retired this round whether or not slots are free)
        while True:
            req = queue.pop()
            if req is None:
                break
            err = validate(req)
            if err is not None:
                drop.append((req, "rejected", err))
                continue
            self._backlog.append(req)
        # burst re-order: urgent first, then earliest deadline, then arrival
        def rank(r: Request):
            dt = self._deadline_t(r)
            return (-r.priority, dt if dt is not None else float("inf"), r.rid)
        self._backlog.sort(key=rank)
        kept: list[Request] = []
        for r in self._backlog:
            dt = self._deadline_t(r)
            if dt is not None:
                # slot-granular projection: ranked-ahead work fills the
                # slots in waves, each holding its slot for a full decode
                # residence; mid-decode rounds (free == 0) cost one more
                waves = len(kept) // self._slots + (1 if free == 0 else 0)
                projected = (now + self._prefill_est
                             + (1 + waves) * r.n_steps * self._step_est)
                if projected > dt:
                    self.shed_total += 1
                    drop.append((r, "shed", (
                        f"shed: projected finish +{projected - r.submitted_t:.3f}s "
                        f"exceeds deadline {dt - r.submitted_t:.3f}s "
                        f"(backlog={len(self._backlog)}, "
                        f"step_est={self._step_est * 1e3:.2f}ms)"
                    )))
                    continue
            kept.append(r)
        admit, self._backlog = kept[:free], kept[free:]
        return admit, drop


@dataclass
class SchedulerStats:
    """Aggregate loop accounting (per-request numbers live on each
    ``Request.stats``; step-shared costs — the union fault, the batched
    decode — are only meaningful at the loop level)."""

    steps: int = 0          # batched decode steps executed
    admitted: int = 0
    rejected: int = 0
    shed: int = 0           # SLO-policy drops (never under FIFO)
    completed: int = 0
    failed: int = 0         # admitted requests killed by a decode-step failure
    decode_s: float = 0.0
    fault_s: float = 0.0
    faulted_units: int = 0
    faulted_bytes: int = 0
    decode_retries: int = 0
    max_active: int = 0     # high-water concurrent slots
    # paged-KV accounting (DESIGN.md §16.2): cache positions the masked
    # decode streams at max shape vs. what the paged layout would stream
    # (occupied pages of active slots only) — the roofline gate's numbers
    kv_tokens_dense: int = 0
    kv_tokens_paged: int = 0
    kv_pages_high_water: int = 0

    def to_dict(self) -> dict:
        return dict(self.__dict__)


class ContinuousBatchingScheduler:
    """Slot-based continuous batching over ``GenerationEngine`` primitives.

    Single-consumer: exactly one thread drives ``step()``/``run()`` (the
    serving loop); any thread may ``submit()``. The decode cache, slot
    arrays, and stats are owned by the loop thread — the underlying
    ``TieredParams`` residency layer provides its own locking for the
    fault/prefetch traffic the loop generates.
    """

    def __init__(
        self,
        engine: GenerationEngine,
        *,
        max_batch: int = 4,
        queue: Optional[RequestQueue] = None,
        admission: Optional[AdmissionPolicy] = None,
        kv_page_size: Optional[int] = None,
        kv_pages: Optional[int] = None,
    ):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.engine = engine
        self.server = engine.server
        self.model = engine.model
        self.max_batch = max_batch
        self.queue = queue if queue is not None else RequestQueue()
        # admission policy (DESIGN.md §15.2): explicit kwarg wins, then the
        # server's cold_start(admission=...) default, then strict FIFO
        self.admission = (
            admission
            if admission is not None
            else getattr(self.server, "admission", None) or FIFOAdmission()
        )
        # paged-KV pool (DESIGN.md §16.2): explicit kwargs win, then the
        # server's cold_start(kv_page_size=/kv_pages=) defaults; the pool
        # defaults to exactly max_batch × max_seq worth of pages, where
        # exhaustion is impossible and admission is byte-identical
        ps = kv_page_size or getattr(self.server, "kv_page_size", None) or 16
        per_slot = -(-engine.max_seq // ps)
        n_pages = kv_pages or getattr(self.server, "kv_pages", None) or max_batch * per_slot
        self.page_pool = PagePool(n_pages, ps, max_batch)
        self.stats = SchedulerStats()
        self._slots: list[Optional[Request]] = [None] * max_batch
        self._pos = np.zeros(max_batch, np.int32)       # next decode position
        self._last_tok = np.zeros(max_batch, np.int32)  # token feeding the next step
        self._caches = self.model.init_cache(max_batch, engine.max_seq, multimodal=False)
        self._decode = self.server.compiled_decode_masked(max_batch)
        # one jitted graft for every (group size, prompt len) signature;
        # donating the batched cache lets XLA update the slot rows in place
        # instead of copying every leaf per admission
        self._graft = jax.jit(_graft_slot_cache, donate_argnums=(0,))

    def warm_compile(self) -> None:
        """Pre-compile the masked decode at the slot batch shape so the
        first traffic step serves instead of compiling (admission prefills
        and grafts still compile per prompt length on first use)."""
        model, B = self.model, self.max_batch
        cache = model.abstract_cache(B, self.engine.max_seq, multimodal=False)
        db, _ = model.decode_masked_batch_spec(B)
        # lower() takes the ShapeDtypeStruct trees directly — materializing
        # a zero cache here would transiently double device cache memory
        self._decode.lower(self.server.live_params(), cache, db).compile()

    # -- submission ------------------------------------------------------------
    def submit(self, tokens, n_steps: int) -> Request:
        """Enqueue one prompt. Decoding is greedy (argmax) — the
        sequential-equivalence contract is only defined for greedy."""
        return self.queue.submit(tokens, n_steps)

    @property
    def _tracing(self) -> bool:
        """True when a live AccessTrace would record request attribution."""
        tiered = self.server.tiered
        return tiered is not None and tiered.trace is not None

    @property
    def active(self) -> list[int]:
        return [i for i, r in enumerate(self._slots) if r is not None]

    @property
    def idle(self) -> bool:
        # the policy's backlog is outstanding work too: an SLO policy may
        # have drained the queue into itself without admitting everything
        return (not self.active and len(self.queue) == 0
                and self.admission.pending() == 0)

    def _validate(self, req: Request) -> Optional[str]:
        """Canonical structural check; the policy-independent rejection
        contract (message unchanged from the pre-policy scheduler)."""
        S = int(req.tokens.size)
        if S == 0 or S + req.n_steps > self.engine.max_seq or req.n_steps < 1:
            return (
                f"rejected: prompt {S} + {req.n_steps} steps exceeds "
                f"max_seq={self.engine.max_seq} (or is empty)"
            )
        return None

    # -- admission ---------------------------------------------------------------
    def _select(self) -> dict[int, list[tuple[int, Request]]]:
        """One admission round's policy select and page grants: the
        requests to admit, as ``(slot, request)`` groups by prompt length.
        Dropped and ungranted requests are retired here."""
        free = [i for i, r in enumerate(self._slots) if r is None]
        to_admit, dropped = self.admission.select(
            self.queue, len(free), time.perf_counter(), self._validate
        )
        for req, kind, err in dropped:
            if kind == "shed":
                self.stats.shed += 1
            else:
                self.stats.rejected += 1
            req.finish(error=err)
        picked: list[tuple[int, Request]] = [
            (free[i], req) for i, req in enumerate(to_admit[: len(free)])
        ]
        # paged-KV grant (§16.2): each request owns the pages its
        # prompt + n_steps positions need before any prefill is spent on
        # it. Exhaustion is an admission rejection with slot state
        # untouched — the loop keeps serving, the submitter sees an error.
        granted: list[tuple[int, Request]] = []
        for slot, req in picked:
            need = int(req.tokens.size) + req.n_steps
            if not self.page_pool.alloc(slot, need):
                self.stats.rejected += 1
                req.finish(error=(
                    f"rejected: kv page pool exhausted "
                    f"(need {self.page_pool.pages_for(need)} pages, "
                    f"{self.page_pool.free_pages} free of {self.page_pool.n_pages})"
                ))
                continue
            granted.append((slot, req))
        # group same-length prompts (everything picked is admitted this
        # round, so grouping cannot reorder anyone past anyone else)
        groups: dict[int, list[tuple[int, Request]]] = {}
        for slot, req in granted:
            groups.setdefault(req.tokens.size, []).append((slot, req))
        return groups

    def _admit(self) -> int:
        """Fill free slots per the admission policy (FIFO by default).
        Same-length prompts admitted in the same round share ONE batched
        prefill (the step primitives are batch-agnostic, so their
        vocab/expert faults union for free); the resulting cache rows are
        grafted into the slots in a single jitted call. Returns the
        number of requests admitted."""
        with span("repro.sched.admit"):
            groups = self._select()
        if not groups:
            return 0
        admitted = 0
        hints: list[list[str]] = []
        observed: list[str] = []
        by_request: dict[int, list[str]] = {}
        for S, grp in groups.items():
            slots = [s for s, _ in grp]
            reqs = [r for _, r in grp]
            now = time.perf_counter()
            for r in reqs:
                r.admitted_t = now
            shared = RequestStats()
            try:
                toks = jnp.asarray(np.stack([r.tokens for r in reqs]))
                with span("repro.sched.prefill",
                          rid=reqs[0].rid if len(reqs) == 1 else None):
                    logits, caches, expert_keys = self.engine.prefill_step(
                        toks, shared, hint=False
                    )
            except Exception as e:
                # a failed fault-in must not kill the loop (or leave the
                # submitters waiting forever) — fail the group's requests,
                # return their slots, keep serving
                self.stats.failed += len(reqs)
                for s, r in grp:
                    self.page_pool.free(s)  # a failed request leaks no pages
                    r.finish(error=f"prefill failed: {e!r}")
                continue
            self.admission.note_prefill(shared.prefill_s + shared.fault_s)
            with span("repro.sched.graft"):
                self._caches = self._graft(self._caches, caches, jnp.asarray(slots, jnp.int32))
            with span("repro.sched.tokens"):
                lg = np.asarray(logits)
                # per-request attribution (§12.3): each prompt's own row-groups;
                # expert keys are exact only when the prefill wasn't shared.
                # Skipped entirely when no trace is attached — the common
                # tracing-off path pays nothing for it.
                if self._tracing:
                    for r in reqs:
                        by_request[r.rid] = self.engine.row_keys_for(r.tokens) + (
                            list(expert_keys) if len(reqs) == 1 else []
                        )
                for i, (slot, req) in enumerate(grp):
                    # group costs are shared: every member waited out the batch
                    req.stats.prefill_s += shared.prefill_s
                    req.stats.fault_s += shared.fault_s
                    req.stats.prefill_retries += shared.prefill_retries
                    req.stats.faulted_units += shared.faulted_units
                    req.stats.faulted_bytes += shared.faulted_bytes
                    tok = int(lg[i].argmax())
                    req.out.append(tok)
                    req.stats.steps = 1  # the prefill-produced token
                    req.first_token_t = time.perf_counter()
                    self._pos[slot] = S
                    self._last_tok[slot] = tok
                    self._slots[slot] = req
                    self.stats.admitted += 1
                    admitted += 1
                    if len(req.out) >= req.n_steps:  # single-token request
                        self._retire(slot)
            with span("repro.sched.hints"):
                hints.extend(self.engine.topk_row_hints(lg[i]) for i in range(len(grp)))
                if expert_keys:
                    hints.append(list(expert_keys))
                observed += self.engine.row_keys_for(
                    np.concatenate([r.tokens for r in reqs])
                ) + list(expert_keys)
        with span("repro.sched.hints"):
            self._emit_hints(hints, observed=observed, by_request=by_request)
        return admitted

    def _retire(self, slot: int) -> None:
        req = self._slots[slot]
        assert req is not None
        self._slots[slot] = None
        self._last_tok[slot] = 0
        self._pos[slot] = 0
        self.page_pool.free(slot)  # pages return at retire, ready for reuse
        self.stats.completed += 1
        req.finish()

    def _emit_hints(self, per_slot_hints: list[list[str]],
                    observed: list[str] = (),
                    by_request: Optional[dict] = None) -> None:
        """Feed the prefetcher — first the units this step *actually*
        accessed (``observe`` expands them through the profile-trained
        predictor into ahead-of-schedule hints — DESIGN.md §11.3), then
        the round-robin-merged per-slot next-step hints — and tag the
        live trace with per-request attribution (``by_request``: rid →
        the keys THAT request accessed this step). The unioned demand
        batch already landed in the trace via ``ensure()``; the tags add
        the coincidence-free association signal the replanner and the
        daemon's predictor refresh prefer (DESIGN.md §12.3). Requests
        that finished this step are recorded FIRST (their final step's
        transitions matter too), then their chain state is dropped so a
        freed slot's next occupant never links to them."""
        if by_request:
            tiered = self.server.tiered
            if tiered is not None:
                live = {r.rid for r in self._slots if r is not None}
                for rid, keys in by_request.items():
                    if keys:
                        tiered.record_request(rid, keys)
                    if rid not in live:
                        tiered.end_request(rid)
        pf = self.engine.prefetcher
        if pf is None:
            return
        if observed:
            pf.observe(observed)
        merged = merge_hints(*per_slot_hints)
        if merged:
            pf.hint(merged)

    # -- the serving loop --------------------------------------------------------
    def step(self) -> bool:
        """Admit new work, then advance every active slot one token with a
        single masked decode over the union of their faults. Returns True
        if anything happened (admission or decode)."""
        if self.idle:
            # still a step boundary: the re-tier daemon may tick on
            # wall-clock cadence even while the queue is drained (§12)
            self.engine.tick_retier(steps=0)
            return False
        with span("repro.sched.step"):
            return self._step()

    def _step(self) -> bool:
        admitted = self._admit()
        active = self.active
        self.stats.max_active = max(self.stats.max_active, len(active))
        if not active:
            self.engine.tick_retier(steps=0)
            return admitted > 0

        mask = np.zeros(self.max_batch, bool)
        mask[active] = True
        dbatch = {
            "tokens": jnp.asarray(self._last_tok[:, None]),
            "pos": jnp.asarray(self._pos),
            "active": jnp.asarray(mask),
        }
        # union fault handling: ONE pinned ensure over every active slot's
        # row-groups + one expert retry loop over the union of misses
        step_stats = RequestStats()
        try:
            logits, self._caches, expert_keys = self.engine.decode_once(
                self._decode, self._caches, dbatch, step_stats,
                prefault_tokens=self._last_tok[active], hint=False,
            )
        except Exception as e:
            # same contract as admission: a failed step fault-in must not
            # kill the loop or leave the active slots' submitters waiting
            # forever — fail those requests, return their slots, keep
            # serving the queue
            self.stats.failed += len(active)
            tiered = self.server.tiered
            for i in active:
                req = self._slots[i]
                self._slots[i] = None
                self._last_tok[i] = 0
                self._pos[i] = 0
                self.page_pool.free(i)  # failed slots leak no pages
                if tiered is not None:
                    # failed requests never reach _emit_hints — drop their
                    # trace chain state here or it leaks forever (§12.3)
                    tiered.end_request(req.rid)
                req.finish(error=f"decode step failed: {e!r}")
            return True
        with span("repro.sched.tokens"):
            self.stats.decode_s += step_stats.decode_s
            self.stats.fault_s += step_stats.fault_s
            self.stats.faulted_units += step_stats.faulted_units
            self.stats.faulted_bytes += step_stats.faulted_bytes
            self.stats.decode_retries += step_stats.decode_retries
            self.stats.steps += 1
            self.admission.note_step(step_stats.decode_s + step_stats.fault_s, len(active))
            # paged-KV accounting (§16.2): the masked decode streams the full
            # (max_batch, max_seq) cache; the paged layout would stream only
            # the active slots' occupied pages. The roofline gate compares.
            self.stats.kv_tokens_dense += self.max_batch * self.engine.max_seq
            self.stats.kv_tokens_paged += self.page_pool.step_kv_positions(
                {i: int(self._pos[i]) + 1 for i in active}
            )
            self.stats.kv_pages_high_water = self.page_pool.stats.high_water_pages

            # units this step demand-accessed: the active slots' embed
            # row-groups plus every routed expert (resident ones included —
            # post-retier they key most of the transition table)
            observed = self.engine.row_keys_for(self._last_tok[active]) + list(expert_keys)
            # per-request attribution (§12.3), captured before the token
            # updates below overwrite _last_tok: each slot's own row-groups;
            # union-detected experts are exact only with a single active slot.
            # Skipped when no trace is attached (nothing would record it).
            by_request = {
                self._slots[i].rid: self.engine.row_keys_for(self._last_tok[i:i + 1]) + (
                    list(expert_keys) if len(active) == 1 else []
                )
                for i in active
            } if self._tracing else {}

            lg = np.asarray(logits)
            going: list[int] = []  # slots that decode again next step
            for i in active:
                req = self._slots[i]
                tok = int(lg[i].argmax())
                req.out.append(tok)
                req.stats.steps += 1
                self._last_tok[i] = tok
                self._pos[i] += 1
                if len(req.out) >= req.n_steps:
                    self._retire(i)
                else:
                    going.append(i)
        with span("repro.sched.hints"):
            hints = [self.engine.topk_row_hints(lg[i]) for i in going]
            if expert_keys:
                hints.append(list(expert_keys))
            self._emit_hints(hints, observed=observed, by_request=by_request)
        # the step is fully over (pins released, outputs materialized):
        # the ONLY place the serving loop advances the re-tier daemon
        self.engine.tick_retier()
        return True

    def run(self, *, max_steps: Optional[int] = None) -> None:
        """Drive the loop until the queue is empty and every slot is free
        (or ``max_steps`` decode steps have run)."""
        steps = 0
        while not self.idle:
            self.step()
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break

    def serve_forever(self, stop: threading.Event, poll_s: float = 0.002) -> None:
        """Loop until ``stop`` is set, sleeping briefly when idle — the
        threaded form used by the traffic benchmark and launcher."""
        while not stop.is_set():
            if not self.step():
                time.sleep(poll_s)


def _graft_slot_cache(big: Any, small: Any, slots: jax.Array) -> Any:
    """Write an admission group's prefill cache (B=k) into slot rows
    ``slots`` ((k,) int32) of the batched decode cache.

    Each slot row is rebuilt from zeros (matching ``Model.init_cache``)
    with the prefill prefix written along the sequence axis — exactly the
    sequential path's ``_graft_prefill_cache`` semantics, applied per
    batch row. Scanned-group leaves are (n_groups, B, ...): batch is axis
    1 there, axis 0 everywhere else. Jit-compiled by the scheduler (one
    signature per group size × prompt length) with the big cache donated,
    so steady-state admission is a handful of in-place row updates, not a
    full-cache copy."""
    big_flat = dict(flatten_with_paths(big))
    out = dict(big_flat)
    for path, s in flatten_with_paths(small):
        b = out[path]
        s = jnp.asarray(s)
        ax = 1 if path.startswith("groups.") else 0
        row_shape = b.shape[:ax] + b.shape[ax + 1:]
        for i in range(s.shape[ax]):
            src = jax.lax.index_in_dim(s, i, axis=ax, keepdims=False).astype(b.dtype)
            if src.shape == row_shape:
                row = src  # carry-state leaf (mlstm C/n/m, lru, conv): full copy
            else:
                idx = tuple(slice(0, d) for d in src.shape)
                row = jnp.zeros(row_shape, b.dtype).at[idx].set(src)
            b = jax.lax.dynamic_update_index_in_dim(b, row, slots[i], ax)
        out[path] = b
    return tree_from_flat(out)
