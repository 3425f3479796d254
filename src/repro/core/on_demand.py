"""④ On-demand loading — the ``rewrite_template`` analogue (DESIGN.md §8).

The paper rewrites each optional function to a 2-line stub that, on first
invocation, reads the lightweight file, materializes the separated code, and
executes it. Here the "stub" is a *placeholder buffer*: tier-1 leaves start
as zero-filled device arrays (correctly sharded, so the compiled executable
is identical to the fully-loaded one); the ``OnDemandLoader`` faults real
bytes in unit-by-unit when requests need them.

Correctness backstop, as in the paper: a misprediction (cold expert routed
to, cold vocab row sampled) is a *latency* event — fetch + decompress +
device upload + row scatter — never a failure. ``ensure()`` is idempotent
and thread-safe.

Beyond the seed's monotone loaded-set, residency is a per-unit state
machine governed by a ``ResidencyManager`` (DESIGN.md §8.1):

    COLD ──ensure()/prefetch──▶ LOADING ──install──▶ RESIDENT
      ▲                                                 │
      └───────────── evict (LRU, unpinned) ◀────────────┘

A configurable device-bytes budget bounds the RESIDENT set; when an
install would exceed it, least-recently-used unpinned units are evicted
back to placeholder zeros before the new bytes land — resident bytes never
exceed the budget while any victim is evictable. Eviction never touches a
LOADING unit (an in-flight read can't be yanked) and never touches a
pinned unit (``ensure(pin=True)`` / ``release()`` bracket a request step).

Telemetry (DESIGN.md §11): ``start_trace()`` attaches an ``AccessTrace``
that records every request-path ``ensure()`` batch — per-unit fault and
touch counts, request-phase tags, co-access pairs, and batch→batch
transitions. The trace is the input to the profile-guided replanner
(``core/retier.py``) and the predictive prefetcher (``core/prefetch.py``).
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.optional_store import OptionalStore, ReadStats
from repro.core.partition import TierPlan, Unit
from repro.utils.spans import span
from repro.utils.tree import flatten_with_paths, tree_from_flat

# residency states (DESIGN.md §8.1)
COLD = "cold"          # placeholder zeros on device; bytes not charged
LOADING = "loading"    # a read/decode/upload is in flight; never evictable
RESIDENT = "resident"  # real bytes on device; charged against the budget


@dataclass
class LoadEvent:
    key: str
    nbytes: int
    fetch_s: float
    upload_s: float
    t: float = 0.0          # monotonic completion time
    source: str = "fault"   # "fault" | "prefetch" | "preload"
    phase: str = ""         # request phase at load time ("prefill" | "decode" | "")


class AccessTrace:
    """Demand-access telemetry for profile-guided re-tiering (DESIGN.md §11).

    One trace aggregates every *request-path* access batch (an
    ``ensure(source="fault")`` call) into the four signals the replanner
    and the predictive prefetcher consume:

      * ``touches[key]``  — demand touches, warm or cold (a preloaded
        resident that is never touched is a demotion candidate);
      * ``faults[key]``   — demand touches that found the unit not yet
        RESIDENT (the cold-start misses re-tiering should promote away);
      * ``phases[key]``   — per-phase fault counts (``prefill``/``decode``
        tags set by the engine via ``TieredParams.set_phase``);
      * ``pairs`` / ``transitions`` — co-access pairs within one batch and
        batch→next-batch unit transitions, the predictor's raw material.

    Pair/transition recording is skipped for batches larger than
    ``max_assoc_batch`` keys (a bulk ``ensure_all`` would otherwise record
    a quadratic blob of meaningless associations). Serialization is
    deterministic: ``to_json`` sorts every key so record → JSON → replan
    is reproducible byte-for-byte (tests/test_retier.py).

    **Request attribution** (DESIGN.md §12.3): in traffic mode one demand
    batch unions every active slot's accesses, so ``pairs``/``transitions``
    conflate per-request patterns with cross-request coincidence. The
    scheduler additionally calls ``record_request(rid, keys)`` with each
    request's *own* accesses per step; those land in ``request_pairs`` /
    ``request_transitions`` — the coincidence-free association signal.
    ``end_request(rid)`` drops the per-request chain state at retirement
    so a long-lived trace never links across unrelated requests.

    **Higher-order signals** (DESIGN.md §14.2, schema v3): alongside the
    first-order ``transitions``, ``record`` keeps

      * ``phase_transitions[phase][a][b]`` — the same batch→next-batch
        counts split by the *current* batch's request phase, so a
        predictor can rank prefill successors and decode successors
        separately (a unit hot during prefill is often cold in decode);
      * ``transitions2[(a2, a1)][b]`` — second-order context: ``a2`` from
        the batch two steps back, ``a1`` from the previous batch, ``b``
        in the current one. Recorded only for batches of at most
        ``max_order2_batch`` keys (the pair fan-out is quadratic where
        first-order is linear).

    **Lifecycle** (DESIGN.md §12.2): one trace = one observation window.
    ``merge(newer, decay=d)`` folds windows across cadence ticks (and
    across replicas): this window's counts are scaled by ``d`` before the
    newer window's are added, so the hot set tracks shifting workloads
    (``d=1`` → plain lifetime sum, ``d=0`` → newest window only). Entries
    decaying below ``prune_below`` are dropped. ``merge_all`` folds a
    *list* of same-tick windows (one per fleet replica) with plain-sum
    semantics — commutative and associative, so the fleet plan cannot
    depend on replica pull order (DESIGN.md §14.1). The schema carries a
    ``version`` field next to artifact.json's; merging or loading across
    schema versions raises (v1/v2 documents, which predate the request-
    attribution and higher-order fields respectively, still load).
    """

    VERSION = 3

    def __init__(self, *, max_assoc_batch: int = 64, max_order2_batch: int = 8):
        self.version = self.VERSION
        self.max_assoc_batch = max_assoc_batch
        self.max_order2_batch = max_order2_batch
        self.batches = 0
        self.touches: dict[str, int] = {}
        self.faults: dict[str, int] = {}
        self.phases: dict[str, dict[str, int]] = {}
        self.pairs: dict[tuple, int] = {}           # (a, b) with a < b
        self.transitions: dict[str, dict[str, int]] = {}
        self.request_pairs: dict[tuple, int] = {}   # same-request co-access
        self.request_transitions: dict[str, dict[str, int]] = {}
        # schema v3: phase-conditioned + second-order successor counts
        self.phase_transitions: dict[str, dict[str, dict[str, int]]] = {}
        self.transitions2: dict[tuple, dict[str, int]] = {}  # (a2, a1) -> {b: n}
        self._last_batch: list[str] = []
        self._last2_batch: list[str] = []  # the batch before _last_batch
        self._last_by_request: dict[int, list[str]] = {}

    def record(self, keys: Iterable[str], cold: Iterable[str], phase: str = "") -> None:
        """Record one demand batch. ``keys`` is everything the request
        touched; ``cold`` the subset that was not RESIDENT. Caller holds
        the owning loader's lock (one writer at a time)."""
        keys, cold = list(keys), list(cold)
        if not keys:
            return
        self.batches += 1
        for k in keys:
            self.touches[k] = self.touches.get(k, 0) + 1
        for k in cold:
            self.faults[k] = self.faults.get(k, 0) + 1
            by_phase = self.phases.setdefault(k, {})
            by_phase[phase] = by_phase.get(phase, 0) + 1
        if len(keys) <= self.max_assoc_batch:
            for i, a in enumerate(keys):
                for b in keys[i + 1:]:
                    if a != b:
                        pair = (a, b) if a < b else (b, a)
                        self.pairs[pair] = self.pairs.get(pair, 0) + 1
            # _last_batch is [] or an under-cap batch by construction
            cur = set(keys)
            by_phase = self.phase_transitions.setdefault(phase, {})
            for a in self._last_batch:
                succ = [b for b in cur if b != a]
                if not succ:
                    continue  # never leave an empty successor dict behind
                nxt = self.transitions.setdefault(a, {})
                pnxt = by_phase.setdefault(a, {})
                for b in succ:
                    nxt[b] = nxt.get(b, 0) + 1
                    pnxt[b] = pnxt.get(b, 0) + 1
            if not by_phase:
                del self.phase_transitions[phase]
            # second-order context (DESIGN.md §14.2): the quadratic
            # (a2, a1) fan-out gets a tighter cap than first-order
            cap2 = self.max_order2_batch
            if (
                len(keys) <= cap2
                and 0 < len(self._last_batch) <= cap2
                and 0 < len(self._last2_batch) <= cap2
            ):
                for a2 in self._last2_batch:
                    for a1 in self._last_batch:
                        succ = [b for b in cur if b != a1 and b != a2]
                        if not succ:
                            continue
                        nxt2 = self.transitions2.setdefault((a2, a1), {})
                        for b in succ:
                            nxt2[b] = nxt2.get(b, 0) + 1
            self._last2_batch = self._last_batch
            self._last_batch = keys
        else:
            self._last_batch = []
            self._last2_batch = []

    # -- request attribution (DESIGN.md §12.3) ---------------------------------
    def record_request(self, rid: int, keys: Iterable[str]) -> None:
        """Record the units ONE request accessed this step. Unlike
        ``record`` (which sees the scheduler's unioned batch), pairs and
        step→step transitions recorded here are same-request by
        construction — the replanner/predictor can separate per-request
        patterns from cross-request coincidence. Caller holds the owning
        loader's lock."""
        keys = list(dict.fromkeys(keys))
        if not keys or len(keys) > self.max_assoc_batch:
            self._last_by_request.pop(rid, None)
            return
        for i, a in enumerate(keys):
            for b in keys[i + 1:]:
                pair = (a, b) if a < b else (b, a)
                self.request_pairs[pair] = self.request_pairs.get(pair, 0) + 1
        cur = set(keys)
        for a in self._last_by_request.get(rid, ()):
            succ = [b for b in cur if b != a]
            if not succ:
                continue
            nxt = self.request_transitions.setdefault(a, {})
            for b in succ:
                nxt[b] = nxt.get(b, 0) + 1
        self._last_by_request[rid] = keys

    def end_request(self, rid: int) -> None:
        """Retire one request's chain state: its last step never links to
        whatever unrelated request next reuses the slot."""
        self._last_by_request.pop(rid, None)

    # -- window merging (DESIGN.md §12.2) ---------------------------------------
    def merge(self, newer: "AccessTrace", *, decay: float = 1.0,
              prune_below: float = 0.5) -> "AccessTrace":
        """Fold a newer observation window onto this one: every count here
        is scaled by ``decay`` (0 ≤ decay ≤ 1), then the newer window's
        counts are added; entries below ``prune_below`` after scaling are
        dropped (a unit nobody touches for a few windows genuinely leaves
        the profile instead of lingering at 1e-9). Returns a NEW trace;
        neither input is mutated, and the merged trace carries no
        in-flight chain state (``_last_batch``/``_last_by_request``).
        Deterministic: same inputs → byte-identical ``to_json``. Raises on
        schema-version mismatch, and on ``newer is self`` (an aliased
        merge would read counts it is also summing into — fold a window
        into a *different* history object, or snapshot first)."""
        if newer is self:
            raise ValueError(
                "cannot merge an AccessTrace into itself (aliasing); "
                "merge a rotated window or a snapshot copy instead"
            )
        if not 0.0 <= decay <= 1.0:
            raise ValueError(f"decay must be in [0, 1], got {decay!r}")
        if self.version != newer.version:
            raise ValueError(
                f"cannot merge AccessTrace schema v{self.version} with v{newer.version}"
            )

        def norm(v):
            # canonical numbers: integral floats store as ints, so a
            # decay=1 merge of int windows round-trips byte-identically
            return int(v) if isinstance(v, float) and v.is_integer() else v

        def counts(old: dict, new: dict) -> dict:
            out: dict = {}
            for k, v in old.items():
                sv = v if decay == 1 else v * decay
                if sv >= prune_below:
                    out[k] = norm(sv)
            for k, v in new.items():
                out[k] = norm(out.get(k, 0) + v)
            return {k: v for k, v in out.items() if v >= prune_below}

        def nested(old: dict, new: dict) -> dict:
            sub = {k: counts(old.get(k, {}), new.get(k, {}))
                   for k in set(old) | set(new)}
            return {k: v for k, v in sub.items() if v}

        merged = AccessTrace(
            max_assoc_batch=max(self.max_assoc_batch, newer.max_assoc_batch),
            max_order2_batch=max(self.max_order2_batch, newer.max_order2_batch))
        merged.batches = norm(
            (self.batches if decay == 1 else self.batches * decay) + newer.batches)
        merged.touches = counts(self.touches, newer.touches)
        merged.faults = counts(self.faults, newer.faults)
        merged.phases = nested(self.phases, newer.phases)
        merged.pairs = counts(self.pairs, newer.pairs)
        merged.transitions = nested(self.transitions, newer.transitions)
        merged.request_pairs = counts(self.request_pairs, newer.request_pairs)
        merged.request_transitions = nested(
            self.request_transitions, newer.request_transitions)
        merged.phase_transitions = {
            ph: sub
            for ph in set(self.phase_transitions) | set(newer.phase_transitions)
            if (sub := nested(self.phase_transitions.get(ph, {}),
                              newer.phase_transitions.get(ph, {})))
        }
        merged.transitions2 = nested(self.transitions2, newer.transitions2)
        return merged

    @classmethod
    def merge_all(cls, windows, *, prune_below: float = 0.5) -> "AccessTrace":
        """Fold a list of observation windows into one trace with *plain
        sum* semantics (``decay=1``). Integer counts make the sum
        commutative and associative, so the result — and any fleet plan
        derived from it — is independent of the order replicas were
        pulled in (DESIGN.md §14.1, property-tested in tests/test_fleet.py).
        An empty window list returns an empty trace (a fleet tick where
        every replica was idle is a no-op, not an error)."""
        out = cls()
        for w in windows:
            out = out.merge(w, decay=1.0, prune_below=prune_below)
        return out

    # -- serialization (deterministic; the --profile-out format) --------------
    def to_dict(self) -> dict:
        return {
            "version": self.version,
            "batches": self.batches,
            "touches": {k: self.touches[k] for k in sorted(self.touches)},
            "faults": {k: self.faults[k] for k in sorted(self.faults)},
            "phases": {
                k: {p: v[p] for p in sorted(v)}
                for k, v in sorted(self.phases.items())
            },
            "pairs": [[a, b, self.pairs[(a, b)]] for a, b in sorted(self.pairs)],
            "transitions": {
                k: {n: v[n] for n in sorted(v)}
                for k, v in sorted(self.transitions.items())
            },
            "request_pairs": [
                [a, b, self.request_pairs[(a, b)]] for a, b in sorted(self.request_pairs)
            ],
            "request_transitions": {
                k: {n: v[n] for n in sorted(v)}
                for k, v in sorted(self.request_transitions.items())
            },
            "phase_transitions": {
                ph: {
                    k: {n: v[n] for n in sorted(v)}
                    for k, v in sorted(tbl.items())
                }
                for ph, tbl in sorted(self.phase_transitions.items())
            },
            # tuple keys flatten to sorted [a2, a1, b, n] rows (JSON-safe)
            "transitions2": [
                [a2, a1, b, v[b]]
                for (a2, a1), v in sorted(self.transitions2.items())
                for b in sorted(v)
            ],
        }

    def to_json(self) -> str:
        import json

        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_dict(cls, d: dict) -> "AccessTrace":
        # older documents still load — v1 predates request attribution,
        # v2 the higher-order tables; the absent fields default empty.
        # Anything else is a schema we don't know.
        if d.get("version") not in (1, 2, cls.VERSION):
            raise ValueError(f"unsupported AccessTrace version {d.get('version')!r}")
        t = cls()
        # counts stay as-parsed (int, or float from a decayed merge) so a
        # save → load → save round-trip is byte-identical
        t.batches = d.get("batches", 0)
        t.touches = dict(d.get("touches", {}))
        t.faults = dict(d.get("faults", {}))
        t.phases = {k: dict(v) for k, v in d.get("phases", {}).items()}
        t.pairs = {(a, b): n for a, b, n in d.get("pairs", [])}
        t.transitions = {k: dict(v) for k, v in d.get("transitions", {}).items()}
        t.request_pairs = {(a, b): n for a, b, n in d.get("request_pairs", [])}
        t.request_transitions = {
            k: dict(v) for k, v in d.get("request_transitions", {}).items()
        }
        t.phase_transitions = {
            ph: {k: dict(v) for k, v in tbl.items()}
            for ph, tbl in d.get("phase_transitions", {}).items()
        }
        for a2, a1, b, n in d.get("transitions2", []):
            t.transitions2.setdefault((a2, a1), {})[b] = n
        return t

    @classmethod
    def from_json(cls, s: str) -> "AccessTrace":
        import json

        return cls.from_dict(json.loads(s))

    def save(self, path: str) -> None:
        """Atomic temp+rename write (the same commit rule every artifact
        writer in this repo follows)."""
        import json
        import os

        tmp = path + ".partial"
        with open(tmp, "w") as f:
            json.dump(self.to_dict(), f, sort_keys=True)
        os.replace(tmp, path)

    @classmethod
    def load(cls, path: str) -> "AccessTrace":
        import json

        with open(path) as f:
            return cls.from_dict(json.load(f))


@dataclass
class LoaderStats:
    events: list = field(default_factory=list)
    misses: int = 0          # synchronous request-path loads
    hits: int = 0            # already-resident touches
    prefetch_hits: int = 0   # first demand-touch of a prefetch-loaded unit
    prefetch_waits: int = 0  # demand overlapped an in-flight prefetch load
    evictions: int = 0
    evicted_bytes: int = 0
    refaults: int = 0        # loads of a previously-evicted unit
    stalls: list = field(default_factory=list)  # per-ensure miss-stall seconds
    preads_issued: int = 0     # pread syscalls the demand path issued
    frames_fetched: int = 0    # store frames those reads delivered
    coalesced_bytes: int = 0   # payload bytes arriving via multi-frame preads

    @property
    def total_miss_bytes(self) -> int:
        return sum(e.nbytes for e in self.events if e.source != "prefetch")

    @property
    def request_fault_bytes(self) -> int:
        """Bytes moved synchronously ON the request path (source="fault"
        only — excludes cold-start preload and background prefetch). The
        quantity one profile→re-tier cycle should shrink (RQ7)."""
        return sum(e.nbytes for e in self.events if e.source == "fault")

    @property
    def total_loaded_bytes(self) -> int:
        return sum(e.nbytes for e in self.events)

    @property
    def prefetch_hit_rate(self) -> float:
        """Of demand-touched cold units, fraction hidden by the prefetcher."""
        n = self.prefetch_hits + self.prefetch_waits + self.misses
        return (self.prefetch_hits + self.prefetch_waits) / n if n else 0.0

    def stall_percentile(self, q: float) -> float:
        if not self.stalls:
            return 0.0
        return float(np.percentile(np.asarray(self.stalls), q))


class ResidencyManager:
    """Per-unit residency state machine + device-bytes budget accounting.

    All mutation happens under a shared lock (the owner's ``RLock``); a
    condition on that lock lets demand loads wait for in-flight prefetch
    loads instead of duplicating the read. LRU order is an ``OrderedDict``
    over RESIDENT keys, refreshed on every touch; eviction walks it oldest
    first, skipping pinned units.
    """

    def __init__(self, lock: threading.RLock, *, budget_bytes: Optional[int] = None):
        self._lock = lock
        self.cv = threading.Condition(lock)
        self.budget_bytes = budget_bytes
        self._state: dict[str, str] = {}
        self._nbytes: dict[str, int] = {}
        self._pins: dict[str, int] = {}
        # logical access clock: advanced once per public access (one ensure
        # batch = one tick), stamped onto keys at commit/touch. Keys
        # committed by the same batch share a stamp; ``select_victims``
        # breaks those ties by key so eviction order never depends on dict
        # insertion order (reproducible rq2/rq8 byte counts).
        self._clock = 0
        self._stamp: dict[str, int] = {}
        # ordered set of RESIDENT keys, old→new; dict order IS the recency
        self._lru: OrderedDict[str, None] = OrderedDict()
        self._loaders: dict[str, str] = {}   # LOADING key -> claimant source
        self._sources: dict[str, str] = {}   # RESIDENT key -> load source
        self._unclaimed_prefetch: set[str] = set()  # prefetched, not yet demanded
        self._evicted_once: set[str] = set()
        self.resident_bytes = 0
        self.max_resident_bytes = 0  # high-water mark (budget invariant probe)
        self.overshoot_events = 0    # installs that couldn't make room

    # -- queries (lock held by caller or uncontended reads) -------------------
    def state_of(self, key: str) -> str:
        return self._state.get(key, COLD)

    def is_resident(self, key: str) -> bool:
        return self._state.get(key) == RESIDENT

    @property
    def resident_keys(self) -> set:
        with self._lock:
            return set(self._lru)

    def pins_of(self, key: str) -> int:
        return self._pins.get(key, 0)

    def loader_of(self, key: str) -> str:
        """Source that owns an in-flight LOADING key ("" if none)."""
        return self._loaders.get(key, "")

    def charged_bytes(self) -> int:
        """Recomputed sum of per-key charges over the RESIDENT set — the
        audit cross-check against the running ``resident_bytes`` counter
        (caller holds the lock)."""
        return sum(self._nbytes.get(k, 0) for k in self._lru)

    def advance_clock(self) -> int:
        """One tick per public access batch (caller holds the lock). Every
        commit/touch within the batch shares the new stamp."""
        self._clock += 1
        return self._clock

    # -- transitions (caller MUST hold the lock) ------------------------------
    def begin_load(self, key: str, source: str) -> bool:
        """COLD → LOADING. False if already loading/resident (caller skips
        or waits); the claimant that got True owns the read."""
        if self._state.get(key, COLD) != COLD:
            return False
        self._state[key] = LOADING
        self._loaders[key] = source
        return True

    def commit_load(self, key: str, nbytes: int, source: str) -> None:
        """LOADING → RESIDENT: charge the budget, make the key MRU."""
        assert self._state.get(key) == LOADING, (key, self._state.get(key))
        self._state[key] = RESIDENT
        self._nbytes[key] = nbytes
        self._sources[key] = source
        self._loaders.pop(key, None)
        self._lru[key] = None
        self._lru.move_to_end(key)
        self._stamp[key] = self._clock
        if source == "prefetch":
            self._unclaimed_prefetch.add(key)
        self.resident_bytes += nbytes
        self.max_resident_bytes = max(self.max_resident_bytes, self.resident_bytes)
        self.cv.notify_all()

    def abort_load(self, key: str) -> None:
        """LOADING → COLD (read failed or prefetcher shut down mid-claim)."""
        if self._state.get(key) == LOADING:
            self._state[key] = COLD
            self._loaders.pop(key, None)
            self.cv.notify_all()

    def touch(self, key: str, *, claim_prefetch: bool = True) -> str:
        """Refresh LRU recency on an access. With ``claim_prefetch`` (demand
        touches) returns "prefetch" exactly once per prefetch-loaded unit —
        the hit-accounting credit; hint touches pass False so they don't
        consume the credit a later demand touch should claim."""
        if key in self._lru:
            self._lru.move_to_end(key)
            self._stamp[key] = self._clock
        if claim_prefetch and key in self._unclaimed_prefetch:
            self._unclaimed_prefetch.discard(key)
            return "prefetch"
        return ""

    def pin(self, keys: Iterable[str]) -> None:
        for k in keys:
            self._pins[k] = self._pins.get(k, 0) + 1

    def release(self, keys: Iterable[str]) -> None:
        for k in keys:
            n = self._pins.get(k, 0) - 1
            if n <= 0:
                self._pins.pop(k, None)
            else:
                self._pins[k] = n

    def select_victims(self, need_bytes: int) -> list[str]:
        """Oldest-first unpinned RESIDENT keys freeing ≥ need_bytes (best
        effort — may free less if the evictable pool is too small). Keys
        with equal access stamps (committed by one batched ensure) tie-break
        by key, so eviction order is deterministic regardless of the dict
        insertion order the batch happened to produce."""
        victims, freed = [], 0
        for k in sorted(self._lru, key=lambda k: (self._stamp.get(k, 0), k)):
            if freed >= need_bytes:
                break
            if self._pins.get(k, 0) > 0:
                continue
            victims.append(k)
            freed += self._nbytes.get(k, 0)
        return victims

    def evict_commit(self, key: str) -> int:
        """RESIDENT → COLD after the placeholder reinstall; credits bytes."""
        assert self._state.get(key) == RESIDENT and self._pins.get(key, 0) == 0
        nb = self._nbytes.pop(key, 0)
        self._state[key] = COLD
        self._lru.pop(key, None)
        self._stamp.pop(key, None)
        self._sources.pop(key, None)
        self._unclaimed_prefetch.discard(key)
        self._evicted_once.add(key)
        self.resident_bytes -= nb
        return nb

    def was_evicted(self, key: str) -> bool:
        return key in self._evicted_once

    def wait_resident(self, key: str, timeout: float = 30.0) -> bool:
        """Block until ``key`` leaves LOADING (caller holds the lock via the
        condition). True if it became RESIDENT; False on abort/timeout."""
        deadline = time.monotonic() + timeout
        while self._state.get(key) == LOADING:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return False
            self.cv.wait(remaining)
        return self._state.get(key) == RESIDENT


class TieredParams:
    """The live parameter tree of a cold-started server.

    * tier-0 leaves: real weights, device-resident from cold start.
    * tier-1 leaves: allocated at full shape (placeholder zeros) and filled
      in-place per unit (experts: ``at[e].set``; rows: row-slice scatter;
      whole-leaf: swap). Allocation is eager but *bytes* move lazily —
      device memory for tier-1 is the explicit rent paid for the identical
      executable; strict deployments can zero-page it.

    ``tree()`` returns the current param pytree to pass into compiled fns.
    ``device_budget_bytes`` bounds real-resident tier-1 bytes; see
    ``ResidencyManager`` for the eviction contract.
    """

    def __init__(
        self,
        tree: dict,
        plan: TierPlan,
        store: Optional[OptionalStore],
        *,
        device_budget_bytes: Optional[int] = None,
        shard_divisors: Optional[dict] = None,
    ):
        self._tree = tree
        self._flat = dict(flatten_with_paths(tree))
        self.plan = plan
        self.store = store
        # mesh-sharded serving (DESIGN.md §15.1): per-leaf shard counts.
        # A unit of a leaf split D ways costs nbytes/D *per device*, so the
        # budget/arbiter charge is divided by the owning leaf's divisor
        # (absent → 1 → byte-identical to unsharded accounting). IO stats
        # (LoadEvent, faulted_bytes) always keep raw host bytes.
        self._shard_div: dict[str, int] = dict(shard_divisors or {})
        self.stats = LoaderStats()
        self.trace: Optional[AccessTrace] = None  # attach via start_trace()
        self._phase = ""  # request phase tag for trace/LoadEvent (DESIGN.md §11)
        self._lock = threading.RLock()
        self.residency = ResidencyManager(self._lock, budget_bytes=device_budget_bytes)
        # host-level governance (core/arbiter.py, DESIGN.md §13): when a
        # HostArbiter registers this instance it sets these, disables the
        # private budget, and the install paths below route make-room
        # through it — called with NO lock held (arbiter lock orders
        # before every tenant lock).
        self.arbiter = None
        self.tenant_name = ""
        self._all_units: dict[str, Unit] = {}
        for d in plan.decisions.values():
            for u in d.units:
                self._all_units[u.key] = u

    # -- telemetry (DESIGN.md §11) --------------------------------------------
    def start_trace(self, trace: Optional[AccessTrace] = None) -> AccessTrace:
        """Attach an ``AccessTrace``; every subsequent request-path
        ``ensure()`` batch is recorded into it. Returns the trace."""
        with self._lock:
            self.trace = trace if trace is not None else AccessTrace()
            return self.trace

    def rotate_trace(self, fresh: Optional[AccessTrace] = None) -> Optional[AccessTrace]:
        """Atomically swap in a fresh trace and return the finished window
        (None if tracing was never started). The re-tiering daemon's
        cadence primitive (DESIGN.md §12): the returned window is no
        longer written to and can be read/merged without the loader lock."""
        with self._lock:
            old = self.trace
            if old is not None:
                self.trace = fresh if fresh is not None else AccessTrace(
                    max_assoc_batch=old.max_assoc_batch)
            return old

    def trace_snapshot(self) -> Optional[AccessTrace]:
        """A consistent copy of the live trace (None if tracing is off) —
        readable while request threads keep recording into the original."""
        with self._lock:
            return AccessTrace.from_dict(self.trace.to_dict()) if self.trace else None

    def record_request(self, rid: int, keys: Iterable[str]) -> None:
        """Attribute one request's step accesses in the live trace
        (scheduler-aware profiling, DESIGN.md §12.3). No-op without a trace."""
        with self._lock:
            if self.trace is not None:
                self.trace.record_request(rid, keys)

    def end_request(self, rid: int) -> None:
        with self._lock:
            if self.trace is not None:
                self.trace.end_request(rid)

    def set_phase(self, phase: str) -> None:
        """Tag subsequent loads/trace batches with a request phase
        ("prefill" | "decode" | ""). Set by the engine around each step."""
        self._phase = phase

    # -- residency ----------------------------------------------------------
    def is_resident(self, key: str) -> bool:
        return self.residency.is_resident(key)

    def mark_resident(self, key: str) -> None:
        """Force-mark without moving bytes (testing/bootstrap escape hatch)."""
        with self._lock:
            if self.residency.begin_load(key, "mark"):
                self.residency.advance_clock()
                self.residency.commit_load(key, self.unit_charge(key), "mark")

    @property
    def resident_keys(self) -> set:
        return self.residency.resident_keys

    @property
    def resident_bytes(self) -> int:
        return self.residency.resident_bytes

    def resident_fraction(self) -> float:
        n = len(self._all_units)
        return len(self.residency.resident_keys) / n if n else 1.0

    def _unit_nbytes(self, key: str) -> int:
        u = self._all_units.get(key)
        if u is not None and u.nbytes:
            return u.nbytes
        if self.store is not None and key in self.store.entries:
            return self.store.entries[key].rsize
        return 0

    def unit_charge(self, key: str, nbytes: Optional[int] = None) -> int:
        """Device-budget charge for one unit: its host bytes divided by the
        owning leaf's shard count (§15.1 per-shard accounting; ceil so a
        charge is never rounded to free). Equal to the raw bytes when the
        leaf is replicated or no mesh is attached."""
        nb = self._unit_nbytes(key) if nbytes is None else nbytes
        u = self._all_units.get(key)
        div = self._shard_div.get(u.path, 1) if u is not None else 1
        return nb if div <= 1 else -(-nb // div)

    # -- the rewrite_template analogue ---------------------------------------
    def ensure(self, keys: Iterable[str], *, pin: bool = False, source: str = "fault") -> int:
        """Fault in the given unit keys. Returns bytes moved (0 = warm hit).

        This is the two-line stub body grown into the state machine: check
        residency, claim COLD keys, read+decode off the lock, evict-to-fit,
        install, and wait out any loads another thread (the prefetcher)
        already owns. Idempotent and thread-safe; with ``pin=True`` the
        keys stay unevictable until a matching ``release()``.
        """
        keys = list(dict.fromkeys(keys))
        t_start = time.perf_counter()
        res = self.residency
        to_load: list[str] = []
        wait_for: list[tuple[str, str]] = []  # (key, in-flight loader source)
        cold: list[str] = []  # not RESIDENT at demand time (trace faults)
        with self._lock:
            res.advance_clock()  # one stamp per ensure batch
            for k in keys:
                st = res.state_of(k)
                if st == RESIDENT:
                    if res.touch(k) == "prefetch":
                        self.stats.prefetch_hits += 1
                    else:
                        self.stats.hits += 1
                elif st == LOADING:
                    cold.append(k)
                    wait_for.append((k, res.loader_of(k)))
                else:
                    cold.append(k)
                    if res.begin_load(k, source):
                        to_load.append(k)
            if pin:
                res.pin(keys)
            if self.trace is not None and source == "fault":
                self.trace.record(keys, cold, self._phase)
        if not to_load and not wait_for:
            return 0

        moved = 0
        if to_load:
            if self.store is None:
                with self._lock:
                    for k in to_load:
                        res.abort_load(k)
                raise RuntimeError(
                    f"tier-1 units {to_load[:3]}... required but no optional store attached"
                )
            ordered = sorted(to_load, key=lambda k: self.store.entries[k].offset)
            # vectored fault-in (DESIGN.md §17.2): one coalesced read pass
            # per chunk, then decode+install per key. Chunking bounds the
            # compressed bytes held at once to ~a chunk's worth while still
            # letting manifest-adjacent frames share preads.
            CHUNK = 32
            for base in range(0, len(ordered), CHUNK):
                chunk = ordered[base:base + CHUNK]
                try:
                    rs = ReadStats()
                    with span("repro.tier1.read", source=source) as read:
                        bufs = self.store.read_raw_many(chunk, stats=rs)
                        read.nbytes = sum(len(b) for b in bufs.values())
                except Exception:
                    with self._lock:
                        # roll back every not-yet-loaded claim, or they'd
                        # sit in LOADING with no loader forever
                        for k in ordered[base:]:
                            res.abort_load(k)
                    raise
                self.stats.preads_issued += rs.preads
                self.stats.frames_fetched += rs.frames
                self.stats.coalesced_bytes += rs.coalesced_bytes
                total_csize = sum(
                    self.store.entries[k].csize for k in chunk) or 1
                for j, key in enumerate(chunk):
                    try:
                        with span("repro.tier1.decode", source=source) as dec:
                            arr = self.store.decode(key, bufs[key])  # no lock
                            dec.nbytes = arr.nbytes
                    except Exception:
                        with self._lock:
                            for k in ordered[base + j:]:
                                res.abort_load(k)
                        raise
                    # amortize the chunk's read wall csize-proportionally so
                    # per-event fetch_s still sums to time actually spent
                    fetch_s = dec.seconds + read.seconds * (
                        self.store.entries[key].csize / total_csize)
                    with span("repro.tier1.install", source=source,
                              nbytes=arr.nbytes) as inst:
                        charge = self.unit_charge(key, arr.nbytes)
                        if self.arbiter is not None:
                            # cross-tenant make-room BEFORE taking our own lock
                            # (arbiter lock orders first; it may lock other tenants)
                            self.arbiter.make_room(self, charge)
                        with self._lock:
                            self._evict_to_fit(charge)
                            self._install(self._all_units[key], arr)
                            res.commit_load(key, charge, source)
                            if res.was_evicted(key):
                                self.stats.refaults += 1
                            if source == "fault":  # preload is not a request-path miss
                                self.stats.misses += 1
                    self.stats.events.append(
                        LoadEvent(key, arr.nbytes, fetch_s, inst.seconds,
                                  t=time.monotonic(), source=source,
                                  phase=self._phase)
                    )
                    moved += arr.nbytes

        if wait_for:
            with self._lock:
                for k, loader in wait_for:
                    while not res.is_resident(k):
                        if res.begin_load(k, source):
                            # the other loader aborted — take over synchronously
                            self._lock.release()
                            try:
                                moved += self._load_one(k, source)
                            finally:
                                self._lock.acquire()
                            break
                        with span("repro.tier1.wait", source=source):
                            became = res.wait_resident(k)
                        if not became and res.state_of(k) == LOADING:
                            # never return with the key silently cold — the
                            # caller would compute on placeholder zeros
                            raise RuntimeError(
                                f"timed out waiting for in-flight load of {k!r}"
                            )
                        # COLD after an abort: loop back and try to claim
                    else:
                        res.touch(k)
                        if loader == "prefetch":
                            self.stats.prefetch_waits += 1
                        # a sibling demand load already counted its miss
        if source == "fault":  # miss-stall percentiles are request-path only
            self.stats.stalls.append(time.perf_counter() - t_start)
        return moved

    def _load_one(self, key: str, source: str) -> int:
        """Synchronous load of one already-claimed key (takeover path)."""
        res = self.residency
        try:
            rs = ReadStats()
            with span("repro.tier1.read", source=source) as read:
                buf = self.store.read_raw(key, stats=rs)
                read.nbytes = len(buf)
            with span("repro.tier1.decode", source=source) as dec:
                arr = self.store.decode(key, buf)
                dec.nbytes = arr.nbytes
        except Exception:
            with self._lock:
                res.abort_load(key)
            raise
        self.stats.preads_issued += rs.preads
        self.stats.frames_fetched += rs.frames
        with span("repro.tier1.install", source=source, nbytes=arr.nbytes) as inst:
            charge = self.unit_charge(key, arr.nbytes)
            if self.arbiter is not None:
                self.arbiter.make_room(self, charge)
            with self._lock:
                self._evict_to_fit(charge)
                self._install(self._all_units[key], arr)
                res.commit_load(key, charge, source)
                if source == "fault":
                    self.stats.misses += 1
        self.stats.events.append(
            LoadEvent(key, arr.nbytes, read.seconds + dec.seconds, inst.seconds,
                      t=time.monotonic(), source=source, phase=self._phase)
        )
        return arr.nbytes

    def ensure_all(self) -> int:
        """Load every tier-1 unit (degrades to the 'full' baseline)."""
        return self.ensure(list(self._all_units))

    def touch(self, keys: Iterable[str]) -> None:
        """Refresh LRU recency without demand-access accounting (used by
        predictive hints on already-resident units)."""
        with self._lock:
            self.residency.advance_clock()
            for k in keys:
                self.residency.touch(k, claim_prefetch=False)

    def release(self, keys: Iterable[str]) -> None:
        """Unpin keys pinned by ``ensure(pin=True)`` — they become
        evictable again once every pin is released. If pinned installs
        overshot the budget, the excess is reclaimed here (LRU first), so
        over-budget residency never outlives the step that forced it."""
        with self._lock:
            self.residency.release(keys)
            self._evict_to_budget()
        if self.arbiter is not None:
            # host-level reclaim happens outside our lock (lock ordering:
            # the arbiter may need to lock other tenants)
            self.arbiter.rebalance()

    def _evict_to_budget(self) -> None:
        """Evict LRU unpinned units until resident bytes fit the budget.
        Caller holds the lock."""
        res = self.residency
        if res.budget_bytes is None:
            return
        need = res.resident_bytes - res.budget_bytes
        if need <= 0:
            return
        for k in res.select_victims(need):
            self._evict_one(k)

    # -- prefetch integration (DESIGN.md §8.2) -------------------------------
    def claim_for_prefetch(self, key: str) -> bool:
        """COLD → LOADING on behalf of the prefetcher's reader thread."""
        if key not in self._all_units:
            return False
        with self._lock:
            return self.residency.begin_load(key, "prefetch")

    def abort_prefetch(self, key: str) -> None:
        with self._lock:
            self.residency.abort_load(key)

    def install_prefetched(self, key: str, arr: np.ndarray, fetch_s: float = 0.0) -> int:
        """Upload one staged host array claimed via ``claim_for_prefetch``.

        The host-side dtype conversion/copy happens *before* taking the
        shared lock (leaf dtypes are fixed at allocation), so request-path
        ``ensure()`` calls are not serialized behind the bulk of the
        background upload work.
        """
        unit = self._all_units.get(key)
        if unit is None or self.residency.state_of(key) != LOADING:
            return 0
        nbytes = arr.nbytes
        charge = self.unit_charge(key, nbytes)
        with span("repro.prefetch.install", source="prefetch", nbytes=nbytes) as inst:
            host = jnp.asarray(arr, dtype=self._flat[unit.path].dtype)
            if self.arbiter is not None:
                self.arbiter.make_room(self, charge)
            with self._lock:
                if self.residency.state_of(key) != LOADING:
                    return 0
                self.residency.advance_clock()
                self._evict_to_fit(charge)
                self._install(unit, host)
                self.residency.commit_load(key, charge, "prefetch")
        self.stats.events.append(
            LoadEvent(key, nbytes, fetch_s, inst.seconds,
                      t=time.monotonic(), source="prefetch", phase=self._phase)
        )
        return nbytes

    # -- eviction -------------------------------------------------------------
    def _evict_to_fit(self, incoming_nbytes: int) -> None:
        """Evict LRU unpinned units until the incoming bytes fit the budget.
        Caller holds the lock. If nothing is evictable the install proceeds
        (correctness over budget) and the overshoot is counted."""
        res = self.residency
        budget = res.budget_bytes
        if budget is None:
            return
        need = res.resident_bytes + incoming_nbytes - budget
        if need <= 0:
            return
        for k in res.select_victims(need):
            self._evict_one(k)
        if res.resident_bytes + incoming_nbytes > budget:
            res.overshoot_events += 1

    def _evict_one(self, key: str) -> int:
        """Reinstall the placeholder for one RESIDENT unpinned unit."""
        unit = self._all_units[key]
        self._install_placeholder(unit)
        nb = self.residency.evict_commit(key)
        self.stats.evictions += 1
        self.stats.evicted_bytes += nb
        return nb

    def evict(self, keys: Iterable[str]) -> int:
        """Explicitly evict resident, unpinned units. Returns bytes freed."""
        freed = 0
        with self._lock:
            for k in keys:
                if self.residency.is_resident(k) and self.residency.pins_of(k) == 0:
                    freed += self._evict_one(k)
        return freed

    def eviction_candidates(self) -> list:
        """Locked snapshot of this instance's evictable pool for the host
        arbiter's global victim pass (DESIGN.md §13.1): ``(key, nbytes,
        stamp)`` for every RESIDENT, unpinned unit, oldest stamp first.
        LOADING and pinned keys are structurally absent; the arbiter's
        subsequent ``evict()`` re-validates under the lock anyway (the
        snapshot may race a pin)."""
        with self._lock:
            res = self.residency
            return [
                (k, res._nbytes.get(k, 0), res._stamp.get(k, 0))
                for k in res._lru
                if res.pins_of(k) == 0
            ]

    # -- installation --------------------------------------------------------
    def _install(self, unit: Unit, arr: np.ndarray) -> None:
        leaf = self._flat[unit.path]
        host = jnp.asarray(arr, dtype=leaf.dtype)
        if not unit.sel and unit.rows is None:
            new = jax.device_put(host, self._leaf_sharding(leaf))
        elif unit.rows is not None:
            lo, hi = unit.rows
            new = leaf.at[unit.sel + (slice(lo, hi),)].set(host) if unit.sel else leaf.at[lo:hi].set(host)
        else:  # (layer,) expert slice
            new = leaf.at[unit.sel].set(host)
        self._set_leaf(unit.path, new)

    def _install_placeholder(self, unit: Unit) -> None:
        """The eviction inverse of ``_install``: zero the unit's slice."""
        leaf = self._flat[unit.path]
        if not unit.sel and unit.rows is None:
            new = jax.device_put(jnp.zeros(leaf.shape, leaf.dtype), self._leaf_sharding(leaf))
        elif unit.rows is not None:
            lo, hi = unit.rows
            new = leaf.at[unit.sel + (slice(lo, hi),)].set(0) if unit.sel else leaf.at[lo:hi].set(0)
        else:
            new = leaf.at[unit.sel].set(0)
        self._set_leaf(unit.path, new)

    def _leaf_sharding(self, leaf):
        try:
            return leaf.sharding
        except Exception:
            return None

    def _set_leaf(self, path: str, new) -> None:
        self._flat[path] = new
        node = self._tree
        parts = path.split(".")
        for p in parts[:-1]:
            node = node[p]
        node[parts[-1]] = new

    # -- access ----------------------------------------------------------------
    def tree(self) -> dict:
        return self._tree

    def leaf(self, path: str):
        return self._flat[path]


def placeholder_tree(abstract: Any, tier0: dict[str, np.ndarray], plan: TierPlan, put: Callable) -> dict:
    """Build the initial live tree: tier-0 leaves from real weights, tier-1
    leaves as placeholder zeros (identical shapes/shardings → identical
    compiled executable; the paper's rewritten function with an empty body).

    ``put(path, host_array_or_none, leaf_spec)`` -> device array; the
    cold-start manager passes a sharded device_put.
    """
    out: dict[str, Any] = {}
    for path, leaf in flatten_with_paths(abstract):
        if plan.decisions[path].tier == 0:
            out[path] = put(path, tier0[path], leaf)
        else:
            out[path] = put(path, None, leaf)
    return tree_from_flat(out)
