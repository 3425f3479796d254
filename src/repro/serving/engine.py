"""Batched generation engine with on-demand fault-in (the request path).

The request loop implements the paper's runtime contract: execution never
fails on a cold unit — it *faults*. Two fault classes:

  * vocab rows — exact pre-fault: the ids a step will embed are known
    before the step runs, so the engine ensures their row-groups first
    (zero retries, the paper's best case);
  * routed experts — detected post-hoc from the step's router-usage masks
    (riding the cache pytree, see models.transformer._stash_usage); a miss
    faults the expert units in and re-runs the step. Because routing can
    shift once real weights replace placeholders, the retry iterates to a
    fixed point (bounded; ≤3 in practice — measured in RQ4).

With a prefetcher attached (DESIGN.md §8.2) the engine also *emits access
hints* per decoded batch so the next step's units load off the request
path: the top-k candidate tokens of the current logits hint the vocab
row-groups the next embed will touch, and each step's routed-expert set
hints the experts the next step is most likely to reuse (keeping them
LRU-fresh and re-pulling them if the budget evicted them).

Under a device-bytes budget, every step's units are pinned for the
duration of the step (``ensure(pin=True)`` … ``release()``), so eviction
can never zero a unit between its fault-in and the compute that needs it.

Decode caches round-trip through the engine, which strips the usage masks
before the next step (they are outputs, not state).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.cold_start import ColdStartServer
from repro.utils.spans import span
from repro.utils.tree import flatten_with_paths

MAX_FAULT_RETRIES = 3


@dataclass
class RequestStats:
    prefill_s: float = 0.0
    decode_s: float = 0.0
    fault_s: float = 0.0
    prefill_retries: int = 0
    decode_retries: int = 0
    faulted_bytes: int = 0
    faulted_units: int = 0
    steps: int = 0
    prefetch_hits: int = 0   # demand touches served by the prefetcher
    hinted_units: int = 0    # hints this request emitted (accepted)


def _strip_usage(tree: Any) -> Any:
    if isinstance(tree, dict):
        return {k: _strip_usage(v) for k, v in tree.items() if k != "moe_usage"}
    return tree


def _usage_masks(caches: Any) -> dict[str, np.ndarray]:
    return {
        p: np.asarray(v)
        for p, v in flatten_with_paths(caches)
        if p.endswith("moe_usage")
    }


class GenerationEngine:
    def __init__(self, server: ColdStartServer, *, max_seq: int = 256, hint_topk: int = 8):
        self.server = server
        self.model = server.model
        self.max_seq = max_seq
        self.hint_topk = hint_topk
        self.prefetcher = getattr(server, "prefetcher", None)
        self.retier_daemon = getattr(server, "retier_daemon", None)
        self._expert_units_index = self._build_expert_index()
        self._row_group = self._embed_row_group()

    def tick_retier(self, steps: int = 1) -> None:
        """Advance the online re-tiering daemon (DESIGN.md §12). Call sites
        sit BETWEEN steps — after a step's pins are released, before the
        next step's fault-in — never inside one. ``generate()`` ticks per
        decode step; the scheduler ticks at its own step() boundary (this
        method is NOT called from prefill_step/decode_once, which the
        scheduler runs inside its step)."""
        if self.retier_daemon is not None:
            self.retier_daemon.maybe_tick(steps)

    def _embed_row_group(self) -> int:
        tiered = self.server.tiered
        if tiered is None:
            return 0
        dec = tiered.plan.decisions.get("embed")
        if dec is None or dec.tier != 1 or dec.granularity != "rows":
            return 0
        return dec.units[0].rows[1] - dec.units[0].rows[0]

    # -- expert usage → unit keys --------------------------------------------
    def _build_expert_index(self) -> dict[str, list[str]]:
        """usage path ("groups.u0.moe_usage") -> expert-table param paths."""
        tiered = self.server.tiered
        if tiered is None:
            return {}
        idx: dict[str, list[str]] = {}
        for path, dec in tiered.plan.decisions.items():
            if dec.granularity != "expert" or dec.tier != 1:
                continue
            # "<prefix>.moe.w_gate" is signalled by "<prefix>.moe_usage"
            prefix = path.rsplit(".moe.", 1)[0]
            idx.setdefault(f"{prefix}.moe_usage", []).append(path)
        return idx

    def _expert_keys_from_usage(self, usage: dict[str, np.ndarray]) -> list[str]:
        """Every expert unit the step's router selected — resident ones
        included (the caller separates misses; demand-touching residents
        keeps the §11 access trace honest about what the step used)."""
        keys: list[str] = []
        for upath, mask in usage.items():
            for table in self._expert_units_index.get(upath, ()):
                if mask.ndim == 2:  # scanned: (n_groups, E)
                    for l, e in zip(*np.nonzero(mask)):
                        keys.append(f"{table}#l{l}e{e}")
                else:  # unscanned: (E,)
                    for e in np.nonzero(mask)[0]:
                        keys.append(f"{table}#e{e}")
        return keys

    # -- vocab pre-fault -------------------------------------------------------
    def row_keys_for(self, tokens: np.ndarray) -> list[str]:
        """Embed row-group unit keys the given token ids live in ([] when
        the embed table is not row-tiered). Used for the exact pre-fault
        and, by the scheduler, to tell the predictive prefetcher which
        units a step actually accessed (DESIGN.md §11.3)."""
        if not self._row_group:
            return []
        return [f"embed#rg{g}" for g in np.unique(np.asarray(tokens) // self._row_group)]

    def _prefault_rows(self, tokens: np.ndarray, stats: RequestStats, pins: list) -> list[str]:
        """Ensure (and pin) the row-groups this step will embed. Keys are
        appended to ``pins`` *before* the ensure so the caller's finally
        block releases them even if the load raises mid-batch. Returns the
        accessed keys."""
        tiered = self.server.tiered
        if tiered is None or not self._row_group:
            return []
        needed = self.row_keys_for(tokens)
        n_cold = sum(1 for k in needed if not tiered.is_resident(k))
        pins.extend(needed)
        with span("repro.engine.row_ensure") as ens:
            moved = ens.nbytes = tiered.ensure(needed, pin=True)
        stats.fault_s += ens.seconds
        stats.faulted_bytes += moved
        stats.faulted_units += n_cold  # incl. waits on in-flight prefetch
        return needed

    def _fault_experts(
        self, caches: Any, stats: RequestStats, pins: list, wait: str
    ) -> tuple[list[str], list[str]]:
        """Ensure (and pin) every expert the last step routed to — resident
        experts included: their demand touches keep the access trace honest
        (an unprofiled preloaded expert would look demotable, DESIGN.md
        §11.1) and their pins block mid-step eviction. Returns
        ``(newly_faulted, used)``: retry is needed only while the first is
        nonempty, while hints/predictor observations want the second (a
        warm expert is still the strongest predictor of the next step);
        pins are registered before the load, as in ``_prefault_rows``.
        Pulling the usage masks is the host's first wait on the step's
        outputs: a span named ``wait``."""
        tiered = self.server.tiered
        if tiered is None:
            return [], []
        with span(wait):
            usage = _usage_masks(caches)
        used = self._expert_keys_from_usage(usage)
        if not used:
            return [], []
        miss = [k for k in used if not tiered.is_resident(k)]
        pins.extend(used)
        with span("repro.engine.expert_ensure") as ens:
            moved = ens.nbytes = tiered.ensure(used, pin=True)
        stats.fault_s += ens.seconds
        stats.faulted_bytes += moved
        stats.faulted_units += len(miss)
        return miss, used

    # -- hint emission (DESIGN.md §8.2) ----------------------------------------
    def topk_row_hints(self, logits) -> list[str]:
        """Embed row-group keys for the top-k candidate tokens of ``logits``
        ((V,), (B, V), …) — the vocab half of a predictive hint. The
        scheduler calls this per active slot and round-robin-merges the
        lists (``core.prefetch.merge_hints``) so no slot starves another."""
        if not self._row_group:
            return []
        flat = np.asarray(logits).reshape(-1, np.asarray(logits).shape[-1])
        k = min(self.hint_topk, flat.shape[-1])
        top = np.argpartition(-flat, k - 1, axis=-1)[:, :k]
        return [f"embed#rg{g}" for g in np.unique(top // self._row_group)]

    def _hint_next_step(
        self, logits, expert_keys: list[str], stats: RequestStats,
        accessed: list[str] = (),
    ) -> None:
        """Predictively warm the units the *next* step will likely touch:
        the learned successors of what this step actually accessed (via
        ``Prefetcher.observe`` when a profile-trained predictor is
        attached — DESIGN.md §11.3), then row-groups of the top-k candidate
        tokens, plus this step's routed experts (the strongest predictor of
        next-step routing)."""
        if self.prefetcher is None:
            return
        if accessed:
            stats.hinted_units += self.prefetcher.observe(accessed)
        hints: list[str] = list(expert_keys) + self.topk_row_hints(logits)
        if hints:
            stats.hinted_units += self.prefetcher.hint(hints)

    # -- step primitives (shared by generate() and the scheduler) ---------------
    def prefill_step(self, tokens: jax.Array, stats: RequestStats, *, hint: bool = True):
        """Prefill one prompt batch under the fault-in contract: exact vocab
        pre-fault, expert retry to fixed point, with the step's units pinned
        until its outputs are materialized. Returns
        ``(logits, caches, expert_keys)`` — caches usage-stripped, ready for
        grafting; ``expert_keys`` are the experts this step routed to,
        resident ones included (the scheduler merges them into its
        cross-slot hint/observe stream when ``hint`` is off)."""
        server = self.server
        tiered = server.tiered
        B, S = tokens.shape
        prefill = server.compiled_prefill(B, S)
        step_pins: list[str] = []
        expert_keys: list[str] = []
        accessed: list[str] = []
        if tiered is not None:
            tiered.set_phase("prefill")
        try:
            accessed += self._prefault_rows(np.asarray(tokens), stats, step_pins)
            fault0 = stats.fault_s
            t0 = time.perf_counter()
            batch = {"tokens": tokens}
            with span("repro.engine.prefill_dispatch"):
                logits, caches = prefill(server.live_params(), batch)
            for _ in range(MAX_FAULT_RETRIES):
                newly, used = self._fault_experts(caches, stats, step_pins,
                                                  "repro.engine.prefill_wait")
                seen = set(expert_keys)
                expert_keys.extend(k for k in used if k not in seen)
                if not newly:
                    break
                stats.prefill_retries += 1
                with span("repro.engine.prefill_dispatch"):
                    logits, caches = prefill(server.live_params(), batch)
            with span("repro.engine.prefill_wait"):
                jax.block_until_ready(logits)
            stats.prefill_s += time.perf_counter() - t0 - (stats.fault_s - fault0)
        finally:
            if tiered is not None and step_pins:
                tiered.release(step_pins)
        # hint after release: evicted/still-cold predictions are loadable now
        if hint:
            with span("repro.engine.hints"):
                self._hint_next_step(logits, expert_keys, stats,
                                     accessed=accessed + expert_keys)
        return logits, _strip_usage(caches), expert_keys

    def decode_once(
        self,
        decode_fn,
        caches: Any,
        dbatch: dict,
        stats: RequestStats,
        *,
        prefault_tokens: Optional[np.ndarray] = None,
        hint: bool = True,
    ):
        """One decode step under the fault-in contract. ``prefault_tokens``
        defaults to the batch tokens; the scheduler passes only the active
        slots' tokens so free/completed slots never fault vocab rows.
        Returns ``(logits, new_caches, expert_keys)``, caches
        usage-stripped and ready for the next step."""
        server = self.server
        tiered = server.tiered
        if prefault_tokens is None:
            prefault_tokens = np.asarray(dbatch["tokens"])
        step_pins: list[str] = []
        expert_keys: list[str] = []
        accessed: list[str] = []
        if tiered is not None:
            tiered.set_phase("decode")
        try:
            accessed += self._prefault_rows(np.asarray(prefault_tokens), stats, step_pins)
            fault0 = stats.fault_s
            t0 = time.perf_counter()
            with span("repro.engine.decode_dispatch"):
                logits, new_caches = decode_fn(server.live_params(), caches, dbatch)
            for _ in range(MAX_FAULT_RETRIES):
                newly, used = self._fault_experts(new_caches, stats, step_pins,
                                                  "repro.engine.decode_wait")
                seen = set(expert_keys)
                expert_keys.extend(k for k in used if k not in seen)
                if not newly:
                    break
                stats.decode_retries += 1
                with span("repro.engine.decode_dispatch"):
                    logits, new_caches = decode_fn(server.live_params(), caches, dbatch)
            with span("repro.engine.decode_wait"):
                jax.block_until_ready(logits)
            stats.decode_s += time.perf_counter() - t0 - (stats.fault_s - fault0)
        finally:
            if tiered is not None and step_pins:
                tiered.release(step_pins)
        if hint:
            with span("repro.engine.hints"):
                self._hint_next_step(logits, expert_keys, stats,
                                     accessed=accessed + expert_keys)
        return logits, _strip_usage(new_caches), expert_keys

    # -- request path -----------------------------------------------------------
    def generate(
        self,
        tokens: jax.Array,  # (B, S) prompt
        n_steps: int,
        *,
        greedy: bool = True,
    ) -> tuple[np.ndarray, RequestStats]:
        model, server = self.model, self.server
        tiered = server.tiered
        stats = RequestStats()
        hits_before = tiered.stats.prefetch_hits + tiered.stats.prefetch_waits if tiered else 0
        B, S = tokens.shape
        S_max = self.max_seq
        if S + n_steps > S_max:
            # a bare assert would vanish under ``python -O``; the request
            # path must reject over-length work unconditionally (the
            # scheduler turns this into an admission rejection)
            raise ValueError(
                f"request needs {S + n_steps} positions (prompt {S} + {n_steps} steps) "
                f"but the engine was compiled for max_seq={S_max}"
            )

        decode = server.compiled_decode(B)

        logits, caches, _ = self.prefill_step(tokens, stats)
        self.tick_retier()  # between steps, never inside one (§12.1)

        # move prefill caches into a max-length decode cache
        big = model.init_cache(B, S_max, multimodal=False)
        caches = _graft_prefill_cache(big, caches)

        out = [np.asarray(jnp.argmax(logits, -1), np.int32)]
        stats.steps = 1  # the prefill-produced token is step #1 (RQ4's
        # faults/step would otherwise be skewed for short generations)
        for step in range(n_steps - 1):
            tok = jnp.asarray(out[-1])[:, None]
            pos = jnp.full((B,), S + step, jnp.int32)
            dbatch = {"tokens": tok, "pos": pos}
            logits, caches, _ = self.decode_once(decode, caches, dbatch, stats)
            out.append(np.asarray(jnp.argmax(logits, -1), np.int32))
            stats.steps += 1
            self.tick_retier()
        if tiered is not None:
            stats.prefetch_hits = (
                tiered.stats.prefetch_hits + tiered.stats.prefetch_waits - hits_before
            )
        return np.stack(out, axis=1), stats


def _graft_prefill_cache(big: Any, small: Any) -> Any:
    """Write prefill-sized K/V prefixes into max-length zero caches; carry
    states (lru/mlstm/conv/latent) transfer as-is."""

    def graft(b, s):
        s = jnp.asarray(s)
        if b.shape == s.shape:
            return s
        # match leading dims; write the prefix along the (single) seq axis
        idx = tuple(slice(0, d) for d in s.shape)
        return b.at[idx].set(s)

    return jax.tree.map(graft, big, small)
