"""Cold-start manager: artifact → serving-ready state, with the paper's
three variants measured end to end.

Phases mirror Fig. 1 of the paper, adapted per DESIGN.md §2:

  read    — storage → host RAM (the paper's "application transmission")
  upload  — host → device + placeholder allocation ("code loading", part 1)
  compile — XLA compilation of the warm entry set ("code loading", part 2 —
            the interpreter-import analogue)

Modes:
  before — monolithic bundle: every collection read, all params uploaded
  after1 — collection-pruned bundle (① Optional File Elimination applied)
  after2 — two-tier artifact: tier-0 read+uploaded, tier-1 placeholder-
           allocated, hot units preloaded from the optional store; misses
           fault in at request time (the full FaaSLight pipeline)

Residency policies (DESIGN.md §4.2) — device-budget presets for the tier-1
residency layer (``RESIDENCY_PRESETS``):
  strict — tight budget (25% of tier-1 bytes), no prefetch: misses pay the
           full fault latency, cold units are evicted aggressively
  stats  — medium budget (50% of tier-1 bytes) + async prefetch driven by
           engine hints (the profile-guided follow-up's predictive load)
  full   — unlimited budget + prefetch (≈ *before* warm performance once
           every unit has been touched; tiered artifact layout retained)
An explicit ``device_budget_bytes`` overrides the preset's budget.

Multi-model hosting (DESIGN.md §13): pass the same ``host_arbiter=`` handle
to several ``cold_start()`` calls and the servers share ONE host-wide
device budget — each preset's budget *fraction* is reinterpreted as the
tenant's relative **share** of that budget (strict→0.25, stats→0.5,
full→1.0), and eviction becomes a global, heat-weighted decision across
every co-resident model instead of a private per-model one.
"""

from __future__ import annotations

import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import tensorstore_lite as tsl
from repro.core.analyzer import AnalysisResult
from repro.core.arbiter import HostArbiter
from repro.core.on_demand import AccessTrace, TieredParams
from repro.core.optional_store import OptionalStore
from repro.core.prefetch import Prefetcher, TransitionPredictor
from repro.core.retier_daemon import RetierDaemon
from repro.core import snapshot as server_snapshot
from repro.models.zoo import Model
from repro.sharding.rules import param_shardings, spec_shard_divisor
from repro.utils.spans import span
from repro.utils.tree import flatten_with_paths, tree_from_flat

# residency policy -> (tier-1 budget fraction, prefetch enabled); DESIGN.md §4.2
RESIDENCY_PRESETS: dict = {
    "strict": (0.25, False),
    "stats": (0.5, True),
    "full": (None, True),
}


@dataclass
class ColdStartReport:
    """The phases of one cold start, each timed by a span of the same
    interval (``repro.utils.spans``): ``read_s`` (``repro.cold.read``),
    ``upload_s`` (``repro.cold.upload``), ``compile_s``
    (``repro.cold.compile``). After2's upload has three parts, one span
    each: ``tier0_put_s`` puts tier-0 on the device; ``placeholder_s``
    builds the tier-1 placeholders on the host, puts them, and waits for
    every put of both; ``preload_s`` faults the hot set in.
    ``bytes_uploaded`` counts every byte put on the device: tier-0,
    ``placeholder_bytes`` (each tier-1 leaf at full shape), the preload's
    and a restore's. ``t_start`` is the cold start's start on the span
    clock (``time.perf_counter``)."""

    mode: str
    read_s: float = 0.0
    upload_s: float = 0.0
    compile_s: float = 0.0
    bytes_read: int = 0
    bytes_uploaded: int = 0
    tier0_put_s: float = 0.0
    placeholder_s: float = 0.0
    placeholder_bytes: int = 0
    preload_s: float = 0.0
    t_start: float = 0.0

    @property
    def total_s(self) -> float:
        return self.read_s + self.upload_s + self.compile_s

    def to_dict(self) -> dict:
        return {
            "mode": self.mode,
            "read_s": self.read_s,
            "upload_s": self.upload_s,
            "compile_s": self.compile_s,
            "total_s": self.total_s,
            "bytes_read": self.bytes_read,
            "bytes_uploaded": self.bytes_uploaded,
            "tier0_put_s": self.tier0_put_s,
            "placeholder_s": self.placeholder_s,
            "placeholder_bytes": self.placeholder_bytes,
            "preload_s": self.preload_s,
            "t_start": self.t_start,
        }


def _block_until_ready(tree: Any) -> None:
    for leaf in jax.tree.leaves(tree):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()


class ColdStartServer:
    """A cold-started model server: live params + compiled warm entries."""

    def __init__(
        self,
        model: Model,
        params: Any,
        report: ColdStartReport,
        *,
        tiered: Optional[TieredParams] = None,
        store: Optional[OptionalStore] = None,
        prefetcher: Optional[Prefetcher] = None,
        retier_daemon: Optional[RetierDaemon] = None,
        artifact_dir: Optional[str] = None,
        admission: Any = None,
        kv_page_size: Optional[int] = None,
        kv_pages: Optional[int] = None,
    ):
        self.model = model
        self.params = params
        self.report = report
        self.tiered = tiered
        self.store = store
        self.prefetcher = prefetcher
        self.retier_daemon = retier_daemon
        self.artifact_dir = artifact_dir
        # default AdmissionPolicy for schedulers built on this server
        # (DESIGN.md §15.2); None → the scheduler's FIFO default
        self.admission = admission
        # default paged-KV pool shape for schedulers (DESIGN.md §16.2);
        # None → page size 16 and a pool exactly covering max_batch×max_seq
        self.kv_page_size = kv_page_size
        self.kv_pages = kv_pages
        self.restore_report: Optional[dict] = None  # set by restore_from=
        self._compiled: dict[tuple, Callable] = {}

    def close(self) -> None:
        """Stop the prefetch threads, flush any in-flight background
        compaction, leave the host pool (if arbitered), and release the
        store handle."""
        if self.prefetcher is not None:
            self.prefetcher.stop()
            self.prefetcher = None
        if self.retier_daemon is not None:
            # a periodic compaction may still be rewriting the artifact on
            # its worker thread (DESIGN.md §17.3) — let it finish (it reads
            # the source store through its own handle) before closing up
            self.retier_daemon.join_compaction(timeout=60.0)
        if self.tiered is not None and self.tiered.arbiter is not None:
            self.tiered.arbiter.unregister(self.tiered.tenant_name)
        if self.store is not None:
            self.store.close()
            self.store = None

    # context-manager form: the launcher/benchmarks wrap serving in
    # ``with cold_start(...) as server`` so a raising request path can
    # never leak the prefetcher's reader/uploader threads
    def __enter__(self) -> "ColdStartServer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    # -- warm-set / on-demand compilation ------------------------------------
    def compiled_prefill(self, B: int, S: int):
        key = ("prefill", B, S)
        if key not in self._compiled:
            fn = jax.jit(lambda p, b: self.model.prefill(p, b))
            self._compiled[key] = fn
        return self._compiled[key]

    def compiled_decode(self, B: int):
        key = ("decode", B)
        if key not in self._compiled:
            fn = jax.jit(lambda p, c, b: self.model.decode_step(p, c, b))
            self._compiled[key] = fn
        return self._compiled[key]

    def compiled_decode_masked(self, B: int):
        """Masked decode over ``max_batch`` slots — the continuous-batching
        scheduler's one compiled decode shape (DESIGN.md §9): inactive rows
        contribute nothing to the usage masks (so a free slot can never
        fault a unit in); their cache rows are rebuilt at next admission."""
        key = ("decode_masked", B)
        if key not in self._compiled:
            fn = jax.jit(lambda p, c, b: self.model.decode_step_masked(p, c, b))
            self._compiled[key] = fn
        return self._compiled[key]

    def live_params(self) -> Any:
        return self.tiered.tree() if self.tiered is not None else self.params

    # -- warm snapshot (DESIGN.md §15.3) --------------------------------------
    def snapshot(self) -> dict:
        """Serialize this server's warm state — residency set + LRU stamps,
        predictor table, artifact identity — as a plain-JSON dict a new
        replica can restore from (``cold_start(restore_from=...)``)."""
        if self.tiered is None:
            raise ValueError("snapshot() needs a tiered (after2) server")
        return server_snapshot.capture(
            self.tiered, prefetcher=self.prefetcher, artifact_dir=self.artifact_dir
        )


def cold_start(
    model: Model,
    artifact_dir: str,
    result: Optional[AnalysisResult] = None,
    *,
    mode: str = "after2",
    warm_shapes: tuple = ((1, 64),),  # (B, S) pairs to pre-compile
    compile_warm_set: bool = True,
    put: Optional[Callable] = None,  # leaf device_put override (sharded serving)
    residency: Optional[str] = None,  # RESIDENCY_PRESETS name (after2 only)
    device_budget_bytes: Optional[int] = None,  # overrides the preset budget
    host_arbiter: Optional[HostArbiter] = None,  # shared host budget (DESIGN.md §13)
    tenant_name: Optional[str] = None,   # arbiter registration name (default: cfg.name)
    tenant_share: Optional[float] = None,  # overrides the preset-derived share
    tenant_floor_bytes: int = 0,         # arbiter never evicts below this
    prefetch: Optional[bool] = None,  # overrides the preset prefetch default
    prefetch_batch_units: int = 8,
    trace: bool = False,  # attach an AccessTrace for profiling (DESIGN.md §11)
    predictor: Optional[TransitionPredictor] = None,  # profile-trained prefetch
    retier_online: bool = False,  # live hot-set adaptation (DESIGN.md §12)
    retier_interval: int = 32,    # daemon cadence, serving steps per tick
    retier_interval_s: Optional[float] = None,  # or wall-clock seconds
    retier_decay: float = 0.5,    # trace-window merge decay per tick
    retier_compact_every: int = 0,  # artifact rewrite every N applies (0 = never)
    fleet=None,                   # FleetController to join (DESIGN.md §14)
    replica_name: Optional[str] = None,  # fleet registration name
    mesh=None,                    # jax Mesh: shard tier-0/tier-1 puts (DESIGN.md §15.1)
    admission=None,               # default AdmissionPolicy for schedulers (§15.2)
    kv_page_size: Optional[int] = None,  # default paged-KV page size (§16.2)
    kv_pages: Optional[int] = None,      # default paged-KV pool size (§16.2)
    restore_from=None,            # snapshot dict or path: warm restore (§15.3)
) -> ColdStartServer:
    """Run one timed cold start. ``result`` is required for after2.

    ``trace=True`` attaches an ``AccessTrace`` to the tiered params so the
    serving run records per-unit demand telemetry (saved by the launcher's
    ``--profile-out``); ``predictor`` arms the prefetcher with a learned
    unit→next-unit table from a prior profiling run (``--retier-from``).
    ``retier_online=True`` attaches a ``RetierDaemon`` (which implies a
    live trace) so the hot set adapts in place without a restart — the
    engine/scheduler tick it between batches. ``fleet=`` registers the
    daemon with a ``FleetController`` (DESIGN.md §14) before the server
    is returned — i.e. before any traffic — so a late joiner against a
    controller with learned state is warm-bootstrapped synchronously.
    All are after2-only and ignored for the monolithic baselines.

    ``mesh=`` threads a jax Mesh through every device_put: tier-0 leaves
    and tier-1 placeholders land as *shards* resolved via the logical-axis
    rules (repro.sharding), and the residency budget/arbiter charge
    per-device bytes (nbytes / shard count) instead of replicated bytes
    (DESIGN.md §15.1). ``restore_from=`` (a snapshot dict or JSON path)
    re-faults a previously-warmed server's residency set and arms its
    predictor before the server is returned (DESIGN.md §15.3).
    """
    if residency is not None and residency not in RESIDENCY_PRESETS:
        raise ValueError(f"unknown residency policy {residency!r}; want one of {sorted(RESIDENCY_PRESETS)}")
    if restore_from is not None and mode != "after2":
        raise ValueError("restore_from= is after2-only (monolithic modes have no residency set)")
    report = ColdStartReport(mode=mode, t_start=time.perf_counter())
    abstract = model.abstract()

    # path-aware device placement: an explicit put= wins; else a mesh
    # resolves each leaf's logical axes to a NamedSharding (same rules as
    # training, so serving shards match checkpointed shards); else plain.
    shardings_flat = None
    if mesh is not None and put is None:
        shardings_flat = dict(
            flatten_with_paths(
                param_shardings(
                    model.logical_axes(), abstract, mesh,
                    fsdp=bool(getattr(model.cfg, "fsdp", True)),
                )
            )
        )
    if put is not None:
        user_put = put
        def _put(path, host):
            return user_put(host)
    elif shardings_flat is not None:
        def _put(path, host):
            sh = shardings_flat.get(path)
            return jax.device_put(host, sh) if sh is not None else jax.device_put(host)
    else:
        def _put(path, host):
            return jax.device_put(host)

    if mode in ("before", "after1"):
        prefix = os.path.join(artifact_dir, mode)
        with span("repro.cold.read") as read:
            flat = tsl.read_bundle(prefix, mmap=False)  # move all bytes
            report.bytes_read = read.nbytes = sum(v.nbytes for v in flat.values())
        with span("repro.cold.upload") as upload:
            # upload the params collection only (other collections have no
            # device-side consumer at serving time, but their bytes were read)
            pflat = {
                p[len("params."):]: v for p, v in flat.items() if p.startswith("params.")
            }
            tree = tree_from_flat({p: _put(p, v) for p, v in pflat.items()})
            _block_until_ready(tree)
            upload.nbytes = sum(v.nbytes for v in pflat.values())
        report.read_s, report.upload_s = read.seconds, upload.seconds
        report.bytes_uploaded = upload.nbytes
        server = ColdStartServer(model, tree, report,
                                 artifact_dir=artifact_dir, admission=admission,
                                 kv_page_size=kv_page_size, kv_pages=kv_pages)
    elif mode == "after2":
        if result is None:
            raise ValueError("after2 cold start needs the AnalysisResult (plan)")
        plan = result.plan
        with span("repro.cold.read") as read:
            tier0 = tsl.read_bundle(os.path.join(artifact_dir, "tier0"), mmap=False)
            store = OptionalStore(os.path.join(artifact_dir, "optional.blob"))
            report.bytes_read = read.nbytes = sum(v.nbytes for v in tier0.values())
        with span("repro.cold.upload") as upload:
            flat_abs = dict(flatten_with_paths(abstract))
            live_flat = dict.fromkeys(flat_abs)
            with span("repro.cold.tier0_put") as put0:
                for path in flat_abs:
                    if plan.decisions[path].tier == 0:
                        live_flat[path] = _put(path, tier0[path])
                        put0.nbytes += tier0[path].nbytes
            with span("repro.cold.placeholder") as placeholder:
                for path, leaf in flat_abs.items():
                    if plan.decisions[path].tier != 0:
                        # the rewritten stub: placeholder zeros, full shape/sharding
                        host = np.zeros(leaf.shape, leaf.dtype)
                        live_flat[path] = _put(path, host)
                        placeholder.nbytes += host.nbytes
                tree = tree_from_flat(live_flat)
                with span("repro.cold.put_wait"):  # every put above, tier-0's too
                    _block_until_ready(tree)
            # per-leaf shard counts for residency accounting (DESIGN.md §15.1):
            # a unit of a D-way-sharded leaf costs nbytes/D per device
            shard_divisors = None
            if shardings_flat is not None:
                shard_divisors = {
                    path: spec_shard_divisor(shardings_flat[path].spec, mesh)
                    for path in flat_abs
                }
            # resolve the residency preset into a budget + prefetch default —
            # or, under a host arbiter, into a relative SHARE of its budget
            budget = device_budget_bytes
            want_prefetch = prefetch
            share = tenant_share
            if residency is not None:
                frac, preset_prefetch = RESIDENCY_PRESETS[residency]
                if host_arbiter is not None:
                    if share is None:
                        share = frac if frac is not None else 1.0
                elif budget is None and frac is not None:
                    # budget fractions apply to *charged* (per-device) tier-1
                    # bytes: under a mesh each leaf counts nbytes/divisor, so
                    # the same preset means the same per-device pressure
                    tier1_charged = plan.tier1_bytes
                    if shard_divisors:
                        tier1_charged = 0
                        for path, leaf in flat_abs.items():
                            if plan.decisions[path].tier != 0:
                                nb = int(np.prod(leaf.shape, dtype=np.int64)) * np.dtype(leaf.dtype).itemsize
                                d = shard_divisors.get(path, 1)
                                tier1_charged += nb if d <= 1 else -(-nb // d)
                    budget = int(frac * tier1_charged)
                    # keep the machine functional: never below two of the
                    # largest units (one incoming + one pinned)
                    max_unit = max((e.rsize for e in store.entries.values()), default=0)
                    budget = max(budget, 2 * max_unit)
                if want_prefetch is None:
                    want_prefetch = preset_prefetch
            tiered = TieredParams(tree, plan, store, device_budget_bytes=budget,
                                  shard_divisors=shard_divisors)
            if host_arbiter is not None:
                # join the host pool BEFORE the hot preload so even cold-start
                # bytes are admitted by the global make-room path
                name = tenant_name or getattr(model.cfg, "name", "") or f"tenant-{id(tiered):x}"
                host_arbiter.register(
                    name, tiered,
                    share=share if share is not None else 1.0,
                    floor_bytes=tenant_floor_bytes,
                )
            if trace or retier_online:  # the daemon needs a live trace to watch
                tiered.start_trace(AccessTrace())
            # preload the hot set (the paper's offline-profiled module-init list)
            hot = [k for d in plan.decisions.values() for k in d.resident_units]
            with span("repro.cold.preload") as preload:
                preload.nbytes = tiered.ensure(hot, source="preload") if hot else 0
        report.read_s, report.upload_s = read.seconds, upload.seconds
        report.tier0_put_s, report.placeholder_s = put0.seconds, placeholder.seconds
        report.preload_s = preload.seconds
        report.placeholder_bytes = placeholder.nbytes
        report.bytes_uploaded = put0.nbytes + placeholder.nbytes + preload.nbytes
        prefetcher = (
            Prefetcher(tiered, batch_units=prefetch_batch_units, predictor=predictor)
            if want_prefetch
            else None
        )
        daemon = None
        if fleet is not None and not retier_online:
            raise ValueError("fleet= needs retier_online=True (the fleet "
                             "federates RetierDaemons, not bare loaders)")
        if retier_online:
            daemon = RetierDaemon(
                tiered, result.reach, prefetcher=prefetcher,
                interval_steps=retier_interval, interval_s=retier_interval_s,
                decay=retier_decay, compact_every=retier_compact_every,
                artifact_dir=artifact_dir,
            )
            if fleet is not None:
                # join the fleet BEFORE traffic: a controller with learned
                # state warm-bootstraps this replica here, synchronously
                name = replica_name or f"replica-{len(fleet.replicas)}"
                fleet.register(name, daemon)
        server = ColdStartServer(model, tree, report, tiered=tiered, store=store,
                                 prefetcher=prefetcher, retier_daemon=daemon,
                                 artifact_dir=artifact_dir, admission=admission,
                                 kv_page_size=kv_page_size, kv_pages=kv_pages)
        if restore_from is not None:
            # warm restore (DESIGN.md §15.3): re-fault the donor's residency
            # set (in LRU order, through the arbiter make-room path) and arm
            # the predictor BEFORE the server admits traffic. Counted in the
            # upload phase — it is bytes moved as part of becoming ready.
            with span("repro.cold.restore") as restore:
                snap = (
                    server_snapshot.load(restore_from)
                    if isinstance(restore_from, str) else restore_from
                )
                server.restore_report = server_snapshot.restore(
                    tiered, snap, prefetcher=prefetcher, artifact_dir=artifact_dir
                )
            report.upload_s += restore.seconds
            report.bytes_uploaded += server.restore_report.get("moved_bytes", 0)
    else:
        raise ValueError(f"unknown mode {mode!r}")

    if compile_warm_set:
        with span("repro.cold.compile") as compile_:
            p = server.live_params()
            for B, S in warm_shapes:
                pb, _ = model.prefill_batch_spec(B, S, multimodal=False)
                pb.pop("frames", None)
                pb.pop("image_embeds", None)
                fn = server.compiled_prefill(B, S)
                _ = fn.lower(p, _zeros_batch(pb)).compile()
                dfn = server.compiled_decode(B)
                cache = model.abstract_cache(B, S, multimodal=False)
                db, _ = model.decode_batch_spec(B)
                _ = dfn.lower(p, _abs_zeros(cache), _zeros_batch(db)).compile()
        report.compile_s = compile_.seconds
    return server


def _zeros_batch(spec: dict) -> dict:
    return {k: jnp.zeros(v.shape, v.dtype) for k, v in spec.items()}


def _abs_zeros(tree: Any) -> Any:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype), tree)
