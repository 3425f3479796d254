"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

The FaaSLight pipeline end-to-end: analyze → build two-tier artifact →
timed cold start (before / after1 / after2) → serve generation requests
through the on-demand engine. This is the paper's experiment harness in
CLI form (benchmarks/bench_rq*.py drive the same path).

Two request modes:
  * one-shot (default): a single batched ``GenerationEngine.generate()``;
  * traffic (``--concurrency N``): N continuous-batching slots served by
    the scheduler (DESIGN.md §9), with ``--requests`` prompts arriving
    open-loop at ``--arrival-rate`` req/s (0 = all at once), reporting
    throughput and per-request p50/p99 latency. Exits nonzero if any
    request failed or never finished.

Profile → re-tier → re-serve (DESIGN.md §11): ``--profile-out t.json``
records the demand-access trace of this serving run (profile with
``--no-prefetch`` so the trace sees every fault); a later run with
``--retier-from t.json`` replans the tier split from the trace, rewrites
the artifact next to the original (``<artifact>/<arch>-retier``), and
arms the prefetcher with the trace's learned unit→next-unit predictor.

Online re-tiering (DESIGN.md §12): ``--retier-online`` replaces that
restart cycle with a live daemon — the serving loop ticks it every
``--retier-interval`` steps; each tick merges the newest trace window
into a ``--retier-decay``-weighted history, replans, and applies the
hot set to the running server (promote = prefetch preload, demote =
eviction). ``--retier-compact-every N`` additionally rewrites the
artifact every N applications so future cold starts boot the adapted
hot set.

Fleet federation (DESIGN.md §14): ``--fleet N`` serves the one-shot
workload through N in-process replicas sharing one ``FleetController``
— each replica's daemon contributes its trace window at every
``fleet.sync()``, the controller replans ONCE from the federated
history, and pushes the residency overlay back to every replica, so a
hot-set shift one replica sees pre-warms all of them.
"""

from __future__ import annotations

import argparse
import json
import os
import threading
import time

import jax
import numpy as np

from repro.checkpoint.manager import clean_partials
from repro.configs import get_config, get_reduced
from repro.configs.base import ModelConfig
from repro.core import (
    AccessTrace,
    DeploymentProfile,
    FleetController,
    HostArbiter,
    TransitionPredictor,
    analyze,
    build_artifact,
    replan_from_trace,
    retier_artifact,
    write_monolithic,
)
from repro.core import snapshot as server_snapshot
from repro.data import DataConfig, SyntheticTokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_debug_mesh
from repro.models.zoo import build_model
from repro.serving import (
    ColdStartServer,
    ContinuousBatchingScheduler,
    GenerationEngine,
    SLOAdmission,
    cold_start,
)
from repro.sharding.rules import param_shardings


def cut_depth(cfg: ModelConfig, layers: int) -> ModelConfig:
    """The published config with only its first ``layers`` layers; every
    width stays as published. The cut must keep every kind of layer the
    model has (a whole period of its layer pattern) and, for MoE models
    with leading dense layers, at least one expert layer."""
    if not 1 <= layers <= cfg.num_layers:
        raise ValueError(f"--layers wants 1..{cfg.num_layers} for {cfg.name}, got {layers}")
    cut = cfg.replace(name=f"{cfg.name}-{layers}L", num_layers=layers)
    missing = set(cfg.attn_kinds) - set(cut.attn_kinds)
    if missing:
        raise ValueError(f"--layers {layers} drops {sorted(missing)} layers of {cfg.name}; "
                         f"keep a whole period of {sorted(set(cfg.attn_kinds))}")
    if cfg.moe is not None and layers <= cfg.moe.first_dense_layers:
        raise ValueError(f"--layers {layers} keeps only the leading dense layers of "
                         f"{cfg.name} ({cfg.moe.first_dense_layers}); keep an expert layer")
    return cut


def parse_args(argv=None):
    """The launcher's command line -> ``(args, mesh)``; usage errors exit 2."""
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="the architecture's toy-width CPU variant")
    ap.add_argument("--layers", type=int, default=0,
                    help="keep only the first N layers, every width as "
                         "published: the depth cut that fits one chip "
                         "(0 = published depth)")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and of the prompts")
    ap.add_argument("--mode", default="after2", choices=["before", "after1", "after2"])
    ap.add_argument("--artifact-dir", default="artifacts")
    ap.add_argument("--batch", type=int, default=2)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen-steps", type=int, default=8)
    ap.add_argument("--resident-experts", type=int, default=1)
    ap.add_argument("--hot-vocab", type=float, default=0.25)
    ap.add_argument("--policy", default="stats", choices=["strict", "stats", "full"],
                    help="residency budget preset (DESIGN.md §4.2); also shapes the profile")
    ap.add_argument("--device-budget-bytes", type=int, default=0,
                    help="override the preset's tier-1 device budget (0 = preset default)")
    ap.add_argument("--host-budget-bytes", type=int, default=0,
                    help="govern residency through a HostArbiter with this "
                         "host-wide device budget (DESIGN.md §13) instead of a "
                         "private per-model budget — the single-tenant form of "
                         "the multi-model pool benchmarks/bench_rq9_zoo.py "
                         "exercises (after2 only; 0 = off)")
    ap.add_argument("--no-prefetch", action="store_true",
                    help="disable the async prefetcher even where the preset enables it")
    ap.add_argument("--concurrency", type=int, default=0,
                    help="traffic mode: serve through N continuous-batching slots (0 = one-shot)")
    ap.add_argument("--requests", type=int, default=8,
                    help="traffic mode: number of requests to submit")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="traffic mode: open-loop Poisson arrivals, req/s (0 = all at once)")
    ap.add_argument("--profile-out", default="",
                    help="write this run's demand-access trace (AccessTrace JSON) "
                         "here at exit; profile with --no-prefetch so the trace "
                         "sees every fault (DESIGN.md §11; after2 only)")
    ap.add_argument("--retier-from", default="",
                    help="re-tier the artifact from a prior --profile-out trace "
                         "before cold start (promote demand-faulted units, demote "
                         "untouched residents) and drive the predictive "
                         "prefetcher from its transition table (after2 only)")
    ap.add_argument("--retier-online", action="store_true",
                    help="attach the online re-tiering daemon (DESIGN.md §12): "
                         "watch the live access trace and adapt the hot set in "
                         "place — promote = prefetch preload, demote = eviction "
                         "— with ZERO restarts (after2 only)")
    ap.add_argument("--retier-interval", type=int, default=16,
                    help="online re-tier cadence in serving steps (default 16)")
    ap.add_argument("--retier-decay", type=float, default=0.5,
                    help="per-tick decay of the merged trace history in [0, 1]: "
                         "1 = lifetime counts, 0 = newest window only")
    ap.add_argument("--retier-compact-every", type=int, default=0,
                    help="online mode: rewrite the artifact (out-of-place, "
                         "rename-committed) every N plan applications so the "
                         "NEXT cold start boots the adapted hot set (0 = never)")
    ap.add_argument("--mesh", default="",
                    help="shard serving over a DATAxMODEL debug mesh (e.g. 2x4): "
                         "tier-0 load and tier-1 faults device_put shards, the "
                         "residency budget charges per-device bytes (DESIGN.md "
                         "§15.1; needs that many devices — on CPU set "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N)")
    ap.add_argument("--admission", default="fifo", choices=["fifo", "slo"],
                    help="scheduler admission policy (DESIGN.md §15.2): fifo = "
                         "strict arrival order (default), slo = deadline-aware "
                         "shed/re-order (traffic mode)")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="SLO admission: per-request latency deadline in ms "
                         "(0 = none; requests projected to miss it are shed)")
    ap.add_argument("--snapshot-out", default="",
                    help="write the warmed server's snapshot (residency set + "
                         "LRU order + predictor + artifact identity, DESIGN.md "
                         "§15.3) here at exit (after2 only)")
    ap.add_argument("--restore-from", default="",
                    help="restore a --snapshot-out document before admitting "
                         "traffic: the replica cold-starts RESIDENT-warm "
                         "instead of re-faulting its hot set (after2 only)")
    ap.add_argument("--fleet", type=int, default=0,
                    help="serve through N in-process replicas federated by a "
                         "FleetController (DESIGN.md §14): each replica runs "
                         "the one-shot workload, the controller syncs traces "
                         "and pushes the learned hot set to all of them "
                         "(implies --retier-online; after2 one-shot only)")
    args = ap.parse_args(argv)
    if args.layers:
        if args.reduced:
            ap.error("--layers cuts the published config; --reduced is a toy-width one")
        try:
            cut_depth(get_config(args.arch), args.layers)
        except ValueError as e:
            ap.error(str(e))
    if (args.profile_out or args.retier_from or args.retier_online) and args.mode != "after2":
        ap.error("--profile-out/--retier-from/--retier-online need the "
                 "two-tier runtime (--mode after2)")
    if args.host_budget_bytes and args.mode != "after2":
        ap.error("--host-budget-bytes governs the tier-1 residency layer "
                 "(--mode after2 only)")
    if (args.snapshot_out or args.restore_from) and args.mode != "after2":
        ap.error("--snapshot-out/--restore-from serialize the tier-1 "
                 "residency set (--mode after2 only)")
    if args.admission == "fifo" and args.deadline_ms:
        ap.error("--deadline-ms needs --admission slo (FIFO never sheds)")
    if args.deadline_ms < 0:
        ap.error("--deadline-ms must be >= 0")
    mesh = None
    if args.mesh:
        try:
            data_ax, model_ax = (int(x) for x in args.mesh.lower().split("x"))
        except ValueError:
            ap.error(f"--mesh wants DATAxMODEL (e.g. 2x4), got {args.mesh!r}")
        try:
            mesh = make_debug_mesh(data_ax, model_ax)
        except ValueError as e:  # not enough devices: surface the XLA_FLAGS hint
            ap.error(str(e))
    if args.host_budget_bytes < 0:
        ap.error("--host-budget-bytes must be >= 0")
    if not 0.0 <= args.retier_decay <= 1.0:
        ap.error("--retier-decay must be in [0, 1]")
    if args.retier_interval < 1:
        # fail as a usage error here, not as a traceback after the whole
        # cold start has already run (RetierDaemon validates too, but by
        # then the tier-0 read + hot-set preload were paid for)
        ap.error("--retier-interval must be >= 1")
    if args.fleet:
        if args.fleet < 2:
            ap.error("--fleet needs at least 2 replicas to federate")
        if args.mode != "after2":
            ap.error("--fleet needs the two-tier runtime (--mode after2)")
        if args.concurrency > 0:
            ap.error("--fleet drives the one-shot path; drop --concurrency")
        if args.host_budget_bytes or args.profile_out or args.retier_from:
            ap.error("--fleet composes with none of --host-budget-bytes/"
                     "--profile-out/--retier-from (yet)")
        args.retier_online = True  # the fleet federates RetierDaemons
    if args.retier_from and (args.no_prefetch or args.policy == "strict"):
        # without a prefetcher (explicit --no-prefetch, or the strict
        # preset's prefetch-off default) the trained predictor would be
        # silently dropped — the opposite of what the flag promises
        ap.error("--retier-from drives the predictive prefetcher; drop "
                 "--no-prefetch / use --policy stats|full (profiling runs "
                 "want --no-prefetch, re-serve runs don't)")
    return args, mesh


def load_config(args) -> ModelConfig:
    """The architecture's config: toy widths (``--reduced``), the published
    config cut in depth (``--layers``), or the published config."""
    if args.reduced:
        cfg = get_reduced(args.arch)
    else:
        full = get_config(args.arch)
        cfg = cut_depth(full, args.layers) if args.layers else full
        print(f"[serve] {cfg.name}: {cfg.num_layers} of {full.num_layers} layers at "
              f"published widths (d_model {cfg.d_model}, heads {cfg.num_heads}/"
              f"{cfg.num_kv_heads}, head_dim {cfg.resolved_head_dim}, d_ff {cfg.d_ff}"
              + (f", {cfg.moe.num_experts} experts top-{cfg.moe.top_k} "
                 f"d_ff {cfg.moe.expert_d_ff}" if cfg.moe else "")
              + f", vocab {cfg.vocab_size}, window {cfg.sliding_window})")
    return cfg.replace(collect_moe_usage=cfg.moe is not None, param_dtype=cfg.dtype)


def analyze_plan(model, args):
    """The deployment profile of ``--policy`` and the analyzer's plan."""
    cfg = model.cfg
    if args.policy == "strict":
        profile = DeploymentProfile(resident_experts=0, hot_vocab_fraction=0.0,
                                    min_tier1_bytes=1 << 14, vocab_row_group=max(64, cfg.vocab_size // 16))
        stats = None
    elif args.policy == "full":
        profile = DeploymentProfile(resident_experts=-1, hot_vocab_fraction=1.0)
        stats = None
    else:  # stats
        profile = DeploymentProfile(
            resident_experts=args.resident_experts,
            hot_vocab_fraction=args.hot_vocab,
            min_tier1_bytes=1 << 14,
            vocab_row_group=max(64, cfg.vocab_size // 16),
        )
        pipe = SyntheticTokenPipeline(DataConfig(cfg.vocab_size, 128, 8))
        stats = pipe.vocab_row_stats(row_group=profile.vocab_row_group)

    print(f"[serve] analyzing {cfg.name} under profile {profile.name}/{args.policy}")
    result = analyze(model, profile, hot_units_stats=stats, trace_B=1, trace_S=32)
    print("[serve] plan:", json.dumps(result.summary(), default=str)[:400])
    return result


def init_weights(model, seed: int, mesh=None):
    """Random weights from ``seed``, made on the device by one jitted
    program (no fp32 temporaries of whole leaves) and, under a mesh,
    sharded as ``cold_start`` shards them."""
    shardings = None
    if mesh is not None:
        shardings = param_shardings(model.logical_axes(), model.abstract(), mesh,
                                    fsdp=model.cfg.fsdp)
    return jax.jit(model.init, out_shardings=shardings)(jax.random.PRNGKey(seed))


def write_artifact(model, params, result, outdir: str, mode: str, *,
                   compress_level: int = 6) -> None:
    """Write what ``mode`` cold-starts from: the monolithic bundle
    (before/after1, with AdamW state as host zeros — serving never reads
    it, but the paper's baseline ships it) or the two-tier artifact."""
    os.makedirs(outdir, exist_ok=True)
    # crash recovery before any writer exists: a prior run killed mid-way
    # through an artifact rewrite (retier compaction, checkpoint save)
    # leaves *.partial staging dirs behind — never committed, safe to drop
    removed = clean_partials(outdir)
    if removed:
        print(f"[serve] removed {len(removed)} orphaned partial(s): "
              + ", ".join(os.path.basename(p) for p in removed))
    if mode in ("before", "after1"):
        def host_zeros(a):
            return np.zeros(a.shape, np.float32)

        abstract = model.abstract()
        opt = {"m": jax.tree.map(host_zeros, abstract), "v": jax.tree.map(host_zeros, abstract)}
        write_monolithic({"params": params, "opt_state": opt}, outdir, pruned=mode == "after1")
    else:
        build_artifact(params, result, outdir, compress_level=compress_level)


def main(argv=None) -> int:
    args, mesh = parse_args(argv)
    cfg = load_config(args)
    model = build_model(cfg)
    outdir = os.path.join(args.artifact_dir, cfg.name)
    result = analyze_plan(model, args)
    # the weights live only until the artifact holds them: the cold start
    # begins from an empty device, and its loader is the only uploader
    write_artifact(model, init_weights(model, args.seed, mesh), result, outdir, args.mode)

    predictor = None
    if args.retier_from:
        # one profile→re-tier cycle (DESIGN.md §11): replan from the trace,
        # rewrite the artifact out-of-place, serve from the re-tiered copy
        # with the trace-trained predictor armed
        prof_trace = AccessTrace.load(args.retier_from)
        result.plan, rep = replan_from_trace(result.plan, prof_trace, result.reach)
        retier_dir = outdir.rstrip("/") + "-retier"
        retier_artifact(outdir, result.plan, out_dir=retier_dir, report=rep)
        outdir = retier_dir
        predictor = TransitionPredictor.from_trace(prof_trace)
        print(f"[serve] re-tiered from {args.retier_from} -> {retier_dir}:",
              json.dumps(rep.summary()))

    if args.fleet:
        return _serve_fleet(model, result, outdir, args, cfg)

    # the context manager guarantees prefetcher/store teardown even when
    # the request path raises (a leaked reader/uploader thread would hang
    # the process on exit)
    failed = 0
    with open_server(model, result, outdir, args, mesh, predictor=predictor) as server:
        engine = make_engine(server, args)
        if args.concurrency > 0:
            failed = serve_traffic(engine, args, cfg)
        else:
            generate_once(engine, args, cfg)
        print_residency(server)
        if server.retier_daemon is not None:
            _print_daemon_stats(server)
        if args.profile_out and server.tiered is not None and server.tiered.trace is not None:
            # with the daemon on, the live trace is only the newest window —
            # save the decayed merge of everything the run observed instead
            t = (server.retier_daemon.trace_snapshot()
                 if server.retier_daemon is not None else server.tiered.trace)
            t.save(args.profile_out)
            print(f"[serve] wrote access trace to {args.profile_out} "
                  f"({t.batches} batches, {len(t.faults)} faulted units, "
                  f"{len(t.transitions)} transition sources)")
        if args.snapshot_out and server.tiered is not None:
            snap = server.snapshot()
            server_snapshot.save(snap, args.snapshot_out)
            print(f"[serve] wrote server snapshot to {args.snapshot_out} "
                  f"({len(snap['resident'])} resident units, "
                  f"predictor {'included' if snap['predictor'] else 'absent'})")
    if failed:
        print(f"[serve] FAILED: {failed} request(s) failed or never finished")
    return 1 if failed else 0


def open_server(model, result, outdir: str, args, mesh=None, *,
                predictor=None) -> ColdStartServer:
    """The timed cold start ``args`` describe (``--mode``, ``--policy``,
    budgets, prefetch, re-tiering, mesh, admission, restore); prints its
    report. Use it as a context manager."""
    warm_B = 1 if args.concurrency > 0 else args.batch
    arbiter = HostArbiter(args.host_budget_bytes) if args.host_budget_bytes else None
    admission = None
    if args.admission == "slo":
        admission = SLOAdmission(
            default_deadline_s=(args.deadline_ms / 1e3) if args.deadline_ms else None
        )
    server = cold_start(model, outdir, result if args.mode == "after2" else None,
                        mode=args.mode, warm_shapes=((warm_B, args.prompt_len),),
                        residency=args.policy if args.mode == "after2" else None,
                        device_budget_bytes=args.device_budget_bytes or None,
                        host_arbiter=arbiter,
                        prefetch=False if args.no_prefetch else None,
                        trace=bool(args.profile_out), predictor=predictor,
                        retier_online=args.retier_online,
                        retier_interval=args.retier_interval,
                        retier_decay=args.retier_decay,
                        retier_compact_every=args.retier_compact_every,
                        mesh=mesh, admission=admission,
                        restore_from=args.restore_from or None)
    print(f"[serve] cold start ({args.mode}):", json.dumps(server.report.to_dict(), default=float))
    if server.restore_report is not None:
        rr = server.restore_report
        print(f"[serve] warm restore: {rr['restored']}/{rr['requested']} units "
              f"resident ({rr['moved_bytes']:,}B replayed, "
              f"predictor {'armed' if rr['predictor_armed'] else 'absent'})")
    return server


def print_residency(server: ColdStartServer) -> None:
    """The tier-1 residency layer's summary lines (after2 only)."""
    if server.tiered is None:
        return
    ts = server.tiered.stats
    budget = server.tiered.residency.budget_bytes
    print(f"[serve] resident fraction: {server.tiered.resident_fraction():.3f}; "
          f"resident {server.tiered.resident_bytes:,}B"
          + (f" / budget {budget:,}B" if budget else " (no budget)"))
    print(f"[serve] prefetch hit rate {ts.prefetch_hit_rate:.2f}; "
          f"evictions {ts.evictions}; refaults {ts.refaults}; "
          f"stall p99 {ts.stall_percentile(99)*1e3:.2f}ms")
    if server.prefetcher is not None and server.prefetcher.predictor is not None:
        ps = server.prefetcher.stats
        print(f"[serve] predictor: observed {ps.observed} keys, "
              f"predicted {ps.predicted} ahead-of-schedule loads")
    arbiter = server.tiered.arbiter
    if arbiter is not None:
        audit = arbiter.audit()
        hs = arbiter.stats
        print(f"[serve] host arbiter: {audit['resident_bytes']:,}B resident "
              f"/ {audit['budget_bytes']:,}B host budget "
              f"({audit['pinned_bytes']:,}B pinned); "
              f"{hs.evictions} evictions ({hs.evicted_bytes:,}B), "
              f"{hs.overshoots} overshoots, "
              f"{hs.headroom_denials} prefetch headroom denials")


def make_engine(server: ColdStartServer, args) -> GenerationEngine:
    return GenerationEngine(server, max_seq=args.prompt_len + args.gen_steps + 8)


def generate_once(engine: GenerationEngine, args, cfg):
    """The one-shot workload: one batched greedy ``generate`` of
    ``--batch`` prompts. Returns ``(tokens (B, gen_steps), RequestStats)``."""
    prompts = jax.random.randint(jax.random.PRNGKey(args.seed + 1),
                                 (args.batch, args.prompt_len), 0, cfg.vocab_size)
    out, st = engine.generate(prompts, args.gen_steps)
    print(f"[serve] generated {out.shape}; prefill={st.prefill_s*1e3:.1f}ms "
          f"decode={st.decode_s*1e3:.1f}ms faults={st.faulted_units} "
          f"({st.faulted_bytes/2**20:.1f}MiB, {st.fault_s*1e3:.1f}ms)")
    return out, st


def _print_daemon_stats(server, label: str = "online retier") -> None:
    """One line of daemon accounting + the predictor counters the daemon's
    refresh cycle feeds (hit rate / observed / predicted)."""
    ds = server.retier_daemon.stats
    pred = ""
    if server.tiered is not None and server.prefetcher is not None:
        ts, ps = server.tiered.stats, server.prefetcher.stats
        pred = (f", predictor hit rate {ts.prefetch_hit_rate:.2f} "
                f"({ps.observed} observed, {ps.predicted} predicted)")
    print(f"[serve] {label}: {ds.ticks} ticks, {ds.applies} applies "
          f"(+{ds.promoted_units}/-{ds.demoted_units} units, "
          f"{ds.evicted_bytes:,}B evicted, "
          f"{ds.predictor_refreshes} predictor refreshes, "
          f"{ds.compactions} compactions{pred}); zero restarts")


def _serve_fleet(model, result, outdir, args, cfg) -> int:
    """``--fleet N``: the one-shot workload through N in-process replicas
    federated by one FleetController (DESIGN.md §14). Each replica cold-
    starts with its own daemon registered to the fleet, serves the batch,
    and the controller syncs after every replica — so by the time replica
    k serves, it already carries the hot set replicas 0..k-1 learned."""
    fleet = FleetController(decay=args.retier_decay)
    servers = []
    failed = 0
    try:
        for i in range(args.fleet):
            s = cold_start(
                model, outdir, result, mode="after2",
                warm_shapes=((args.batch, args.prompt_len),),
                residency=args.policy,
                device_budget_bytes=args.device_budget_bytes or None,
                prefetch=False if args.no_prefetch else None,
                retier_online=True,
                retier_interval=args.retier_interval,
                retier_decay=args.retier_decay,
                retier_compact_every=args.retier_compact_every,
                fleet=fleet, replica_name=f"replica-{i}",
            )
            servers.append(s)
            print(f"[serve] replica-{i} cold start:",
                  json.dumps(s.report.to_dict(), default=float))
        for i, s in enumerate(servers):
            engine = make_engine(s, args)
            prompts = jax.random.randint(
                jax.random.PRNGKey(args.seed + 1), (args.batch, args.prompt_len), 0, cfg.vocab_size)
            out, st = engine.generate(prompts, args.gen_steps)
            if out.shape[0] != args.batch:
                failed += 1
            print(f"[serve] replica-{i}: generated {out.shape}; "
                  f"faults={st.faulted_units} ({st.faulted_bytes/2**20:.2f}MiB, "
                  f"{st.fault_s*1e3:.1f}ms)")
            rep = fleet.sync()
            print(f"[serve] fleet sync: {rep['windows']}/{rep['pulled']} windows, "
                  f"pushed to {len(rep['pushed'])} replicas "
                  f"(+{rep['promoted']}/-{rep['demoted']} units)"
                  + (f", FAILED {sorted(rep['failed'])}" if rep["failed"] else ""))
        for i, s in enumerate(servers):
            _print_daemon_stats(s, label=f"replica-{i} retier")
        fs = fleet.stats
        print(f"[serve] fleet: {fs.syncs} syncs, {fs.replans} replans, "
              f"{fs.pushes} pushes ({fs.push_failures} failed), "
              f"{fs.bootstraps} warm bootstraps")
    finally:
        for s in servers:
            s.close()
    if failed:
        print(f"[serve] FAILED: {failed} replica run(s) produced short output")
    return 1 if failed else 0


def serve_traffic(engine: GenerationEngine, args, cfg) -> int:
    """Open-loop traffic through the continuous-batching scheduler.
    Returns the number of failed/unfinished requests so the launcher can
    exit nonzero (CI smoke must catch silent request failures)."""
    sched = ContinuousBatchingScheduler(engine, max_batch=args.concurrency)
    sched.warm_compile()  # first step should serve, not compile
    rng = np.random.default_rng(args.seed)
    prompts = [
        np.asarray(jax.random.randint(jax.random.PRNGKey(args.seed + 100 + i),
                                      (args.prompt_len,), 0, cfg.vocab_size))
        for i in range(args.requests)
    ]
    deadline_s = (args.deadline_ms / 1e3) if args.deadline_ms else None
    stop = threading.Event()
    loop = threading.Thread(target=sched.serve_forever, args=(stop,), name="sched-loop")
    loop.start()
    t0 = time.perf_counter()
    reqs = []
    try:
        for p in prompts:
            reqs.append(sched.queue.submit(p, args.gen_steps, deadline_s=deadline_s))
            if args.arrival_rate > 0:
                time.sleep(rng.exponential(1.0 / args.arrival_rate))
        # bail out early if the loop thread dies instead of blocking the
        # full timeout per request
        deadline = time.perf_counter() + 600.0
        pending = list(reqs)
        while pending and loop.is_alive() and time.perf_counter() < deadline:
            if pending[0].wait(1.0):
                pending.pop(0)
        pending = [r for r in pending if not r.done]
        if pending:
            print(f"[serve] WARNING: {len(pending)}/{len(reqs)} requests unfinished "
                  f"(loop alive={loop.is_alive()})")
    finally:
        stop.set()
        loop.join()
    wall = time.perf_counter() - t0
    done = [r for r in reqs if r.done and r.error is None]
    shed = [r for r in reqs if r.shed]
    lat = np.array([r.latency_s for r in done]) if done else np.zeros(1)
    ttft = np.array([r.ttft_s for r in done]) if done else np.zeros(1)
    print(f"[serve] traffic: {len(done)}/{len(reqs)} ok in {wall:.2f}s "
          f"({len(done) / wall:.2f} req/s over {sched.stats.steps} batched steps, "
          f"max_active={sched.stats.max_active}"
          + (f", shed={len(shed)}" if shed else "") + ")")
    print(f"[serve] latency p50={np.percentile(lat, 50) * 1e3:.0f}ms "
          f"p99={np.percentile(lat, 99) * 1e3:.0f}ms; "
          f"ttft p50={np.percentile(ttft, 50) * 1e3:.0f}ms; "
          f"step faults={sched.stats.faulted_units} ({sched.stats.fault_s * 1e3:.1f}ms)")
    for r in reqs:
        if r.error and not r.shed:
            print(f"[serve] request {r.rid} failed: {r.error}")
    # an SLO shed is the policy doing its job — a deliberate drop, not a
    # serving failure; rejects/exceptions/unfinished still exit nonzero
    return sum(1 for r in reqs if (r.error is not None and not r.shed) or not r.done)


if __name__ == "__main__":
    print(f"[serve] compile cache: {enable_compile_cache()}")
    raise SystemExit(main())
