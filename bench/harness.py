"""One run of one cell: set-up, the measured window, the check.

The program is driven as a library, in this process, through its own
entry points: ``launch.serve``'s ``analyze_plan`` and ``write_artifact``,
``serving.cold_start``, ``GenerationEngine`` and
``ContinuousBatchingScheduler`` in its serving thread. The benchmark adds
only what the program lacks: seeded weights (``bench.weights``), an
open-loop generator thread that submits each request when it is due,
wrappers around the engine's two step calls that record when each step
ended and what it computed, host spans for the profiler, and the plain
reference of the configuration's family (``bench/arch.py``) that decides
``correct`` once the window has closed.

Timing starts at each request's due time, not at its submission, so a
stall shows in every request that waits behind it.
"""

from __future__ import annotations

import faulthandler
import gc
import importlib.util
import json
import os
import random
import shutil
import sys
import threading
import time
from dataclasses import dataclass, field
from pathlib import Path
from types import SimpleNamespace
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench import traffic, weights, work
from bench.arch import arch_of, load_reference, program_config

BENCH = Path(__file__).resolve().parent
WORKDIR = BENCH / ".work"  # artifacts and traces of a run; git-ignored

SAMPLE_TOKENS = 400  # served tokens the reference re-reads, at least
SAMPLE_MAX_REQUESTS = 8
STALL_S = 1.0        # a submission this late dumps every thread's stack
STALL_DUMPS = 3      # at most this many dumps a run


@dataclass
class Step:
    kind: str            # "prefill" | "decode"
    start: float         # seconds after the window opened
    end: float
    lengths: list        # prompt lengths (prefill) or key/value lengths (decode)
    fault_s: float
    fault_bytes: int
    experts: int         # distinct (layer, expert) pairs routed


@dataclass
class Record:
    """What one run saw, read by the end-to-end metrics and by the
    per-layer readers in ``bench/metrics``."""

    arch: object                                   # the family's shapes (bench/arch.py)
    mix: dict
    seconds: float
    requests: list = field(default_factory=list)   # dicts, see _request_rows
    steps: list = field(default_factory=list)      # Step, in the window
    cold: Optional[dict] = None                    # ColdStartReport of an in-window cold start
    t_open: Optional[float] = None                 # the window's opening, time.perf_counter
    compiles: int = 0                              # programs compiled or loaded in the window
    lateness_s: list = field(default_factory=list)
    trace: Optional[dict] = None                   # bench.trace.reduce of the traced span
    trace_window: Optional[tuple] = None           # (start, end) seconds after the window opened
    device_kind: str = ""


# -- end-to-end metrics: fixed code, never read from the program ------------

def _p95(xs) -> Optional[float]:
    return float(np.percentile(np.asarray(xs, float), 95)) if len(xs) else None


def ttft_s(r: dict, seconds: float) -> float:
    """Due time to first token; a request still waiting when the window
    closes counts with its wait so far."""
    ft = r["first_token"]
    return (ft if ft is not None and ft <= seconds else seconds) - r["due"]


def token_times(r: dict, seconds: float) -> list:
    return [t for t in r["token_times"] if t <= seconds]


E2E: dict[str, Callable[[Record], Optional[float]]] = {
    "cold_ttft_s": lambda rec: ttft_s(rec.requests[0], rec.seconds) if rec.cold else None,
    "ttft_p95_ms": lambda rec: _p95([1e3 * ttft_s(r, rec.seconds) for r in rec.requests]),
    "itl_p95_ms": lambda rec: _p95([1e3 * g for r in rec.requests
                                    for g in np.diff(token_times(r, rec.seconds))]),
    "tokens_per_s": lambda rec: sum(len(token_times(r, rec.seconds))
                                    for r in rec.requests) / rec.seconds,
}


def load_reader(name: str, root: Path = BENCH) -> Callable[[Record], Optional[float]]:
    """``<root>/metrics/<name>.py``'s ``read``: a per-layer metric."""
    path = root / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the run -----------------------------------------------------------------

class _CompileCounter:
    """Programs compiled (or loaded from the persistent cache) while on."""

    def __init__(self):
        self.on = False
        self.compiles = 0
        self.misses = 0
        from jax import monitoring

        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **kw):
        if self.on and event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _drop_page_cache(root: Path) -> None:
    """Make the artifact's next read come from the disk, as a fresh
    replica's would: write back, then drop its pages from the page cache."""
    for p in sorted(root.rglob("*")):
        if p.is_file():
            fd = os.open(p, os.O_RDONLY)
            try:
                os.fsync(fd)
                os.posix_fadvise(fd, 0, 0, os.POSIX_FADV_DONTNEED)
            finally:
                os.close(fd)


def warm_groups(mix: dict, reqs: list) -> list[tuple[int, int]]:
    """(group size, prompt length) prefill shapes the window can admit:
    every length at up to ``warm_group`` requests per admission. A cold
    window opens on a backlog, so there each length also goes up to as
    many as the first ``slots`` requests hold of it, since the first
    admission round takes whichever of them are due by then; a warm
    replica meets each request as it comes."""
    first = [len(r.prompt) for r in reqs[: mix["slots"]]] if mix["start"] == "cold" else []
    return sorted({(g, S) for S in mix["prompt_lens"]
                   for g in range(1, max(mix["warm_group"], first.count(S)) + 1)})


def _aot_warm(model, server, shapes, slots: int, max_seq: int) -> None:
    """Compile, without running, every program an in-window cold start
    will load: the launcher's warm set, each admission prefill and graft,
    and the masked decode. Fills the persistent compilation cache."""
    from repro.serving.scheduler import _graft_slot_cache
    from repro.serving.engine import _strip_usage

    abs_params = model.abstract()
    graft = jax.jit(_graft_slot_cache, donate_argnums=(0,))
    big = model.abstract_cache(slots, max_seq, multimodal=False)
    for g, S in shapes:
        batch = {"tokens": jax.ShapeDtypeStruct((g, S), jnp.int32)}
        fn = server.compiled_prefill(g, S)
        fn.lower(abs_params, batch).compile()
        _, small = jax.eval_shape(fn, abs_params, batch)
        graft.lower(big, _strip_usage(small), jax.ShapeDtypeStruct((g,), jnp.int32)).compile()
    db, _ = model.decode_masked_batch_spec(slots)
    server.compiled_decode_masked(slots).lower(abs_params, big, db).compile()
    one, _ = model.decode_batch_spec(1)
    for S in sorted({S for _, S in shapes}):
        cache = model.abstract_cache(1, S, multimodal=False)
        server.compiled_decode(1).lower(abs_params, cache, one).compile()


class _Instruments:
    """Wrappers around the engine's step calls and the residency layer's
    fault-in, installed on one engine instance: they record each step and
    open a host span for the profiler. The program's code is unchanged."""

    def __init__(self, engine, t0: float):
        self.t0 = t0
        self.steps: list[Step] = []
        self.recording = True
        prefill_step, decode_once = engine.prefill_step, engine.decode_once

        def prefill(tokens, stats, **kw):
            s = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.prefill"):
                out = prefill_step(tokens, stats, **kw)
            self._add("prefill", s, [int(tokens.shape[1])] * int(tokens.shape[0]), stats, out[2])
            return out

        def decode(decode_fn, caches, dbatch, stats, **kw):
            s = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.decode"):
                out = decode_once(decode_fn, caches, dbatch, stats, **kw)
            active = np.asarray(dbatch["active"]) if "active" in dbatch else None
            pos = np.asarray(dbatch["pos"])
            kv = (pos[active] if active is not None else pos) + 1
            self._add("decode", s, kv.tolist(), stats, out[2])
            return out

        engine.prefill_step, engine.decode_once = prefill, decode
        tiered = engine.server.tiered
        if tiered is not None:
            ensure = tiered.ensure

            def fault(keys, **kw):
                with jax.profiler.TraceAnnotation("bench.fault"):
                    return ensure(keys, **kw)

            tiered.ensure = fault

    def _add(self, kind, s, lengths, stats, expert_keys):
        if self.recording:
            self.steps.append(Step(kind, s - self.t0, time.perf_counter() - self.t0, lengths,
                                   float(stats.fault_s), int(stats.faulted_bytes),
                                   work.routed_experts(expert_keys)))


def _spans(sched) -> None:
    """Host spans around the scheduler's step (admission, bookkeeping and
    hints around the engine's calls) and its own jitted calls: the
    admission graft and the dispatch of the masked decode."""
    graft, decode, step = sched._graft, sched._decode, sched.step

    def spanned_step():
        with jax.profiler.TraceAnnotation("bench.step"):
            return step()

    def spanned_graft(*a):
        with jax.profiler.TraceAnnotation("bench.graft"):
            return graft(*a)

    def spanned_decode(*a):
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            return decode(*a)

    sched._graft, sched._decode, sched.step = spanned_graft, spanned_decode, spanned_step


def _generator(queue, reqs, t0: float, submitted: list, lateness: list, stop: threading.Event,
               stalls: _Stalls):
    """Open loop: submit each request at its due time, whatever the server
    is doing; record how late each submission ran."""
    for i, r in enumerate(reqs):
        wait = t0 + r.due - time.perf_counter()
        stalls.arm(wait)
        if wait > 0 and stop.wait(wait):
            return
        lateness.append(time.perf_counter() - (t0 + r.due))
        stalls.disarm(lateness[-1])
        with jax.profiler.TraceAnnotation("bench.submit"):
            submitted[i] = queue.submit(r.prompt, r.out_len)


def _request_rows(reqs, submitted, t0: float, steps: list[Step]) -> list[dict]:
    """Per request: due, first token, every token's time (seconds after the
    window opened) and outputs. A request's later tokens come one per
    decode step after its first (the scheduler advances every active slot
    by one token a step), so its k-th token is the end of the k-th decode
    step that began after its first token."""
    decode_ends = [(s.start, s.end) for s in steps if s.kind == "decode"]
    starts = [a for a, _ in decode_ends]
    rows = []
    for r, q in zip(reqs, submitted):
        row = {"due": r.due, "prompt": r.prompt, "out_len": r.out_len, "out": [],
               "first_token": None, "admitted": None, "token_times": [], "error": None,
               "finished": False}
        if q is not None:
            out = list(q.out)
            row["out"] = out
            row["error"] = q.error
            row["finished"] = q.done and q.error is None and len(out) >= r.out_len
            if q.admitted_t:
                row["admitted"] = q.admitted_t - t0
            if out and q.first_token_t:
                ft = q.first_token_t - t0
                row["first_token"] = ft
                k = int(np.searchsorted(starts, ft, side="right"))
                row["token_times"] = [ft] + [e for _, e in decode_ends[k: k + len(out) - 1]]
        rows.append(row)
    return rows


def _sample(rows: list[dict], seed: int) -> list[dict]:
    """Finished requests for the reference: the one with the most served
    tokens, then others drawn from the seed, to ``SAMPLE_TOKENS``."""
    done = [r for r in rows if r["finished"]]
    if not done:
        return []
    longest = max(done, key=lambda r: (len(r["out"]), len(r["prompt"])))
    rest = [r for r in done if r is not longest]
    random.Random(seed).shuffle(rest)
    pick, n = [longest], len(longest["out"])
    for r in rest:
        if n >= SAMPLE_TOKENS or len(pick) >= SAMPLE_MAX_REQUESTS:
            break
        pick.append(r)
        n += len(r["out"])
    return pick


def _gap_stats(gaps: list[float], best: list[float]) -> dict:
    """Of the gaps at the served tokens: the widest, and the share that
    lie more than two bfloat16 steps of the best logit below it."""
    g = np.asarray(gaps, float)
    two_ulp = 2.0 ** (np.floor(np.log2(np.abs(np.asarray(best, float)))) - 6)
    return {"tokens_compared": len(g), "logit_gap": float(g.max()),
            "beyond_2ulp_share": float(np.mean(g > two_ulp))}


NEEDS = {"tokens_compared": ">=", "logit_gap": "<=", "beyond_2ulp_share": "<="}


def _judged(stats: dict, limits: dict) -> dict:
    return {k: {"value": stats.get(k), "limit": v, "need": NEEDS[k]} for k, v in limits.items()}


def check(reference, arch, seed: int, sample: list[dict], pad_to: int, limits: dict,
          *, control: bool = False, log=print) -> tuple[dict, Optional[dict]]:
    """The family's plain reference (a module, ``bench/arch.py``) re-reads
    each sampled request (prompt and served tokens) and reads, at every
    served token, how far that token's logit lies below the reference's
    best. Of those gaps (``_gap_stats``) the numbers that ``limits`` names
    are compared. ``control`` also judges, by the same limits, the tokens
    the fp8 reference puts first on the same prompts and tokens, as if
    they had been served; the second value returned is that judgement,
    else None."""
    if not sample:
        return _judged({"tokens_compared": 0}, limits), None
    seqs = [np.concatenate([r["prompt"], np.asarray(r["out"][:-1], np.int32)]) for r in sample]
    first = [len(r["prompt"]) - 1 for r in sample]
    margins: list = []
    ref = reference.logits(arch, seed, seqs, first, pad_to=pad_to, margins=margins)
    best = [float(np.max(lg[j])) for lg in ref for j in range(len(lg))]
    where = []  # (gap, prompt length, served token index, router margin)
    for i, (lg, r) in enumerate(zip(ref, sample)):
        for j, tok in enumerate(r["out"]):
            where.append((float(np.max(lg[j]) - lg[j][tok]), len(r["prompt"]), j,
                          float(margins[i][j]) if margins else None))
    stats = _gap_stats([w[0] for w in where], best)
    log(f"[bench] served tokens against the reference: {stats}")
    log("[bench] widest gaps (gap, prompt length, served token index, router margin): "
        + str(sorted(where, key=lambda w: -w[0])[:6]))
    if margins:
        allm = np.concatenate(margins)
        log(f"[bench] router margin (k-th minus next probability, least over layers): "
            f"median {float(np.median(allm))!r}, 1st percentile {float(np.percentile(allm, 1))!r}")
    controlled = None
    if control:
        low = reference.logits(arch, seed, seqs, first, pad_to=pad_to, precision="fp8")
        cg = [float(np.max(lg[j]) - lg[j][int(np.argmax(lo[j]))])
              for lg, lo in zip(ref, low) for j in range(len(lg))]
        cstats = _gap_stats(cg, best)
        log(f"[bench] fp8 control against the reference: {cstats}")
        controlled = _judged(cstats, limits)
    return _judged(stats, limits), controlled


def passed(checks: dict) -> bool:
    """Every compared number within its limit."""
    for c in checks.values():
        v = c["value"]
        if v is None or (v < c["limit"] if c["need"] == ">=" else v > c["limit"]):
            return False
    return True


class _Stalls:
    """What stalls the process in the window: the garbage collector's
    pauses (``gc.callbacks``), and, where a submission runs ``STALL_S``
    late, every thread's stack at that moment (``faulthandler``'s
    watchdog runs without the interpreter lock, so it sees a stall the
    Python threads cannot)."""

    def __init__(self):
        self.on = False
        self.pauses: list[tuple[float, float, int]] = []  # (start, seconds, generation)
        self.dumps = 0
        self._t = 0.0
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self.on:
            self.pauses.append((self._t, time.perf_counter() - self._t, info["generation"]))

    def arm(self, wait: float) -> None:
        if self.dumps < STALL_DUMPS:
            faulthandler.dump_traceback_later(max(wait, 0.0) + STALL_S, file=sys.__stderr__)

    def disarm(self, late: float) -> None:
        faulthandler.cancel_dump_traceback_later()
        if late > STALL_S:
            self.dumps += 1

    def close(self) -> None:
        faulthandler.cancel_dump_traceback_later()
        self.on = False
        gc.callbacks.remove(self._gc)

    def summary(self, t0: float) -> str:
        longest = sorted(self.pauses, key=lambda p: -p[1])[:3]
        return (f"{len(self.pauses)} collections, {sum(p[1] for p in self.pauses):.6f} s in all; "
                f"longest (window time, seconds, generation): "
                + str([(round(a - t0, 3), d, g) for a, d, g in longest]))


def _artifact(model, arch, conf: dict, result, seed: int, workdir: Path, log) -> Path:
    """The seed's two-tier artifact: built once and kept, one per
    configuration, keyed by the configuration file and the seed, so a
    later run of the same seed reads it rather than building it again."""
    from repro.launch import serve

    artifact = workdir / conf["name"]
    stamp = workdir / f"{conf['name']}.key"
    key = json.dumps({"config": conf, "seed": seed}, sort_keys=True)
    if artifact.is_dir() and stamp.is_file() and stamp.read_text() == key:
        log(f"[bench] artifact of seed {seed} kept from an earlier run")
        return artifact
    stamp.unlink(missing_ok=True)
    shutil.rmtree(artifact, ignore_errors=True)
    params = weights.program_params(arch, model, seed)
    jax.block_until_ready(params)
    serve.write_artifact(model, params, result, str(artifact), "after2", compress_level=0)
    del params
    gc.collect()
    stamp.write_text(key)
    return artifact


def run_cell(cell: dict, conf: dict, mix: dict, *, seed: int, seconds: float, trace: bool,
             e2e_names: list[str], per_layer: list[str], t_start: float,
             control: bool = False, workdir: Path = WORKDIR, root: Path = BENCH,
             log=print) -> dict:
    """One run; returns the result line's object (see ``bench/run.py``).
    ``control`` also judges the fp8 control on the same sample
    (``control_correct``, ``control_checks``). The configuration's family
    and the per-layer readers are found under ``root``."""
    from repro.launch import serve
    from repro.models.zoo import build_model
    from repro.serving import ColdStartServer, ColdStartReport, ContinuousBatchingScheduler
    from repro.serving import GenerationEngine, cold_start
    from repro.serving.scheduler import RequestQueue

    devices = jax.local_devices()[: cell["chips"]]
    arch = arch_of(conf, root)
    reference = load_reference(conf, root)
    model = build_model(program_config(arch))
    reqs = traffic.schedule(mix, seed, seconds, arch.vocab)
    max_seq = traffic.max_seq(mix)
    slots = mix["slots"]
    cold_in_window = mix["start"] == "cold"
    counter = _CompileCounter()

    # -- set-up: plan, seeded weights, artifact ------------------------------
    plan_args = SimpleNamespace(**conf["plan"])
    result = serve.analyze_plan(model, plan_args)
    workdir.mkdir(parents=True, exist_ok=True)
    artifact = _artifact(model, arch, conf, result, seed, workdir, log)

    shapes = warm_groups(mix, reqs)
    warm_shapes = tuple((1, S) for S in mix["prompt_lens"])

    def open_server():
        return cold_start(model, str(artifact), result, mode="after2",
                          warm_shapes=warm_shapes, residency=conf["residency"])

    queue = RequestQueue()
    server = sched = inst = None
    if cold_in_window:
        _aot_warm(model, ColdStartServer(model, None, ColdStartReport("aot")), shapes, slots, max_seq)
        _drop_page_cache(artifact)
    else:
        server = open_server()
        server.tiered.ensure_all()
        engine = GenerationEngine(server, max_seq=max_seq)
        sched = ContinuousBatchingScheduler(engine, max_batch=slots, queue=queue)
        sched.warm_compile()
        # run every admission shape once through the serving path itself
        for g, S in shapes:
            for _ in range(g):
                queue.submit(np.zeros(S, np.int32), 2)
            sched.run()
        inst = _Instruments(engine, 0.0)
        _spans(sched)
    log(f"[bench] set-up done: {len(shapes)} admission shapes warmed, "
        f"{len(reqs)} requests due in {seconds} s")

    # -- the window -----------------------------------------------------------
    stop = threading.Event()
    submitted: list = [None] * len(reqs)
    lateness: list = []
    trace_dir = workdir / "trace"
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    if trace and cold_in_window:
        jax.profiler.start_trace(str(trace_dir))
        traced = jax.profiler.TraceAnnotation("bench.traced")  # marks the traced span
        traced.__enter__()
    t0 = time.perf_counter()
    counter.on = True
    if inst is not None:
        inst.t0 = t0
        inst.steps.clear()
    stalls = _Stalls()
    stalls.on = True
    gen = threading.Thread(target=_generator, name="bench-generator", daemon=True,
                           args=(queue, reqs, t0, submitted, lateness, stop, stalls))
    gen.start()
    cold_report = None
    if cold_in_window:
        with jax.profiler.TraceAnnotation("bench.cold_start"):
            server = open_server()
            cold_report = server.report.to_dict()
            engine = GenerationEngine(server, max_seq=max_seq)
            sched = ContinuousBatchingScheduler(engine, max_batch=slots, queue=queue)
            sched.warm_compile()
        inst = _Instruments(engine, t0)
        _spans(sched)
    loop_stop = threading.Event()
    loop = threading.Thread(target=sched.serve_forever, args=(loop_stop,),
                            name="bench-serving", daemon=True)
    loop.start()
    trace_span = None
    if trace:
        if cold_in_window:
            # from the window's start to the first token, and a few seconds on
            while submitted[0] is None or not submitted[0].first_token_t:
                if time.perf_counter() - t0 > seconds:
                    break
                time.sleep(0.01)
            time.sleep(min(3.0, max(0.0, t0 + seconds - time.perf_counter())))
            traced.__exit__(None, None, None)
            jax.profiler.stop_trace()
            trace_span = (0.0, time.perf_counter() - t0)
        else:
            a = max(0.0, seconds / 2 - 2.5)
            time.sleep(max(0.0, t0 + a - time.perf_counter()))
            jax.profiler.start_trace(str(trace_dir))
            ta = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.traced"):
                time.sleep(min(5.0, max(0.0, t0 + seconds - ta)))
            jax.profiler.stop_trace()
            trace_span = (ta - t0, time.perf_counter() - t0)
    time.sleep(max(0.0, t0 + seconds - time.perf_counter()))
    counter.on = False
    inst.recording = False
    stop.set()
    loop_stop.set()
    gen.join()
    stalls.close()
    loop.join(timeout=120.0)
    if loop.is_alive():
        raise RuntimeError("the serving loop did not stop within 120 s of the window's close")

    rec = Record(arch=arch, mix=mix, seconds=seconds, cold=cold_report, t_open=t0,
                 compiles=counter.compiles, lateness_s=lateness,
                 device_kind=devices[0].device_kind)
    rec.steps = list(inst.steps)
    rec.requests = _request_rows(reqs, submitted, t0, rec.steps)
    peak_bytes = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)
    n_failed = sum(1 for r in rec.requests if r["error"] is not None)
    log(f"[bench] window: {len(reqs)} due, {sum(r['finished'] for r in rec.requests)} finished, "
        f"{n_failed} failed; {len(rec.steps)} steps; generator late by at most "
        f"{max(lateness, default=0.0):.6f} s; {counter.compiles} programs compiled or loaded "
        f"in the window ({counter.misses} persistent-cache misses)")
    log(f"[bench] garbage collection in the window: {stalls.summary(t0)}")
    groups = sorted({(len(s.lengths), s.lengths[0]) for s in rec.steps if s.kind == "prefill"})
    log(f"[bench] admission groups (requests, prompt length) in the window: {groups}")
    if cold_report:
        log(f"[bench] cold start: {cold_report}")

    if trace_span is not None:
        from bench import trace as tr

        ev = tr.load(tr.find_xplane(str(trace_dir)))
        marks = [(s, s + d) for n, s, d in ev["spans"] if n == "bench.traced"]
        rec.trace = tr.reduce(ev, marks[0] if marks else None)
        tr.save_events(ev, str(workdir / "trace_events.json.gz"))
        shutil.rmtree(trace_dir, ignore_errors=True)
        rec.trace_window = trace_span

    # -- free the program, then the check ----------------------------------------
    sample = _sample(rec.requests, seed)
    server.close()
    del sched, engine, server, inst, loop
    gc.collect()
    jax.clear_caches()
    checks, control_checks = check(reference, arch, seed, sample, max_seq, conf["check"],
                                   control=control, log=log)

    if trace:
        metrics = {}
        for name, unit in per_layer:
            v = load_reader(name, root)(rec)
            if v is not None:
                metrics[name] = {"value": v, "unit": unit}
    else:
        metrics = {}
        for name, unit in e2e_names:
            v = E2E[name](rec) if name != "setup_s" else t0 - t_start
            metrics[name] = {"value": v, "unit": unit}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    line = {"correct": passed(checks), "attempted": len(reqs), "failed": n_failed,
            "metrics": metrics, "device": device}
    log("[bench] end to end: " + str({n: E2E[n](rec) for n in E2E}))
    if trace and rec.trace:
        device["busy_s"] = rec.trace["busy_s"]
        device["window_s"] = rec.trace["window_s"]
        line["breakdown"] = {"device_ops": [list(x) for x in rec.trace["device_ops"]],
                             "idle_gaps": rec.trace["idle_gaps"]}
    if control_checks is not None:
        line["control_correct"] = passed(control_checks)
        line["control_checks"] = control_checks
    line["checks"] = checks
    return line
