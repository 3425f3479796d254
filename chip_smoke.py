"""Chip smoke: the serving path end to end on a TPU, at published widths.

Drives the launcher's own functions (``repro.launch.serve``) for
``mixtral-8x22b --layers 1 --policy stats --mode after2``: every width as
published (d_model 6144, 48/8 heads of 128, 8 experts of d_ff 16384 top-2,
vocab 32768, window 4096), cut in depth to one layer — one whole period,
since every mixtral layer is alike. Weights and prompts are random, made
from ``--seed``; everything else is built from the checkout.

One chip (default)::

    python chip_smoke.py [--seed N]

1. greedy tokens of the untiered model (the reference), and one prefill
   through the Pallas kernels (``use_pallas=True``): its program must hold
   a ``tpu_custom_call`` and its logits must agree with the jnp path;
2. the two-tier artifact, after which the weights are dropped, so the cold
   start begins from an empty device;
3. the timed cold start, a one-shot ``generate`` (batch 2, prompt 16,
   8 steps) whose tokens must equal the reference and which must fault
   tier-1 units in, then a continuous-batching pass of 8 requests on 4
   slots, every one of which must finish without error.

Four chips::

    python chip_smoke.py --chips 4

runs only mesh-sharded serving (DESIGN.md §15.1) on a 1x4 mesh: greedy
tokens of tiered ``after2`` serving must equal an untiered run on the same
mesh, and every resolved leaf must be bit-identical to the artifact.

Earlier lines are bring-up observations, not benchmark numbers. The last
line is ``{"ok": true, "device": {...}}``; any failed check raises and the
script exits non-zero. Without a TPU it exits non-zero before any work.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path

import jax
import numpy as np

ROOT = Path(__file__).resolve().parent
WORKDIR = ROOT / "artifacts" / "chip_smoke"

# Byte-planed zlib at the store's default level 6 compresses random bf16 at
# a few MB/s on one host core: ~800 s for this cut's 4.8 GB of tier-1, most
# of the time limit. Level 0 writes raw frames (the store's "raw" codec).
COMPRESS_LEVEL = 0

# The Pallas and jnp prefills differ only in how attention accumulates its
# fp32 softmax before the bf16 cast, which moves a few activations by one
# bf16 ulp (2^-8 relative). Allow 2^-5 of the largest reference logit: eight
# such ulps at the logits' own scale.
PALLAS_LOGIT_RTOL = 2.0**-5

SERVE_ARGV = [
    "--arch", "mixtral-8x22b", "--layers", "1", "--mode", "after2",
    "--batch", "2", "--prompt-len", "16", "--gen-steps", "8",
    "--concurrency", "4", "--requests", "8",
]


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    print(f"[smoke] ok: {what}")


def require_tpu(chips: int):
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SmokeFailure(f"needs a TPU; JAX found platform {platform!r} "
                           f"({len(devices)} x {devices[0].device_kind})")
    if len(devices) < chips:
        raise SmokeFailure(f"--chips {chips} needs {chips} TPU devices; found {len(devices)}")
    return devices


def memory_line(devices) -> str:
    parts = []
    for d in devices:
        st = d.memory_stats() or {}
        parts.append(f"dev{d.id} in_use={st.get('bytes_in_use', 'n/a')} "
                     f"peak={st.get('peak_bytes_in_use', 'n/a')} "
                     f"limit={st.get('bytes_limit', 'n/a')}")
    return "; ".join(parts)


def pallas_prefill_check(model, params, sargs) -> None:
    """One prefill with ``use_pallas=True`` on the given weights: the
    compiled program must call the kernels, and its logits must agree
    with the jnp path's."""
    from repro.models.zoo import build_model

    cfg = model.cfg
    tokens = jax.random.randint(jax.random.PRNGKey(sargs.seed + 1),
                                (sargs.batch, sargs.prompt_len), 0, cfg.vocab_size)
    batch = {"tokens": tokens}
    pallas_model = build_model(cfg.replace(use_pallas=True))
    t0 = time.perf_counter()
    compiled = jax.jit(pallas_model.prefill).lower(params, batch).compile()
    n_kernels = compiled.as_text().count("tpu_custom_call")
    print(f"[smoke] pallas prefill: compiled in {time.perf_counter() - t0:.3f}s, "
          f"{n_kernels} tpu_custom_call in its HLO")
    check(n_kernels > 0, "the use_pallas prefill compiles to tpu_custom_call")
    got = np.asarray(compiled(params, batch)[0], np.float32)
    want = np.asarray(jax.jit(model.prefill)(params, batch)[0], np.float32)
    err = float(np.abs(got - want).max())
    bound = PALLAS_LOGIT_RTOL * float(np.abs(want).max())
    print(f"[smoke] pallas vs jnp prefill logits {got.shape}: max|diff|={err!r} "
          f"bound={bound!r} (max|logit|={float(np.abs(want).max())!r})")
    check(bool(np.isfinite(got).all()) and err <= bound,
          "pallas prefill logits agree with the jnp path")


def build(sargs, workdir: Path, mesh=None):
    """Config, model, plan, seeded weights; the untiered reference tokens;
    then the artifact. Returns ``(cfg, model, result, outdir, ref_tokens)``
    with the weights already dropped."""
    from repro.launch import serve
    from repro.models.zoo import build_model
    from repro.serving import ColdStartReport, ColdStartServer

    cfg = serve.load_config(sargs)
    model = build_model(cfg)
    outdir = str(workdir / cfg.name)
    t0 = time.perf_counter()
    result = serve.analyze_plan(model, sargs)
    print(f"[smoke] analyze: {time.perf_counter() - t0:.3f}s")
    t0 = time.perf_counter()
    params = serve.init_weights(model, sargs.seed, mesh)
    jax.block_until_ready(params)
    print(f"[smoke] init weights (seed {sargs.seed}): {time.perf_counter() - t0:.3f}s, "
          f"{sum(x.nbytes for x in jax.tree.leaves(params)):,} B")

    t0 = time.perf_counter()
    ref = ColdStartServer(model, params, ColdStartReport(mode="untiered"))
    with ref:
        ref_tokens, _ = serve.generate_once(serve.make_engine(ref, sargs), sargs, cfg)
    print(f"[smoke] untiered reference: {time.perf_counter() - t0:.3f}s, "
          f"tokens {ref_tokens.tolist()}")
    if mesh is None:
        pallas_prefill_check(model, params, sargs)

    free = shutil.disk_usage(workdir).free
    print(f"[smoke] artifact: compress_level={COMPRESS_LEVEL} (raw frames; level 6 zlib "
          f"would spend most of the time limit on tier-1), {free:,} B free on disk")
    t0 = time.perf_counter()
    serve.write_artifact(model, params, result, outdir, sargs.mode,
                         compress_level=COMPRESS_LEVEL)
    print(f"[smoke] artifact build: {time.perf_counter() - t0:.3f}s; "
          f"tier0 {result.plan.tier0_bytes:,} B, tier1 {result.plan.tier1_bytes:,} B")
    # the server's compiled closures refer back to it: only the cycle
    # collector frees its weights
    del params, ref
    gc.collect()
    return cfg, model, result, outdir, ref_tokens


def run_one_chip(serve_argv, workdir: Path) -> None:
    from repro.launch import serve

    sargs, _ = serve.parse_args(serve_argv + ["--policy", "stats",
                                              "--artifact-dir", str(workdir)])
    devices = jax.local_devices()[:1]
    cfg, model, result, outdir, ref_tokens = build(sargs, workdir)
    live = sum(x.nbytes for x in jax.live_arrays())
    print(f"[smoke] device memory before cold start: {memory_line(devices)}; "
          f"live arrays {live:,} B")
    check(live < 1 << 26, "no weights live on the device when the cold start begins")

    with serve.open_server(model, result, outdir, sargs) as server:
        rep = server.report
        print(f"[smoke] cold start phases: read_s={rep.read_s!r} upload_s={rep.upload_s!r} "
              f"compile_s={rep.compile_s!r} total_s={rep.total_s!r} "
              f"bytes_read={rep.bytes_read:,} bytes_uploaded={rep.bytes_uploaded:,}")
        engine = serve.make_engine(server, sargs)
        tokens, st = serve.generate_once(engine, sargs, cfg)
        print(f"[smoke] one-shot: faulted_units={st.faulted_units} "
              f"faulted_bytes={st.faulted_bytes:,} fault_s={st.fault_s!r} "
              f"prefill_retries={st.prefill_retries} decode_retries={st.decode_retries} "
              f"tokens {tokens.tolist()}")
        check(np.array_equal(tokens, ref_tokens), "tiered tokens equal the untiered reference")
        check(st.faulted_units > 0, f"tier-1 units faulted in ({st.faulted_units})")
        failed = serve.serve_traffic(engine, sargs, cfg)
        check(failed == 0, f"all {sargs.requests} scheduler requests finished without error")
        serve.print_residency(server)
        errors = server.prefetcher.stats.errors
        check(errors == 0, f"prefetcher errors == 0 ({errors})")
    print(f"[smoke] device memory after serving: {memory_line(devices)}")


def run_mesh(serve_argv, workdir: Path) -> None:
    """§15.1 on a 1x4 mesh: tokens exact against an untiered run on the
    same mesh; every resolved leaf bit-identical to the artifact."""
    from repro.checkpoint import tensorstore_lite as tsl
    from repro.core.analyzer import _slice_unit
    from repro.core.optional_store import OptionalStore
    from repro.launch import serve
    from repro.utils.tree import flatten_with_paths

    sargs, mesh = serve.parse_args(serve_argv + ["--policy", "stats", "--mesh", "1x4",
                                                 "--artifact-dir", str(workdir)])
    devices = list(mesh.devices.flat)
    cfg, model, result, outdir, ref_tokens = build(sargs, workdir, mesh)
    plan = result.plan

    with serve.open_server(model, result, outdir, sargs, mesh) as server:
        rep = server.report
        print(f"[smoke] mesh cold start phases: read_s={rep.read_s!r} "
              f"upload_s={rep.upload_s!r} compile_s={rep.compile_s!r} total_s={rep.total_s!r}")
        engine = serve.make_engine(server, sargs)
        tokens, st = serve.generate_once(engine, sargs, cfg)
        print(f"[smoke] mesh one-shot: faulted_units={st.faulted_units} "
              f"faulted_bytes={st.faulted_bytes:,} fault_s={st.fault_s!r} "
              f"tokens {tokens.tolist()}")
        check(np.array_equal(tokens, ref_tokens),
              "mesh tiered tokens equal the untiered run on the same mesh")
        tiered = server.tiered
        sharded = [p for p, d in plan.decisions.items()
                   if d.tier == 1 and tiered._shard_div.get(p, 1) > 1]
        check(bool(sharded), f"tier-1 leaves are sharded over the mesh: {sharded}")
        print(f"[smoke] device memory after serving: {memory_line(devices)}")

        # resolve every unit in turn (pinned while it is read back, so the
        # budget or a prefetch cannot evict it mid-compare)
        t0 = time.perf_counter()
        tier0 = tsl.read_bundle(os.path.join(outdir, "tier0"))
        store = OptionalStore(os.path.join(outdir, "optional.blob"))
        n_units = 0
        try:
            for path, live in flatten_with_paths(tiered.tree()):
                dec = plan.decisions[path]
                if dec.tier == 0:
                    np.testing.assert_array_equal(np.asarray(live), tier0[path], err_msg=path)
                    continue
                for unit in dec.units:
                    tiered.ensure([unit.key], pin=True)
                    try:
                        got = np.asarray(_slice_unit(tiered.leaf(path), unit))
                    finally:
                        tiered.release([unit.key])
                    np.testing.assert_array_equal(got, store.fetch(unit.key), err_msg=unit.key)
                    n_units += 1
        finally:
            store.close()
        check(True, f"every resolved leaf is bit-identical to the artifact "
                    f"({len(tier0)} tier-0 leaves, {n_units} tier-1 units; "
                    f"{time.perf_counter() - t0:.3f}s)")
    print(f"[smoke] per-device memory: {memory_line(devices)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the serving path on one chip; 4: only mesh-sharded "
                         "serving over four chips")
    ap.add_argument("--seed", type=int, default=0, help="seed of the weights and prompts")
    args = ap.parse_args(argv)

    devices = require_tpu(args.chips)
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[smoke] device: {devices[0].platform} {devices[0].device_kind} x {len(devices)}")
    print(f"[smoke] compile cache: {enable_compile_cache()}")
    serve_argv = SERVE_ARGV + ["--seed", str(args.seed)]
    print(f"[smoke] cut: serve {' '.join(serve_argv)}")
    WORKDIR.mkdir(parents=True, exist_ok=True)
    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_mesh(serve_argv, WORKDIR)
        else:
            run_one_chip(serve_argv, WORKDIR)
    finally:
        shutil.rmtree(WORKDIR, ignore_errors=True)
    print(f"[smoke] wall: {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": {"platform": devices[0].platform,
                                             "kind": devices[0].device_kind,
                                             "count": len(devices)}}))
    return 0

if __name__ == "__main__":
    raise SystemExit(main())
