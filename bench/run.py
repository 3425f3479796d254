"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout. The cell, its configuration, its traffic
mix and its metrics are named in ``BENCHMARK.json``; each configuration
is ``bench/configs/<name>.json``, each mix ``bench/traffic/<name>.json``,
each per-layer metric ``bench/metrics/<name>.py``, and a configuration's
family (its ``reference``) has its adapter in ``bench/families/<family>.py``
and its plain reference in ``bench/reference/<family>.py``. With ``--trace 0``
the result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, from a run whose window is partly traced.

The last line of standard output is the result, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` ``breakdown``, with ``--control 1`` ``control_correct``
and ``control_checks`` (the fp8 control judged by the same limits), and
last ``checks``: each number compared with the reference beside its
limit, also the last lines of standard error. Without a TPU, or with fewer chips than the cell asks for, it
exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also judge the fp8 control on the same sample by the same limits "
                         "(control_correct; calibrates the limits, the benchmark's own runs "
                         "leave it off)")
    return ap.parse_args(argv)


def cell_spec(bench: dict, workload: str) -> tuple[dict, list, list]:
    """The cell, its end-to-end (name, unit) and per-layer (name, unit)
    metrics, as ``BENCHMARK.json`` assigns them."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]

    def applies(m):
        return workload in m.get("workloads", cells)

    e2e = [(m["name"], m["unit"]) for m in bench["end_to_end"] if applies(m)]
    reported = {n for n, _ in e2e}
    per_layer = [(m["name"], m["unit"]) for m in bench["per_layer"]
                 if applies(m) and m["moves"] in reported]
    return cell, e2e, per_layer


def main(argv=None) -> int:
    t_start = time.perf_counter()
    args = parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell, e2e, per_layer = cell_spec(bench, args.workload)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    # the TPU runtime's logs stay inside the checkout, not at a fixed path
    os.environ.setdefault("TPU_LOG_DIR", str(ROOT / "bench" / ".work" / "tpu_logs"))
    os.makedirs(os.environ["TPU_LOG_DIR"], exist_ok=True)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"[bench] needs a TPU; JAX found platform {platform!r} "
              f"({len(devices)} x {devices[0].device_kind})", file=sys.stderr)
        return 2
    if len(devices) < cell["chips"]:
        print(f"[bench] {args.workload} needs {cell['chips']} chips; found {len(devices)}",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import enable_compile_cache

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    print(f"[bench] compile cache: {enable_compile_cache()}", file=sys.stderr)

    from bench import harness
    from bench.arch import load_config
    from bench.traffic import load_mix

    line = harness.run_cell(
        cell, load_config(cell["config"]), load_mix(cell["traffic"]),
        seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        e2e_names=e2e, per_layer=per_layer, t_start=t_start, control=bool(args.control),
        log=lambda s: print(s, file=sys.stderr, flush=True),
    )
    for key in ("control_checks", "checks"):
        for name, c in line.get(key, {}).items():
            who = "control" if key == "control_checks" else "check"
            print(f"[bench] {who} {name}: {c['value']!r} (need {c['need']} {c['limit']!r})",
                  file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
