"""Benchmark aggregator: one section per paper table/figure + the roofline.

``PYTHONPATH=src python -m benchmarks.run [--fast] [--runs N] [--out DIR]``

Prints ``name,us_per_call,derived`` CSV rows (assignment contract); with
``--json-out FILE`` the same rows are also written as a JSON document
(section → rows) for machine consumers (CI uploads this as a build
artifact). The RQ benchmarks measure the reduced configs live on CPU;
the roofline section reads the dry-run artifacts if present.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import traceback


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--runs", type=int, default=5, help="cold-start repetitions (paper: 20)")
    ap.add_argument("--fast", action="store_true", help="3 runs, fewer archs")
    ap.add_argument("--smoke", action="store_true",
                    help="CI smoke: rq2 (one arch, 2 runs, no warm-set compile) "
                         "+ the rq7 profile→re-tier cycle + the rq8 online "
                         "re-tier shift + the rq9 multi-model zoo + the rq10 "
                         "fleet federation + the rq11 scale-out mesh/snapshot "
                         "(~7 min)")
    ap.add_argument("--out", default="", help="artifact scratch dir (default: temp)")
    ap.add_argument("--only", default="",
                    help="comma list: rq1,rq2,rq3,rq4,rq5,traffic,rq6,rq7,rq8,rq9,rq10,rq11,roofline")
    ap.add_argument("--json-out", default="",
                    help="also write all rows as JSON {section: [rows]} here")
    args = ap.parse_args(argv)
    n_runs = 3 if args.fast else args.runs

    from benchmarks import (
        bench_rq1_size,
        bench_rq2_cold,
        bench_rq3_warm,
        bench_rq4_overhead,
        bench_rq5_comparison,
        bench_rq5_traffic,
        bench_rq6_generality,
        bench_rq7_retier,
        bench_rq8_online,
        bench_rq9_zoo,
        bench_rq10_fleet,
        bench_rq11_scaleout,
        roofline,
    )

    only = set(filter(None, args.only.split(",")))
    want = lambda k: not only or k in only

    scratch = args.out or tempfile.mkdtemp(prefix="faaslight_bench_")
    os.makedirs(scratch, exist_ok=True)
    print(f"# FaaSLight-JAX benchmarks (artifacts: {scratch}; runs={n_runs})")
    print("name,us_per_call,derived")

    by_section: dict[str, list[str]] = {}

    def _flush_json() -> None:
        if args.json_out:
            tmp = args.json_out + ".partial"
            with open(tmp, "w") as f:
                json.dump(by_section, f, indent=2)
            os.replace(tmp, args.json_out)

    sections = []
    if args.smoke:
        smoke = [
            ("rq2", lambda: bench_rq2_cold.main(
                scratch, n_runs=2, archs=("mixtral-8x22b",), compile_warm=False)),
            ("rq7", lambda: bench_rq7_retier.main(scratch, smoke=True)),
            ("rq8", lambda: bench_rq8_online.main(scratch, smoke=True)),
            ("rq9", lambda: bench_rq9_zoo.main(scratch, smoke=True)),
            ("rq10", lambda: bench_rq10_fleet.main(scratch, smoke=True)),
            ("rq11", lambda: bench_rq11_scaleout.main(scratch, smoke=True)),
        ]
        # --only filters smoke sections too (CI's dedicated scale-out job
        # runs `--smoke --only rq11` under an 8-device host platform)
        sections = [(f"{k}_smoke", fn) for k, fn in smoke if want(k)]
    else:
        if want("rq1"):
            sections.append(("rq1", lambda: bench_rq1_size.main(scratch)))
        if want("rq2"):
            sections.append(("rq2", lambda: bench_rq2_cold.main(scratch, n_runs=n_runs)))
        if want("rq3"):
            sections.append(("rq3", lambda: bench_rq3_warm.main(scratch, n_runs=n_runs)))
        if want("rq4"):
            sections.append(("rq4", lambda: bench_rq4_overhead.main(scratch)))
        if want("rq5"):
            sections.append(("rq5", lambda: bench_rq5_comparison.main(scratch)))
        if want("traffic"):
            sections.append(("traffic", lambda: bench_rq5_traffic.main(scratch)))
        if want("rq6"):
            sections.append(("rq6", lambda: bench_rq6_generality.main(scratch)))
        if want("rq7"):
            sections.append(("rq7", lambda: bench_rq7_retier.main(scratch)))
        if want("rq8"):
            sections.append(("rq8", lambda: bench_rq8_online.main(scratch)))
        if want("rq9"):
            sections.append(("rq9", lambda: bench_rq9_zoo.main(scratch)))
        if want("rq10"):
            sections.append(("rq10", lambda: bench_rq10_fleet.main(scratch)))
        if want("rq11"):
            sections.append(("rq11", lambda: bench_rq11_scaleout.main(scratch)))
        if want("roofline"):
            sections.append(("roofline", roofline.main))

    failures = 0
    for name, fn in sections:
        try:
            rows = list(fn())
        except Exception:
            failures += 1
            print(f"{name}/ERROR,0.0,exception", file=sys.stdout)
            traceback.print_exc()
            continue
        by_section[name] = rows
        for row in rows:
            print(row)
    _flush_json()
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    print(f"# compile cache: {enable_compile_cache()}", file=sys.stderr)
    sys.exit(main())
