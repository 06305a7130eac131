#!/usr/bin/env python3
"""Readings a correctness limit is set from, for one cell, in one
process: each seed runs as a benchmark run does (set-up, a window of
``--seconds``, the reference over the same seeded sample), and the
reference also reads its control, the same forward with every matrix
rounded to block-32 e4m3. One JSON line per seed on standard output.

    python3 bench/limits.py --workload <cell> --seeds 1,2,3 --seconds 40

A limit goes above the largest sound reading over a dozen seeds or
more and below the smallest control reading. Benchmark runs never run
the control.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax

    from bench import harness
    from repro.runtime import enable_compile_cache
    if jax.devices()[0].platform != "tpu":
        print("limits: no TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = harness.load_cell(ROOT, args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run_cell(cell, seed, args.seconds, False, t0, control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"], "compared": out["compared"],
                          "metrics": {k: v["value"] for k, v in out["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
