#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Cells, metrics and their files are listed in ``BENCHMARK.json`` at the
root of the checkout; ``bench/harness.py`` says what a run does. The
last line of standard output is the result as one JSON object; the
numbers the correctness check compared, each beside its limit, are the
last lines of standard error. The run needs the chips the cell asks
for: where JAX finds no TPU, or too few, it exits non-zero and prints
no result. Compiled programs are kept in JAX's persistent cache
(``$JAX_COMPILATION_CACHE_DIR``, else ``<checkout>/.jax_cache``).
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()     # set-up is timed from here

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: {ROOT / 'src'} holds no repro package", file=sys.stderr)
        return 2
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    from bench import harness
    cell = harness.load_cell(ROOT, args.workload)

    import jax
    from repro.runtime import enable_compile_cache
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"bench: no TPU (JAX found {devices[0].platform})", file=sys.stderr)
        return 1
    if len(devices) < cell.entry["chips"]:
        print(f"bench: {args.workload} needs {cell.entry['chips']} chips, "
              f"found {len(devices)}", file=sys.stderr)
        return 1
    cache = enable_compile_cache()
    # keep every executable, small ones too, so a later run loads all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    harness.log(f"device: {devices[0].platform} {devices[0].device_kind} "
                f"x {len(devices)}; compile cache {cache}")
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T_PROCESS, trace_dir=ROOT / ".bench_trace" / args.workload)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
