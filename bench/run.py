#!/usr/bin/env python3
"""Serving benchmark of repro's ``ServingEngine`` on one TPU.

    python3 bench/run.py --workload qwen3-1.7b.decode-batch --seed 7 --seconds 30 --trace 0

Runs one cell of ``BENCHMARK.json`` once, from the root of a checkout:
weights on the device from ``--seed``, every shape of the cell's traffic
warmed up (set-up), then ``--seconds`` of traffic on the wall clock,
then the correctness check against the plain reference.  With
``--trace 0`` the result line holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics read from a profiler trace of the
window.  Lines before the last are counts (compiles in the window among
them); the last line of standard output is the result object, and the
last lines of standard error give each number compared with its limit.

Without a TPU, or with fewer chips than the cell asks for, it exits 3
and prints no result: it never falls back to the CPU.  JAX's persistent
compilation cache is kept in ``.jax_cache/`` at the root of the checkout.
"""
import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
    from lib.boot import place_cache, tpu_devices

    place_cache()
    from lib.registry import load_cell

    cell = load_cell(args.workload)
    tpu_devices(cell.chips, args.workload)
    from lib.compiles import CompileCounter
    from lib.measure import measure

    counter = CompileCounter()
    result, info, checks = measure(cell, args.seed, args.seconds, bool(args.trace),
                                   t_start=T_START, counter=counter)
    print(json.dumps({"counts": info}), flush=True)
    for line in checks:
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
