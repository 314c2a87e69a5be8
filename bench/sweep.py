#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip.

    python3 bench/sweep.py --workload <open-loop cell> --rates 3,4,5,6 --seconds 30 --seed 1

One set-up, then one window per rate with the cell's traffic at that
rate.  The knee is the highest rate at which no request is shed and the
waiting does not grow across the window (time to first token of the last
third of the requests no worse than twice that of the first third).  One
JSON line per rate.
"""
import argparse
import dataclasses
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True, help="comma-separated requests/s")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--seed", type=int, required=True)
    args = p.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
    from lib.boot import place_cache, tpu_devices

    place_cache()
    from lib.registry import load_cell

    cell = load_cell(args.workload)
    tpu_devices(cell.chips, args.workload)
    import numpy as np

    from lib.harness import Session, counts, end_to_end
    from repro.launch.serve import RequestQueue

    sess = Session(cell, args.seed)
    sess.build()
    sess.warm_up()
    for rate in (float(r) for r in args.rates.split(",")):
        e = sess.engine
        e.queue = RequestQueue(cell.traffic.queue_limit)
        e.slots = [None] * e.max_batch
        e.completed.clear()
        sess.cell = dataclasses.replace(cell, traffic=dataclasses.replace(cell.traffic, rate_rps=rate))
        rec = sess.window(args.seconds)
        order = sorted((d, i) for i, d in rec.due.items())
        waits = [rec.tokens[i][0] - d for d, i in order if i in rec.tokens]
        third = max(1, len(waits) // 3)
        first, last = float(np.mean(waits[:third])), float(np.mean(waits[-third:]))
        info = counts(rec)
        print(json.dumps({"rate_rps": rate, **end_to_end(rec), **info,
                          "ttft_first_third_ms": 1e3 * first, "ttft_last_third_ms": 1e3 * last,
                          "sustained": info["shed"] == 0 and last <= 2 * first}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
