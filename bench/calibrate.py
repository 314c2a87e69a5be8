#!/usr/bin/env python3
"""Readings that the correctness limits are set from, on the chip.

    python3 bench/calibrate.py --workload qwen3-1.7b.decode-batch --seeds 1,2,3 --seconds 12

For each seed, in one process: the cell's set-up and a window at its own
load, then, on the sample of finished requests that a benchmark run
would check, the numbers of the program's served tokens and of the
control's (the reference computed in bfloat16 in the program's place),
each judged against the configuration's limits as a run judges it.  One
JSON line per seed; the benchmark's own runs never run the control.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--seconds", type=float, required=True)
    args = p.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]
    from lib.boot import place_cache, tpu_devices

    place_cache()
    from lib.registry import load_cell

    cell = load_cell(args.workload)
    tpu_devices(cell.chips, args.workload)
    import gc

    from lib import check as C
    from lib.harness import Session
    from lib.measure import numbers, served_sample

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.time()
        sess = Session(cell, seed)
        sess.build()
        sess.warm_up()
        rec = sess.window(args.seconds)
        seqs, nonfinite, faults = served_sample(sess, rec, seed)
        ref = C.Reference(cell.reference, sess.dims, seed)
        t1 = time.time()
        line = {"workload": cell.name, "seed": seed, "sample_requests": len(seqs),
                "sample_tokens": int(sum(g for *_, g in seqs))}
        for who, gaps in (("program", ref.gaps(seqs)), ("control", ref.control_gaps(seqs))):
            judged = numbers(sess.dims["limits"], gaps, nonfinite, faults)
            line[who] = {"correct": C.verdict(judged), **C.gap_numbers(gaps)}
        line.update(reference_s=time.time() - t1, run_s=time.time() - t0)
        print(json.dumps(line), flush=True)
        del ref, sess
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
